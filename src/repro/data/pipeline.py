"""Deterministic synthetic LM data: per-shard Markov-mixture streams.

The paper trains on C4 with i.i.d. (random) vs non-i.i.d. (k-Means
clustered) shards. Offline we reproduce the *statistical structure* that
matters to DiLoCo — shards with identical vs distinct distributions and a
shared, learnable generative process — with first-order Markov chains:

  - A base chain (seeded) shared by all shards.
  - Per-shard perturbations; shard i samples from
    softmax(base + alpha * pert_i). alpha=0 -> i.i.d.; alpha>0 -> non-i.i.d.
  - The validation stream samples from the *mixture* over shards,
    mirroring C4's global validation split.

Transition logits are low rank, so no V×V array is ever built, on the
host or on the device, at any vocabulary size:

    logits_i[tok] = U_0[tok]·W_0ᵀ + alpha · U_i[tok]·W_iᵀ

with (V, RANK) factors per chain (entries N(0, 1) and N(0, 1/RANK), so
every logit has unit variance as in a dense N(0, 1) table). The factors
are drawn in-graph from the seed, which keeps them out of every compiled
program's constants. A sampling step is one (batch, r)·(r, V) matmul;
a mixture step first draws the component, then samples from it, which is
exactly a draw from the mixture's probabilities.

Models can genuinely reduce perplexity toward the chain entropy floor, so
all of the paper's comparisons (DiLoCo vs baselines, i.i.d. vs non-i.i.d.,
outer optimizers, ...) are measurable end-to-end.
"""
from __future__ import annotations

import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np

RANK = 32                   # rank of each chain's transition logits
_BLOCK_ELEMS = 1 << 25      # entropy_floor: elements of one (g, n, V) block
_HI = jax.lax.Precision.HIGHEST


class MarkovMixture:
    """Deterministic, stateless batch sampler over k shard distributions.

    ``k`` is the number of streams (workers); each stream samples from
    the uniform probability mixture of its ``groups`` entry, a tuple of
    the underlying shard chains (one shard each until ``regroup``)."""

    def __init__(self, vocab_size: int = 256, k: int = 8,
                 alpha: float = 2.0, seed: int = 0,
                 shard_sizes: np.ndarray | None = None):
        self.vocab_size = vocab_size
        self.k = k
        self.n_shards = k
        self.alpha = float(alpha)
        self.seed = int(seed)
        self.groups = tuple((i,) for i in range(k))
        if shard_sizes is None:
            shard_sizes = np.ones((k,), np.float32)
        self.shard_sizes = np.asarray(shard_sizes, np.float32)

    # ---- chain factors ----
    def _factors(self):
        """(U, W), each (n_shards + 1, V, RANK): row 0 the base chain,
        row 1 + i shard i's perturbation."""
        ku, kw = jax.random.split(jax.random.PRNGKey(self.seed))
        shape = (self.n_shards + 1, self.vocab_size, RANK)
        return (jax.random.normal(ku, shape),
                jax.random.normal(kw, shape) / np.sqrt(RANK))

    def _mixture(self, groups):
        """(members (W, g) int32, log-weights (W, g)) of uniform mixtures
        over each group; short groups are padded with weight 0."""
        g = max(len(grp) for grp in groups)
        members = np.zeros((len(groups), g), np.int32)
        logw = np.full((len(groups), g), -np.inf, np.float32)
        for w, grp in enumerate(groups):
            members[w, :len(grp)] = grp
            logw[w, :len(grp)] = -np.log(len(grp))
        return jnp.asarray(members), jnp.asarray(logw)

    def _validation(self):
        return self._mixture((tuple(range(self.n_shards)),))

    def _logit_maps(self, members):
        """Factors of a mixture over ``members`` (g,): ``(u0 (V, r),
        w0 (V, r), um (g, V, r), wm (V, g·r))`` — in ``wm`` component
        c's right factor occupies columns [c·r, (c+1)·r)."""
        u, w = self._factors()
        g = members.shape[0]
        comps = jnp.transpose(w[1 + members], (1, 0, 2))
        return (u[0], w[0], u[1 + members],
                comps.reshape(self.vocab_size, g * RANK))

    # ---- sampling ----
    @functools.partial(jax.jit, static_argnums=(0, 3, 4))
    def sample_shard(self, key, shard_id, batch: int, seq_len: int):
        """tokens (batch, seq_len) int32 from stream ``shard_id``."""
        members, logw = self._mixture(self.groups)
        return self._sample_chain(key, members[shard_id], logw[shard_id],
                                  batch, seq_len)

    @functools.partial(jax.jit, static_argnums=(0, 2, 3))
    def sample_all_shards(self, key, batch: int, seq_len: int):
        """tokens (k, batch, seq_len): one batch per stream (vmapped)."""
        members, logw = self._mixture(self.groups)
        keys = jax.random.split(key, self.k)
        return jax.vmap(
            lambda kk, mm, lw: self._sample_chain(kk, mm, lw, batch,
                                                  seq_len)
        )(keys, members, logw)

    @functools.partial(jax.jit, static_argnums=(0, 2, 3))
    def sample_validation(self, key, batch: int, seq_len: int):
        members, logw = self._validation()
        return self._sample_chain(key, members[0], logw[0], batch, seq_len)

    @jax.named_scope("diloco.sample")
    def _sample_chain(self, key, members, logw, batch: int, seq_len: int):
        """One mixture chain: members (g,) shard ids, logw (g,). Its ops
        carry the ``diloco.sample`` scope in the compiled program."""
        u0, w0, um, wm = self._logit_maps(members)
        g = members.shape[0]
        right = jnp.concatenate([w0, self.alpha * wm], axis=1)
        k0, k1 = jax.random.split(key)
        first = jax.random.randint(k0, (batch,), 0, self.vocab_size)

        def step(tok, kk):
            kc, kt = jax.random.split(kk)
            comp = jax.random.categorical(kc, logw, shape=(batch,))
            sel = jax.nn.one_hot(comp, g, dtype=u0.dtype)      # (B, g)
            left = jnp.concatenate(
                [u0[tok], (sel[:, :, None] * jnp.swapaxes(um[:, tok], 0, 1))
                 .reshape(batch, g * RANK)], axis=1)
            logits = jnp.dot(left, right.T, precision=_HI)      # (B, V)
            nxt = jax.random.categorical(kt, logits, axis=-1)
            return nxt, nxt

        keys = jax.random.split(k1, seq_len - 1)
        _, rest = jax.lax.scan(step, first, keys)
        return jnp.concatenate([first[None], rest], 0).T.astype(jnp.int32)

    # ---- resharding ----
    def regroup(self, k_workers: int) -> "MarkovMixture":
        """Redistribute this mixture's shards among ``k_workers``
        (round-robin), holding the DATA-GENERATING PROCESS fixed: the
        validation mixture is unchanged, each worker samples from the
        probability-mixture of its assigned shards. This is how the
        paper varies the replica count — the dataset (C4) stays the
        same, only its partitioning changes."""
        if not 1 <= k_workers <= self.k:
            raise ValueError(f"regroup: need 1 <= k_workers <= {self.k}, "
                             f"got {k_workers}")
        groups, sizes = [], []
        for i in range(k_workers):
            idx = list(range(i, self.k, k_workers))
            groups.append(tuple(s for j in idx for s in self.groups[j]))
            sizes.append(float(self.shard_sizes[idx].sum()))
        new = copy.copy(self)
        new.k = k_workers
        new.groups = tuple(groups)
        new.shard_sizes = np.asarray(sizes, np.float32)
        return new

    # ---- statistics ----
    def entropy_floor(self) -> float:
        """Per-token entropy (nats) of the validation mixture chain =
        best achievable validation loss; exp() of it is the perplexity
        floor. Computed blockwise over rows: no V×V matrix is built."""
        members, logw = self._validation()
        return float(_entropy_floor(self, members[0], logw[0]))


def _mixture_rows(sampler: MarkovMixture, members, logw, toks):
    """(n, V) mixture probabilities at current tokens ``toks`` (n,)."""
    u0, w0, um, wm = sampler._logit_maps(members)
    g = members.shape[0]
    base = jnp.dot(u0[toks], w0.T, precision=_HI)                # (n, V)
    pert = jnp.einsum("gnr,vgr->gnv", um[:, toks],
                      wm.reshape(-1, g, RANK), precision=_HI)    # (g,n,V)
    probs = jax.nn.softmax(base[None] + sampler.alpha * pert, axis=-1)
    return jnp.einsum("g,gnv->nv", jnp.exp(logw), probs, precision=_HI)


@functools.partial(jax.jit, static_argnums=(0,))
def _entropy_floor(sampler: MarkovMixture, members, logw):
    V = sampler.vocab_size
    n = max(1, min(V, _BLOCK_ELEMS // (members.shape[0] * V)))
    nb = -(-V // n)
    rows = jnp.arange(nb * n).reshape(nb, n)
    valid = (rows < V).astype(jnp.float32)
    rows = jnp.minimum(rows, V - 1)
    block = lambda r: _mixture_rows(sampler, members, logw, r)

    def power(_, pi):
        def body(acc, xs):
            r, m = xs
            return acc + jnp.dot(pi[r] * m, block(r), precision=_HI), None
        return jax.lax.scan(body, jnp.zeros((V,), jnp.float32),
                            (rows, valid))[0]

    # stationary distribution via power iteration
    pi = jax.lax.fori_loop(0, 64, power,
                           jnp.full((V,), 1.0 / V, jnp.float32))

    def entropy(acc, xs):
        r, m = xs
        p = block(r)
        return acc - jnp.sum((pi[r] * m)[:, None] * p
                             * jnp.log(p + 1e-12)), None
    return jax.lax.scan(entropy, jnp.zeros((), jnp.float32),
                        (rows, valid))[0]


def batch_iterator(sampler: MarkovMixture, batch: int, seq_len: int,
                   seed: int = 0, mode: str = "shards"):
    """Infinite deterministic iterator; mode: shards|validation."""
    step = 0
    key = jax.random.PRNGKey(seed)
    while True:
        sub = jax.random.fold_in(key, step)
        if mode == "shards":
            yield sampler.sample_all_shards(sub, batch, seq_len)
        else:
            yield sampler.sample_validation(sub, batch, seq_len)
        step += 1
