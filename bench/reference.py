"""Plain reference of the first rounds of a classic DiLoCo job on the
simulated transport, in float32, written from the job's description
alone. The model is the one the configuration's block file gives
(``bench/blocks/<block>.py``: its weights, its loss and the rows of a
gradient block), with matmuls at ``Precision.HIGH`` (three bfloat16
passes, about 2**-16 relative, against the program's one pass).

It regenerates from the seed what the trainer generates (the synthetic
token streams and the initial weights), with the same random draws, and
then runs DiLoCo (arXiv:2311.08105, Algorithm 1) in straightforward
``jax.numpy``: k replicas take H AdamW steps each on their own stream,
the mean of their parameter deltas is the outer gradient, and an outer
Nesterov step moves the global parameters, which every replica adopts.

Departures, each without effect on the mathematics:
  * the gradient of a batch is the mean of the gradients of blocks of
    rows (``row_block``), so that a reference step fits on one chip;
  * the global parameters, the outer momentum and the outer step live
    on the host in numpy float32 between rounds.

Nothing here imports the trainer or takes anything it made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import blocks
from bench.check import leaf_norms

SAMPLE = jax.lax.Precision.HIGHEST  # the chains' logits: tokens match bit for bit
RANK = 32               # rank of the synthetic chains' transition logits
MIN_LR_RATIO = 0.1      # cosine decay floor of the inner learning rate


# ---------------------------------------------------------------------------
# data: per-stream low-rank Markov chains
# ---------------------------------------------------------------------------

def _factors(seed: int, n_shards: int, V: int):
    ku, kw = jax.random.split(jax.random.PRNGKey(seed))
    shape = (n_shards + 1, V, RANK)
    return (jax.random.normal(ku, shape),
            jax.random.normal(kw, shape) / np.sqrt(RANK))


def _chain(u, w, key, members, logw, alpha, B, S):
    """One stream drawing from the uniform mixture of chains ``members``:
    next-token logits u_0[t].w_0 + alpha * u_c[t].w_c of a component c
    drawn per token."""
    V = u.shape[1]
    g = members.shape[0]
    u0, w0, um = u[0], w[0], u[1 + members]
    wm = jnp.transpose(w[1 + members], (1, 0, 2)).reshape(V, g * RANK)
    right = jnp.concatenate([w0, alpha * wm], axis=1)
    k0, k1 = jax.random.split(key)
    first = jax.random.randint(k0, (B,), 0, V)

    def step(tok, kk):
        kc, kt = jax.random.split(kk)
        comp = jax.random.categorical(kc, logw, shape=(B,))
        sel = jax.nn.one_hot(comp, g, dtype=u0.dtype)
        left = jnp.concatenate(
            [u0[tok], (sel[:, :, None] * jnp.swapaxes(um[:, tok], 0, 1))
             .reshape(B, g * RANK)], axis=1)
        nxt = jax.random.categorical(
            kt, jnp.dot(left, right.T, precision=SAMPLE), axis=-1)
        return nxt, nxt

    _, rest = jax.lax.scan(step, first, jax.random.split(k1, S - 1))
    return jnp.concatenate([first[None], rest], 0).T.astype(jnp.int32)


def round_tokens(seed, k, alpha, V, key, H, B, S):
    """(k, H, B, S): stream i's batch for each of the round's H steps."""
    u, w = _factors(seed, k, V)
    members = jnp.arange(k, dtype=jnp.int32)[:, None]
    logw = jnp.full((k, 1), -np.log(1), jnp.float32)

    def all_streams(kk):
        return jax.vmap(lambda ki, mi, li: _chain(u, w, ki, mi, li, alpha,
                                                  B, S))(
            jax.random.split(kk, k), members, logw)

    toks = jax.vmap(all_streams)(jax.random.split(key, H))   # (H,k,B,S)
    return jnp.swapaxes(toks, 0, 1)


# ---------------------------------------------------------------------------
# gradients
# ---------------------------------------------------------------------------

def _blocks(tokens, rows):
    B, S = tokens.shape
    return tokens.reshape(B // rows, rows, S)


def batch_grad(block, cfg, params, tokens, rows):
    """(loss, grads) of the batch mean of ``block``'s loss, accumulated
    over row blocks."""
    parts = _blocks(tokens, rows)

    def add(carry, t):
        l, g = jax.value_and_grad(functools.partial(block.loss, cfg))(
            params, t)
        return jax.tree.map(jnp.add, carry, (l, g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    (l, g), _ = jax.lax.scan(add, zero, parts)
    n = parts.shape[0]
    return l / n, jax.tree.map(lambda x: x / n, g)


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------

def inner_lr(step, job: dict):
    """Linear warmup to ``inner_lr`` then cosine decay to a tenth."""
    step = jnp.asarray(step, jnp.float32)
    peak, warm = job["inner_lr"], job["warmup"]
    total = job["schedule_rounds"] * job["inner_steps"]
    progress = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = MIN_LR_RATIO + (1 - MIN_LR_RATIO) * 0.5 * (
        1 + jnp.cos(jnp.pi * progress))
    return jnp.where(step < warm, peak * step / max(warm, 1), peak * cos)


def adamw_step(block, cfg, job, rows, p, m, v, tokens, step):
    """One inner step: gradient clipped to a global norm, then AdamW
    with bias correction and decoupled weight decay. ``step`` counts the
    replica's steps before this one."""
    l, g = batch_grad(block, cfg, p, tokens, rows)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(
        1.0, job["grad_clip"] / (gnorm + 1e-12)), g)
    b1, b2, eps, wd = job["b1"], job["b2"], job["eps"], job["weight_decay"]
    t = jnp.asarray(step + 1, jnp.float32)
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    lr = inner_lr(step, job)
    m = jax.tree.map(lambda mm, gg: b1 * mm + (1.0 - b1) * gg, m, g)
    v = jax.tree.map(lambda vv, gg: b2 * vv + (1.0 - b2) * gg * gg, v, g)
    p = jax.tree.map(lambda pp, mm, vv: pp - lr * (
        (mm / c1) / (jnp.sqrt(vv / c2) + eps) + wd * pp), p, m, v)
    return p, m, v, l, g


# ---------------------------------------------------------------------------
# the job
# ---------------------------------------------------------------------------

def run(cfg: dict, job: dict, seed: int, rounds: int, log=lambda msg: None):
    """The first ``rounds`` rounds of the job from ``seed``.

    Returns a dict: per round ``inner_loss`` (mean over replicas and
    steps); ``outer_grad`` (per-leaf norms of round 1's outer gradient);
    ``change`` (per-leaf norms of the global parameters' change over the
    rounds); ``grad0`` (per-leaf norms of replica 0's first gradient).
    """
    k, H = job["replicas"], job["inner_steps"]
    if job["transport"] != "simulated" or job.get("fragments"):
        raise ValueError("this reference runs classic DiLoCo on the "
                         "simulated transport; a streaming or sharded job "
                         "names a reference of its own")
    block = blocks.load(cfg)
    B, S = job["batch"], job["seq"]
    rows = block.row_block(cfg, B, S)
    V = cfg["vocab_size"]
    alpha = job["data_alpha"]
    key, init_key = jax.random.split(jax.random.PRNGKey(seed))
    theta = jax.device_get(jax.jit(
        functools.partial(block.init_params, cfg=cfg))(init_key))
    theta0 = theta
    buf = jax.tree.map(np.zeros_like, theta)
    step_fn = jax.jit(functools.partial(adamw_step, block, cfg, job, rows),
                      donate_argnums=(0, 1, 2))
    toks_fn = jax.jit(functools.partial(round_tokens, seed, k, alpha, V,
                                        H=H, B=B, S=S))
    log("reference: weights built")
    moments = [(jax.tree.map(jnp.zeros_like, theta),
                jax.tree.map(jnp.zeros_like, theta)) for _ in range(k)]
    w = np.full((k,), 1.0 / k, np.float32)
    denom = np.float32(max(float(w.sum()), 1e-9))
    mu, olr = job["outer_momentum"], job["outer_lr"]
    out = {"inner_loss": []}
    for r in range(rounds):
        key, sub = jax.random.split(key)
        toks = toks_fn(sub)
        losses, finals = [], []
        for i in range(k):
            p = jax.device_put(theta)
            m, v = moments[i]
            for h in range(H):
                p, m, v, l, g = step_fn(p, m, v, toks[i, h], r * H + h)
                if r == 0 and i == 0 and h == 0:
                    out["grad0"] = leaf_norms(g)
                del g
                losses.append(l)
            moments[i] = (m, v)
            finals.append(jax.device_get(p))
            del p
        out["inner_loss"].append(float(np.mean([float(l) for l in losses])))
        avg = jax.tree.map(
            lambda t, *ps: sum(wi * (t - pi) for wi, pi in zip(w, ps))
            / denom, theta, *finals)
        if r == 0:
            out["outer_grad"] = leaf_norms(avg)
        buf = jax.tree.map(lambda b, d: mu * b + d, buf, avg)
        theta = jax.tree.map(lambda t, b, d: t - olr * (mu * b + d),
                             theta, buf, avg)
        log(f"reference: round {r + 1} done")
    out["change"] = leaf_norms(jax.tree.map(np.subtract, theta, theta0))
    return out
