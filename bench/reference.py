"""Plain reference of the first rounds of a classic DiLoCo job on the
simulated transport, in float32 with matmuls at ``Precision.HIGH``
(three bfloat16 passes, about 2**-16 relative, against the program's one
pass), written from the job's description alone.

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

from bench.check import leaf_norms

HI = jax.lax.Precision.HIGH        # the model's matmuls
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
# model: pre-norm decoder, RMSNorm, rotary positions, an MLP (gated or
# plain, by ``mlp_gated``) and an output head (tied to the embedding, by
# ``tie_embeddings``)
# ---------------------------------------------------------------------------

def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _std(scale, fan_in):
    return min(scale, (1.0 / max(fan_in, 1)) ** 0.5)


def init_params(key, cfg: dict):
    """Initial weights drawn from ``key`` as the trainer draws them:
    N(0, min(scale, fan_in**-0.5)) matrices, unit norm scales; no gate
    in a plain MLP, no head matrix where the head is tied."""
    D, V, F, L = (cfg["d_model"], cfg["vocab_size"], cfg["d_ff"],
                  cfg["n_layers"])
    H, G, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    s = cfg["init_scale"]
    ks = jax.random.split(key, 9)
    ones = lambda: jnp.ones((D,), jnp.float32)

    def layer(lk):
        bk = jax.random.split(lk, 8)
        ak = jax.random.split(bk[0], 6)
        mk = jax.random.split(bk[1], 3)
        mlp = {"w_up": _normal(mk[0], (D, F), _std(s, D)),
               "w_down": _normal(mk[1], (F, D), _std(s, F))}
        if cfg["mlp_gated"]:
            mlp["w_gate"] = _normal(mk[2], (D, F), _std(s, D))
        return {
            "ln1": {"scale": ones()},
            "attn": {"wq": _normal(ak[0], (D, H, hd), _std(s, D)),
                     "wk": _normal(ak[1], (D, G, hd), _std(s, D)),
                     "wv": _normal(ak[2], (D, G, hd), _std(s, D)),
                     "wo": _normal(ak[3], (H, hd, D), _std(s, H))},
            "ln2": {"scale": ones()},
            "mlp": mlp}

    layers = [layer(lk) for lk in jax.random.split(ks[3], L)]
    params = {"embed": {"table": _normal(ks[0], (V, D), _std(1.0, V))},
              "ln_f": {"scale": ones()},
              "stack0": jax.tree.map(lambda *ls: jnp.stack(ls), *layers)}
    if not cfg["tie_embeddings"]:
        params["head"] = {"w": _normal(ks[1], (D, V), _std(s, D))}
    return params


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _rope(x, theta):
    """Rotate interleaved pairs (x[2i], x[2i+1]) of each head by
    position * theta**(-2i/hd)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None] * inv)[None, :, None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _layer(cfg, x, lp):
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    H, G, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    S = x.shape[1]
    a = lp["attn"]
    h = _rmsnorm(x, lp["ln1"]["scale"], eps)
    q = _rope(jnp.einsum("bsd,dhk->bshk", h, a["wq"], precision=HI), theta)
    k = _rope(jnp.einsum("bsd,dgk->bsgk", h, a["wk"], precision=HI), theta)
    v = jnp.einsum("bsd,dgk->bsgk", h, a["wv"], precision=HI)
    k, v = jnp.repeat(k, H // G, axis=2), jnp.repeat(v, H // G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q * hd ** -0.5, k, precision=HI)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)
    x = x + jnp.einsum("bshk,hkd->bsd", o, a["wo"], precision=HI)
    m = lp["mlp"]
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[cfg["act"]]
    h = _rmsnorm(x, lp["ln2"]["scale"], eps)
    up = jnp.einsum("bsd,df->bsf", h, m["w_up"], precision=HI)
    if cfg["mlp_gated"]:
        up = act(jnp.einsum("bsd,df->bsf", h, m["w_gate"], precision=HI)) * up
    else:
        up = act(up)
    return x + jnp.einsum("bsf,fd->bsd", up, m["w_down"], precision=HI)


def loss(cfg, params, tokens):
    """Mean next-token cross-entropy of ``tokens`` (B, S)."""
    x = params["embed"]["table"][tokens]
    x, _ = jax.lax.scan(lambda x, lp: (_layer(cfg, x, lp), None), x,
                        params["stack0"])
    x = _rmsnorm(x, params["ln_f"]["scale"], cfg["norm_eps"])
    head = (params["embed"]["table"].T if cfg["tie_embeddings"]
            else params["head"]["w"])
    logits = jnp.einsum("bsd,dv->bsv", x, head, precision=HI)[:, :-1]
    nll = (jax.nn.logsumexp(logits, -1)
           - jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0])
    return jnp.mean(nll)


ROW_BUDGET = 4 << 30    # bytes of activations one block of rows may hold


def row_block(cfg: dict, batch: int, seq: int) -> int:
    """Rows per block of a batch's gradient: the largest divisor of
    ``batch`` whose f32 activations, kept for the backward pass with no
    recompute (each layer's scores and probabilities, a dozen tensors of
    the width and three of the MLP's, and the logits), fit
    ``ROW_BUDGET``."""
    per_layer = (2 * cfg["n_heads"] * seq * seq
                 + seq * (12 * cfg["d_model"] + 3 * cfg["d_ff"]))
    per_row = 4 * (cfg["n_layers"] * per_layer + 3 * seq * cfg["vocab_size"])
    return max([r for r in range(1, batch + 1)
                if batch % r == 0 and r * per_row <= ROW_BUDGET], default=1)


def _blocks(tokens, rows):
    B, S = tokens.shape
    return tokens.reshape(B // rows, rows, S)


def batch_grad(cfg, params, tokens, rows):
    """(loss, grads) of the batch mean, accumulated over row blocks."""
    blocks = _blocks(tokens, rows)

    def add(carry, t):
        l, g = jax.value_and_grad(functools.partial(loss, cfg))(params, t)
        return jax.tree.map(jnp.add, carry, (l, g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    (l, g), _ = jax.lax.scan(add, zero, blocks)
    n = blocks.shape[0]
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


def adamw_step(cfg, job, rows, p, m, v, tokens, step):
    """One inner step: gradient clipped to a global norm, then AdamW
    with bias correction and decoupled weight decay. ``step`` counts the
    replica's steps before this one."""
    l, g = batch_grad(cfg, p, tokens, rows)
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
    block = {k: cfg[k] for k in ("family", "pos_emb", "norm", "compute_dtype")}
    if block != {"family": "dense", "pos_emb": "rope", "norm": "rmsnorm",
                 "compute_dtype": "float32"}:
        raise ValueError(f"this reference has no such block: {block}")
    B, S = job["batch"], job["seq"]
    rows = row_block(cfg, B, S)
    V = cfg["vocab_size"]
    alpha = job["data_alpha"]
    key, init_key = jax.random.split(jax.random.PRNGKey(seed))
    theta = jax.device_get(jax.jit(
        functools.partial(init_params, cfg=cfg))(init_key))
    theta0 = theta
    buf = jax.tree.map(np.zeros_like, theta)
    step_fn = jax.jit(functools.partial(adamw_step, cfg, job, rows),
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
