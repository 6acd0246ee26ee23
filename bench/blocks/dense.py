"""The dense block: a pre-norm decoder-only transformer with RMSNorm,
rotary positions, an MLP (gated or plain, by ``mlp_gated``) and an output
head (tied to the embedding, by ``tie_embeddings``). Its parameter and
model-FLOP counts, from the configuration file's sizes alone, and its
plain reference model, in float32 with matmuls at ``Precision.HIGH``.

Model FLOPs per trained token follow PaLM (arXiv:2204.02311, App. B):
6 * N_matmul + 12 * L * S * (n_heads * head_dim). N_matmul counts the
weights of every matrix multiplication: the attention projections, the
MLP and the output head, but not the embedding lookup (a gather) and not
the norms. Recomputed operations (remat) are not counted.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGH        # the model's matmuls

# the model keys a dense configuration file states (``remat`` changes
# no result)
KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
        "vocab_size", "rope_theta", "act", "mlp_gated", "tie_embeddings",
        "init_scale", "remat")

# what the reference implements and nothing else: a file may state these
# only at these values, and the program has to have them
FIXED = {"family": "dense", "pos_emb": "rope", "norm": "rmsnorm",
         "compute_dtype": "float32", "rope_pct": 1.0, "qk_norm": False,
         "attn_bias": False, "mlp_bias": False, "parallel_block": False,
         "window": 0, "logit_softcap": 0.0, "mla": False, "n_experts": 0}


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------
def n_params(cfg: dict) -> int:
    """Every parameter: embedding, head, final norm and the layers."""
    return (cfg["vocab_size"] * cfg["d_model"] + n_head(cfg)
            + cfg["d_model"] + cfg["n_layers"] * _layer_params(cfg))


def n_head(cfg: dict) -> int:
    return 0 if cfg["tie_embeddings"] else cfg["d_model"] * cfg["vocab_size"]


def _layer_params(cfg: dict) -> int:
    return _layer_matmul(cfg) + 2 * cfg["d_model"]


def _layer_matmul(cfg: dict) -> int:
    D, hd = cfg["d_model"], cfg["head_dim"]
    attn = D * hd * (2 * cfg["n_heads"] + 2 * cfg["n_kv_heads"])
    mlp = (3 if cfg["mlp_gated"] else 2) * D * cfg["d_ff"]
    return attn + mlp


def n_matmul(cfg: dict) -> int:
    return cfg["n_layers"] * _layer_matmul(cfg) + cfg["d_model"] * cfg["vocab_size"]


def flops_per_token(cfg: dict, seq: int) -> float:
    attn = 12 * cfg["n_layers"] * seq * cfg["n_heads"] * cfg["head_dim"]
    return 6.0 * n_matmul(cfg) + attn


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
