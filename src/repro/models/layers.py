"""Core transformer building blocks, pure-functional JAX.

All init fns return trees of ``Boxed(value, logical_axes)`` leaves (see
sharding/spec.py). All apply fns take plain param trees (unboxed).

The attention implementation is a chunked online-softmax ("flash-style")
formulation in pure jnp: it never materializes the (Sq, Skv) score matrix
for long sequences, which keeps dry-run compile memory bounded at 32k/500k
context, and doubles as the numerical oracle for the Pallas TPU kernel in
``repro.kernels.flash_attention``.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.sharding.spec import Boxed

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, axes, scale=0.02, dtype=jnp.float32):
    fan_in = shape[0] if len(shape) > 1 else 1
    std = min(scale, (1.0 / max(fan_in, 1)) ** 0.5)
    return Boxed(jax.random.normal(key, shape, dtype) * std, axes)


def zeros_init(shape, axes, dtype=jnp.float32):
    return Boxed(jnp.zeros(shape, dtype), axes)


def ones_init(shape, axes, dtype=jnp.float32):
    return Boxed(jnp.ones(shape, dtype), axes)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(kind: str, dim: int):
    if kind == "rmsnorm":
        return {"scale": ones_init((dim,), (None,))}
    return {"scale": ones_init((dim,), (None,)),
            "bias": zeros_init((dim,), (None,))}


def apply_norm(params, x, kind: str, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True)
                               + eps)
    else:
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32)
    if "bias" in params:
        y = y + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_head_norm(scale, x, eps: float = 1e-6):
    """qk-norm: RMSNorm over the head dim of (B, S, H, hd)."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# positions
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float, pct: float = 1.0):
    rot = int(head_dim * pct) // 2 * 2
    inv = 1.0 / (theta ** (np.arange(0, rot, 2, dtype=np.float32) / rot))
    return jnp.asarray(inv), rot


def apply_rope(x, positions, theta: float, pct: float = 1.0):
    """x: (B, S, H, hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    inv, rot = rope_freqs(hd, theta, pct)
    if rot == 0:
        return x
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * inv[None]     # (S, r/2)
        ang = ang[None, :, None, :]                                   # (1,S,1,r/2)
    else:
        ang = positions[..., None].astype(jnp.float32) * inv         # (B,S,r/2)
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], -1).reshape(xr.shape)
    return jnp.concatenate([out, xp], -1).astype(x.dtype)


def sincos_positions(seq_len: int, dim: int, dtype=jnp.float32):
    pos = np.arange(seq_len)[:, None]
    i = np.arange(dim // 2)[None]
    ang = pos / (10_000 ** (2 * i / dim))
    emb = np.concatenate([np.sin(ang), np.cos(ang)], -1)
    return jnp.asarray(emb, dtype)


# ---------------------------------------------------------------------------
# attention (chunked online-softmax == flash oracle)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _mask_bias(q_pos, k_pos, causal: bool, window: int, kv_valid):
    """(..., q, k) additive bias. q_pos (Sq,); k_pos (Sk,) or (B, Sk)
    (per-slot position tracks — continuous batching); kv_valid same
    leading shape as k_pos."""
    kp = k_pos[..., None, :]                   # (..., 1, Sk)
    qp = q_pos[:, None]                        # (Sq, 1)
    ok = jnp.ones(jnp.broadcast_shapes(kp.shape, qp.shape), bool)
    if causal:
        ok &= kp <= qp
    if window and window > 0:
        ok &= kp > qp - window
    if kv_valid is not None:
        ok &= kv_valid[..., None, :]
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def attention(q, k, v, *, causal=True, window=0, q_offset=0,
              kv_positions=None, kv_valid=None, chunk=1024,
              softcap: float = 0.0, scale: float | None = None,
              kv_shard: str | None = None):
    """GQA attention. q: (B,Sq,H,dh); k: (B,Sk,G,dh); v: (B,Sk,G,dv).

    Uses a direct path for short kv and a lax.scan chunked online-softmax
    path for long kv (bounded memory: never materializes (Sq, Sk)).
    ``q_offset``: absolute position of q[0] (decode). ``kv_positions``:
    absolute positions of kv entries (defaults to arange, used by ring
    caches). ``kv_valid``: bool (Sk,) validity (partially-filled caches).
    """
    B, Sq, H, dh = q.shape
    _, Sk, G, _ = k.shape
    dv = v.shape[-1]
    rep = H // G
    scale = dh ** -0.5 if scale is None else scale
    qh = (q * scale).reshape(B, Sq, G, rep, dh)
    q_pos = q_offset + jnp.arange(Sq)
    if kv_positions is None:
        kv_positions = jnp.arange(Sk)

    # Direct path when the score matrix is small: short kv, OR few
    # queries (decode: Sq==1 — scores are (B,G,r,1,Sk), trivially small;
    # the chunked lax.scan would shuffle the sharded KV cache through
    # per-chunk reshapes that GSPMD reshards with cache-sized
    # all-reduces every layer).
    if Sk <= max(2 * chunk, 2048) or Sq <= 8:
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qh, k,
                       preferred_element_type=jnp.float32)
        if kv_shard:
            # flash-decoding: keep the kv dim of the scores sharded so
            # the partitioner computes windowed partial softmax + a tiny
            # psum instead of all-gathering the (huge) sequence-sharded
            # KV cache to every device
            from repro.sharding.spec import constrain as _c
            from jax.sharding import PartitionSpec as _P
            s = _c(s, _P(None, None, None, None, kv_shard))
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        bias = _mask_bias(q_pos, kv_positions, causal, window, kv_valid)
        if bias.ndim == 3:          # per-slot tracks: (B, Sq, Sk)
            bias = bias[:, None, None]
        s = s + bias
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o.reshape(B, Sq, H, dv).astype(q.dtype)

    # chunked path (shared position track only — per-slot (B, Sk)
    # tracks always take the direct path above since they imply Sq<=8)
    assert kv_positions.ndim == 1, "chunked path needs shared positions"
    assert Sk % chunk == 0, (Sk, chunk)
    nchunks = Sk // chunk
    ks = jnp.moveaxis(k.reshape(B, nchunks, chunk, G, dh), 1, 0)
    vs = jnp.moveaxis(v.reshape(B, nchunks, chunk, G, dv), 1, 0)
    kpos = kv_positions.reshape(nchunks, chunk)
    kval = (kv_valid.reshape(nchunks, chunk) if kv_valid is not None
            else jnp.ones((nchunks, chunk), bool))

    def body(carry, xs):
        acc, m, l = carry
        kc, vc, kp, kvld = xs
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qh, kc,
                       preferred_element_type=jnp.float32)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = s + _mask_bias(q_pos, kp, causal, window, kvld)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bgrqk,bkgd->bgrqd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return (acc, m_new, l), None

    acc0 = jnp.zeros((B, G, rep, Sq, dv), jnp.float32)
    m0 = jnp.full((B, G, rep, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, G, rep, Sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0),
                                  (ks, vs, kpos, kval))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(o, 3, 1).reshape(B, Sq, H, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# standard GQA attention block (init + apply, with optional KV cache)
# ---------------------------------------------------------------------------

def init_attention(key, cfg):
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 6)
    D, H, G = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    p = {
        "wq": dense_init(ks[0], (D, H, hd), ("embed", "heads", None),
                         cfg.init_scale),
        "wk": dense_init(ks[1], (D, G, hd), ("embed", "kv_heads", None),
                         cfg.init_scale),
        "wv": dense_init(ks[2], (D, G, hd), ("embed", "kv_heads", None),
                         cfg.init_scale),
        "wo": dense_init(ks[3], (H, hd, D), ("heads", None, "embed"),
                         cfg.init_scale),
    }
    if cfg.attn_bias:
        p["bq"] = zeros_init((H, hd), ("heads", None))
        p["bk"] = zeros_init((G, hd), ("kv_heads", None))
        p["bv"] = zeros_init((G, hd), ("kv_heads", None))
        p["bo"] = zeros_init((D,), (None,))
    if cfg.qk_norm:
        p["q_norm"] = ones_init((hd,), (None,))
        p["k_norm"] = ones_init((hd,), (None,))
    return p


def project_cross_kv(p, cfg, kv_x):
    """Project cross-attention K/V once (cached at prefill; recomputing
    them per decode step costs ~2·S_src·D² FLOPs per layer per step)."""
    dt = kv_x.dtype
    k = jnp.einsum("bsd,dgk->bsgk", kv_x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dgk->bsgk", kv_x, p["wv"].astype(dt))
    if "bk" in p:
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return k, v


def paged_kv_update(cache, page_table, k, v, cache_pos):
    """Write new tokens into a paged K/V pool and gather the dense ring
    view.

    cache: {"kp": (n_pages, psize, G, hd), "vp": ..., "posp":
    (n_pages, psize)} — a pool of fixed-size pages shared by all slots.
    page_table: (B, pages_per_slot) int32, the physical page backing
    each logical page of each slot's ring (-1 = unmapped: writes are
    dropped, reads come back empty). The logical ring has length
    C = pages_per_slot * psize; token at absolute position p lives at
    logical page (p % C) // psize, offset (p % C) % psize — exactly the
    contiguous ring layout, so the gathered dense view is value-equal
    to a contiguous cache and attention over it is bit-identical.

    Returns (new_cache, k_dense (B,C,G,hd), v_dense, kv_pos (B,C)).
    """
    kp, vp, posp = cache["kp"], cache["vp"], cache["posp"]
    n_pages, psize = kp.shape[0], kp.shape[1]
    B_, pages_per_slot = page_table.shape
    C = pages_per_slot * psize
    S_new = k.shape[1]
    if S_new > C:               # static shapes: python-level branch
        k = k[:, -C:]
        v = v[:, -C:]
        cache_pos_eff = cache_pos + (S_new - C)
        S_eff = C
    else:
        cache_pos_eff = cache_pos
        S_eff = S_new
    offs = jnp.arange(S_eff, dtype=jnp.int32)
    ring = (cache_pos_eff + offs) % C                   # (S_eff,)
    # unmapped table entries become an out-of-range sentinel: scatters
    # drop them (mode="drop"), gathers read back fill values — so a
    # slot with no page mapped never corrupts the shared pool (the
    # batched decode "writes" for empty slots too, like the contiguous
    # engine, but here those writes vanish instead of landing in a row)
    phys = jnp.where(page_table >= 0, page_table, n_pages)
    page_i = phys[:, ring // psize]                      # (B, S_eff)
    off_b = jnp.broadcast_to((ring % psize)[None], (B_, S_eff))
    upd = jnp.broadcast_to((cache_pos_eff + offs)[None], (B_, S_eff))
    kp = kp.at[page_i, off_b].set(k, mode="drop")
    vp = vp.at[page_i, off_b].set(v, mode="drop")
    posp = posp.at[page_i, off_b].set(upd, mode="drop")
    kd = jnp.take(kp, phys, axis=0, mode="fill",
                  fill_value=0).reshape((B_, C) + kp.shape[2:])
    vd = jnp.take(vp, phys, axis=0, mode="fill",
                  fill_value=0).reshape((B_, C) + vp.shape[2:])
    kv_pos = jnp.take(posp, phys, axis=0, mode="fill",
                      fill_value=-1).reshape(B_, C)
    return {"kp": kp, "vp": vp, "posp": posp}, kd, vd, kv_pos


def apply_attention(p, x, cfg, *, positions, cache=None, cache_pos=None,
                    window=0, causal=True, kv_x=None, kv_positions=None,
                    cross_kv=None, page_table=None):
    """Self- or cross-attention with optional decode cache.

    cache: dict {"k": (B, C, G, hd), "v": ..., } ring buffer of size C;
    a paged cache ({"kp", "vp", "posp"} page pool, see paged_kv_update)
    is used instead when present — ``page_table`` is required then.
    cache_pos: int32 scalar — absolute position of the incoming token(s).
    kv_x: if given, cross-attention keys/values come from kv_x.
    cross_kv: (k, v) precomputed cross K/V (see project_cross_kv).
    Returns (out, new_cache).
    """
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    if cross_kv is not None:
        k, v = cross_kv
        k = k.astype(dt)
        v = v.astype(dt)
        kv_x = True          # marks the cross-attention path below
        if "bq" in p:
            q = q + p["bq"].astype(dt)
    else:
        src = x if kv_x is None else kv_x
        k = jnp.einsum("bsd,dgk->bsgk", src, p["wk"].astype(dt))
        v = jnp.einsum("bsd,dgk->bsgk", src, p["wv"].astype(dt))
        if "bq" in p:
            q = q + p["bq"].astype(dt)
            k = k + p["bk"].astype(dt)
            v = v + p["bv"].astype(dt)
    if cfg.qk_norm:
        q = rms_head_norm(p["q_norm"], q)
        k = rms_head_norm(p["k_norm"], k)
    if cfg.pos_emb == "rope" and kv_x is None:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_pct)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_pct)
    elif cfg.pos_emb == "rope":   # cross-attn: rotate queries only
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_pct)

    new_cache = None
    if cache is not None and kv_x is None and "kp" in cache:
        # paged ring: same layout/maths as the contiguous branch below,
        # but the storage is a page pool indexed through the engine's
        # per-slot page table
        if page_table is None:
            raise ValueError("paged attention cache needs a page_table")
        new_cache, ck, cv, kv_pos = paged_kv_update(
            cache, page_table, k, v, cache_pos)
        kv_pos1 = kv_pos if q.shape[1] <= 8 else kv_pos[0]
        kv_valid = kv_pos1 >= 0
        out = attention(q, ck, cv, causal=causal, window=window,
                        q_offset=cache_pos, kv_positions=kv_pos1,
                        kv_valid=kv_valid, chunk=cfg.attn_chunk)
    elif cache is not None and kv_x is None:
        # Ring buffer of size C: token at absolute position p lives in slot
        # p % C. A "pos" track records each slot's absolute position
        # (-1 = empty) so masking stays exact after wrap-around. Writes
        # use a scatter over explicit slot indices (wrap-correct); when
        # more than C tokens arrive at once only the last C survive.
        C = cache["k"].shape[1]
        B_ = cache["k"].shape[0]
        S_new = k.shape[1]
        if S_new > C:               # static shapes: python-level branch
            k = k[:, -C:]
            v = v[:, -C:]
            cache_pos_eff = cache_pos + (S_new - C)
            S_eff = C
        else:
            cache_pos_eff = cache_pos
            S_eff = S_new
        offs = jnp.arange(S_eff, dtype=jnp.int32)
        upd = jnp.broadcast_to((cache_pos_eff + offs)[None, :],
                               (B_, S_eff))
        if S_eff == 1:
            # decode hot path: a 1-token write never wraps — use
            # dynamic_update_slice, which SPMD-partitions locally
            # (array-index scatters fall back to a select+all-reduce of
            # the whole cache per layer)
            slot0 = cache_pos_eff % C
            ck = jax.lax.dynamic_update_slice(cache["k"], k,
                                              (0, slot0, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], v,
                                              (0, slot0, 0, 0))
            kv_pos = jax.lax.dynamic_update_slice(cache["pos"], upd,
                                                  (0, slot0))
        else:
            slots = (cache_pos_eff + offs) % C                # unique
            ck = cache["k"].at[:, slots].set(k)
            cv = cache["v"].at[:, slots].set(v)
            kv_pos = cache["pos"].at[:, slots].set(upd)
        new_cache = {"k": ck, "v": cv, "pos": kv_pos}
        # decode (direct path): per-slot (B, C) position tracks so
        # continuous batching masks each slot's own history; prefill
        # (chunked path): rows share a clock — pass row 0
        if q.shape[1] <= 8:
            kv_pos1 = kv_pos
        else:
            kv_pos1 = kv_pos[0]
        kv_valid = kv_pos1 >= 0
        out = attention(q, ck, cv, causal=causal, window=window,
                        q_offset=cache_pos, kv_positions=kv_pos1,
                        kv_valid=kv_valid, chunk=cfg.attn_chunk,
                        kv_shard=cfg.decode_kv_shard or None)
    elif (kv_x is None and kv_positions is None
            and kops.flash_attention_engages(cfg.kernel_mode, q)):
        # Pallas flash kernel: the (B, H, S, S) scores stay in VMEM tiles
        out = kops.flash_attention(q, k, v, causal=causal, window=window,
                                   mode=cfg.kernel_mode)
    else:
        q_offset = 0
        out = attention(q, k, v, causal=causal, window=window,
                        q_offset=q_offset,
                        kv_positions=kv_positions, chunk=cfg.attn_chunk)

    o = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    if "bo" in p:
        o = o + p["bo"].astype(dt)
    return o, new_cache


def init_attn_cache(cfg, batch: int, cache_len: int, dtype):
    hd = cfg.resolved_head_dim
    G = cfg.n_kv_heads
    return {
        "k": jnp.zeros((batch, cache_len, G, hd), dtype),
        "v": jnp.zeros((batch, cache_len, G, hd), dtype),
        "pos": jnp.full((batch, cache_len), -1, jnp.int32),
    }


def init_paged_attn_cache(cfg, n_pages: int, page_size: int, dtype):
    """Shared page pool replacing the per-slot (B, C) ring rows: slots
    map logical ring pages to pool pages through the engine-held page
    table, so short requests only occupy the pages they touch."""
    hd = cfg.resolved_head_dim
    G = cfg.n_kv_heads
    return {
        "kp": jnp.zeros((n_pages, page_size, G, hd), dtype),
        "vp": jnp.zeros((n_pages, page_size, G, hd), dtype),
        "posp": jnp.full((n_pages, page_size), -1, jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg, d_ff: int | None = None):
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], (D, F), ("embed", "ff"), cfg.init_scale),
         "w_down": dense_init(ks[1], (F, D), ("ff", "embed"),
                              cfg.init_scale)}
    if cfg.mlp_gated:
        p["w_gate"] = dense_init(ks[2], (D, F), ("embed", "ff"),
                                 cfg.init_scale)
    if cfg.mlp_bias:
        p["b_up"] = zeros_init((F,), ("ff",))
        p["b_down"] = zeros_init((D,), (None,))
    return p


def _act(x, kind: str):
    return jax.nn.silu(x) if kind == "silu" else jax.nn.gelu(x)


def apply_mlp(p, x, cfg):
    dt = x.dtype
    h = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt))
    if "b_up" in p:
        h = h + p["b_up"].astype(dt)
    if "w_gate" in p:
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dt))
        h = _act(g, cfg.act) * h
    else:
        h = _act(h, cfg.act)
    o = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(dt))
    if "b_down" in p:
        o = o + p["b_down"].astype(dt)
    return o


# ---------------------------------------------------------------------------
# embeddings / head
# ---------------------------------------------------------------------------

def init_embedding(key, cfg):
    return {"table": dense_init(key, (cfg.vocab_size, cfg.d_model),
                                ("vocab", "embed"), 1.0)}


def embed(p, tokens, cfg):
    return p["table"][tokens].astype(_dt(cfg))


def init_lm_head(key, cfg):
    if cfg.tie_embeddings:
        return {}
    return {"w": dense_init(key, (cfg.d_model, cfg.vocab_size),
                            ("embed", "vocab"), cfg.init_scale)}


def lm_logits(head_p, emb_p, x, cfg):
    if cfg.tie_embeddings:
        w = emb_p["table"].astype(x.dtype).T
    else:
        w = head_p["w"].astype(x.dtype)
    logits = jnp.einsum("bsd,dv->bsv", x, w,
                        preferred_element_type=jnp.float32)
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits


def _dt(cfg):
    return jnp.dtype(cfg.compute_dtype)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def next_token_loss(logits, tokens, mask=None):
    """Cross-entropy of logits[:, :-1] predicting tokens[:, 1:].

    Fused formulation: nll = logsumexp(logits) − logits[target].
    log_softmax would materialize a second (B, S, V) f32 tensor — at
    train_4k × 128k vocab that is ~134 GB of extra HBM traffic per step
    (§Perf iteration: memory-term lever shared by every train pair)."""
    lg = logits[:, :-1].astype(jnp.float32)
    tgt = tokens[:, 1:]
    lse = jax.nn.logsumexp(lg, axis=-1)                       # (B, S-1)
    picked = jnp.take_along_axis(lg, tgt[..., None], -1)[..., 0]
    nll = lse - picked
    if mask is not None:
        m = mask[:, 1:].astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)
