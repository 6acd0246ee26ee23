"""Blocked online-softmax (flash) attention — Pallas TPU kernel.

DiLoCo's inner-loop compute at long context is dominated by attention;
this kernel is the TPU-native formulation: the (Sq, Skv) score matrix is
never materialized in HBM — q/k/v tiles stream HBM→VMEM per BlockSpec,
the MXU consumes (block_q × d)·(d × block_k) tiles, and the running
max/denominator live in VMEM scratch across the sequential kv grid axis.

Layout: q (B, H, Sq, d); k/v (B, G, Skv, d), GQA via H % G == 0 (the
kv-head index_map folds h -> h // rep so kv tiles are re-read, not
replicated, across the query heads of a group).

Grid: (B, H, n_qblocks, n_kvblocks) — first three parallel, the kv axis
"arbitrary" (sequential) so scratch accumulators carry across it.
Causal/sliding-window masking is applied per-tile from absolute
positions; fully-masked tiles short-circuit via ``pl.when``.

Supports self-attention (Sq == Skv, causal, optional window) — the
training/prefill hot path. Decode (Sq == 1) uses the jnp ref (a matvec —
memory-bound, no MXU win from a custom kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import compat

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 scale: float, causal: bool, window: int, block_q: int,
                 block_k: int, n_kv: int, kv_len: int, q_offset: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # absolute positions of this tile's queries and keys
    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # tile-level skip: causal => skip tiles strictly above the diagonal;
    # window => skip tiles entirely left of the window
    q_first = q_offset + iq * block_q
    q_last = q_first + block_q - 1
    k_first = ik * block_k
    k_last = k_first + block_k - 1
    live = True
    if causal:
        live = k_first <= q_last
    if window and window > 0:
        live = jnp.logical_and(live, k_last > q_first - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bq, bk)
        ok = k_pos < kv_len
        if causal:
            ok = jnp.logical_and(ok, k_pos <= q_pos)
        if window and window > 0:
            ok = jnp.logical_and(ok, k_pos > q_pos - window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[:, :1]                                 # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                                # (bq, bk)
        corr = jnp.exp(m_prev - m_new)                        # (bq, 1)
        l_ref[:, :1] = l_ref[:, :1] * corr + jnp.sum(p, 1, keepdims=True)
        m_ref[:, :1] = m_new
        v = v_ref[0, 0].astype(jnp.float32)                   # (bk, d)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _attn_kernel_fwd(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref,
                     m_ref, l_ref, *, scale, causal, window, block_q,
                     block_k, n_kv, kv_len, q_offset):
    """Forward that additionally writes the per-row logsumexp L = m +
    log(l) — the single residual the backward kernels need to
    recompute the probabilities on-chip."""
    _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                 scale=scale, causal=causal, window=window,
                 block_q=block_q, block_k=block_k, n_kv=n_kv,
                 kv_len=kv_len, q_offset=q_offset)

    @pl.when(pl.program_id(3) == n_kv - 1)
    def _store_lse():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        lse_ref[0, 0] = (m_ref[:, :1] + jnp.log(l))[:, 0]


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, acc_ref, *, scale, causal, window, block_q,
                   block_k, n_kv, kv_len, q_offset):
    """dq: grid (B, H, n_q, n_kv); kv sequential; p recomputed per tile
    from (q, k, L) — the (Sq, Skv) matrix never exists in HBM."""
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    live = True
    if causal:
        live = ik * block_k <= q_offset + iq * block_q + block_q - 1
    if window and window > 0:
        live = jnp.logical_and(
            live, ik * block_k + block_k - 1
            > q_offset + iq * block_q - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        ok = k_pos < kv_len
        if causal:
            ok = jnp.logical_and(ok, k_pos <= q_pos)
        if window and window > 0:
            ok = jnp.logical_and(ok, k_pos > q_pos - window)
        p = jnp.where(ok, jnp.exp(s - lse_ref[0, 0][:, None]), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None])
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(ik == n_kv - 1)
    def _finish():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    window, block_q, block_k, n_q, kv_len, q_offset):
    """dk/dv: grid (B, H, n_kv, n_q); q sequential; accumulates the
    per-query-head contributions (summed over the GQA group outside)."""
    ik, iq = pl.program_id(2), pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    live = True
    if causal:
        live = ik * block_k <= q_offset + iq * block_q + block_q - 1
    if window and window > 0:
        live = jnp.logical_and(
            live, ik * block_k + block_k - 1
            > q_offset + iq * block_q - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        ok = k_pos < kv_len
        if causal:
            ok = jnp.logical_and(ok, k_pos <= q_pos)
        if window and window > 0:
            ok = jnp.logical_and(ok, k_pos > q_pos - window)
        p = jnp.where(ok, jnp.exp(s - lse_ref[0, 0][:, None]), 0.0)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None])
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == n_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, q_offset: int = 0,
                    interpret: bool = False):
    """q: (B, H, Sq, d); k/v: (B, G, Skv, d). Returns (B, H, Sq, d).

    Sq/Skv are padded to block multiples internally; ``q_offset`` is the
    absolute position of q[0] (prefill continuation). d should be a
    multiple of 128 for MXU alignment on real TPUs (not enforced —
    interpret mode accepts anything).
    """
    B, H, Sq, d = q.shape
    _, G, Sk, _ = k.shape
    assert H % G == 0, (H, G)
    rep = H // G
    scale = d ** -0.5 if scale is None else scale

    bq = min(block_q, max(Sq, 8))
    bk = min(block_k, max(Sk, 8))
    Sq_p = -(-Sq // bq) * bq
    Sk_p = -(-Sk // bk) * bk
    if Sq_p != Sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sq_p - Sq), (0, 0)))
    if Sk_p != Sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Sk_p - Sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Sk_p - Sk), (0, 0)))
    n_q, n_kv = Sq_p // bq, Sk_p // bk

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        block_q=bq, block_k=bk, n_kv=n_kv, kv_len=Sk,
        q_offset=q_offset + (Sk - Sq if causal and Sq != Sk else 0))

    out = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b, h, i, j, rep=rep: (b, h // rep, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b, h, i, j, rep=rep: (b, h // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=compat.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary")),
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq]


# ---------------------------------------------------------------------------
# differentiable flash attention (fwd saves only (o, L); backward
# kernels recompute the probabilities on-chip — the (Sq, Skv) matrix
# never reaches HBM in either pass)
# ---------------------------------------------------------------------------

def _pad_to(x, dim, mult):
    size = x.shape[dim]
    pad = -size % mult
    if pad == 0:
        return x
    cfgp = [(0, 0)] * x.ndim
    cfgp[dim] = (0, pad)
    return jnp.pad(x, cfgp)


def _fwd_lse(q, k, v, *, causal, window, scale, bq, bk, q_offset,
             interpret):
    B, H, Sq, d = q.shape
    _, G, Sk, _ = k.shape
    rep = H // G
    q = _pad_to(q, 2, bq)
    k = _pad_to(k, 2, bk)
    v = _pad_to(v, 2, bk)
    Sq_p, Sk_p = q.shape[2], k.shape[2]
    n_q, n_kv = Sq_p // bq, Sk_p // bk
    kernel = functools.partial(
        _attn_kernel_fwd, scale=scale, causal=causal, window=window,
        block_q=bq, block_k=bk, n_kv=n_kv, kv_len=Sk,
        q_offset=q_offset + (Sk - Sq if causal and Sq != Sk else 0))
    o, lse = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b, h, i, j, rep=rep: (b, h // rep, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b, h, i, j, rep=rep: (b, h // rep, j, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, h, i)),
        ),
        out_shape=(jax.ShapeDtypeStruct((B, H, Sq_p, d), q.dtype),
                   jax.ShapeDtypeStruct((B, H, Sq_p), jnp.float32)),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=compat.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary")),
        name="flash_attention_fwd",
        interpret=interpret,
    )(q, k, v)
    return o[:, :, :Sq], lse[:, :, :Sq]


def _bwd(res, do, *, causal, window, scale, bq, bk, q_offset, interpret):
    q, k, v, o, lse = res
    B, H, Sq, d = q.shape
    _, G, Sk, _ = k.shape
    rep = H // G
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                  # (B,H,Sq)
    qp = _pad_to(q, 2, bq)
    dop = _pad_to(do, 2, bq)
    lsep = _pad_to(lse, 2, bq)
    dltp = _pad_to(delta, 2, bq)
    kp = _pad_to(k, 2, bk)
    vp = _pad_to(v, 2, bk)
    Sq_p, Sk_p = qp.shape[2], kp.shape[2]
    n_q, n_kv = Sq_p // bq, Sk_p // bk
    off = q_offset + (Sk - Sq if causal and Sq != Sk else 0)

    common = dict(scale=scale, causal=causal, window=window, block_q=bq,
                  block_k=bk, kv_len=Sk, q_offset=off)
    qspec = pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0))
    kspec = pl.BlockSpec((1, 1, bk, d),
                         lambda b, h, i, j, rep=rep: (b, h // rep, j, 0))
    rowspec = pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, h, i))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, n_kv=n_kv, **common),
        grid=(B, H, n_q, n_kv),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=compat.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary")),
        name="flash_attention_bwd_dq",
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, dltp)[:, :, :Sq]

    # dk/dv per QUERY head (grid kv-parallel, q sequential), then summed
    # over each GQA group's rep query heads
    kq = pl.BlockSpec((1, 1, bk, d),
                      lambda b, h, j, i, rep=rep: (b, h // rep, j, 0))
    qq = pl.BlockSpec((1, 1, bq, d), lambda b, h, j, i: (b, h, i, 0))
    rq = pl.BlockSpec((1, 1, bq), lambda b, h, j, i: (b, h, i))
    okv = pl.BlockSpec((1, 1, bk, d), lambda b, h, j, i: (b, h, j, 0))
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, n_q=n_q, **common),
        grid=(B, H, n_kv, n_q),
        in_specs=[qq, kq, kq, qq, rq, rq],
        out_specs=(okv, okv),
        out_shape=(jax.ShapeDtypeStruct((B, H, Sk_p, d), k.dtype),
                   jax.ShapeDtypeStruct((B, H, Sk_p, d), v.dtype)),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=compat.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary")),
        name="flash_attention_bwd_dkv",
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, dltp)
    dk = dk_h[:, :, :Sk].reshape(B, G, rep, Sk, d).sum(2).astype(k.dtype)
    dv = dv_h[:, :, :Sk].reshape(B, G, rep, Sk, d).sum(2).astype(v.dtype)
    return dq, dk, dv


def make_flash_attention_vjp(*, causal: bool = True, window: int = 0,
                             scale: float | None = None,
                             block_q: int = 128, block_k: int = 128,
                             q_offset: int = 0,
                             interpret: bool = False):
    """Differentiable flash attention: q (B,H,Sq,d), k/v (B,G,Skv,d).

    Forward saves only (q, k, v, o, logsumexp); both backward kernels
    recompute probabilities tile-by-tile in VMEM (flash backward)."""

    @jax.custom_vjp
    def fa(q, k, v):
        sc = (q.shape[-1] ** -0.5) if scale is None else scale
        bq = min(block_q, max(q.shape[2], 8))
        bk = min(block_k, max(k.shape[2], 8))
        o, _ = _fwd_lse(q, k, v, causal=causal, window=window, scale=sc,
                        bq=bq, bk=bk, q_offset=q_offset,
                        interpret=interpret)
        return o

    def fwd(q, k, v):
        sc = (q.shape[-1] ** -0.5) if scale is None else scale
        bq = min(block_q, max(q.shape[2], 8))
        bk = min(block_k, max(k.shape[2], 8))
        o, lse = _fwd_lse(q, k, v, causal=causal, window=window,
                          scale=sc, bq=bq, bk=bk, q_offset=q_offset,
                          interpret=interpret)
        return o, (q, k, v, o, lse)

    def bwd(res, do):
        q = res[0]
        sc = (q.shape[-1] ** -0.5) if scale is None else scale
        bq = min(block_q, max(q.shape[2], 8))
        bk = min(block_k, max(res[1].shape[2], 8))
        return _bwd(res, do, causal=causal, window=window, scale=sc,
                    bq=bq, bk=bk, q_offset=q_offset, interpret=interpret)

    fa.defvjp(fwd, bwd)
    return fa
