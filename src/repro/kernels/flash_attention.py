"""Blocked online-softmax (flash) attention — Pallas TPU kernel.

DiLoCo's inner-loop compute at long context is dominated by attention;
this kernel is the TPU-native formulation: the (Sq, Skv) score matrix is
never materialized in HBM — q/k/v tiles stream HBM→VMEM per BlockSpec,
the MXU consumes (block_q × d)·(d × block_k) tiles, and the running
max/denominator live in VMEM scratch across the sequential kv grid axis.

Layout: q (B, H, Sq, d); k/v (B, G, Skv, d), GQA via H % G == 0 (the
kv-head index_map folds h -> h // rep so kv tiles are re-read, not
replicated, across the query heads of a group). The per-row logsumexp
and the backward's ``delta`` ride as (B, H, Sq, 1) columns: a
(1, 1, block_q, 1) block meets the TPU's tiling rule at any head count.

Grid: (B, H, n_qblocks, n_kvblocks) — first three parallel, the kv axis
"arbitrary" (sequential) so scratch accumulators carry across it.
Causal/sliding-window masks are built only on the tiles that cut the
diagonal or the window's edge; tiles no query sees are skipped
(``pl.when``).

Arithmetic: the matmuls take their operands in ``mxu_dtype`` and
accumulate in f32; the scale, the running max, the denominator and the
logsumexp stay f32.

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
# q/kv block sizes the kernel picks from, largest first (multiples of
# the TPU's 128-lane tile): on a v5e one masked (1024, 1024) tile beat
# (512, 512) tiles with the causal skip, and walking the masked tile in
# 128-512 pieces, at (8, 16, 1024, 64) and (4, 12, 1024, 128), forward
# and backward
BLOCKS = (1024, 512, 256, 128)

NT = ((1,), (1,))          # a · bᵀ
NN = ((1,), (0,))          # a · b
TN = ((0,), (0,))          # aᵀ · b


def block_size(seq_len: int) -> int | None:
    """The block the kernel tiles a sequence of ``seq_len`` with: the
    largest of ``BLOCKS`` that divides it, the whole sequence where it
    is at most one lane tile, else None (the kernel would pad)."""
    for b in BLOCKS:
        if seq_len % b == 0:
            return b
    return seq_len if seq_len <= BLOCKS[-1] else None


def mxu_dtype(dtype, interpret: bool):
    """Operand dtype of the kernel's matmuls. On the chip, what XLA's
    default precision makes of an f32 operand there: one bf16 MXU pass
    with f32 accumulation, unless the program raised
    ``jax_default_matmul_precision`` (then f32 operands). In interpret
    mode the operands stay as given, and the backend's own dot treats
    them at its default precision."""
    dtype = jnp.dtype(dtype)
    if interpret or dtype != jnp.float32:
        return dtype
    if jax.config.jax_default_matmul_precision in (
            None, "default", "bfloat16", "fastest"):
        return jnp.dtype(jnp.bfloat16)
    return dtype


def _dot(a, b, dims, mxu):
    return jax.lax.dot_general(a.astype(mxu), b.astype(mxu), (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _visit(body, iq, ik, *, block_q, block_k, causal, window, kv_len,
           kv_pad, q_offset):
    """Run ``body(ok)`` on tile (iq, ik) where some query sees some key
    of it: ``ok`` is the (block_q, block_k) visibility mask on tiles that
    cut the causal diagonal, the window's edge or the padded keys, and
    None on tiles every query sees whole."""
    q_first = q_offset + iq * block_q
    q_last = q_first + block_q - 1
    k_first = ik * block_k
    k_last = k_first + block_k - 1
    live = True
    edges = [True] if kv_pad else []
    if causal:
        live = k_first <= q_last
        edges.append(k_last > q_first)
    if window and window > 0:
        live = jnp.logical_and(live, k_last > q_first - window)
        edges.append(k_first <= q_last - window)

    def masked():
        shape = (block_q, block_k)
        q_pos = q_first + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        k_pos = k_first + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        ok = k_pos < kv_len
        if causal:
            ok = jnp.logical_and(ok, k_pos <= q_pos)
        if window and window > 0:
            ok = jnp.logical_and(ok, k_pos > q_pos - window)
        body(ok)

    if not edges:
        pl.when(live)(lambda: body(None))
    elif kv_pad:
        pl.when(live)(masked)
    else:
        edge = functools.reduce(jnp.logical_or, edges)
        pl.when(jnp.logical_and(live, edge))(masked)
        pl.when(jnp.logical_and(live, jnp.logical_not(edge)))(
            lambda: body(None))


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale, n_kv, mxu, tile):
    """o (and, where an lse output is given, the per-row logsumexp
    L = m + log(l), the one residual the backward kernels need)."""
    if len(refs) == 5:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    else:
        (o_ref, acc_ref, m_ref, l_ref), lse_ref = refs, None
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def body(ok):
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, d)
        s = _dot(q, k_ref[0, 0], NT, mxu)                    # (bq, bk)
        if ok is not None:
            s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[:, :1]                                 # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = l_ref[:, :1] * corr + jnp.sum(p, 1, keepdims=True)
        m_ref[:, :1] = m_new
        acc_ref[...] = acc_ref[...] * corr + _dot(p, v_ref[0, 0], NN, mxu)

    _visit(body, iq, ik, **tile)

    @pl.when(ik == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0, 0] = m_ref[:, :1] + jnp.log(l)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, acc_ref, *, scale, n_kv, mxu, tile):
    """dq: grid (B, H, n_q, n_kv); kv sequential; p recomputed per tile
    from (q, k, L) — the (Sq, Skv) matrix never exists in HBM."""
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(ok):
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0]
        p = jnp.exp(_dot(q, k, NT, mxu) - lse_ref[0, 0])
        if ok is not None:
            p = jnp.where(ok, p, 0.0)
        dp = _dot(do_ref[0, 0], v_ref[0, 0], NT, mxu)
        ds = p * (dp - delta_ref[0, 0])
        acc_ref[...] += _dot(ds, k, NN, mxu)

    _visit(body, iq, ik, **tile)

    @pl.when(ik == n_kv - 1)
    def _finish():
        dq_ref[0, 0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, n_q, mxu,
                    tile):
    """dk/dv: grid (B, H, n_kv, n_q); q sequential; accumulates the
    per-query-head contributions (summed over the GQA group outside)."""
    ik, iq = pl.program_id(2), pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(ok):
        q = q_ref[0, 0].astype(jnp.float32) * scale
        do = do_ref[0, 0]
        p = jnp.exp(_dot(q, k_ref[0, 0], NT, mxu) - lse_ref[0, 0])
        if ok is not None:
            p = jnp.where(ok, p, 0.0)
        dv_acc[...] += _dot(p, do, TN, mxu)
        dp = _dot(do, v_ref[0, 0], NT, mxu)
        ds = p * (dp - delta_ref[0, 0])
        dk_acc[...] += _dot(ds, q, TN, mxu)

    _visit(body, iq, ik, **tile)

    @pl.when(iq == n_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _pad_to(x, dim, mult):
    pad = -x.shape[dim] % mult
    if pad == 0:
        return x
    cfgp = [(0, 0)] * x.ndim
    cfgp[dim] = (0, pad)
    return jnp.pad(x, cfgp)


def _call(kernel, grid, in_specs, out_specs, out_shape, scratch, name,
          interpret):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=compat.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary")),
        name=name, interpret=interpret)


def _plan(q, k, *, causal, window, bq, bk, q_offset):
    """Block counts and the tile settings ``_visit`` takes."""
    Sq, Sk = q.shape[2], k.shape[2]
    n_q, n_kv = -(-Sq // bq), -(-Sk // bk)
    tile = dict(block_q=bq, block_k=bk, causal=causal, window=window,
                kv_len=Sk, kv_pad=n_kv * bk != Sk,
                q_offset=q_offset + (Sk - Sq if causal and Sq != Sk else 0))
    return n_q, n_kv, tile


def _forward(q, k, v, *, causal, window, scale, bq, bk, q_offset, mxu,
             interpret, lse: bool, name: str):
    B, H, Sq, d = q.shape
    rep = H // k.shape[1]
    n_q, n_kv, tile = _plan(q, k, causal=causal, window=window, bq=bq,
                            bk=bk, q_offset=q_offset)
    qspec = pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0))
    kvspec = pl.BlockSpec((1, 1, bk, d),
                          lambda b, h, i, j: (b, h // rep, j, 0))
    out_specs = [qspec]
    out_shape = [jax.ShapeDtypeStruct((B, H, n_q * bq, d), q.dtype)]
    if lse:
        out_specs.append(pl.BlockSpec((1, 1, bq, 1),
                                      lambda b, h, i, j: (b, h, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, H, n_q * bq, 1),
                                              jnp.float32))
    outs = _call(
        functools.partial(_fwd_kernel, scale=scale, n_kv=n_kv, mxu=mxu,
                          tile=tile),
        (B, H, n_q, n_kv), [qspec, kvspec, kvspec], out_specs, out_shape,
        [pltpu.VMEM((bq, d), jnp.float32),
         pltpu.VMEM((bq, 128), jnp.float32),
         pltpu.VMEM((bq, 128), jnp.float32)], name, interpret,
    )(_pad_to(q, 2, bq), _pad_to(k, 2, bk), _pad_to(v, 2, bk))
    outs = [x[:, :, :Sq] for x in outs]
    return tuple(outs) if lse else outs[0]


def _backward(res, do, *, causal, window, scale, bq, bk, q_offset, mxu,
              interpret):
    q, k, v, o, lse = res
    B, H, Sq, d = q.shape
    _, G, Sk, _ = k.shape
    rep = H // G
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                   # (B,H,Sq,1)
    n_q, n_kv, tile = _plan(q, k, causal=causal, window=window, bq=bq,
                            bk=bk, q_offset=q_offset)
    args = (_pad_to(q, 2, bq), _pad_to(k, 2, bk), _pad_to(v, 2, bk),
            _pad_to(do, 2, bq), _pad_to(lse, 2, bq), _pad_to(delta, 2, bq))
    common = dict(scale=scale, mxu=mxu, tile=tile)

    qspec = pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0))
    kspec = pl.BlockSpec((1, 1, bk, d),
                         lambda b, h, i, j: (b, h // rep, j, 0))
    rowspec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0))
    dq = _call(
        functools.partial(_bwd_dq_kernel, n_kv=n_kv, **common),
        (B, H, n_q, n_kv), [qspec, kspec, kspec, qspec, rowspec, rowspec],
        qspec, jax.ShapeDtypeStruct((B, H, n_q * bq, d), q.dtype),
        [pltpu.VMEM((bq, d), jnp.float32)], "flash_attention_bwd_dq",
        interpret)(*args)[:, :, :Sq]

    # dk/dv per QUERY head (grid kv-parallel, q sequential), then summed
    # over each GQA group's rep query heads (in f32 where rep > 1)
    qq = pl.BlockSpec((1, 1, bq, d), lambda b, h, j, i: (b, h, i, 0))
    kq = pl.BlockSpec((1, 1, bk, d), lambda b, h, j, i: (b, h // rep, j, 0))
    rq = pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0))
    okv = pl.BlockSpec((1, 1, bk, d), lambda b, h, j, i: (b, h, j, 0))
    acc_dt = (k.dtype, v.dtype) if rep == 1 else (jnp.float32,) * 2
    dk, dv = _call(
        functools.partial(_bwd_dkv_kernel, n_q=n_q, **common),
        (B, H, n_kv, n_q), [qq, kq, kq, qq, rq, rq], [okv, okv],
        [jax.ShapeDtypeStruct((B, H, n_kv * bk, d), dt) for dt in acc_dt],
        [pltpu.VMEM((bk, d), jnp.float32), pltpu.VMEM((bk, d), jnp.float32)],
        "flash_attention_bwd_dkv", interpret)(*args)
    dk, dv = dk[:, :, :Sk], dv[:, :, :Sk]
    if rep > 1:
        dk = dk.reshape(B, G, rep, Sk, d).sum(2)
        dv = dv.reshape(B, G, rep, Sk, d).sum(2)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


def _settings(q, k, *, causal, window, scale, block_q, block_k, q_offset,
              interpret):
    """Static settings of one call: blocks from the lengths (where the
    caller names none), the scale, the MXU operand dtype."""
    assert q.shape[1] % k.shape[1] == 0, (q.shape, k.shape)
    Sq, Sk = q.shape[2], k.shape[2]
    bq = block_q or block_size(Sq) or BLOCKS[-1]
    bk = block_k or block_size(Sk) or BLOCKS[-1]
    return dict(causal=causal, window=window,
                scale=q.shape[-1] ** -0.5 if scale is None else scale,
                bq=min(bq, max(Sq, 8)), bk=min(bk, max(Sk, 8)),
                q_offset=q_offset, mxu=mxu_dtype(q.dtype, interpret),
                interpret=interpret)


def flash_attention(q, k, v, **kw):
    """q: (B, H, Sq, d); k/v: (B, G, Skv, d). Returns (B, H, Sq, d).

    The forward of ``make_flash_attention_vjp(**kw)``: Sq/Skv are padded
    to block multiples internally; blocks default to ``block_size`` of
    each length.
    """
    return make_flash_attention_vjp(**kw)(q, k, v)


# ---------------------------------------------------------------------------
# differentiable flash attention (fwd saves only (o, L); backward
# kernels recompute the probabilities on-chip — the (Sq, Skv) matrix
# never reaches HBM in either pass)
# ---------------------------------------------------------------------------

def make_flash_attention_vjp(*, causal: bool = True, window: int = 0,
                             scale: float | None = None,
                             block_q: int | None = None,
                             block_k: int | None = None,
                             q_offset: int = 0,
                             interpret: bool = False):
    """Differentiable flash attention: q (B,H,Sq,d), k/v (B,G,Skv,d).

    ``q_offset`` is the absolute position of q[0] (prefill
    continuation). Forward saves only (q, k, v, o, logsumexp); both
    backward kernels recompute probabilities tile-by-tile in VMEM (flash
    backward)."""
    settings = functools.partial(
        _settings, causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k, q_offset=q_offset,
        interpret=interpret)

    @jax.custom_vjp
    def fa(q, k, v):
        return _forward(q, k, v, lse=False, name="flash_attention",
                        **settings(q, k))

    def fwd(q, k, v):
        o, lse = _forward(q, k, v, lse=True, name="flash_attention_fwd",
                          **settings(q, k))
        return o, (q, k, v, o, lse)

    def bwd(res, do):
        return _backward(res, do, **settings(res[0], res[1]))

    fa.defvjp(fwd, bwd)
    return fa
