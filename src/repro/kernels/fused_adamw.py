"""Fused AdamW update — Pallas TPU kernels.

The inner optimizer is DiLoCo's per-step memory bill: each AdamW step
reads (p, g, m, v) and writes (p, m, v) — 7 tensor-sized HBM transfers
that XLA sometimes splits across fusions. These kernels perform the
whole update in ONE VMEM pass per tile: a (block_r, 128)-tile of each
operand streams in, the update math runs on the VPU in f32, and the
outputs stream out. Bandwidth-optimal: bytes moved = the operand reads
plus the result writes, nothing else.

Two variants share one tiling scaffold:

  * ``fused_adamw``       — uniform precision: reads (p, g, m, v),
    writes (p, m, v) at their own dtypes;
  * ``fused_adamw_mixed`` — mixed precision (see optim/precision.py):
    reads the low-precision grads/moments and the high-precision master
    params, writes the updated master AND the ``param_dtype`` working
    copy in the same pass, so the working-copy cast XLA would otherwise
    materialize as a separate HBM round trip is fused away. Bytes moved
    (bf16 state, f32 master): 2+2+2+4 reads, 2+2+2+4 writes per element
    vs the all-f32 kernel's 16/12.

Scalars (lr and the bias corrections c1 = 1-β1^t, c2 = 1-β2^t) arrive as
a small SMEM-resident array so the same compiled kernel serves every
step of the schedule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import compat


def _to_blocks(tensors, block_rows: int):
    """Flatten same-shape tensors to a shared padded (rows_p, 128)
    layout. Returns (tensors_2d, rows_p, block_rows, n_elems)."""
    n = tensors[0].size
    cols = 128
    rows = -(-n // cols)
    br = min(block_rows, rows)
    rows_p = -(-rows // br) * br

    def to2d(x):
        x = x.reshape(-1)
        if rows_p * cols != n:
            x = jnp.pad(x, (0, rows_p * cols - n))
        return x.reshape(rows_p, cols)

    return [to2d(x) for x in tensors], rows_p, br, n


def _call_blocked(kernel, name, tensors_2d, rows_p, br, out_dtypes,
                  scalars, interpret):
    """Run ``kernel`` (named ``name`` in the compiled program) over the
    (rows_p, 128) layout with the shared SMEM-scalars +
    one-tile-per-operand grid spec."""
    tile = pl.BlockSpec((br, 128), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(rows_p // br,),
        in_specs=[pl.BlockSpec(memory_space=compat.SMEM)]
        + [tile] * len(tensors_2d),
        out_specs=(tile,) * len(out_dtypes),
        out_shape=tuple(jax.ShapeDtypeStruct((rows_p, 128), d)
                        for d in out_dtypes),
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel",)),
        name=name,
        interpret=interpret,
    )(scalars, *tensors_2d)


def _scalars(lr, c1, c2):
    return jnp.stack([jnp.asarray(lr, jnp.float32),
                      jnp.asarray(c1, jnp.float32),
                      jnp.asarray(c2, jnp.float32)])


def _adamw_kernel(sc_ref, p_ref, g_ref, m_ref, v_ref,
                  p_out, m_out, v_out, *, b1, b2, eps, weight_decay):
    lr, c1, c2 = sc_ref[0], sc_ref[1], sc_ref[2]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * g * g
    step = (m_new / c1) / (jnp.sqrt(v_new / c2) + eps) + weight_decay * p
    p_out[...] = (p - lr * step).astype(p_out.dtype)
    m_out[...] = m_new.astype(m_out.dtype)
    v_out[...] = v_new.astype(v_out.dtype)


def fused_adamw(p, g, m, v, *, lr, c1, c2, b1=0.9, b2=0.95, eps=1e-8,
                weight_decay=0.1, block_rows: int = 256,
                interpret: bool = False):
    """One AdamW step on a single tensor of any shape.

    lr/c1/c2 may be traced scalars. Returns (p_new, m_new, v_new).
    """
    shape = p.shape
    out_dtypes = (p.dtype, m.dtype, v.dtype)
    t2d, rows_p, br, n = _to_blocks((p, g, m, v), block_rows)
    kernel = functools.partial(_adamw_kernel, b1=b1, b2=b2, eps=eps,
                               weight_decay=weight_decay)
    outs = _call_blocked(kernel, "fused_adamw", t2d, rows_p, br,
                         out_dtypes, _scalars(lr, c1, c2), interpret)
    return tuple(o.reshape(-1)[:n].reshape(shape).astype(d)
                 for o, d in zip(outs, out_dtypes))


# ---------------------------------------------------------------------------
# mixed-precision variant: bf16 replica state + higher-precision master
# ---------------------------------------------------------------------------

def _adamw_mixed_kernel(sc_ref, g_ref, m_ref, v_ref, w_ref,
                        p_out, m_out, v_out, w_out,
                        *, b1, b2, eps, weight_decay):
    lr, c1, c2 = sc_ref[0], sc_ref[1], sc_ref[2]
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)          # master — authoritative
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * g * g
    step = (m_new / c1) / (jnp.sqrt(v_new / c2) + eps) + weight_decay * w
    w_new = w - lr * step
    p_out[...] = w_new.astype(p_out.dtype)      # bf16 working copy
    m_out[...] = m_new.astype(m_out.dtype)
    v_out[...] = v_new.astype(v_out.dtype)
    w_out[...] = w_new.astype(w_out.dtype)


def fused_adamw_mixed(g, m, v, master, *, lr, c1, c2, b1=0.9, b2=0.95,
                      eps=1e-8, weight_decay=0.1,
                      param_dtype=jnp.bfloat16, block_rows: int = 256,
                      interpret: bool = False):
    """One mixed-precision AdamW step on a single tensor of any shape
    (see the module docstring). lr/c1/c2 may be traced scalars.
    Returns (p_working, m_new, v_new, master_new).
    """
    shape = master.shape
    out_dtypes = (jnp.dtype(param_dtype), m.dtype, v.dtype, master.dtype)
    t2d, rows_p, br, n = _to_blocks((g, m, v, master), block_rows)
    kernel = functools.partial(_adamw_mixed_kernel, b1=b1, b2=b2,
                               eps=eps, weight_decay=weight_decay)
    outs = _call_blocked(kernel, "fused_adamw_mixed", t2d, rows_p, br,
                         out_dtypes, _scalars(lr, c1, c2), interpret)
    return tuple(o.reshape(-1)[:n].reshape(shape).astype(d)
                 for o, d in zip(outs, out_dtypes))
