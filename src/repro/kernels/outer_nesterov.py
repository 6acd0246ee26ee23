"""Fused outer Nesterov update — Pallas TPU kernel.

DiLoCo's outer step (Algorithm 1 line 14) touches every parameter once
per round: read (θ, Δ, b), write (θ, b). Fusing the momentum update and
the Nesterov-corrected parameter step into one VMEM pass makes the outer
step strictly bandwidth-bound at 3 reads + 2 writes — it runs in the
shadow of the cross-pod all-reduce that produced Δ.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import compat


def _nesterov_kernel(sc_ref, p_ref, d_ref, b_ref, p_out, b_out, *,
                     momentum):
    lr = sc_ref[0]
    p = p_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    b_new = momentum * b + d
    p_out[...] = (p - lr * (momentum * b_new + d)).astype(p_out.dtype)
    b_out[...] = b_new.astype(b_out.dtype)


def outer_nesterov(p, delta, buf, *, lr, momentum=0.9,
                   block_rows: int = 256, interpret: bool = False):
    """θ ← θ − lr·(μ·b_new + Δ), b_new = μ·b + Δ. Any-shape tensor.
    Returns (p_new, buf_new)."""
    shape, dtype = p.shape, p.dtype
    n = p.size
    cols = 128
    rows = -(-n // cols)
    pad = rows * cols - n

    def to2d(x):
        x = x.reshape(-1)
        if pad:
            x = jnp.pad(x, (0, pad))
        return x.reshape(rows, cols)

    p2, d2, b2 = map(to2d, (p, delta, buf))
    br = min(block_rows, rows)
    rows_p = -(-rows // br) * br
    if rows_p != rows:
        padr = rows_p - rows
        p2, d2, b2 = (jnp.pad(x, ((0, padr), (0, 0)))
                      for x in (p2, d2, b2))
    scalars = jnp.asarray([lr], jnp.float32)

    tile = pl.BlockSpec((br, cols), lambda i: (i, 0))
    outs = pl.pallas_call(
        functools.partial(_nesterov_kernel, momentum=momentum),
        grid=(rows_p // br,),
        in_specs=[pl.BlockSpec(memory_space=compat.SMEM),
                  tile, tile, tile],
        out_specs=(tile, tile),
        out_shape=(jax.ShapeDtypeStruct((rows_p, cols), dtype),
                   jax.ShapeDtypeStruct((rows_p, cols), buf.dtype)),
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel",)),
        name="outer_nesterov",
        interpret=interpret,
    )(scalars, p2, d2, b2)

    def back(x, dt):
        return x.reshape(-1)[:n].reshape(shape).astype(dt)

    return back(outs[0], dtype), back(outs[1], buf.dtype)
