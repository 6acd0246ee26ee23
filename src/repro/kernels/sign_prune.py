"""Per-neuron sign pruning of outer gradients — Pallas TPU kernel.

Table 6: pruning 50% of outer-gradient values before averaging costs
+0.39% perplexity, halving DiLoCo's (already rare) communication. The
fused kernel runs right before the cross-pod all-reduce: one VMEM pass
per row-tile performs (1) sign election by magnitude mass, (2) a
fixed-iteration bisection for the per-row magnitude threshold (a
quantile is not a single-pass operation; bisection over the count is,
and matches ``ref.sign_prune`` exactly), (3) the mask-and-zero.

Rows of a weight matrix = neurons; each tile holds ``block_rows``
complete rows so the row-reductions stay tile-local.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import compat


def _prune_kernel(x_ref, o_ref, *, keep_count, valid_cols, iters):
    x = x_ref[...].astype(jnp.float32)                        # (br, C)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = col < valid_cols
    x = jnp.where(valid, x, 0.0)
    mag = jnp.abs(x)

    pos = jnp.sum(jnp.where(x > 0, mag, 0.0), -1, keepdims=True)
    neg = jnp.sum(jnp.where(x < 0, mag, 0.0), -1, keepdims=True)
    elected = jnp.where(pos >= neg, 1.0, -1.0)
    agrees = jnp.sign(x) == elected

    lo = jnp.zeros((x.shape[0], 1), jnp.float32)
    hi = jnp.max(mag, axis=-1, keepdims=True) * (1.0 + 1e-6) + 1e-30

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((mag >= mid).astype(jnp.int32), -1, keepdims=True)
        too_many = cnt > keep_count
        return jnp.where(too_many, mid, lo), jnp.where(too_many, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    keep = agrees & (mag >= hi)
    o_ref[...] = jnp.where(keep, x_ref[...],
                           jnp.zeros_like(x_ref[...]))


def sign_prune(x, frac: float, *, block_rows: int = 64,
               iters: int = 26, interpret: bool = False):
    """x: (R, C) — per-row sign-consistent magnitude pruning.

    Matches ``ref.sign_prune`` bit-for-bit (same election, same
    bisection). Columns are padded to a multiple of 128 for lane
    alignment; padding never survives (masked to zero).
    """
    if frac <= 0:
        return x
    R, C = x.shape
    keep_count = max(int(round((1.0 - frac) * C)), 1)
    C_p = -(-C // 128) * 128
    br = min(block_rows, R)
    R_p = -(-R // br) * br
    xp = jnp.pad(x, ((0, R_p - R), (0, C_p - C)))

    out = pl.pallas_call(
        functools.partial(_prune_kernel, keep_count=keep_count,
                          valid_cols=C, iters=iters),
        grid=(R_p // br,),
        in_specs=[pl.BlockSpec((br, C_p), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, C_p), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R_p, C_p), x.dtype),
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel",)),
        name="sign_prune",
        interpret=interpret,
    )(xp)
    return out[:R, :C]
