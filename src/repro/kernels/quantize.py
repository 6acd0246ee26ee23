"""Low-precision outer-gradient transport — Pallas TPU kernels.

Streaming DiLoCo sends each fragment's outer gradient through the
cross-pod collective in low precision. On hardware that is a real
pack/unpack around the all-reduce; in this repo's simulated transport
the gradient takes a quantize→dequantize round trip before the in-graph
replica average, so the *numerics* of the low-precision collective are
exact while the bytes saved are accounted analytically.

Kernels, all on the (blocks, 128) layout every optimizer kernel
in this package uses (one f32 scale per 128-element block):

  * ``quantize_int4``   — codes int8 in [-7, 7] + per-block f32 scale
                          (the wire format: 0.5 B/elem + 4 B/block);
  * ``dequantize_int4`` — codes × scale back to f32;
  * ``pack_int4``       — nibble-pack (R, 128) codes into (R, 64) wire
                          bytes (two 4-bit two's-complement codes per
                          int8 byte; flattening the output row-major
                          gives bytes in element order);
  * ``unpack_int4``     — the exact inverse, with sign extension;
  * ``fake_quant``      — the fused round trip in ONE VMEM pass (codes
                          and scales never touch HBM), used on the
                          simulated transport path. Also serves bf16
                          (cast down/up in-register);
  * ``quantize_pack_int4``       — the fused SENDER pass: f32 blocks →
                          (R, 64) packed wire bytes + (R, 1) scales +
                          the dequantized local payload, all in ONE
                          VMEM pass (the intermediate unpacked codes
                          never touch HBM — previously quantize then
                          pack then dequantize, three launches);
  * ``unpack_dequantize_int4``   — the fused RECEIVER pass: wire bytes
                          × scales → f32 values, one launch;
  * ``unpack_dequantize_reduce`` — the fused receiver pass over every
                          replica at once: (k, R, 64) wire bytes ×
                          (k, R, 1) scales × (k,) mask → the masked
                          sum (R, 128), decode and reduction in one
                          launch (the deferred streaming consumer).

The jnp oracles live in ``ref.py``; ``ops.quant_roundtrip`` (and the
packed-wire codecs ``ops.wire_encode``/``ops.wire_decode``) dispatch
between them and these kernels via ``kernel_mode``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import compat
from .fused_adamw import _to_blocks
from .ref import INT4_LEVELS, INV_INT4_LEVELS


def _pad2d(x, block_rows):
    """Flatten any-shape x to a padded (rows_p, 128) f32 layout —
    the shared block scaffold of ``fused_adamw._to_blocks``.
    Returns (x2d, rows_p, br, n)."""
    (x2d,), rows_p, br, n = _to_blocks(
        (x.astype(jnp.float32),), block_rows)
    return x2d, rows_p, br, n


def _quantize_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = amax * INV_INT4_LEVELS
    q = jnp.round(x / jnp.where(scale > 0, scale, 1.0))
    q_ref[...] = jnp.clip(q, -INT4_LEVELS, INT4_LEVELS).astype(q_ref.dtype)
    s_ref[...] = scale.astype(s_ref.dtype)


def _dequantize_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = (q_ref[...].astype(jnp.float32)
                  * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _pack_kernel(c_ref, o_ref):
    # (br, 128) codes -> (br, 64) bytes: lane pairs (2j, 2j+1) fold into
    # byte j, so the row-major flatten of the output is in element order
    c = c_ref[...].astype(jnp.int32) & 0xF
    pairs = c.reshape(c.shape[0], -1, 2)
    o_ref[...] = (pairs[..., 0] | (pairs[..., 1] << 4)).astype(jnp.int8)


def _unpack_kernel(p_ref, o_ref):
    p = p_ref[...].astype(jnp.int32) & 0xFF
    nib = jnp.stack([p & 0xF, (p >> 4) & 0xF], axis=-1)
    nib = nib.reshape(nib.shape[0], -1)
    # 4-bit two's complement sign extension
    o_ref[...] = ((nib ^ 8) - 8).astype(jnp.int8)


def _quantize_pack_kernel(x_ref, p_ref, s_ref, l_ref):
    # one VMEM pass: block scale, int4 codes, nibble-pack AND the
    # sender's dequantized local payload — the (br, 128) code tile
    # lives only in registers/VMEM, never in HBM
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = amax * INV_INT4_LEVELS
    q = jnp.clip(jnp.round(x / jnp.where(scale > 0, scale, 1.0)),
                 -INT4_LEVELS, INT4_LEVELS)
    c = q.astype(jnp.int32) & 0xF
    pairs = c.reshape(c.shape[0], -1, 2)
    p_ref[...] = (pairs[..., 0] | (pairs[..., 1] << 4)).astype(jnp.int8)
    s_ref[...] = scale.astype(s_ref.dtype)
    l_ref[...] = (q * scale).astype(l_ref.dtype)


def _unpack_dequant_kernel(p_ref, s_ref, o_ref):
    p = p_ref[...].astype(jnp.int32) & 0xFF
    nib = jnp.stack([p & 0xF, (p >> 4) & 0xF], axis=-1)
    nib = nib.reshape(nib.shape[0], -1)
    codes = ((nib ^ 8) - 8).astype(jnp.float32)
    o_ref[...] = (codes * s_ref[...].astype(jnp.float32)
                  ).astype(o_ref.dtype)


def _unpack_dequant_reduce_kernel(p_ref, s_ref, m_ref, o_ref):
    # (k, br, 64) wire bytes -> masked sum over k, decoded in-register
    p = p_ref[...].astype(jnp.int32) & 0xFF
    nib = jnp.stack([p & 0xF, (p >> 4) & 0xF], axis=-1)
    nib = nib.reshape(nib.shape[0], nib.shape[1], -1)
    codes = ((nib ^ 8) - 8).astype(jnp.float32)
    vals = codes * s_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.sum(m_ref[...].astype(jnp.float32) * vals,
                         axis=0).astype(o_ref.dtype)


def _fake_quant_kernel(x_ref, o_ref, *, dtype):
    x = x_ref[...].astype(jnp.float32)
    if dtype == "bfloat16":
        o_ref[...] = x.astype(jnp.bfloat16).astype(o_ref.dtype)
        return
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = amax * INV_INT4_LEVELS
    q = jnp.clip(jnp.round(x / jnp.where(scale > 0, scale, 1.0)),
                 -INT4_LEVELS, INT4_LEVELS)
    o_ref[...] = (q * scale).astype(o_ref.dtype)


def quantize_int4(x2d, *, block_rows: int = 256, interpret: bool = False):
    """x2d: (R, 128) f32 blocks -> (codes (R, 128) int8, scales (R, 1)
    f32). Rows must already be padded to the block layout."""
    rows, cols = x2d.shape
    br = min(block_rows, rows)
    rows_p = -(-rows // br) * br
    if rows_p != rows:
        x2d = jnp.pad(x2d, ((0, rows_p - rows), (0, 0)))
    tile = pl.BlockSpec((br, cols), lambda i: (i, 0))
    stile = pl.BlockSpec((br, 1), lambda i: (i, 0))
    codes, scales = pl.pallas_call(
        _quantize_kernel,
        grid=(rows_p // br,),
        in_specs=[tile],
        out_specs=(tile, stile),
        out_shape=(jax.ShapeDtypeStruct((rows_p, cols), jnp.int8),
                   jax.ShapeDtypeStruct((rows_p, 1), jnp.float32)),
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel",)),
        name="quantize_int4",
        interpret=interpret,
    )(x2d)
    return codes[:rows], scales[:rows]


def dequantize_int4(codes, scales, *, block_rows: int = 256,
                    interpret: bool = False):
    """(R, 128) int8 codes × (R, 1) f32 scales -> (R, 128) f32."""
    rows, cols = codes.shape
    br = min(block_rows, rows)
    rows_p = -(-rows // br) * br
    if rows_p != rows:
        codes = jnp.pad(codes, ((0, rows_p - rows), (0, 0)))
        scales = jnp.pad(scales, ((0, rows_p - rows), (0, 0)))
    tile = pl.BlockSpec((br, cols), lambda i: (i, 0))
    stile = pl.BlockSpec((br, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        _dequantize_kernel,
        grid=(rows_p // br,),
        in_specs=[tile, stile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows_p, cols), jnp.float32),
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel",)),
        name="dequantize_int4",
        interpret=interpret,
    )(codes, scales)
    return out[:rows]


def pack_int4(codes, *, block_rows: int = 256, interpret: bool = False):
    """Nibble-pack (R, 128) int8 codes -> (R, 64) int8 wire bytes (two
    4-bit two's-complement codes per byte; row-major flatten of the
    output is element-ordered — ``ref.pack_int4`` on the flat codes)."""
    rows, cols = codes.shape
    br = min(block_rows, rows)
    rows_p = -(-rows // br) * br
    if rows_p != rows:
        codes = jnp.pad(codes, ((0, rows_p - rows), (0, 0)))
    tile = pl.BlockSpec((br, cols), lambda i: (i, 0))
    otile = pl.BlockSpec((br, cols // 2), lambda i: (i, 0))
    out = pl.pallas_call(
        _pack_kernel,
        grid=(rows_p // br,),
        in_specs=[tile],
        out_specs=otile,
        out_shape=jax.ShapeDtypeStruct((rows_p, cols // 2), jnp.int8),
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel",)),
        name="pack_int4",
        interpret=interpret,
    )(codes)
    return out[:rows]


def unpack_int4(packed, *, block_rows: int = 256,
                interpret: bool = False):
    """Inverse of ``pack_int4``: (R, 64) int8 bytes -> (R, 128) int8
    codes in [-7, 7]."""
    rows, cols = packed.shape
    br = min(block_rows, rows)
    rows_p = -(-rows // br) * br
    if rows_p != rows:
        packed = jnp.pad(packed, ((0, rows_p - rows), (0, 0)))
    tile = pl.BlockSpec((br, cols), lambda i: (i, 0))
    otile = pl.BlockSpec((br, cols * 2), lambda i: (i, 0))
    out = pl.pallas_call(
        _unpack_kernel,
        grid=(rows_p // br,),
        in_specs=[tile],
        out_specs=otile,
        out_shape=jax.ShapeDtypeStruct((rows_p, cols * 2), jnp.int8),
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel",)),
        name="unpack_int4",
        interpret=interpret,
    )(packed)
    return out[:rows]


def quantize_pack_int4(x2d, *, block_rows: int = 256,
                       interpret: bool = False):
    """The fused sender pass: (R, 128) f32 blocks -> (packed (R, 64)
    int8 wire bytes, scales (R, 1) f32, local (R, 128) f32 dequantized
    payload) in ONE kernel launch. Bitwise equal to the composition
    ``quantize_int4`` → ``pack_int4`` → ``dequantize_int4``
    (``ref.quantize_pack_int4`` — tested)."""
    rows, cols = x2d.shape
    br = min(block_rows, rows)
    rows_p = -(-rows // br) * br
    if rows_p != rows:
        x2d = jnp.pad(x2d, ((0, rows_p - rows), (0, 0)))
    tile = pl.BlockSpec((br, cols), lambda i: (i, 0))
    ptile = pl.BlockSpec((br, cols // 2), lambda i: (i, 0))
    stile = pl.BlockSpec((br, 1), lambda i: (i, 0))
    packed, scales, local = pl.pallas_call(
        _quantize_pack_kernel,
        grid=(rows_p // br,),
        in_specs=[tile],
        out_specs=(ptile, stile, tile),
        out_shape=(jax.ShapeDtypeStruct((rows_p, cols // 2), jnp.int8),
                   jax.ShapeDtypeStruct((rows_p, 1), jnp.float32),
                   jax.ShapeDtypeStruct((rows_p, cols), jnp.float32)),
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel",)),
        name="quantize_pack_int4",
        interpret=interpret,
    )(x2d)
    return packed[:rows], scales[:rows], local[:rows]


def unpack_dequantize_int4(packed, scales, *, block_rows: int = 256,
                           interpret: bool = False):
    """The fused receiver pass: (R, 64) int8 wire bytes × (R, 1) f32
    scales -> (R, 128) f32 values in ONE kernel launch (previously
    unpack then dequantize, two launches)."""
    rows, cols = packed.shape
    br = min(block_rows, rows)
    rows_p = -(-rows // br) * br
    if rows_p != rows:
        packed = jnp.pad(packed, ((0, rows_p - rows), (0, 0)))
        scales = jnp.pad(scales, ((0, rows_p - rows), (0, 0)))
    tile = pl.BlockSpec((br, cols), lambda i: (i, 0))
    stile = pl.BlockSpec((br, 1), lambda i: (i, 0))
    otile = pl.BlockSpec((br, cols * 2), lambda i: (i, 0))
    out = pl.pallas_call(
        _unpack_dequant_kernel,
        grid=(rows_p // br,),
        in_specs=[tile, stile],
        out_specs=otile,
        out_shape=jax.ShapeDtypeStruct((rows_p, cols * 2), jnp.float32),
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel",)),
        name="unpack_dequantize_int4",
        interpret=interpret,
    )(packed, scales)
    return out[:rows]


def unpack_dequantize_reduce(packed, scales, m, *, block_rows: int = 256,
                             interpret: bool = False):
    """The fused deferred-consume pass: decode EVERY replica's wire
    blocks and mask-combine them in one launch. packed (k, R, 64) int8,
    scales (k, R, 1) f32, m (k,) f32 -> (R, 128) f32 masked sum
    Σ_k m_k · codes_k · scale_k (caller divides by the mask sum).
    Oracle: ``ref.unpack_dequantize_reduce``."""
    k, rows, cols = packed.shape
    br = min(block_rows, rows)
    rows_p = -(-rows // br) * br
    if rows_p != rows:
        packed = jnp.pad(packed, ((0, 0), (0, rows_p - rows), (0, 0)))
        scales = jnp.pad(scales, ((0, 0), (0, rows_p - rows), (0, 0)))
    m3 = m.reshape(k, 1, 1).astype(jnp.float32)
    tile = pl.BlockSpec((k, br, cols), lambda i: (0, i, 0))
    stile = pl.BlockSpec((k, br, 1), lambda i: (0, i, 0))
    mtile = pl.BlockSpec((k, 1, 1), lambda i: (0, 0, 0))
    otile = pl.BlockSpec((br, cols * 2), lambda i: (i, 0))
    out = pl.pallas_call(
        _unpack_dequant_reduce_kernel,
        grid=(rows_p // br,),
        in_specs=[tile, stile, mtile],
        out_specs=otile,
        out_shape=jax.ShapeDtypeStruct((rows_p, cols * 2), jnp.float32),
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel",)),
        name="unpack_dequantize_reduce",
        interpret=interpret,
    )(packed, scales, m3)
    return out[:rows]


def fake_quant(x, dtype: str, *, block_rows: int = 256,
               interpret: bool = False):
    """Fused quantize→dequantize round trip on a tensor of any shape.
    ``dtype``: "bfloat16" or "int4". Returns x's shape/dtype."""
    if dtype == "float32":
        return x
    shape, out_dtype = x.shape, x.dtype
    x2d, rows_p, br, n = _pad2d(x, block_rows)
    tile = pl.BlockSpec((br, 128), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_fake_quant_kernel, dtype=dtype),
        grid=(rows_p // br,),
        in_specs=[tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows_p, 128), jnp.float32),
        compiler_params=compat.CompilerParams(
            dimension_semantics=("parallel",)),
        name="fake_quant",
        interpret=interpret,
    )(x2d)
    return out.reshape(-1)[:n].reshape(shape).astype(out_dtype)
