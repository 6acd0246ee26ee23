"""Backend dispatch for the Pallas kernels.

Each op picks the Pallas kernel on TPU (or when forced via
``mode='pallas'`` / ``mode='interpret'``) and the pure-jnp oracle from
``ref.py`` otherwise — so CPU runs (tests, benchmarks) and TPU runs
share one call site. Tree-level helpers apply the fused optimizer
kernels leaf-by-leaf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_attention as _flash
from . import fused_adamw as _adamw
from . import outer_nesterov as _nesterov
from . import quantize as _quant
from . import sign_prune as _prune
from . import ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(mode: str):
    """-> (use_kernel, interpret)."""
    if mode == "auto":
        return (_on_tpu(), False)
    if mode == "pallas":
        return (True, False)
    if mode == "interpret":
        return (True, True)
    if mode == "ref":
        return (False, False)
    raise ValueError(mode)


# ---------------------------------------------------------------------------
# flash attention — q: (B, S, H, d) model layout; kernel uses (B, H, S, d)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _fa_vjp(causal, window, scale, interpret):
    return _flash.make_flash_attention_vjp(
        causal=causal, window=window, scale=scale, interpret=interpret)


def flash_attention_engages(mode: str, q) -> bool:
    """Whether self-attention with queries ``q`` (B, S, H, d) takes the
    Pallas flash kernel under kernel mode ``mode``: where the mode
    resolves to the kernels, the kernel tiles S without padding, and
    ``q`` is one device's array — on no mesh, or inside ``shard_map``
    with every mesh axis manual. A Pallas call is not partitioned across
    devices, so a ``q`` on a mesh the compiler partitions (the sharded
    transport's eval) keeps the XLA path."""
    if not _resolve(mode)[0] or _flash.block_size(q.shape[1]) is None:
        return False
    return all(t == jax.sharding.AxisType.Manual
               for t in jax.typeof(q).sharding.mesh.axis_types)


def flash_attention(q, k, v, *, causal=True, window=0, scale=None,
                    mode: str = "auto"):
    """Differentiable flash attention (custom_vjp with flash backward
    kernels on the kernel path); blocks follow the lengths."""
    use_kernel, interpret = _resolve(mode)
    if not use_kernel:
        return ref.flash_attention(q, k, v, causal=causal, window=window,
                                   scale=scale)
    fa = _fa_vjp(causal, window, scale, interpret)
    out = fa(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
             v.transpose(0, 2, 1, 3))
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# fused AdamW — tree-level
# ---------------------------------------------------------------------------

def adamw_update_tree(params, grads, m, v, *, lr, count, b1=0.9, b2=0.95,
                      eps=1e-8, weight_decay=0.1, mode: str = "auto"):
    """One fused AdamW step over a whole param tree. ``count`` is the
    post-increment step (for bias correction)."""
    use_kernel, interpret = _resolve(mode)
    cf = jnp.asarray(count, jnp.float32)
    c1 = 1.0 - b1 ** cf
    c2 = 1.0 - b2 ** cf

    def one(p, g, mm, vv):
        if use_kernel:
            return _adamw.fused_adamw(
                p, g, mm, vv, lr=lr, c1=c1, c2=c2, b1=b1, b2=b2, eps=eps,
                weight_decay=weight_decay, interpret=interpret)
        return ref.fused_adamw(p, g, mm, vv, lr=lr, b1=b1, b2=b2,
                               eps=eps, weight_decay=weight_decay,
                               c1=c1, c2=c2)

    out = jax.tree.map(one, params, grads, m, v)
    leaves = lambda i: jax.tree.map(
        lambda t: t[i], out, is_leaf=lambda x: isinstance(x, tuple))
    return leaves(0), leaves(1), leaves(2)


def adamw_update_tree_mixed(grads, m, v, master, *, lr, count,
                            param_dtype, b1=0.9, b2=0.95, eps=1e-8,
                            weight_decay=0.1, mode: str = "auto"):
    """One mixed-precision fused AdamW step over a whole tree: the
    high-precision ``master`` tree is authoritative, grads/moments ride
    at the replica storage dtype, and the ``param_dtype`` working copy
    is emitted in the same pass. Returns (params, m, v, master)."""
    use_kernel, interpret = _resolve(mode)
    cf = jnp.asarray(count, jnp.float32)
    c1 = 1.0 - b1 ** cf
    c2 = 1.0 - b2 ** cf

    def one(g, mm, vv, w):
        if use_kernel:
            return _adamw.fused_adamw_mixed(
                g, mm, vv, w, lr=lr, c1=c1, c2=c2, b1=b1, b2=b2,
                eps=eps, weight_decay=weight_decay,
                param_dtype=param_dtype, interpret=interpret)
        return ref.fused_adamw_mixed(
            g, mm, vv, w, lr=lr, b1=b1, b2=b2, eps=eps,
            weight_decay=weight_decay, c1=c1, c2=c2,
            param_dtype=param_dtype)

    out = jax.tree.map(one, grads, m, v, master)
    leaves = lambda i: jax.tree.map(
        lambda t: t[i], out, is_leaf=lambda x: isinstance(x, tuple))
    return leaves(0), leaves(1), leaves(2), leaves(3)


# ---------------------------------------------------------------------------
# sign pruning — matrix + tree-level
# ---------------------------------------------------------------------------

def sign_prune(x, frac: float, *, mode: str = "auto"):
    """x: (R, C)."""
    if frac <= 0:
        return x
    use_kernel, interpret = _resolve(mode)
    if use_kernel:
        return _prune.sign_prune(x, frac, interpret=interpret)
    return ref.sign_prune(x, frac)


def sign_prune_tree(tree, frac: float, *, mode: str = "auto"):
    """Leaves are reshaped to (leading-dim rows, flattened cols)."""
    if frac <= 0:
        return tree

    def one(x):
        if x.ndim == 0:
            return x
        flat = x.reshape(1, -1) if x.ndim == 1 \
            else x.reshape(x.shape[0], -1)
        return sign_prune(flat, frac, mode=mode).reshape(x.shape)

    return jax.tree.map(one, tree)


# ---------------------------------------------------------------------------
# low-precision outer-gradient transport — tensor + tree-level
# ---------------------------------------------------------------------------

# Wire cost of one transported element: int4 carries 0.5 B of codes
# plus one f32 scale per 128-element block. The per-element figure for
# int4 is the large-tensor amortization; exact wire bytes (with the
# ceil'd per-block scale count) come from ``transport_bytes``.
QUANT_BLOCK = 128
# Packed int4 wire sections are padded to this byte boundary so the f32
# scale section that follows the nibble-packed codes stays word-aligned
# (what a real sender's framing would do; charged by the packed model).
WIRE_ALIGN = 4
TRANSPORT_BYTES_PER_ELEM = {
    "float32": 4.0,
    "bfloat16": 2.0,
    "int4": 0.5 + 4.0 / QUANT_BLOCK,
}


def quant_roundtrip(x, dtype: str, *, mode: str = "auto"):
    """Simulated low-precision transport: quantize→dequantize round trip
    at ``dtype`` ("float32" = identity). int4 uses one f32 scale per
    128-element block of the flattened tensor (the same (blocks, 128)
    layout as the fused optimizer kernels)."""
    if dtype == "float32":
        return x
    if dtype not in TRANSPORT_BYTES_PER_ELEM:
        raise ValueError(f"unknown transport dtype {dtype!r}")
    use_kernel, interpret = _resolve(mode)
    if use_kernel:
        return _quant.fake_quant(x, dtype, interpret=interpret)
    if dtype == "bfloat16":
        return ref.fake_quant(x, dtype)
    # int4 oracle on the kernel's block layout, so ref == kernel exactly
    shape, out_dtype = x.shape, x.dtype
    n = x.size
    rows = -(-n // QUANT_BLOCK)
    flat = x.reshape(-1).astype(jnp.float32)
    if rows * QUANT_BLOCK != n:
        flat = jnp.pad(flat, (0, rows * QUANT_BLOCK - n))
    out = ref.fake_quant(flat.reshape(rows, QUANT_BLOCK), dtype)
    return out.reshape(-1)[:n].reshape(shape).astype(out_dtype)


def quant_roundtrip_tree(tree, dtype: str, *, mode: str = "auto"):
    if dtype == "float32":
        return tree
    return jax.tree.map(lambda x: quant_roundtrip(x, dtype, mode=mode),
                        tree)


def transport_bytes(n_elems: int, dtype: str, *,
                    packed: bool = False) -> float:
    """Wire bytes for ``n_elems`` outer-gradient elements.

    ``packed=False`` (the legacy fake-quant model, kept for comparison):
    int4 charges 0.5 B of codes per element plus one f32 scale per
    (started) 128-element block of the flattened tensor — a tensor that
    does not divide evenly still ships a scale for its ragged tail, so
    the scale overhead is ceil(n/128) blocks, not n/128.

    ``packed=True`` is the EXACT byte count of the packed wire buffer
    ``wire_encode`` builds (and the sharded transport all-gathers):
    int4 nibble-packs two codes per int8 byte — an odd element count
    still ships its ragged final byte, so the code section is
    ceil(n/2) bytes, padded to the ``WIRE_ALIGN`` word boundary —
    followed by one f32 scale per started 128-element block. float32 /
    bfloat16 ship whole elements, so their packed and legacy models
    coincide.
    """
    if dtype not in TRANSPORT_BYTES_PER_ELEM:
        raise ValueError(f"unknown transport dtype {dtype!r}")
    if dtype == "int4":
        n = int(n_elems)
        blocks = -(-n // QUANT_BLOCK)
        if packed:
            code_bytes = -(-n // 2)
            code_bytes += (-code_bytes) % WIRE_ALIGN
            return float(code_bytes + 4 * blocks)
        return n * 0.5 + 4.0 * blocks
    return n_elems * TRANSPORT_BYTES_PER_ELEM[dtype]


# ---------------------------------------------------------------------------
# packed int4 wire: codes+scales as one byte buffer (sharded transport)
# ---------------------------------------------------------------------------

def _block_pad(flat, rows):
    if rows * QUANT_BLOCK != flat.shape[0]:
        flat = jnp.pad(flat, (0, rows * QUANT_BLOCK - flat.shape[0]))
    return flat.reshape(rows, QUANT_BLOCK)


def pack_int4(codes, *, mode: str = "auto"):
    """Nibble-pack flat (n,) int8 codes in [-7, 7] -> (ceil(n/2),) int8
    wire bytes (two 4-bit two's-complement codes per byte, element
    order). Exact inverse: ``unpack_int4``."""
    n = codes.shape[0]
    use_kernel, interpret = _resolve(mode)
    if not use_kernel:
        return ref.pack_int4(codes)
    rows = -(-n // QUANT_BLOCK)
    c2d = _block_pad(codes, rows)
    out = _quant.pack_int4(c2d, interpret=interpret)
    return out.reshape(-1)[:-(-n // 2)]


def unpack_int4(packed, n: int, *, mode: str = "auto"):
    """Inverse of ``pack_int4``: (ceil(n/2),) int8 bytes -> (n,) int8
    codes with 4-bit two's-complement sign extension."""
    use_kernel, interpret = _resolve(mode)
    if not use_kernel:
        return ref.unpack_int4(packed, n)
    rows = -(-n // QUANT_BLOCK)
    half = QUANT_BLOCK // 2
    p = packed
    if p.shape[0] != rows * half:
        p = jnp.pad(p, (0, rows * half - p.shape[0]))
    out = _quant.unpack_int4(p.reshape(rows, half), interpret=interpret)
    return out.reshape(-1)[:n]


def wire_dtype(dtype: str):
    """Element dtype of the wire buffer ``wire_encode`` builds. bf16
    rides as bit-cast uint16: shipping raw bits denies XLA the
    convert-hoisting rewrite that would widen the collective back to
    f32 (observed on the CPU backend — the convert is free to cross an
    all-gather, a bitcast is not)."""
    if dtype == "int4":
        return jnp.uint8
    if dtype == "bfloat16":
        return jnp.uint16
    raise ValueError(f"no packed wire for transport dtype {dtype!r}")


def wire_elems(n_elems: int, dtype: str) -> int:
    """Length of the wire buffer for one region of ``n_elems``
    (elements of ``wire_dtype``; for int4 that is exactly
    ``transport_bytes(n, 'int4', packed=True)`` bytes)."""
    if dtype == "int4":
        return int(transport_bytes(n_elems, dtype, packed=True))
    if dtype == "bfloat16":
        return int(n_elems)
    raise ValueError(f"no packed wire for transport dtype {dtype!r}")


def wire_encode(x, dtype: str, *, mode: str = "auto"):
    """Encode one flat (n,) region for the packed wire.

    Returns ``(wire, local)``: ``wire`` is what the collective ships —
    bf16 the raw bf16 elements, int4 ONE uint8 buffer laying out the
    nibble-packed codes (ceil(n/2) bytes, zero-padded to the
    ``WIRE_ALIGN`` boundary) followed by the per-128-block f32 scales
    bit-cast to bytes; ``local`` is the dequantized f32 value of the
    sender's own payload (what ``wire_decode`` will recover on every
    receiver — used for the error-feedback residual without a second
    decode).
    """
    if dtype == "bfloat16":
        w = x.reshape(-1).astype(jnp.bfloat16)
        # ship the raw bf16 bits as uint16 (see wire_dtype)
        return (jax.lax.bitcast_convert_type(w, jnp.uint16),
                w.astype(jnp.float32))
    if dtype != "int4":
        raise ValueError(f"no packed wire for transport dtype {dtype!r}")
    n = x.shape[0]
    rows = -(-n // QUANT_BLOCK)
    x2d = _block_pad(x.reshape(-1).astype(jnp.float32), rows)
    use_kernel, interpret = _resolve(mode)
    if use_kernel:
        # the fused sender pass: scale + codes + nibble-pack + local
        # dequant in ONE kernel launch per region. A ragged tail (n not
        # lane-pair-aligned) is handled by the zero-padded block layout:
        # codes past n quantize to 0, so the ragged final byte's high
        # nibble is 0 — byte-identical to ref.pack_int4's odd-tail pad
        # (tested on the property grid).
        packed2d, scales, local2d = _quant.quantize_pack_int4(
            x2d, interpret=interpret)
        code_bytes = packed2d.reshape(-1)[:-(-n // 2)]
    else:
        codes, scales = ref.quantize_int4(x2d)
        local2d = ref.dequantize_int4(codes, scales)
        code_bytes = ref.pack_int4(codes.reshape(-1)[:n])
    pad = (-code_bytes.shape[0]) % WIRE_ALIGN
    if pad:
        code_bytes = jnp.pad(code_bytes, (0, pad))
    scale_bytes = jax.lax.bitcast_convert_type(
        scales.reshape(rows), jnp.uint8).reshape(-1)
    wire = jnp.concatenate(
        [jax.lax.bitcast_convert_type(code_bytes, jnp.uint8),
         scale_bytes])
    local = local2d.reshape(-1)[:n]
    return wire, local


def wire_decode(wire, n_elems: int, dtype: str, *, mode: str = "auto"):
    """Decode one region's wire buffer back to (n,) f32 — the exact
    value the sender's ``wire_encode`` reported as ``local`` (pack →
    unpack is the identity on the int4 code grid, and the f32 scales
    ride bit-exact)."""
    if dtype == "bfloat16":
        return jax.lax.bitcast_convert_type(
            wire, jnp.bfloat16).astype(jnp.float32)
    if dtype != "int4":
        raise ValueError(f"no packed wire for transport dtype {dtype!r}")
    n = int(n_elems)
    rows = -(-n // QUANT_BLOCK)
    cb = -(-n // 2)
    pad = (-cb) % WIRE_ALIGN
    use_kernel, interpret = _resolve(mode)
    scales = jax.lax.bitcast_convert_type(
        wire[cb + pad:].reshape(rows, 4), jnp.float32)
    if use_kernel:
        # fused unpack+dequantize: ONE launch per region (padding wire
        # bytes with zeros appends zero codes past n — sliced off)
        half = QUANT_BLOCK // 2
        p = jax.lax.bitcast_convert_type(wire[:cb], jnp.int8)
        if cb != rows * half:
            p = jnp.pad(p, (0, rows * half - cb))
        vals = _quant.unpack_dequantize_int4(
            p.reshape(rows, half), scales.reshape(rows, 1),
            interpret=interpret)
    else:
        codes = ref.unpack_int4(
            jax.lax.bitcast_convert_type(wire[:cb], jnp.int8), n)
        vals = ref.dequantize_int4(_block_pad(codes, rows),
                                   scales.reshape(rows, 1))
    return vals.reshape(-1)[:n]


def wire_reduce(gathered, n_elems: int, dtype: str, m, denom, *,
                mode: str = "auto"):
    """Consume one region's GATHERED wire: decode every replica's
    buffer and mask-reduce to the transported mean — the deferred
    streaming round's apply-side op (``tensordot(m, decoded) / denom``,
    the simulated transport's reduction verbatim on the ref path).
    gathered: (k, W) wire buffers in replica order; m: (k,) mask;
    denom: the mask sum. int4 under a kernel mode runs the fused
    unpack+dequantize+reduce consumer — decode and reduction in ONE
    kernel launch instead of per-replica unpack/dequant pairs."""
    use_kernel, interpret = _resolve(mode)
    if dtype == "int4" and use_kernel:
        n = int(n_elems)
        rows = -(-n // QUANT_BLOCK)
        cb = -(-n // 2)
        pad = (-cb) % WIRE_ALIGN
        half = QUANT_BLOCK // 2
        k = gathered.shape[0]
        p = jax.lax.bitcast_convert_type(gathered[:, :cb], jnp.int8)
        if cb != rows * half:
            p = jnp.pad(p, ((0, 0), (0, rows * half - cb)))
        scales = jax.lax.bitcast_convert_type(
            gathered[:, cb + pad:].reshape(k, rows, 4), jnp.float32)
        red = _quant.unpack_dequantize_reduce(
            p.reshape(k, rows, half), scales.reshape(k, rows, 1),
            m, interpret=interpret)
        return red.reshape(-1)[:n] / denom
    vals = jax.vmap(
        lambda w: wire_decode(w, n_elems, dtype, mode=mode))(gathered)
    return jnp.tensordot(m, vals, axes=(0, 0)) / denom


# ---------------------------------------------------------------------------
# outer Nesterov — tree-level
# ---------------------------------------------------------------------------

def nesterov_update_tree(params, delta, buf, *, lr, momentum=0.9,
                         mode: str = "auto"):
    use_kernel, interpret = _resolve(mode)

    def one(p, d, b):
        if use_kernel:
            return _nesterov.outer_nesterov(p, d, b, lr=lr,
                                            momentum=momentum,
                                            interpret=interpret)
        return ref.outer_nesterov(p, d, b, lr=lr, momentum=momentum)

    out = jax.tree.map(one, params, delta, buf)
    leaves = lambda i: jax.tree.map(
        lambda t: t[i], out, is_leaf=lambda x: isinstance(x, tuple))
    return leaves(0), leaves(1)
