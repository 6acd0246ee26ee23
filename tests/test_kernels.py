"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU).

Sweeps shapes/dtypes per kernel; flash attention additionally checks
GQA grouping, causal/window masks and non-block-aligned lengths.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (flash_attention as FK, fused_adamw as FA,
                           outer_nesterov as ON, sign_prune as SP,
                           ops, ref)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

ATTN_CASES = [
    # B, H, G, S, d, causal, window
    (2, 4, 2, 128, 64, True, 0),
    (1, 4, 4, 256, 32, True, 0),
    (2, 8, 2, 96, 64, True, 0),           # not block-aligned
    (1, 2, 1, 192, 64, True, 64),          # sliding window
    (1, 4, 2, 256, 64, False, 0),          # bidirectional (encoder)
    (1, 16, 4, 128, 128, True, 0),         # MXU-aligned head dim
]


@pytest.mark.parametrize("B,H,G,S,d,causal,window", ATTN_CASES)
def test_flash_attention_matches_ref(B, H, G, S, d, causal, window):
    key = jax.random.PRNGKey(hash((B, H, G, S, d)) % (2 ** 31))
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, S, d), jnp.float32)
    k = jax.random.normal(ks[1], (B, G, S, d), jnp.float32)
    v = jax.random.normal(ks[2], (B, G, S, d), jnp.float32)
    out = FK.flash_attention(q, k, v, causal=causal, window=window,
                             block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal,
        window=window).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 4, 128, 64)).astype(dtype)
    k = jax.random.normal(ks[1], (1, 2, 128, 64)).astype(dtype)
    v = jax.random.normal(ks[2], (1, 2, 128, 64)).astype(dtype)
    out = FK.flash_attention(q, k, v, interpret=True)
    want = ref.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out.astype(np.float32),
                               want.astype(np.float32), rtol=tol, atol=tol)


MODEL_ATTN_CASES = [
    # B, S, H, G, d, blocks (None: the kernel's own pick, ops' path)
    (2, 256, 8, 4, 64, None),
    (1, 1024, 2, 2, 64, None),         # the 150M cell's head layout
    (1, 1024, 2, 2, 64, 256),          # causal tile skip
    (1, 1024, 2, 1, 128, None),        # the 400M cell's head width, GQA
    (1, 1024, 2, 1, 128, 128),
]


@pytest.mark.parametrize("B,S,H,G,d,blocks", MODEL_ATTN_CASES)
def test_flash_attention_vs_model_attention(B, S, H, G, d, blocks):
    """The kernel agrees with the model's chunked online-softmax
    (layers.attention) — two independent formulations — forward and
    gradient with respect to q, k and v."""
    from repro.models.layers import attention
    if blocks is None:
        # the cells' length takes one whole-sequence tile
        assert FK.block_size(S) == min(S, 1024)
        fa = lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                                 mode="interpret")
    else:
        vjp = FK.make_flash_attention_vjp(block_q=blocks, block_k=blocks,
                                          interpret=True)
        t = lambda x: x.transpose(0, 2, 1, 3)
        fa = lambda q, k, v: t(vjp(t(q), t(k), t(v)))
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, S, H, d))
    k = jax.random.normal(ks[1], (B, S, G, d))
    v = jax.random.normal(ks[2], (B, S, G, d))
    dout = jax.random.normal(ks[3], (B, S, H, d))
    want, vjp_want = jax.vjp(
        lambda q, k, v: attention(q, k, v, causal=True, chunk=64), q, k, v)
    out, vjp_out = jax.vjp(fa, q, k, v)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    for got, ref_g in zip(vjp_out(dout), vjp_want(dout)):
        np.testing.assert_allclose(got, ref_g, rtol=5e-4, atol=5e-4)


def test_mxu_operands_follow_the_default_matmul_precision():
    f32, bf16 = jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)
    assert FK.mxu_dtype(f32, interpret=False) == bf16     # one MXU pass
    assert FK.mxu_dtype(bf16, interpret=False) == bf16
    assert FK.mxu_dtype(f32, interpret=True) == f32       # backend's own
    with jax.default_matmul_precision("highest"):
        assert FK.mxu_dtype(f32, interpret=False) == f32


def test_flash_attention_bf16_operands(monkeypatch):
    """With the chip's bf16 MXU operands (forced here in interpret mode)
    the kernel stays within bf16 rounding of the f32 oracle, and differs
    from its f32-operand run: the operands are rounded."""
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 4)
    q, k, v, dout = (jax.random.normal(kk, (1, 2, 256, 64)) for kk in ks)

    def run():
        fa = FK.make_flash_attention_vjp(interpret=True)
        o, vjp = jax.vjp(fa, q, k, v)
        return (o,) + vjp(dout)

    exact = run()
    monkeypatch.setattr(FK, "mxu_dtype",
                        lambda dtype, interpret: jnp.dtype(jnp.bfloat16))
    rounded = run()
    t = lambda x: x.transpose(0, 2, 1, 3)
    o_r, vjp_r = jax.vjp(lambda q, k, v: t(ref.flash_attention(
        t(q), t(k), t(v))), q, k, v)
    for got, f32_run, want in zip(rounded, exact, (o_r,) + vjp_r(dout)):
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(got, want, atol=2e-2 * scale)
        assert float(jnp.max(jnp.abs(got - f32_run))) > 1e-4 * scale


def _kernel_names(fn, *args) -> list:
    return re.findall(r"name=(flash_attention\w*)",
                      str(jax.make_jaxpr(fn)(*args)))


@pytest.mark.parametrize("mode,want", [
    ("ref", []), ("auto", []), ("interpret", ["flash_attention"])])
def test_apply_attention_takes_the_kernel_by_kernel_mode(mode, want):
    """Self-attention with no cache runs the Pallas kernel where the
    job's kernel mode resolves to the kernels (auto: not on the CPU)."""
    from repro.models.layers import apply_attention, init_attention
    from repro.models.registry import get_smoke_arch
    from repro.sharding.spec import unbox
    cfg = get_smoke_arch("diloco_150m").cfg.replace(kernel_mode=mode)
    p, _ = unbox(init_attention(jax.random.PRNGKey(0), cfg))
    x = jnp.ones((2, 128, cfg.d_model))
    fn = lambda p, x: apply_attention(p, x, cfg,
                                      positions=jnp.arange(128))[0]
    assert _kernel_names(fn, p, x) == want


def test_flash_attention_engages_only_on_one_devices_arrays():
    """Off a mesh and inside shard_map (manual axes) the kernel takes
    the call; on a mesh the compiler partitions it does not."""
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Explicit,))
    q = jnp.ones((2, 128, 4, 32))
    seen = {}

    def probe(name):
        def f(x):
            seen[name] = ops.flash_attention_engages("interpret", x)
            return x
        return f

    jax.jit(probe("plain"))(q)
    on_mesh = jax.device_put(q, NamedSharding(mesh, P()))
    jax.jit(probe("mesh"))(on_mesh)
    jax.jit(jax.shard_map(probe("manual"), mesh=mesh, in_specs=P(),
                          out_specs=P()))(on_mesh)
    assert seen == {"plain": True, "mesh": False, "manual": True}


@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_smoke_model_trains_and_evaluates_through_the_kernel(mode):
    """The smoke ``diloco_150m``'s eval forward and training step: every
    layer's attention runs the kernel under ``interpret`` (the forward,
    its remat recompute and both backward kernels), none under ``ref``."""
    from repro.models.registry import get_smoke_arch
    arch = get_smoke_arch("diloco_150m")
    cfg = arch.cfg.replace(kernel_mode=mode)
    params, _ = arch.init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.zeros((2, 128), jnp.int32)}
    loss = lambda p: arch.loss(p, batch, cfg=cfg)[0]
    evals, trains = _kernel_names(loss, params), \
        _kernel_names(jax.grad(loss), params)
    if mode == "ref":
        assert evals == trains == []
    else:
        assert evals == ["flash_attention"]
        assert sorted(trains) == ["flash_attention_bwd_dkv",
                                  "flash_attention_bwd_dq",
                                  "flash_attention_fwd",
                                  "flash_attention_fwd"]


# ---------------------------------------------------------------------------
# fused AdamW
# ---------------------------------------------------------------------------

ADAMW_SHAPES = [(17,), (1000,), (37, 53), (4, 16, 130), (256, 128)]


@pytest.mark.parametrize("shape", ADAMW_SHAPES)
def test_fused_adamw_matches_ref(shape):
    key = jax.random.PRNGKey(sum(shape))
    ks = jax.random.split(key, 4)
    p, g, m = (jax.random.normal(kk, shape) for kk in ks[:3])
    v = jnp.abs(jax.random.normal(ks[3], shape))
    args = dict(lr=3e-4, c1=0.19, c2=0.0975, b1=0.9, b2=0.95,
                eps=1e-8, weight_decay=0.1)
    out = FA.fused_adamw(p, g, m, v, interpret=True, **args)
    want = ref.fused_adamw(p, g, m, v, **args)
    for a, b in zip(out, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_fused_adamw_matches_optim_adamw():
    """The kernel's semantics equal the training-loop AdamW
    (optim/adamw.py) for one step."""
    from repro.optim import adamw
    key = jax.random.PRNGKey(1)
    params = {"w": jax.random.normal(key, (32, 16))}
    grads = {"w": jax.random.normal(jax.random.fold_in(key, 1), (32, 16))}
    st = adamw.init(params)
    new_p, new_st = adamw.update(grads, st, params, lr=1e-3)
    out_p, out_m, out_v = ops.adamw_update_tree(
        params, grads, st.m, st.v, lr=1e-3, count=1, mode="interpret")
    np.testing.assert_allclose(out_p["w"], new_p["w"], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(out_m["w"], new_st.m["w"], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(out_v["w"], new_st.v["w"], rtol=1e-6,
                               atol=1e-6)


# ---------------------------------------------------------------------------
# sign pruning
# ---------------------------------------------------------------------------

PRUNE_CASES = [((16, 256), 0.5), ((7, 100), 0.25), ((64, 300), 0.75),
               ((1, 128), 0.5), ((5, 513), 0.5)]


@pytest.mark.parametrize("shape,frac", PRUNE_CASES)
def test_sign_prune_matches_ref(shape, frac):
    x = jax.random.normal(jax.random.PRNGKey(shape[1]), shape)
    out = SP.sign_prune(x, frac, interpret=True)
    want = ref.sign_prune(x, frac)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_sign_prune_elects_majority_sign():
    # a row dominated by positive mass must keep only positive entries
    x = jnp.asarray([[5.0, 4.0, 3.0, -0.1, -0.2, 2.0, 1.0, -0.3]])
    out = np.asarray(ref.sign_prune(x, 0.25))
    assert (out <= 0).sum() == (out == 0).sum()  # no negatives survive


# ---------------------------------------------------------------------------
# outer nesterov
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(77,), (33, 129), (8, 8, 8)])
def test_outer_nesterov_matches_ref(shape):
    key = jax.random.PRNGKey(sum(shape))
    ks = jax.random.split(key, 3)
    p, d, b = (jax.random.normal(kk, shape) for kk in ks)
    out = ON.outer_nesterov(p, d, b, lr=0.7, momentum=0.9, interpret=True)
    want = ref.outer_nesterov(p, d, b, lr=0.7, momentum=0.9)
    for a, w in zip(out, want):
        np.testing.assert_allclose(a, w, rtol=1e-6, atol=1e-6)


def test_outer_nesterov_matches_outer_opt():
    """Kernel == core/outer_opt Nesterov update for one step."""
    from repro.core import outer_opt
    key = jax.random.PRNGKey(2)
    params = {"w": jax.random.normal(key, (16, 8))}
    delta = {"w": 0.01 * jax.random.normal(jax.random.fold_in(key, 1),
                                           (16, 8))}
    st = outer_opt.init(params)
    new_p, new_st = outer_opt.update(delta, st, params, kind="nesterov",
                                     lr=0.7, momentum=0.9)
    out_p, out_b = ops.nesterov_update_tree(params, delta, st.buf,
                                            lr=0.7, momentum=0.9,
                                            mode="interpret")
    np.testing.assert_allclose(out_p["w"], new_p["w"], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(out_b["w"], new_st.buf["w"], rtol=1e-6,
                               atol=1e-6)


# ---------------------------------------------------------------------------
# flash attention backward (custom_vjp, on-chip recompute)
# ---------------------------------------------------------------------------

BWD_CASES = [
    (1, 4, 2, 128, 64, True, 0),
    (2, 2, 1, 96, 32, True, 0),       # non-block-aligned
    (1, 4, 4, 128, 64, True, 48),     # sliding window
    (1, 2, 2, 128, 64, False, 0),     # bidirectional
]


@pytest.mark.parametrize("B,H,G,S,d,causal,window", BWD_CASES)
def test_flash_attention_backward(B, H, G, S, d, causal, window):
    key = jax.random.PRNGKey(S + d)
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, H, S, d))
    k = jax.random.normal(ks[1], (B, G, S, d))
    v = jax.random.normal(ks[2], (B, G, S, d))
    dout = jax.random.normal(ks[3], (B, H, S, d))
    fa = FK.make_flash_attention_vjp(causal=causal, window=window,
                                     block_q=64, block_k=64,
                                     interpret=True)
    o, vjp = jax.vjp(fa, q, k, v)
    dq, dk, dv = vjp(dout)

    def ref_fn(q, k, v):
        return ref.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal,
            window=window).transpose(0, 2, 1, 3)

    o_r, vjp_r = jax.vjp(ref_fn, q, k, v)
    dq_r, dk_r, dv_r = vjp_r(dout)
    np.testing.assert_allclose(o, o_r, rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(dq, dq_r, rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(dk, dk_r, rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(dv, dv_r, rtol=5e-4, atol=5e-4)
