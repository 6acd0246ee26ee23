"""What a profiler sees of a run (``obs/profile.py``): the round
program's phase scopes in the compiled HLO, the Pallas kernels' names,
the trainer's set-up spans and the process's compile log."""
from __future__ import annotations

import json
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import DiLoCoConfig, ModelConfig, TrainConfig
from repro.core import diloco, streaming
from repro.data.sharding import make_regime
from repro.launch import train
from repro.models.registry import Arch
from repro.obs import profile
from repro.obs.metrics import RunRecorder

K, H, B, S, VOCAB = 2, 2, 2, 16, 64
SCOPES = ("diloco.sample", "diloco.inner", "diloco.adamw", "diloco.outer",
          "diloco.eval")


def _op_names(hlo_text: str) -> list:
    return re.findall(r'op_name="([^"]*)"', hlo_text)


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=VOCAB,
                      remat=True, attn_chunk=16)
    arch = Arch(cfg=cfg)
    params, _ = arch.init(jax.random.PRNGKey(0), cfg)
    sampler = make_regime("non_iid", k=K, vocab_size=VOCAB, seed=0)
    val = sampler.sample_validation(jax.random.PRNGKey(1), B, S)
    return arch, params, sampler, val


@pytest.mark.parametrize("fragments", [0, 2], ids=["classic", "streaming"])
def test_compiled_round_carries_every_phase_scope(tiny, fragments):
    arch, params, sampler, val = tiny
    dcfg = DiLoCoConfig(k=K, H=H, streaming_fragments=fragments)
    tcfg = TrainConfig(inner_lr=1e-3, warmup_steps=1, total_steps=4 * H,
                       batch_size=B, seq_len=S)
    run = diloco.make_run(lambda p, b: arch.loss(p, b),
                          sampler.sample_all_shards, dcfg, tcfg,
                          rounds_per_call=1, eval_tokens=val)
    state = (streaming.init_state(params, dcfg) if fragments
             else diloco.init_state(params, dcfg))
    names = _op_names(run.lower(state, jax.random.PRNGKey(2))
                      .compile().as_text())
    want = SCOPES + (("diloco.sync",) if fragments else ())
    for scope in want:
        assert any(scope in n for n in names), scope
    inner = [n for n in names if "diloco.inner" in n
             and "diloco.adamw" not in n]
    assert any("jvp(" in n and "transpose(" not in n for n in inner)
    assert any("transpose(" in n for n in inner)
    # the remat recompute sits inside the backward pass (full paths;
    # a reduction's sub-computation keeps only its local name)
    remat = [n for n in names
             if n.startswith("jit(") and "rematted_computation" in n]
    assert remat and all("transpose(" in n for n in remat)
    # AdamW runs inside the inner step, never outside it
    assert all("diloco.inner" in n for n in names if "diloco.adamw" in n)


# ---------------------------------------------------------------------------
# Pallas kernels carry their names into the compiled program
# ---------------------------------------------------------------------------

def _kernel_cases():
    from repro.kernels import (flash_attention, fused_adamw,
                               outer_nesterov, quantize, sign_prune)
    f32, bf16, i8 = jnp.float32, jnp.bfloat16, jnp.int8
    sd = jax.ShapeDtypeStruct
    x2d, codes = sd((256, 128), f32), sd((256, 128), i8)
    packed, scales = sd((256, 64), i8), sd((256, 1), f32)
    q = sd((1, 2, 128, 128), f32)
    sc = dict(lr=1e-3, c1=0.1, c2=0.05)
    fa_vjp = flash_attention.make_flash_attention_vjp(
        causal=True, block_q=128, block_k=128)
    return {
        "fused_adamw": (lambda p, g, m, v: fused_adamw.fused_adamw(
            p, g, m, v, **sc), [x2d] * 4),
        "fused_adamw_mixed": (lambda g, m, v, w: fused_adamw
                              .fused_adamw_mixed(g, m, v, w, **sc),
                              [sd((256, 128), bf16)] * 3 + [x2d]),
        "outer_nesterov": (lambda p, d, b: outer_nesterov.outer_nesterov(
            p, d, b, lr=0.7), [x2d] * 3),
        "quantize_int4": (quantize.quantize_int4, [x2d]),
        "dequantize_int4": (quantize.dequantize_int4, [codes, scales]),
        "pack_int4": (quantize.pack_int4, [codes]),
        "unpack_int4": (quantize.unpack_int4, [packed]),
        "quantize_pack_int4": (quantize.quantize_pack_int4, [x2d]),
        "unpack_dequantize_int4": (quantize.unpack_dequantize_int4,
                                   [packed, scales]),
        "unpack_dequantize_reduce": (
            quantize.unpack_dequantize_reduce,
            [sd((2, 256, 64), i8), sd((2, 256, 1), f32), sd((2,), f32)]),
        "fake_quant": (lambda x: quantize.fake_quant(x, "int4"), [x2d]),
        "sign_prune": (lambda x: sign_prune.sign_prune(x, 0.5), [x2d]),
        "flash_attention": (flash_attention.flash_attention, [q] * 3),
        # the differentiated forward (the plain call runs the kernel
        # without the log-sum-exp, named flash_attention)
        "flash_attention_fwd": (lambda a, b, c: jax.vjp(
            fa_vjp, a, b, c)[0], [q] * 3),
        "flash_attention_bwd_dq": (lambda a, b, c: jax.grad(
            lambda a: fa_vjp(a, b, c).sum())(a), [q] * 3),
        "flash_attention_bwd_dkv": (lambda a, b, c: jax.grad(
            lambda b: fa_vjp(a, b, c).sum())(b), [q] * 3),
    }


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_pallas_kernel_is_named_in_its_lowered_hlo(name, v5e):
    fn, args = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e)
            for a in args]
    hlo = jax.jit(fn).lower(*args).as_text(dialect="hlo", debug_info=True)
    calls = [_op_names(line) for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    # the compiled instruction takes its name from the op's path, where
    # a transformation may wrap it: transpose(jvp(<name>))/pallas_call
    assert any(re.search(rf"[/(]{name}\)*/pallas_call", n)
               for ns in calls for n in ns), calls


# ---------------------------------------------------------------------------
# compile log and set-up spans
# ---------------------------------------------------------------------------

def test_compile_log_names_and_times_each_compile():
    mark = profile.install()
    assert profile.install() == mark          # one listener per process
    before = time.perf_counter()

    def probe_fn(x):
        return jnp.sin(x) * 3 + 1

    jax.jit(probe_fn)(jnp.ones(7)).block_until_ready()
    log = profile.entries(mark)
    mine = [e for e in log if "probe_fn" in (e["fun_name"] or "")]
    assert {e["stage"] for e in mine} >= {"jaxpr_trace", profile.BACKEND}
    backend = [e for e in mine if e["stage"] == profile.BACKEND]
    assert len(backend) == 1
    e = backend[0]
    assert before <= e["start"] <= e["end"] <= time.perf_counter()
    assert e["cache"] in (None, "hit", "miss")
    assert profile.backend_seconds(mine) == pytest.approx(
        e["end"] - e["start"])
    assert abs(profile.wall_to_perf(time.time())
               - time.perf_counter()) < 0.05


def test_run_records_setup_spans_and_its_compiles(tmp_path):
    lines = []
    rec = RunRecorder(printer=lambda s, **_: lines.append(s))
    rounds = 3
    args = train.make_parser().parse_args(
        ["--k", "2", "--H", "2", "--rounds", str(rounds), "--batch", "2",
         "--seq", "16", "--rounds-per-call", "1", "--seed", "3",
         "--out", str(tmp_path / "run.json")])
    train.run(args, recorder=rec)
    assert rec.ingest_calls == rounds                  # one per chunk
    spans = [s["span"] for s in rec.setup_spans]
    assert spans == ["diloco.setup.build", "diloco.setup.init",
                     "diloco.setup.validation", "diloco.setup.state"]
    assert all(s["start"] <= s["end"] for s in rec.setup_spans)
    compiles = rec.compiles()
    backend = [e["fun_name"] for e in compiles
               if e["stage"] == profile.BACKEND]
    assert "jit(run_fn)" in backend                    # the round program
    assert "jit(sample_validation)" in backend
    # the console shows the same lines as before: the rounds and notes,
    # never a set-up span or a compile
    assert [l for l in lines if l.startswith("[round ")] == [
        f"[round {r['round']}/{rounds}] inner={r['inner_loss']:.4f} "
        f"val={r['val_loss']:.4f} ppl={np.exp(r['val_loss']):.2f} "
        f"active=2" for r in rec.round_records()]
    assert all(l.startswith(("[round ", "done in ", "wrote "))
               for l in lines), lines
    manifest = json.loads((tmp_path / "run.json").read_text())["manifest"]
    assert [s["span"] for s in manifest["setup"]] == spans
    assert len(manifest["compiles"]) == len(compiles)
