"""The flash attention kernel's reader (``bench/metrics/
attention_kernel_ms.py``): which ops it counts, per replica step, and
that it reads nothing from a program without the kernel."""
import importlib
import os
import types

import pytest

from bench import trace as tr
from bench.run import TracedRun

reader = importlib.import_module("bench.metrics.attention_kernel_ms")
DATA = os.path.join(os.path.dirname(__file__), "data")
INNER = "jit(run_fn)/while/body/closed_call/diloco.inner"
CALL = ('{} = f32[8,16,1024,64]{{3,2,1,0}} custom-call(f32[8,16,1024,64] '
        '%copy.1), custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("head,flash", [
    ("%jvp_flash_attention_fwd_.1", True),
    ("%transpose_jvp_flash_attention_bwd_dq__.3", True),
    ("%flash_attention_bwd_dkv.12", True),
    ("%flash_attention.2", True),
    ("%fused_adamw.108", False),
])
def test_kernel_found_by_its_instruction_name(head, flash):
    assert reader.is_flash(CALL.format(head)) is flash


def test_an_xla_op_named_after_the_kernel_is_not_the_kernel():
    assert not reader.is_flash(
        "%flash_attention_fusion.1 = f32[8,1024] fusion(f32[8,1024] %p)")


def test_counts_the_inner_steps_kernels_per_replica_step():
    ms = 1e6
    fwd, dq, dkv, ev, ad = (CALL.format(h) for h in (
        "%jvp_flash_attention_fwd_.1", "%flash_attention_bwd_dq.2",
        "%flash_attention_bwd_dkv.3", "%flash_attention.4",
        "%fused_adamw.5"))
    by_op = {fwd: 4 * ms, dq: 6 * ms, dkv: 6 * ms, ev: 1 * ms, ad: 9 * ms}
    paths = {fwd: f"{INNER}/jvp(loss)/flash_attention_fwd/pallas_call",
             dq: f"{INNER}/transpose(jvp(loss))/flash_attention_bwd_dq",
             dkv: f"{INNER}/transpose(jvp(loss))/flash_attention_bwd_dkv",
             ev: "jit(run_fn)/diloco.eval/flash_attention/pallas_call",
             ad: f"{INNER}/diloco.adamw/fused_adamw/pallas_call"}
    run = types.SimpleNamespace(
        rounds=2, chips=[0], job={"replicas": 2, "inner_steps": 1},
        scope_phases=types.SimpleNamespace(by_op={0: by_op},
                                           paths={0: paths}))
    # 16 ms of the inner steps' kernels over 2 rounds x 2 replica steps
    assert reader.read(run) == pytest.approx(4.0)


@pytest.mark.parametrize("trace", ["small", "scoped"])
def test_a_program_without_the_kernel_reads_nothing(trace):
    path = os.path.join(DATA, f"{trace}.xplane.pb")
    job = {"replicas": 2, "inner_steps": 2, "batch": 2, "seq": 128}
    run = TracedRun(tr.load(path), job, {}, {}, [0], 2)
    run.trace_path = path
    assert reader.read(run) is None
