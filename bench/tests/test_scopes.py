"""The scope readers (``bench/scopes.py`` and the metrics built on it):
the phase and pass of synthetic op paths, self time per phase, the
XSpace metadata reader on the small recorded trace, the kernel and
device readers' values pinned on that trace, the set-up reader's window
stamp on a trace taken on the CPU, and the program's scopes and spans
on a trace of the real round program (``record_scoped_trace.py``)."""
import glob
import importlib
import os

import pytest

from bench import scopes
from bench import trace as tr
from bench.run import TracedRun

DATA = os.path.join(os.path.dirname(__file__), "data")
SMALL = os.path.join(DATA, "small.xplane.pb")
NEW = ("sample_ms", "fwd_ms", "bwd_ms", "inner_mfu", "outer_ms", "eval_ms",
       "setup_compile_s")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def read(name, run):
    return importlib.import_module(f"bench.metrics.{name}").read(run)


INNER = "jit(run_fn)/while/body/closed_call/diloco.inner"


@pytest.mark.parametrize("path,phase,pass_", [
    (f"{INNER}/jvp(loss)/dot_general", "diloco.inner", "fwd"),
    (f"{INNER}/transpose(jvp(loss))/dot_general", "diloco.inner", "bwd"),
    (f"{INNER}/transpose(jvp(loss))/while/body/checkpoint/"
     "rematted_computation/dot_general", "diloco.inner", "bwd"),
    (f"mul;{INNER}/transpose(jvp(loss))/mul", "diloco.inner", "bwd"),
    (f"{INNER}/diloco.adamw/fused_adamw/pallas_call:", "diloco.adamw", ""),
    ("jit(run_fn)/vmap(jit(sample_all_shards))/vmap(diloco.sample)/while",
     "diloco.sample", ""),
    ("jit(run_fn)/diloco.outer/diloco.sync/all-reduce", "diloco.sync", ""),
    ("jit(run_fn)/while/body/cond/branch_1_fun/diloco.eval/dot_general",
     "diloco.eval", ""),
    ("jit(run_fn)/while/body/transpose", "", ""),
    ("", "", ""),
])
def test_phase_and_pass_of_a_path(path, phase, pass_):
    assert scopes.phase(path) == phase
    assert scopes.pass_of(path) == pass_


def test_phase_self_time_excludes_nested_ops():
    # a loop of the inner step encloses the AdamW kernel of its body
    ops = [tr.Op("loop", 0, 100), tr.Op("adamw", 10, 40),
           tr.Op("matmul", 50, 90), tr.Op("copy", 120, 130)]
    paths = {"loop": f"{INNER}/while", "adamw": f"{INNER}/diloco.adamw/k",
             "matmul": f"{INNER}/jvp(loss)/dot_general"}
    acc = scopes.sum_by_path(tr.self_times(ops), paths)
    inner = sum(ns for p, ns in acc.items()
                if scopes.phase(p) == "diloco.inner")
    assert inner == 30 + 40                      # loop's own 30, matmul 40
    assert acc[paths["adamw"]] == 30
    assert acc[""] == 10                         # the copy has no path


@pytest.fixture(scope="module")
def small():
    """The small trace as a run of a tiny job: 2 rounds × 1 step."""
    cfg = {"n_layers": 2, "d_model": 64, "n_heads": 2, "n_kv_heads": 2,
           "head_dim": 32, "d_ff": 256, "vocab_size": 100,
           "mlp_gated": False, "tie_embeddings": True,
           "param_dtype": "float32"}
    job = {"replicas": 1, "inner_steps": 1, "batch": 1, "seq": 16}
    run = TracedRun(tr.load(SMALL), job, cfg, PEAKS, [0], 2)
    run.trace_path = SMALL
    return run


def test_metadata_reader_finds_the_kernels_op_paths():
    meta = scopes.Metadata(SMALL)
    assert list(meta.paths) == ["/device:TPU:0"]
    kernels = {name: op for name, op in meta.paths["/device:TPU:0"].items()
               if 'custom_call_target="tpu_custom_call"' in name}
    assert len(kernels) == 2
    assert all(op.startswith("jit(<lambda>)/pallas_call") for op in
               kernels.values())
    # compiler-inserted copies carry no op path
    copies = [n for n in tr.load(SMALL).ops[0] if "copy-start" in n.name]
    assert copies and not any(c.name in meta.paths["/device:TPU:0"]
                              for c in copies)
    assert meta.start_ns == 1792189627240721488


@pytest.mark.parametrize("name,value", [
    ("device_idle_frac", 0.9994276399415872),
    ("step_mfu", 0.7887427750684547),
    ("adamw_kernel_roofline", 113.75143679911619),
    ("outer_nesterov_roofline", 302.2614784547607)])
def test_older_readers_unchanged_on_the_small_trace(small, name, value):
    assert read(name, small) == pytest.approx(value, rel=1e-12)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_read_nothing_where_no_phase_is_named(small, name):
    assert read(name, small) is None


SCOPED = os.path.join(DATA, "scoped.xplane.pb")


@pytest.fixture(scope="module")
def scoped():
    """The recorded round program: smoke-size ``diloco_150m`` (the
    registry's block), k=2, H=2, batch 2 × 128, two traced rounds."""
    from repro.models.registry import get_smoke_arch
    m = get_smoke_arch("diloco_150m").cfg
    cfg = {k: getattr(m, k) for k in (
        "n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
        "vocab_size", "mlp_gated", "tie_embeddings")}
    cfg["head_dim"] = m.resolved_head_dim
    job = {"replicas": 2, "inner_steps": 2, "batch": 2, "seq": 128}
    run = TracedRun(tr.load(SCOPED), job, cfg, PEAKS, [0], 2)
    run.trace_path = SCOPED
    return run


@pytest.mark.parametrize("name", [n for n in NEW if n != "setup_compile_s"])
def test_scoped_trace_every_phase_reader_reads_a_number(scoped, name):
    value = read(name, scoped)
    assert value is not None and 0 < value < float("inf")


def test_scoped_trace_passes_and_coverage(scoped):
    assert read("bwd_ms", scoped) > read("fwd_ms", scoped)
    phases = scopes.phases(scoped)
    assert phases.coverage() >= 0.97
    rest = phases.unscoped(top=10**6)
    assert sum(share for _, share in rest) == pytest.approx(
        1 - phases.coverage())
    assert all("copy" in op or "convert" in op for op, _ in rest[:3])


@pytest.fixture(scope="module")
def window_on_cpu(tmp_path_factory):
    """A window traced on the CPU, with host stamps read around the
    span that opens it: one compile ends before the window, another
    runs inside it."""
    import time

    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    from repro.obs import profile
    profile.install()
    jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(7)).block_until_ready()
    x = jnp.ones(9).block_until_ready()
    out = tmp_path_factory.mktemp("window")
    jax.profiler.start_trace(str(out))
    time.sleep(0.05)
    before = time.perf_counter()
    with TraceAnnotation("bench.dispatch"):
        after = time.perf_counter()
        jax.jit(lambda x: x * 5.0 - 2.0)(x).block_until_ready()
    with TraceAnnotation("bench.ingest"):
        pass
    jax.profiler.stop_trace()
    path, = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    run = TracedRun(tr.load(path), {"replicas": 1, "inner_steps": 1,
                                    "batch": 1, "seq": 1},
                    {}, PEAKS, [], 1)
    run.trace_path = path
    return run, before, after


def test_window_open_falls_between_the_host_stamps_around_its_span(
        window_on_cpu):
    from bench.metrics.setup_compile_s import window_open
    run, before, after = window_on_cpu
    assert before - 1e-3 <= window_open(run) <= after + 1e-3


def test_setup_compile_counts_compiles_ending_before_the_window(
        window_on_cpu):
    from repro.obs import profile
    run, before, _ = window_on_cpu
    log = profile.entries()
    inside = [e for e in log if e["stage"] == profile.BACKEND
              and e["end"] > before]
    assert len(inside) == 1
    value = read("setup_compile_s", run)
    assert value == pytest.approx(profile.backend_seconds(
        [e for e in log if e["end"] <= before]))
    assert value < profile.backend_seconds(log)


def test_scoped_trace_holds_the_programs_host_spans(scoped):
    from jax.profiler import ProfileData
    spans = sorted((tr.Op(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for plane in ProfileData.from_file(SCOPED).planes
                    if plane.name == "/host:CPU"
                    for line in plane.lines for ev in line.events
                    if ev.name.startswith("diloco.")),
                   key=lambda o: o.start)
    names = {s.name for s in spans}
    assert {"diloco.dispatch", "diloco.ingest.wait", "diloco.ingest.copy",
            "diloco.emit"} <= names
    # the metrics copy runs inside the benchmark's ingest span
    ingest = [s for s in scoped.trace.spans if s.name == "bench.ingest"]
    copies = [s for s in spans if s.name == "diloco.ingest.copy"]
    assert len(copies) == len(ingest) == 2
    assert all(i.start <= c.start and c.end <= i.end
               for i, c in zip(ingest, copies))


def test_a_loop_without_a_path_takes_its_bodys_common_scope():
    adamw = f"{INNER}/diloco.adamw/k"
    sample = "jit(run_fn)/vmap(diloco.sample)/dot_general"
    ops = [tr.Op("loop", 0, 100), tr.Op("step", 10, 40),
           tr.Op("adamw", 50, 60), tr.Op("copy", 70, 80),
           tr.Op("round", 200, 400), tr.Op("draw", 210, 220),
           tr.Op("step", 230, 240), tr.Op("bare", 500, 600),
           tr.Op("copy", 510, 520)]
    paths = {"step": f"{INNER}/jvp(loss)/dot_general", "adamw": adamw,
             "draw": sample}
    assert scopes.loop_phases(ops, paths) == {"loop": "diloco.inner"}
