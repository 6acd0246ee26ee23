"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python -m bench.run --workload 150m-k2-h10 --seed 7 --seconds 30 --trace 0

A cell of ``BENCHMARK.json`` names a traffic file
``bench/traffic/<traffic>.json`` (the job: its replicas, inner steps,
batch, transport, and the limits of the output check) and a
configuration file ``bench/configs/<config>.json`` (the model's
published sizes and dtypes, and the kind of block, whose counts and
plain reference model are in ``bench/blocks/<block>.py``). The run
drives the trainer's own entry point, ``repro.launch.train.run``, with
the cell's flags and every model key the configuration file states:

  set-up   process start, build, compile or cache load, and the first
           ``compare_rounds`` rounds, whose outputs the check compares
           with the plain reference;
  window   whole rounds of the same compiled program, until ``--seconds``
           have passed; a round ends when its metrics reach the host;
  check    after the window, with the trainer's state freed, the
           reference the traffic file names (``bench/<reference>.py``)
           replays the compared rounds from the seed.

``--trace 0`` prints the end-to-end metrics; ``--trace 1`` traces the
window with the JAX profiler and prints the per-layer metrics, each read
by ``bench/metrics/<metric>.py`` from the reduced trace. The last line of
standard output is one JSON object. Without a TPU, with fewer chips than
the cell needs, or on a chip missing from ``bench/peaks.json``, the run
exits 1 and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()            # set-up is timed from here

import argparse
import dataclasses
import gc
import glob
import importlib.util
import json
import math
import os
import shutil
import sys

from bench import blocks

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")   # fixed: the path keys the cache
GIB = 1 << 30

# a rehearsal at smoke size: any backend, interpret-mode kernels
SMOKE = {"seq": 64, "batch": 2, "eval_batch": 2, "inner_steps": 2}


def log(msg: str):
    """A progress line on standard error, stamped with the seconds since
    the process started."""
    print(f"bench: {time.perf_counter() - T0:8.2f}s {msg}", file=sys.stderr,
          flush=True)


class NoChip(RuntimeError):
    pass


class WindowClosed(Exception):
    """Raised from the recorder once the window's last round is in."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, smoke: bool = False):
    """(job, cfg) of cell ``name`` of ``BENCHMARK.json``: its traffic
    file ``bench/traffic/<traffic>.json`` with the cell's chips, and its
    configuration file (the smoke sizes for a rehearsal)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json "
                         f"(cells: {sorted(cells)})")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    job = dict(load_json(BENCH, "traffic", f"{cell['traffic']}.json"),
               chips=cell["chips"])
    cfg = load_json(ROOT, files[cell["config"]])
    if smoke:
        job = dict(job, **SMOKE)
        if "n_params" not in cfg["smoke"]:
            cfg = {k: v for k, v in cfg.items() if k != "n_params"}
        cfg = dict(cfg, **cfg["smoke"])
    return job, cfg


def train_argv(job: dict, cfg: dict, seed: int, smoke: bool) -> list:
    """The trainer's command line for the cell."""
    argv = ["--arch", cfg["arch"], "--smoke" if smoke else "--full",
            "--seed", str(seed),
            "--k", str(job["replicas"]), "--H", str(job["inner_steps"]),
            "--rounds", str(job["schedule_rounds"]),
            "--batch", str(job["batch"]), "--seq", str(job["seq"]),
            "--eval-batch", str(job["eval_batch"]),
            "--regime", job["regime"], "--transport", job["transport"],
            "--inner-lr", str(job["inner_lr"]),
            "--warmup", str(job["warmup"]),
            "--outer-lr", str(job["outer_lr"]),
            "--outer-momentum", str(job["outer_momentum"]),
            "--param-dtype", cfg["param_dtype"],
            "--master-dtype", cfg["master_dtype"],
            "--kernel-mode", "interpret" if smoke else "pallas",
            "--rounds-per-call", "1", "--eval-every", "1"]
    if job.get("fragments"):
        argv += ["--stream-fragments", str(job["fragments"]),
                 "--stream-tau", str(job["stream_tau"]),
                 "--outer-grad-dtype", job["wire_dtype"]]
    if job.get("pods"):
        argv += ["--pods", str(job["pods"])]
    return argv


# fields of the model config that the trainer's own flags set
FLAG_KEYS = frozenset(("param_dtype", "kernel_mode"))


def model_keys(cfg: dict) -> list:
    """The keys of configuration ``cfg`` that set the trainer's model:
    its fields of ``repro.configs.base.ModelConfig``, bar those the
    trainer's flags set and the bookkeeping keys (``bench.blocks``).
    Refuses any other key."""
    from repro.configs.base import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    unknown = sorted(set(cfg) - (fields - FLAG_KEYS) - blocks.BOOKKEEPING)
    if unknown:
        raise SystemExit(f"configuration keys that are neither a model "
                         f"key nor bookkeeping: {unknown}")
    return [k for k in cfg if k in fields and k not in blocks.BOOKKEEPING]


def model_values(cfg: dict) -> dict:
    """key -> value of ``model_keys``; a JSON list becomes a tuple, as
    the model config holds it."""
    return {k: tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k]
            for k in model_keys(cfg)}


def use_config(train, cfg: dict):
    """Hand the trainer the configuration file's model: the registry's
    model config with every key of ``model_keys`` set from the file.
    Returns an undo."""
    from repro.models.registry import Arch
    model = model_values(cfg)
    orig = train.get_arch, train.get_smoke_arch
    train.get_arch, train.get_smoke_arch = (
        (lambda name, get=get: Arch(cfg=get(name).cfg.replace(**model)))
        for get in orig)

    def undo():
        train.get_arch, train.get_smoke_arch = orig

    return undo


# model keys whose value the model config resolves from others when 0
RESOLVED = ("head_dim", "v_head_dim")


def program_size(arch) -> int:
    """Parameters in the trainer's tree of ``arch``, from shapes alone."""
    import jax
    return sum(math.prod(x.shape)
               for x in jax.tree.leaves(arch.abstract_params()[0]))


def program_gaps(train, args, job: dict, cfg: dict) -> dict:
    """name -> (program, file) of every setting in which the program
    built from ``args`` departs from the files: each model key the
    configuration states (head widths as resolved), the values the
    configuration's block implements, its parameter count and the file's
    against the program's tree, the inner optimizer and the data."""
    arch, mcfg, dcfg, tcfg, sampler = train.build(args)
    block = blocks.load(cfg)
    want = dict(model_values(cfg), **block.FIXED)
    have = {k: getattr(mcfg, f"resolved_{k}" if k in RESOLVED else k)
            for k in want}
    size = program_size(arch)
    want["n_params (block)"], have["n_params (block)"] = (
        block.n_params(cfg), size)
    if "n_params" in cfg:
        want["n_params"], have["n_params"] = cfg["n_params"], size
    for k in ("b1", "b2", "eps", "weight_decay", "grad_clip"):
        want[k], have[k] = job[k], getattr(tcfg, k)
    want["data_alpha"], have["data_alpha"] = job["data_alpha"], sampler.alpha
    return {k: (have[k], want[k]) for k in want if have[k] != want[k]}


def check_program(train, args, job: dict, cfg: dict):
    """Refuse to run a program whose model or optimizer settings differ
    from the files: the reference and the counts follow the files."""
    bad = program_gaps(train, args, job, cfg)
    if bad:
        raise SystemExit(f"program differs from the benchmark's files "
                         f"(program, file): {bad}")


class CompileClock:
    """Counts backend compiles and persistent-cache hits (jax.monitoring)."""

    def __init__(self):
        from jax import monitoring
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def host_leaf_norms(tree) -> dict:
    from bench.check import leaf_norms
    import jax
    return leaf_norms(jax.device_get(tree))


class Tap:
    """Wraps the trainer's round program (``diloco.make_run``): marks
    each dispatch with a host span and reads, from the state it hands
    back, what the check compares: the initial global parameters, round
    1's outer gradient (the outer momentum after one step from zero) and
    the global parameters after ``rounds`` rounds."""

    def __init__(self, rounds: int):
        self.rounds = rounds
        self.calls = 0
        self.theta0 = None
        self.outer_grad = None
        self.change = None

    def wrap(self, make_run):
        import jax
        import numpy as np

        def make(*a, **kw):
            fn = make_run(*a, **kw)

            def call(state, *rest, **kw2):
                self.calls += 1
                if self.calls == 1:
                    log("first round dispatched")
                    self.theta0 = jax.device_get(state.global_params)
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    out = fn(state, *rest, **kw2)
                if self.calls == 1:
                    self.outer_grad = host_leaf_norms(out[0].outer_state.buf)
                    log("round 1 done, outer gradient read")
                if self.calls == self.rounds:
                    now = jax.device_get(out[0].global_params)
                    self.change = host_leaf_norms(
                        jax.tree.map(np.subtract, now, self.theta0))
                    self.theta0 = None
                    log(f"round {self.rounds} done, change read")
                return out

            return call

        return make


def make_recorder(transport: str, rounds: int, seconds, trace: bool,
                  clock: CompileClock):
    """A RunRecorder that stamps each round's end on the host clock,
    opens the window after the compared rounds, and closes it (raising
    ``WindowClosed``) at the first round end ``seconds`` after that, or
    at once where ``seconds`` is None."""
    import jax
    from repro.obs.metrics import RunRecorder

    class Recorder(RunRecorder):
        def __init__(self):
            super().__init__(transport=transport,
                             printer=lambda *a, **k: print(
                                 *a, file=sys.stderr, flush=True))
            self.stamps = []
            self.losses = []            # (inner loss, val loss) per round
            self.t_open = None
            self.t_close = None
            self.compiles_at_open = None

        def ingest_chunk(self, stacked_metrics):
            with jax.profiler.TraceAnnotation("bench.ingest"):
                out = super().ingest_chunk(stacked_metrics)
            now = time.perf_counter()
            self.stamps.append(now)
            self.losses.append((float(out["inner_loss"][0]),
                                float(out["val_loss"][0])))
            n = len(self.stamps)
            log(f"round {n} metrics in")
            if n == rounds and seconds is None:
                raise WindowClosed()
            if n == rounds:
                self.compiles_at_open = clock.compiles
                if trace:
                    shutil.rmtree(TRACE_DIR, ignore_errors=True)
                    jax.profiler.start_trace(
                        TRACE_DIR, profiler_options=_profile_options())
                self.t_open = time.perf_counter()
            elif n > rounds and now - self.t_open >= seconds:
                self.t_close = now
                if trace:
                    jax.profiler.stop_trace()
                raise WindowClosed()
            return out

    return Recorder()


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def load_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", os.path.join(BENCH, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class TracedRun:
    """What a per-layer reader sees: the reduced trace and the counts of
    the work done in the traced window."""

    def __init__(self, trace, job, cfg, peaks, chips, rounds):
        from bench.trace import window
        self.trace = trace
        self.job, self.cfg, self.peaks = job, cfg, peaks
        self.chips = chips                     # chip indices in the trace
        self.rounds = rounds                   # whole rounds traced
        self.lo, self.hi = window(trace)
        self.window_s = (self.hi - self.lo) * 1e-9
        self.tokens = rounds * tokens_per_round(job)


def tokens_per_round(job: dict) -> int:
    return (job["replicas"] * job["inner_steps"] * job["batch"]
            * job["seq"])


def reduce_trace(job, cfg, peaks, chips: int, rounds: int, metric_names):
    """(per-layer metrics, busy_s, window_s, breakdown) of the traced
    window."""
    from bench import trace as tr
    paths = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file, found {paths}")
    t = tr.load(paths[0])
    ids = sorted(t.ops)[:chips]
    run = TracedRun(t, job, cfg, peaks, ids, rounds)
    metrics = {}
    for name, unit in metric_names:
        value = load_reader(name)(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}
    busy = [tr.busy_ns([(o.start, o.end) for o in t.ops[c]], run.lo, run.hi)
            for c in ids]
    busy_s = sum(busy) / len(busy) * 1e-9
    selfs: dict = {}
    for c in ids:
        for name, ns in tr.self_times(
                [o for o in t.ops[c] if o.end > run.lo and o.start < run.hi]
        ).items():
            key = tr.short_name(name)
            selfs[key] = selfs.get(key, 0.0) + ns * 1e-9 / len(ids)
    top = sorted(selfs.items(), key=lambda kv: -kv[1])[:10]
    idle = tr.gaps([(o.start, o.end) for o in t.ops[ids[0]]], run.lo, run.hi)
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:10]
    breakdown = {
        "device_ops": [[n, s] for n, s in top],
        "idle_gaps": [[tr.label_gap(t, s, e), (e - s) * 1e-9]
                      for s, e in longest]}
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return metrics, busy_s, run.window_s, breakdown


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             smoke: bool = False, extra_flags=(), patch=None,
             window: bool = True, limits=None) -> dict:
    """One run of cell ``name``. Returns the result object (``metrics``
    empty in a rehearsal). ``extra_flags`` and ``patch`` (a callable that
    breaks the program and returns an undo) serve the control and fault
    readings; ``window=False`` stops after the compared rounds;
    ``limits`` replaces the cell's limits (the readings that set them)."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    bench_cfg = load_json(ROOT, "BENCHMARK.json")
    job, cfg = load_cell(name, smoke)
    if limits is not None:
        job["limits"] = limits
    chips = job["chips"]
    import jax
    devices = jax.devices()
    dev = devices[0]
    peaks = None
    if not smoke:
        from bench import peaks as peak_table
        if dev.platform != "tpu":
            raise NoChip(f"needs a TPU, JAX runs on {dev.platform!r} "
                         f"({dev.device_kind})")
        if len(devices) < chips:
            raise NoChip(f"cell {name} needs {chips} chips, JAX sees "
                         f"{len(devices)}")
        try:
            peaks = peak_table.lookup(dev.device_kind)
        except peak_table.UnknownDevice as e:
            raise NoChip(str(e)) from e
    from repro.core import diloco
    from repro.launch import train
    import numpy as np
    if not smoke:
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    log(f"{dev.platform} {dev.device_kind} x {len(devices)}")
    clock = CompileClock()
    rounds = job["compare_rounds"]
    args = train.make_parser().parse_args(
        train_argv(job, cfg, seed, smoke) + list(extra_flags))
    undo_model = use_config(train, cfg)
    check_program(train, args, job, cfg)
    tap = Tap(rounds)
    rec = make_recorder(args.transport, rounds,
                        seconds if window else None, trace, clock)
    undo = patch() if patch is not None else None
    make_run = diloco.make_run
    diloco.make_run = tap.wrap(make_run)
    try:
        train.run(args, recorder=rec)
    except WindowClosed:
        pass
    finally:
        diloco.make_run = make_run
        undo_model()
        if undo is not None:
            undo()
    gc.collect()
    log("program state freed")
    if window and rec.t_close is None:
        raise RuntimeError("the run ended before the window closed: raise "
                           "schedule_rounds")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:chips])
    window_rounds = max(0, len(rec.stamps) - rounds)
    result = {"correct": False, "attempted": window_rounds,
              "failed": sum(not all(map(math.isfinite, ls))
                            for ls in rec.losses[rounds:]),
              "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": chips, "memory_peak_bytes": peak}}
    if window and not smoke:
        per_chip = tokens_per_round(job) * window_rounds / chips
        e2e = {"tokens_per_s_per_chip":
               per_chip / (rec.t_close - rec.t_open),
               "peak_hbm_gib": peak / GIB,
               "setup_s": rec.t_open - T0}
        units = {m["name"]: m["unit"] for m in bench_cfg["end_to_end"]}
        if trace:
            names = [(m["name"], m["unit"]) for m in bench_cfg["per_layer"]
                     if name in m.get("workloads", [name])]
            log("reducing the trace")
            metrics, busy_s, window_s, breakdown = reduce_trace(
                job, cfg, peaks, chips, window_rounds, names)
            log("trace reduced")
            result["metrics"] = metrics
            result["device"].update(busy_s=busy_s, window_s=window_s)
            result["breakdown"] = breakdown
        else:
            result["metrics"] = {k: {"value": v, "unit": units[k]}
                                 for k, v in e2e.items()}
    result["window"] = {"rounds": window_rounds,
                        "compiles_in_window": (
                            clock.compiles - rec.compiles_at_open
                            if rec.compiles_at_open is not None else None),
                        "compiles_in_setup": rec.compiles_at_open,
                        "cache_hits": clock.cache_hits}
    from bench import check
    reference = importlib.import_module(f"bench.{job['reference']}")
    ref = reference.run(cfg, job, seed, rounds, log)
    log("reference done")
    compared = check.compare(job["limits"], check.numbers(
        [inner for inner, _ in rec.losses], tap, ref, rounds))
    result["correct"] = check.passed(compared)
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = ap.parse_args(argv)
    try:
        result = run_cell(opts.workload, opts.seed, opts.seconds,
                          bool(opts.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 1
    w = result["window"]
    print(f"bench: {w['rounds']} rounds in the window, "
          f"{w['compiles_in_window']} compiles inside it, "
          f"{w['compiles_in_setup']} in set-up "
          f"({w['cache_hits']} persistent-cache hits)", file=sys.stderr)
    for k, c in result["compared"].items():
        print(f"bench: compared {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
