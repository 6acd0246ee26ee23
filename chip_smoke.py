"""Chip smoke test: train the paper's 150M DiLoCo model on a TPU through
the trainer's own entry point (``repro.launch.train.run``).

    python chip_smoke.py              # one chip (the default check)
    python chip_smoke.py --four-chip  # four chips: sharded transport
    python chip_smoke.py --rehearse [--four-chip]   # CPU rehearsal

One chip: ``diloco_150m`` at full width (217,012,096 params), k=2
replicas on the chip, 3 rounds × 5 inner steps, sequence 1024, batch 8
per replica, Pallas optimizer kernels. It fails on a non-finite loss, on a last-round
validation loss that is not below the first round's, and on a
non-finite entropy floor.

Four chips (``--four-chip``, only this phase): the same model with the
sharded transport (2 streaming fragments, f32 wire, k=4, batch 4 per
replica), once with one
replica per chip (``--pods 4``) and once with two replicas banded per
pod over the 2x2 mesh (``--pods 2``). The two layouts must agree: every
final global param within 1e-4, the final validation loss within 1e-3
relative, and no chip's peak memory more than 1.5× another's.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
when every check passed on a TPU. Without a TPU the script exits
non-zero and prints no such line. ``--rehearse`` runs the same phases at
the smoke model size with interpret-mode kernels on any backend; it is a
rehearsal of control flow, never the chip check, and prints no ok line.
Compilation is cached in ``JAX_COMPILATION_CACHE_DIR`` when set, else in
``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
PARAMS_150M = 217_012_096
# per-replica batch: the largest power of two that leaves about 2 GiB of
# HBM free in the v5e compiler's memory plan (four chips at pods=2 hold
# two replicas plus the replicated global state on every chip)
BATCH_ONE, BATCH_FOUR = 8, 4
PARAM_TOL = 1e-4            # four-chip: max |Δ| of final global params
VAL_RTOL = 1e-3             # four-chip: relative Δ of final val loss
PEAK_SKEW = 1.5             # four-chip: max / min per-chip peak bytes


def fail(msg: str) -> int:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    return 1


def train_argv(rehearse: bool, batch: int, *extra) -> list:
    """The CLI arguments of one smoke run (the full-width settings, or
    the smoke-size model with interpret kernels for a rehearsal)."""
    if rehearse:
        size = ["--smoke", "--seq", "64", "--batch", "2",
                "--eval-batch", "2", "--kernel-mode", "interpret"]
    else:
        size = ["--full", "--seq", "1024", "--batch", str(batch),
                "--eval-batch", "8", "--kernel-mode", "pallas"]
    return ["--arch", "diloco_150m", "--H", "5", "--rounds", "3",
            "--warmup", "2", "--rounds-per-call", "1", *size,
            *[str(a) for a in extra]]


def timed_recorder(transport: str):
    """A RunRecorder that stamps the host clock each time a scanned
    chunk's metrics reach the host (one chunk = one round here)."""
    from repro.obs.metrics import RunRecorder

    class Timed(RunRecorder):
        def __init__(self):
            super().__init__(transport=transport)
            self.chunk_done = []

        def ingest_chunk(self, stacked_metrics):
            out = super().ingest_chunk(stacked_metrics)
            self.chunk_done.append(time.perf_counter())
            return out

    return Timed()


def peak_bytes(jax) -> list:
    """peak_bytes_in_use of every device (None where not reported)."""
    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def train_once(train, argv, label: str):
    """One ``train.run`` call; returns (round records, recorder). The
    compile seconds are the backend compiles (persistent-cache loads
    included) of the program's compile log during the call."""
    from repro.obs import profile
    args = train.make_parser().parse_args(argv)
    rec = timed_recorder(args.transport)
    t0 = time.perf_counter()
    rounds = [r for r in train.run(args, recorder=rec)
              if r["kind"] == "round"]
    steady = [b - a for a, b in zip(rec.chunk_done, rec.chunk_done[1:])]
    hits = sum(e.get("cache") == "hit" for e in profile.entries())
    print(f"[{label}] wall {time.perf_counter() - t0:.1f}s, compile "
          f"{profile.backend_seconds(rec.compiles()):.1f}s "
          f"(persistent-cache hits so far: {hits}), steady round "
          + (f"{sum(steady) / len(steady):.2f}s" if steady else "n/a")
          + " (informational host-clock times)", flush=True)
    return rounds, rec


def entropy_floor_of(rec):
    for n in rec.manifest.get("notes", []):
        if "entropy_floor" in n:
            return n["entropy_floor"]
    return None


def check_losses(rounds, label: str):
    """None when every loss is finite and val fell; else the fault."""
    vals = [r["val_loss"] for r in rounds]
    inner = [r["inner_loss"] for r in rounds]
    print(f"[{label}] inner losses {inner}", flush=True)
    print(f"[{label}] val losses {vals}", flush=True)
    if len(rounds) != 3:
        return f"{label}: expected 3 round records, got {len(rounds)}"
    if not all(v is not None and math.isfinite(v) for v in vals + inner):
        return f"{label}: non-finite loss"
    if not vals[-1] < vals[0]:
        return (f"{label}: last val loss {vals[-1]} is not below the "
                f"first {vals[0]}")
    return None


def one_chip(jax, train, rehearse: bool) -> int:
    from repro.models.registry import get_arch, get_smoke_arch
    arch = (get_smoke_arch if rehearse else get_arch)("diloco_150m")
    shapes = jax.eval_shape(lambda k: arch.init(k)[0],
                            jax.random.PRNGKey(0))
    n = sum(math.prod(s.shape) for s in jax.tree.leaves(shapes))
    print(f"[one-chip] diloco_150m params: {n:,}", flush=True)
    if not rehearse and n != PARAMS_150M:
        return fail(f"expected {PARAMS_150M:,} params, got {n:,}")
    rounds, rec = train_once(
        train, train_argv(rehearse, BATCH_ONE, "--k", "2"), "one-chip")
    print(f"[one-chip] peak_bytes_in_use per device: {peak_bytes(jax)}",
          flush=True)
    fault = check_losses(rounds, "one-chip")
    if fault:
        return fail(fault)
    floor = entropy_floor_of(rec)
    print(f"[one-chip] entropy floor (nats): {floor}", flush=True)
    if floor is None or not 0 < floor < math.log(arch.cfg.vocab_size):
        return fail(f"entropy floor {floor} outside (0, log V)")
    return 0


def four_chip(jax, train, rehearse: bool) -> int:
    import numpy as np
    if len(jax.devices()) != 4:
        return fail(f"--four-chip needs 4 devices, found "
                    f"{len(jax.devices())}")
    finals = {}
    with tempfile.TemporaryDirectory() as tmp:
        # pods=4 first: device peaks only grow within a process, so the
        # one-replica-per-chip layout is read before the denser one
        for pods in (4, 2):
            label = f"four-chip pods={pods}"
            path = os.path.join(tmp, f"pods{pods}.npz")
            argv = train_argv(rehearse, BATCH_FOUR, "--k", "4",
                              "--transport", "sharded",
                              "--stream-fragments", "2", "--pods", pods,
                              "--checkpoint", path)
            rounds, _ = train_once(train, argv, label)
            peaks = peak_bytes(jax)
            print(f"[{label}] peak_bytes_in_use per device (process "
                  f"peak so far): {peaks}", flush=True)
            if None not in peaks and max(peaks) > PEAK_SKEW * min(peaks):
                return fail(f"{label}: one chip holds most of the state "
                            f"(peak bytes {peaks})")
            vals = [r["val_loss"] for r in rounds]
            print(f"[{label}] val losses {vals}", flush=True)
            if not all(v is not None and math.isfinite(v) for v in vals):
                return fail(f"{label}: non-finite val loss")
            with np.load(path) as data:      # host arrays, no device
                finals[pods] = (vals[-1], {
                    key: data[key] for key in data.files
                    if key.startswith("params")})
    (v4, p4), (v2, p2) = finals[4], finals[2]
    if sorted(p4) != sorted(p2) or not p4:
        return fail("the two layouts saved different param trees")
    diff = {key: np.abs(p4[key] - p2[key]) for key in p4}
    n = sum(d.size for d in diff.values())
    far = sum(int(np.count_nonzero(d > PARAM_TOL)) for d in diff.values())
    worst = max(diff, key=lambda key: float(diff[key].max()))
    dmax = float(diff[worst].max())
    rel = abs(v4 - v2) / abs(v2)
    print(f"[four-chip] pods=4 vs pods=2: max |Δ global params| = "
          f"{dmax:.3e} (in {worst}, limit {PARAM_TOL}), {far} of {n} "
          f"elements beyond it; val loss rel Δ = {rel:.3e} (limit "
          f"{VAL_RTOL})", flush=True)
    for key in sorted(diff):
        d = diff[key]
        print(f"[four-chip]   {key}: max {float(d.max()):.3e}, "
              f"{int(np.count_nonzero(d > PARAM_TOL))} of {d.size} "
              f"beyond {PARAM_TOL}", flush=True)
    if not dmax <= PARAM_TOL:
        return fail(f"global params differ by {dmax} > {PARAM_TOL}")
    if not rel <= VAL_RTOL:
        return fail(f"final val loss differs by {rel} > {VAL_RTOL}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the 4-chip sharded-transport phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="smoke-size model, interpret kernels, any "
                         "backend; never prints the ok line")
    opts = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.launch import train
    except ImportError as e:
        return fail(f"the repro package is not next to this script ({e})")
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not opts.rehearse:
        return fail(f"needs a TPU, but JAX's platform is "
                    f"{dev.platform!r} ({dev.device_kind})")
    cache = train.use_compile_cache()
    print(f"chip_smoke: {dev.platform} {dev.device_kind} × "
          f"{len(jax.devices())}, compile cache {cache}", flush=True)
    phase = four_chip if opts.four_chip else one_chip
    rc = phase(jax, train, opts.rehearse)
    if rc:
        return rc
    if opts.rehearse:
        print("chip_smoke: rehearsal passed (not a chip check)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
