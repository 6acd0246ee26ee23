"""Readings that set the output check's limits: the program as it is
(``sound``), the program's own bfloat16 path (``bf16``, the control: the
next precision below the configuration's float32), and planted faults
(``half_batch``: the loss over the first half of each batch only;
``frozen``: a round that hands its state back unchanged).

    python -m bench.controls --workload 150m-k2-h10 --variant bf16 \\
        --seeds 11,12,13

Each seed runs the cell's compared rounds (no measured window) and the
reference, in one process, and prints one JSON line with every number
compared. The benchmark's own runs never run these variants.
"""
from __future__ import annotations

import argparse
import json
import sys


def patch_half_batch():
    """The loss (and its gradient) over the first half of the batch's
    rows, the mean taken over those rows."""
    from repro.models import model as M
    orig = M.loss_fn

    def half(params, cfg, batch, **kw):
        toks = batch["tokens"]
        return orig(params, cfg, dict(batch, tokens=toks[:toks.shape[0] // 2]),
                    **kw)

    M.loss_fn = half
    return lambda: setattr(M, "loss_fn", orig)


def patch_frozen():
    """Every round returns the state it was handed, with its metrics."""
    from repro.core import diloco
    orig = diloco.make_run

    def make_run(*a, **kw):
        fn = orig(*a, **dict(kw, donate=False))

        def frozen(state, *rest, **kw2):
            return state, fn(state, *rest, **kw2)[1]

        return frozen

    diloco.make_run = make_run
    return lambda: setattr(diloco, "make_run", orig)


VARIANTS = {
    "sound": {},
    "bf16": {"extra_flags": ("--param-dtype", "bfloat16",
                             "--master-dtype", "bfloat16")},
    "half_batch": {"patch": patch_half_batch},
    "frozen": {"patch": patch_frozen},
}


def reading(name: str, variant: str, seed: int, smoke: bool = False) -> dict:
    from bench.run import load_cell, run_cell
    every = dict.fromkeys(load_cell(name)[0]["limits"], float("inf"))
    r = run_cell(name, seed, 0.0, False, smoke=smoke, window=False,
                 limits=every, **VARIANTS[variant])
    return {"workload": name, "variant": variant, "seed": seed,
            "correct": r["correct"], "compared": r["compared"],
            "memory_peak_bytes": r["device"]["memory_peak_bytes"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variant", choices=sorted(VARIANTS), required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, run one after another")
    opts = ap.parse_args(argv)
    for seed in (int(s) for s in opts.seeds.split(",")):
        print(json.dumps(reading(opts.workload, opts.variant, seed)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
