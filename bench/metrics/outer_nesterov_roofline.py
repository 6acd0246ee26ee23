"""Kernels: the fused outer Nesterov kernel's share of its HBM roofline,
in percent: the bytes the outer step must move for the rounds of the
traced window over the summed device time of the kernel's calls and the
chip's HBM bandwidth. The kernel is found by its calling convention: a
``tpu_custom_call`` with the f32[1] SMEM learning rate first and two
results (theta, momentum)."""
from bench import blocks
from bench import trace as tr
from bench.work.outer_nesterov import bytes_per_round


def is_nesterov(name: str) -> bool:
    return tr.tpu_custom_call(name) == (2, "f32[1]")


def read(run):
    ns = sum(o.end - o.start for c in run.chips for o in run.trace.ops[c]
             if run.lo <= o.start < run.hi and is_nesterov(o.name))
    if ns == 0:
        return None
    moved = run.rounds * bytes_per_round(
        blocks.load(run.cfg).n_params(run.cfg))
    return 100.0 * moved / (ns * 1e-9) / run.peaks["hbm_bytes_per_s"]
