"""Kernels: the fused AdamW kernel's share of its HBM roofline, in
percent: the bytes AdamW must move for the replica steps of the traced
window over the summed device time of the kernel's calls and the chip's
HBM bandwidth. The kernel is found by its calling convention (Pallas
calls carry no name in the trace): a ``tpu_custom_call`` with the f32[3]
SMEM scalars (lr, c1, c2) first and three results (p, m, v)."""
from bench import blocks
from bench import trace as tr
from bench.work.fused_adamw import bytes_per_replica_step


def is_adamw(name: str) -> bool:
    return tr.tpu_custom_call(name) == (3, "f32[3]")


def read(run):
    ns = sum(o.end - o.start for c in run.chips for o in run.trace.ops[c]
             if run.lo <= o.start < run.hi and is_adamw(o.name))
    if ns == 0:
        return None
    steps = run.rounds * run.job["replicas"] * run.job["inner_steps"]
    n = blocks.load(run.cfg).n_params(run.cfg)
    moved = steps * bytes_per_replica_step(n, run.cfg["param_dtype"])
    return 100.0 * moved / (ns * 1e-9) / run.peaks["hbm_bytes_per_s"]
