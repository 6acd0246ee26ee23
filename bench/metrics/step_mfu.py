"""Model step: model FLOPs of the training tokens of the traced rounds
over the time in which an operation ran on the device (the union of the
op intervals in the traced window, mean over the cell's chips), the
cell's chips and their bf16 peak, in percent (the count of the
configuration's block, ``bench/blocks/<block>.py``). The device time is
the whole round program's: sampler, inner steps, outer step and eval.
Host gaps are left out, so this differs from the end-to-end rate by the
idle share."""
from bench import blocks
from bench import trace as tr


def read(run):
    busy = [tr.busy_ns([(o.start, o.end) for o in run.trace.ops[c]],
                       run.lo, run.hi) for c in run.chips]
    busy_s = sum(busy) / len(busy) * 1e-9
    flops = (blocks.load(run.cfg).flops_per_token(run.cfg, run.job["seq"])
             * run.tokens)
    return 100.0 * flops / (busy_s * len(run.chips)
                            * run.peaks["bf16_flops_per_s"])
