"""Device layer: share of the traced window in which no operation runs
on a chip, averaged over the cell's chips."""
from bench import trace as tr


def read(run):
    busy = [tr.busy_ns([(o.start, o.end) for o in run.trace.ops[c]],
                       run.lo, run.hi) for c in run.chips]
    return 1.0 - sum(busy) / len(busy) / (run.hi - run.lo)
