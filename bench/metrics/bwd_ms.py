"""Model step: device self time of the inner step's backward ops (scope
``diloco.inner``, path under ``transpose(``, the remat recompute
included) per replica step, in ms, mean over the cell's chips."""
from bench import scopes


def read(run):
    return scopes.per_step_ms(run, "bwd")
