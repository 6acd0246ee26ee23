"""Model step: device self time of the inner step's forward ops (scope
``diloco.inner``, path under ``jvp(`` and not ``transpose(``) per
replica step, in ms, mean over the cell's chips."""
from bench import scopes


def read(run):
    return scopes.per_step_ms(run, "fwd")
