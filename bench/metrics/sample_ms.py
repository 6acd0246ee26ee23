"""Data: device self time of the in-graph sampler (scope
``diloco.sample``: the token draws for training and validation) per
traced round, in ms, mean over the cell's chips."""
from bench import scopes


def read(run):
    return scopes.per_round_ms(run, "diloco.sample")
