"""Model step (eval): device self time of the in-graph validation
forward (scope ``diloco.eval``) per traced round, in ms, mean over the
cell's chips."""
from bench import scopes


def read(run):
    return scopes.per_round_ms(run, "diloco.eval")
