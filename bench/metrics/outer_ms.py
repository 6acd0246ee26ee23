"""Outer step: device self time of the ``diloco.outer`` scope (deltas,
reduce, Nesterov, re-dispatch; the streaming merge) per traced round,
in ms, mean over the cell's chips. A streaming job's wire (scope
``diloco.sync``) is not part of it."""
from bench import scopes


def read(run):
    return scopes.per_round_ms(run, "diloco.outer")
