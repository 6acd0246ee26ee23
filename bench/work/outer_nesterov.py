"""HBM bytes the outer Nesterov step must move, per round.

theta <- theta - lr * (mu * b' + delta), b' = mu * b + delta reads the
global parameter, the outer gradient and the momentum buffer and writes
the parameter and the buffer: 3 reads and 2 writes of f32 for every
parameter, counted once per parameter whatever slices the kernel is
handed.
"""
from __future__ import annotations


def bytes_per_round(n_params: int) -> float:
    return float(n_params) * 5 * 4
