"""HBM bytes the AdamW update must move, per parameter and replica step.

An AdamW step reads the parameter, its gradient and both moments, and
writes the parameter and both moments back: 4 reads and 3 writes of the
state's element size. Padding the kernel adds to reach its tile is not
work and is not counted.
"""
from __future__ import annotations

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def bytes_per_param(param_dtype: str) -> int:
    return 7 * DTYPE_BYTES[param_dtype]


def bytes_per_replica_step(n_params: int, param_dtype: str) -> float:
    return float(n_params) * bytes_per_param(param_dtype)
