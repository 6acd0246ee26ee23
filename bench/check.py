"""The output check: the program's first rounds against the plain
reference, number by number, each against its limit.

Numbers compared (k replicas, H inner steps, R = ``compare_rounds``):

  loss_r<t>       relative gap of round t's mean inner loss (over the k*H
                  steps, as the trainer reports it);
  outer_grad_r1   worst leaf's gap of the norm of round 1's outer
                  gradient (mean replica delta);
  change_r<R>     worst leaf's gap of the norm of the global parameters'
                  change over the R rounds.

A leaf's gap is |program norm - reference norm| over the larger of the
reference's norm of that leaf and of the median leaf. Leaves whose first
gradient in the reference is under a thousandth of the median leaf's move
by round-off alone and are left out of the leaf numbers.

A cell's traffic file gives each number's limit under ``limits``, by the
number's name.
"""
from __future__ import annotations

import math

import numpy as np

NOUGHT = 1e-3           # leaf gradient, as a share of the median leaf's


def rel(a: float, r: float) -> float:
    return abs(a - r) / abs(r)


def leaf_gap(prog: dict, ref: dict, keep) -> float:
    """Worst gap of per-leaf norms over the leaves in ``keep``."""
    if set(prog) != set(ref):
        raise ValueError(f"leaf sets differ: {sorted(set(prog) ^ set(ref))}")
    floor = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], floor) for k in keep)


def leaf_norms(tree) -> dict:
    """path -> float64 L2 norm of each leaf (device or numpy arrays)."""
    import jax
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(x, np.float32).ravel()
        out[jax.tree_util.keystr(path)] = float(np.sqrt(np.dot(a, a)))
    return out


def moving_leaves(grad0: dict) -> list:
    med = float(np.median(list(grad0.values())))
    return sorted(k for k, g in grad0.items() if g >= NOUGHT * med)


def numbers(losses: list, tap, ref: dict, rounds: int) -> dict:
    """name -> value of every number the check can compare; ``losses``
    holds the program's inner loss of each compared round."""
    keep = moving_leaves(ref["grad0"])
    vals = {f"loss_r{t + 1}": rel(losses[t], ref["inner_loss"][t])
            for t in range(rounds)}
    vals["outer_grad_r1"] = leaf_gap(tap.outer_grad, ref["outer_grad"], keep)
    vals[f"change_r{rounds}"] = leaf_gap(tap.change, ref["change"], keep)
    return vals


def compare(limits: dict, vals: dict) -> dict:
    """name -> {"value", "limit"} for every number ``limits`` names. A
    number that is not finite is reported as None and fails."""
    missing = sorted(set(limits) - set(vals))
    if missing:
        raise KeyError(f"limits name numbers the check has not: {missing}")
    return {k: {"value": vals[k] if math.isfinite(vals[k]) else None,
                "limit": li} for k, li in limits.items()}


def failing(compared: dict, limits: dict) -> list:
    """The numbers of ``compared`` that miss ``limits``."""
    return [k for k, c in compared.items() if k in limits
            and (c["value"] is None or c["value"] > limits[k])]


def passed(compared: dict) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in compared.values())
