"""The kind of block a configuration file states, one file each.

A configuration names its block with ``"block"`` (``"dense"`` where it
names none), and ``bench/blocks/<block>.py`` gives what the benchmark
needs to know of that kind of block:

  KEYS              the model keys the block reads from the file;
  FIXED             model keys whose value the block implements, and
                    nothing else: a file may state them only at that
                    value, and the program has to have it;
  n_params(cfg), flops_per_token(cfg, seq)
                    the counts of the work (per-layer metrics);
  init_params(key, cfg), loss(cfg, params, tokens), row_block(cfg, B, S)
                    the plain reference model (``bench/reference.py``).

A new kind of block is a new file here. ``load`` refuses a file stating
a model key its block does not read, so one block's counts and model are
never handed another block's configuration.
"""
from __future__ import annotations

import importlib

# keys of a configuration file that describe it rather than the model
BOOKKEEPING = frozenset((
    "arch", "source", "source_part", "reduced", "assumed", "smoke",
    "n_params", "norm_eps", "master_dtype", "matmul_precision",
    "param_dtype", "block"))


def load(cfg: dict):
    """The block module that configuration ``cfg`` names, after refusing
    a stated key the block does not read and a fixed key stated at
    another value."""
    name = cfg.get("block", "dense")
    try:
        mod = importlib.import_module(f"{__name__}.{name}")
    except ModuleNotFoundError as e:
        raise ValueError(f"no block file for block {name!r}") from e
    extra = sorted(set(cfg) - BOOKKEEPING - set(mod.KEYS) - set(mod.FIXED))
    if extra:
        raise ValueError(f"block {name!r} reads no {extra}")
    wrong = {k: (cfg[k], v) for k, v in mod.FIXED.items()
             if k in cfg and cfg[k] != v}
    if wrong:
        raise ValueError(f"block {name!r} implements only other values "
                         f"(file, block): {wrong}")
    return mod
