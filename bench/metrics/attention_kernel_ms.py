"""Kernels: device self time of the flash attention kernel's calls in
the inner steps (scope ``diloco.inner``: the forward, its remat
recompute, ``bwd_dq`` and ``bwd_dkv``) per replica step, in ms, mean
over the cell's chips. A kernel is found by its name: a
``tpu_custom_call`` whose instruction name holds ``flash_attention``
(a transformation may wrap it: ``transpose_jvp_flash_attention_bwd_dq_``).
A program that runs no such kernel reads nothing."""
from bench import scopes
from bench import trace as tr


def is_flash(name: str) -> bool:
    return (tr.tpu_custom_call(name) is not None
            and "flash_attention" in name.partition(" = ")[0])


def inner_flash_ns(by_op: dict, paths: dict) -> float:
    """Summed self time of one chip's flash kernel ops under
    ``diloco.inner`` (``by_op``: op name -> self ns; ``paths``: op name
    -> op path)."""
    return sum(ns for name, ns in by_op.items() if is_flash(name)
               and scopes.phase(paths.get(name, "")) == "diloco.inner")


def read(run):
    ph = scopes.phases(run)
    ns = [inner_flash_ns(ph.by_op[c], ph.paths[c]) for c in ph.by_op]
    if not any(ns):
        return None
    steps = (run.rounds * run.job["replicas"] * run.job["inner_steps"]
             / len(run.chips))
    return sum(ns) / len(ns) / steps * 1e-6
