"""Model step: ``step_mfu``'s model FLOPs of the traced rounds' training
tokens over the device self time of the inner steps alone (scope
``diloco.inner``; the AdamW update, ``diloco.adamw``, is left out), the
cell's chips and their bf16 peak, in percent."""
from bench import blocks, scopes


def read(run):
    ns = scopes.phases(run).self_ns(
        lambda p: scopes.phase(p) == "diloco.inner")
    if not ns:
        return None
    flops = (blocks.load(run.cfg).flops_per_token(run.cfg, run.job["seq"])
             * run.tokens)
    return 100.0 * flops / (ns * 1e-9 * len(run.chips)
                            * run.peaks["bf16_flops_per_s"])
