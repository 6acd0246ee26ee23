"""Record ``bench/tests/data/small.xplane.pb``, the trace the reduction's
tests read. Run on a TPU host:

    python -m bench.tests.record_small_trace

Two dispatches, each inside a ``bench.dispatch`` span and followed by a
``bench.ingest`` span, with a 20 ms sleep between them that leaves the
chip idle: the first runs the trainer's fused AdamW kernel on a
1024 x 128 leaf, the second its outer Nesterov kernel on the same leaf
and a 512 x 512 matmul.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "data", "small.xplane.pb")


def main() -> int:
    sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))
    import jax
    import jax.numpy as jnp
    from repro.kernels.fused_adamw import fused_adamw
    from repro.kernels.outer_nesterov import outer_nesterov
    if jax.devices()[0].platform != "tpu":
        print("record_small_trace: needs a TPU", file=sys.stderr)
        return 1
    x = jnp.ones((1024, 128), jnp.float32)
    adamw = jax.jit(lambda p, g, m, v: fused_adamw(
        p, g, m, v, lr=1e-3, c1=0.1, c2=0.05))
    step = jax.jit(lambda p, d, b, a: (outer_nesterov(p, d, b, lr=0.7),
                                       a @ a))
    a = jnp.ones((512, 512), jnp.float32)
    jax.block_until_ready((adamw(x, x, x, x), step(x, x, x, a)))
    tmp = os.path.join(HERE, "data", "tmp_trace")
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.dispatch"):
        out = adamw(x, x, x, x)
    with jax.profiler.TraceAnnotation("bench.ingest"):
        jax.block_until_ready(out)
    time.sleep(0.02)
    with jax.profiler.TraceAnnotation("bench.dispatch"):
        out = step(x, x, x, a)
    with jax.profiler.TraceAnnotation("bench.ingest"):
        jax.block_until_ready(out)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    shutil.copy(path, OUT)
    shutil.rmtree(tmp)
    print(f"wrote {OUT} ({os.path.getsize(OUT)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
