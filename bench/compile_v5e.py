"""Compile a cell's round program for a described TPU v5e, with no chip
attached, and print the compiler's memory plan. Run by hand before chip
time; the TPU compiler refuses here what would not fit or tile there.

    JAX_PLATFORMS=cpu python -m bench.compile_v5e --workload 400m-k1-h10

The program is the trainer's own scanned round (``diloco.make_run``,
one round per call) at the cell's sizes, built from the same flags
``bench.run`` passes, on shapes only (``jax.eval_shape``): nothing is
allocated and nothing runs, so the plan says nothing about time. It
builds the classic round on one chip; a cell on another transport or
mesh brings a script of its own.
"""
from __future__ import annotations

import argparse
import os
import sys

from bench.run import ROOT, load_cell, train_argv, use_config

GIB = 1 << 30


def compile_cell(name: str):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.core import diloco
    from repro.launch import train
    job, cfg = load_cell(name)
    if job["chips"] != 1 or job["transport"] != "simulated":
        raise SystemExit("compile_v5e handles one-chip simulated cells")
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    args = train.make_parser().parse_args(train_argv(job, cfg, 0, False))
    undo = use_config(train, cfg)
    arch, _, dcfg, tcfg, sampler = train.build(args)
    undo()
    state = jax.eval_shape(
        lambda k: diloco.init_state(arch.init(k)[0], dcfg),
        jax.random.PRNGKey(0))
    put = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)
    k = job["replicas"]
    run = diloco.make_run(
        lambda p, b: arch.loss(p, b), sampler.sample_all_shards, dcfg,
        tcfg, rounds_per_call=1, total_steps=tcfg.total_steps,
        batch_size=job["batch"], seq_len=job["seq"],
        eval_tokens=jnp.zeros((job["eval_batch"], job["seq"]), jnp.int32),
        eval_every=1)
    lowered = run.lower(
        jax.tree.map(put, state), put(jax.ShapeDtypeStruct((2,), jnp.uint32)),
        put(jax.ShapeDtypeStruct((1, k), jnp.float32)),
        put(jax.ShapeDtypeStruct((1, k), jnp.float32)),
        put(jax.ShapeDtypeStruct((k,), jnp.float32)),
        round_offset=put(jax.ShapeDtypeStruct((), jnp.int32)))
    return lowered.compile()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    opts = ap.parse_args(argv)
    mem = compile_cell(opts.workload).memory_analysis()
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "alias_size_in_bytes", "temp_size_in_bytes",
                  "generated_code_size_in_bytes"):
        v = getattr(mem, field)
        print(f"{opts.workload}: {field} = {v:,} ({v / GIB:.2f} GiB)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
