"""Record ``bench/tests/data/scoped.xplane.pb``, the trace the scope
readers' tests read. Run on a TPU host:

    python -m bench.tests.record_scoped_trace

The trainer's own entry point (``repro.launch.train.run``) runs the
smoke-size ``diloco_150m`` job (k=2 replicas, H=2 inner steps, batch 2
× 128 tokens, Pallas kernels, one round per call, eval every round)
for three rounds; the profiler traces rounds 2 and 3, each dispatch
inside a ``bench.dispatch`` span and each metrics fetch inside a
``bench.ingest`` span, as ``bench/run.py`` marks them, around the
program's own ``diloco.*`` spans. To keep the file small, only the
device planes' ``XLA Ops`` line, the ``tf_op`` of their op metadata,
the host plane and the profile's start stamp are kept.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
OUT = os.path.join(HERE, "data", "scoped.xplane.pb")
ARGV = ["--arch", "diloco_150m", "--smoke", "--seed", "7", "--k", "2",
        "--H", "2", "--rounds", "3", "--batch", "2", "--seq", "128",
        "--eval-batch", "2", "--kernel-mode", "pallas",
        "--rounds-per-call", "1", "--eval-every", "1"]


def _enc_varint(x: int) -> bytes:
    out = bytearray()
    while True:
        b, x = x & 0x7F, x >> 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def _field(number: int, payload: bytes) -> bytes:
    return _enc_varint(number << 3 | 2) + _enc_varint(len(payload)) \
        + bytes(payload)


def _raw(number: int, value) -> bytes:
    if isinstance(value, int):
        return _enc_varint(number << 3) + _enc_varint(value)
    return _field(number, value)


def strip(space: bytes) -> bytes:
    """The trace without what the readers never read (see above)."""
    from bench import scopes
    from bench import trace as tr
    out = bytearray()
    for f, plane in scopes._fields(memoryview(space)):
        if f != 1:
            continue
        fields = list(scopes._fields(plane))
        name = next((bytes(v).decode() for g, v in fields if g == 2), "")
        if name in ("/host:CPU", "Task Environment"):
            out += _field(1, plane)
            continue
        if not tr.DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for g, v in fields:
            if g == 5:
                md = dict(scopes._fields(scopes._map_values(v)))
                stat_names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
        keep = bytearray()
        for g, v in fields:
            if g == 3:
                line = dict(scopes._fields(v))
                if bytes(line.get(2, b"")).decode() != tr.OPS_LINE:
                    continue
            elif g == 4:
                key, md = dict(scopes._fields(v))[1], scopes._map_values(v)
                body = bytearray()
                for h, w in scopes._fields(md):
                    if h == 5 and stat_names.get(
                            dict(scopes._fields(w)).get(1)) != "tf_op":
                        continue
                    body += _raw(h, w)
                v = _raw(1, key) + _field(2, body)
            keep += _raw(g, v)
        out += _field(1, keep)
    return bytes(out)


def main() -> int:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    import jax
    from repro.core import diloco
    from repro.launch import train
    from repro.obs.metrics import RunRecorder
    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace: needs a TPU", file=sys.stderr)
        return 1
    tmp = os.path.join(HERE, "data", "tmp_trace")
    shutil.rmtree(tmp, ignore_errors=True)

    class Recorder(RunRecorder):
        def ingest_chunk(self, stacked_metrics):
            with jax.profiler.TraceAnnotation("bench.ingest"):
                out = super().ingest_chunk(stacked_metrics)
            if self.ingest_calls == 1:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(tmp, profiler_options=opts)
            elif self.ingest_calls == 3:
                jax.profiler.stop_trace()
            return out

    make_run = diloco.make_run

    def spanned(*a, **kw):
        fn = make_run(*a, **kw)

        def call(*args, **kwargs):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                return fn(*args, **kwargs)

        return call

    diloco.make_run = spanned
    try:
        train.run(train.make_parser().parse_args(ARGV),
                  recorder=Recorder(printer=lambda *a, **k: None))
    finally:
        diloco.make_run = make_run
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    with open(path, "rb") as f:
        raw = f.read()
    data = strip(raw)
    with open(OUT, "wb") as f:
        f.write(data)
    shutil.rmtree(tmp)
    print(f"wrote {OUT} ({len(data)} bytes, {len(raw)} before stripping)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
