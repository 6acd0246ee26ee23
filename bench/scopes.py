"""The program's phase scopes in a JAX profiler trace.

The trainer names each phase of a DiLoCo round in its compiled program
(``jax.named_scope``: ``diloco.sample``, ``diloco.inner``,
``diloco.adamw``, ``diloco.outer``, ``diloco.sync``, ``diloco.eval``;
``src/repro/obs/profile.py`` lists them). A device op's path, its HLO
``op_name``, is the ``tf_op`` stat of the op's event metadata on the
device plane, which ``jax.profiler.ProfileData`` does not expose. This
module reads it from the XSpace protobuf wire format with the standard
library alone:

  XSpace.planes = 1
  XPlane.name = 2, lines = 3 (skipped), event_metadata = 4,
         stat_metadata = 5, stats = 6
  XEventMetadata.name = 2, stats = 5
  XStatMetadata.name = 2
  XStat.metadata_id = 1, uint64_value = 3, str_value = 5, ref_value = 7

An op's phase is the innermost ``diloco.*`` token on its path; a
transformation may wrap the token (``vmap(diloco.sample)``). XLA's loop
ops carry no path in the trace: one whose nested ops all share a phase
takes that phase (``loop_phases``). An op's pass is ``bwd`` where the
path holds ``transpose(`` (the remat recompute included), ``fwd`` where
it holds ``jvp(`` alone. A phase's time is the sum of its ops' self
times (``trace.self_times``), never a union: a loop op in one phase
encloses the ops of its body, which may belong to another.
"""
from __future__ import annotations

import glob
import os
import re

from bench import trace as tr

BENCH = os.path.dirname(os.path.abspath(__file__))
# where bench/run.py leaves the window's trace while the readers run
TRACE_DIR = os.path.join(os.path.dirname(BENCH), ".bench_trace")
PHASE = re.compile(r"\bdiloco\.[a-z_]+")


def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of each field of one message: an int for
    a varint, a memoryview for a length-delimited field, None for the
    fixed-width ones (no field read here uses them)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def _map_values(entry):
    """The value (field 2) of one protobuf map entry."""
    return next((v for f, v in _fields(entry) if f == 2), b"")


def _stats(stats, names) -> dict:
    """name -> value of XStat messages: a string (str or interned ref)
    or an unsigned integer."""
    out = {}
    for raw in stats:
        d = dict(_fields(raw))
        name = names.get(d.get(1))
        if 5 in d:
            out[name] = bytes(d[5]).decode(errors="replace")
        elif 7 in d:
            out[name] = names.get(d[7], "")
        elif 3 in d:
            out[name] = d[3]
    return out


class Metadata:
    """paths: device plane name -> {op event name: tf_op path};
    start_ns: the profile's start, Unix ns (the trace's clock zero)."""

    def __init__(self, path: str):
        with open(path, "rb") as f:
            space = memoryview(f.read())
        self.paths: dict = {}
        self.start_ns = None
        for field, plane in _fields(space):
            if field != 1:
                continue
            name, events, stat_names, stats = "", [], {}, []
            for f, v in _fields(plane):
                if f == 2:
                    name = bytes(v).decode()
                elif f == 4:
                    events.append(_map_values(v))
                elif f == 5:
                    md = dict(_fields(_map_values(v)))
                    stat_names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
                elif f == 6:
                    stats.append(v)
            if name == "Task Environment":
                self.start_ns = _stats(stats, stat_names).get(
                    "profile_start_time")
            elif tr.DEVICE_PLANE.match(name):
                ops = {}
                for ev in events:
                    ev_name, ev_stats = "", []
                    for f, v in _fields(ev):
                        if f == 2:
                            ev_name = bytes(v).decode()
                        elif f == 5:
                            ev_stats.append(v)
                    op = _stats(ev_stats, stat_names).get("tf_op")
                    if op is not None:
                        ops[ev_name] = op
                self.paths[name] = ops


def phase(path: str) -> str:
    """The innermost ``diloco.*`` scope on an op's path, or ''."""
    found = PHASE.findall(path)
    return found[-1] if found else ""


def pass_of(path: str) -> str:
    if "transpose(" in path:
        return "bwd"
    return "fwd" if "jvp(" in path else ""


def trace_file(run) -> str:
    """The trace a run's readers read: ``run.trace_path`` where the run
    names one, else the one file under the benchmark's trace
    directory."""
    path = getattr(run, "trace_path", None)
    if path:
        return path
    found = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one trace file, found {found}")
    return found[0]


def loop_phases(ops, paths: dict) -> dict:
    """name -> phase of the window's ops (sorted by start) that have no
    scoped path but enclose scoped ops: the innermost scope that every
    enclosed op's path starts with (a loop over replicas encloses
    ``diloco.inner`` and ``diloco.inner/../diloco.adamw``: inner). Ops
    without a scope inside them (compiler copies) say nothing."""
    found: dict = {}
    stack: list = []          # [op, scope chains of the ops inside it]

    def close(entry):
        op, inner = entry
        own = tuple(PHASE.findall(paths.get(op.name, "")))
        if not own:
            found.setdefault(op.name, set()).update(inner)
        if stack:
            stack[-1][1].update({own} if own else inner)

    for op in ops:
        while stack and stack[-1][0].end <= op.start:
            close(stack.pop())
        stack.append([op, set()])
    while stack:
        close(stack.pop())
    out = {}
    for name, chains in found.items():
        prefix = []
        for tokens in zip(*chains):
            if len(set(tokens)) > 1:
                break
            prefix.append(tokens[0])
        if prefix:
            out[name] = prefix[-1]
    return out


def sum_by_path(self_ns: dict, paths: dict) -> dict:
    """Self times keyed by op name (``trace.self_times``), summed by the
    ops' paths (``""`` for an op without one)."""
    out: dict = {}
    for name, ns in self_ns.items():
        key = paths.get(name, "")
        out[key] = out.get(key, 0.0) + ns
    return out


class Phases:
    """Per chip of ``run``: summed self time (ns) of the window's ops by
    their path (ops without a path under ``""``), and the metadata."""

    def __init__(self, run):
        self.meta = Metadata(trace_file(run))
        planes = {int(tr.DEVICE_PLANE.match(p).group(1)): ops
                  for p, ops in self.meta.paths.items()}
        self.paths = {}           # chip -> op name -> path or loop phase
        self.by_path = {}
        self.by_op = {}
        for c in run.chips:
            ops = [o for o in run.trace.ops[c]
                   if o.end > run.lo and o.start < run.hi]
            paths = dict(planes.get(c, {}))
            paths.update(loop_phases(ops, paths))
            self.paths[c] = paths
            self.by_op[c] = tr.self_times(ops)
            self.by_path[c] = sum_by_path(self.by_op[c], paths)

    def self_ns(self, keep) -> float | None:
        """Mean over chips of the self time of ops whose path ``keep``
        accepts; None where no op of the window has a ``diloco`` scope
        (a program that names no phases)."""
        if not any(phase(p) for acc in self.by_path.values() for p in acc):
            return None
        return sum(ns for acc in self.by_path.values()
                   for p, ns in acc.items() if keep(p)) / len(self.by_path)

    def coverage(self) -> float:
        """Share of the window's device self time in scoped ops."""
        total = sum(ns for acc in self.by_path.values()
                    for ns in acc.values())
        return (self.self_ns(lambda p: bool(phase(p))) or 0.0) \
            * len(self.by_path) / total

    def unscoped(self, top: int = 10) -> list:
        """[(op, share of device self time)] of the largest ops that
        carry no scope, summed over chips."""
        total = sum(sum(st.values()) for st in self.by_op.values())
        out: dict = {}
        for c, st in self.by_op.items():
            paths = self.paths.get(c, {})
            for name, ns in st.items():
                if not phase(paths.get(name, "")):
                    key = tr.short_name(name)
                    out[key] = out.get(key, 0.0) + ns / total
        return sorted(out.items(), key=lambda kv: -kv[1])[:top]


def phases(run) -> Phases:
    """The run's ``Phases``, read once and kept on the run for the
    other readers."""
    if "scope_phases" not in vars(run):
        run.scope_phases = Phases(run)
    return run.scope_phases


def per_round_ms(run, name: str) -> float | None:
    """Self time of phase ``name`` per traced round, in ms."""
    ns = phases(run).self_ns(lambda p: phase(p) == name)
    return None if ns is None else ns / run.rounds * 1e-6


def per_step_ms(run, which: str) -> float | None:
    """Self time of the ``diloco.inner`` ops of pass ``which`` per
    replica step on a chip, in ms."""
    ns = phases(run).self_ns(
        lambda p: phase(p) == "diloco.inner" and pass_of(p) == which)
    if ns is None:
        return None
    steps = (run.rounds * run.job["replicas"] * run.job["inner_steps"]
             / len(run.chips))
    return ns / steps * 1e-6
