"""Reduce a JAX profiler trace (``.xplane.pb``) to the intervals the
per-layer metrics read.

A TPU trace holds one plane per chip (``/device:TPU:<n>``) whose
``XLA Ops`` line lists every HLO op that ran, with its start and
duration on a clock shared with the host plane (``/host:CPU``). Ops
nest: a ``while`` loop's event spans the ops of its body, so sums over
that line double count, and device busy time is the *union* of the op
intervals. The benchmark's own host spans (``jax.profiler.
TraceAnnotation`` named ``bench.*``) sit on the host plane and label
what the host was doing while the device idled.

Everything here works on plain ``(start_ns, end_ns)`` tuples so the
reduction can be tested on a small recorded trace.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclass
class Op:
    name: str
    start: float          # ns on the trace clock
    end: float


@dataclass
class Trace:
    """ops: chip index -> HLO op events of that chip, sorted by start.
    spans: the benchmark's host spans (``bench.*``), sorted by start."""
    ops: dict = field(default_factory=dict)
    spans: list = field(default_factory=list)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out.ops[int(m.group(1))] = sorted(
                        (Op(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in line.events), key=lambda o: o.start)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        out.spans.append(Op(ev.name, ev.start_ns,
                                            ev.start_ns + ev.duration_ns))
    out.spans.sort(key=lambda o: o.start)
    return out


def window(trace: Trace) -> tuple:
    """(start, end) of the traced window: from the first dispatch span
    to the end of the last ingest span (metrics of the last traced round
    on the host)."""
    disp = [s for s in trace.spans if s.name == "bench.dispatch"]
    ing = [s for s in trace.spans if s.name == "bench.ingest"]
    if not disp or not ing:
        raise ValueError("trace holds no bench.dispatch / bench.ingest span")
    return disp[0].start, ing[-1].end


def union(intervals, lo: float, hi: float) -> list:
    """Merged, clipped [lo, hi] cover of ``intervals`` ((start, end)
    pairs), sorted."""
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(iv) for iv in merged]


def busy_ns(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(intervals, lo, hi))


def gaps(intervals, lo: float, hi: float) -> list:
    """Idle [start, end) stretches of [lo, hi] not covered by
    ``intervals``."""
    out, t = [], lo
    for s, e in union(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def self_times(ops) -> dict:
    """name -> summed self time (ns) of ops sorted by start: an op's
    duration less the part its nested ops cover (a loop's own time)."""
    out: dict = {}
    stack: list = []          # [op, covered_ns] of open enclosing ops

    def close(entry):
        op, covered = entry
        out[op.name] = out.get(op.name, 0.0) + (op.end - op.start) - covered
        if stack:
            stack[-1][1] += op.end - op.start

    for op in ops:
        while stack and stack[-1][0].end <= op.start:
            close(stack.pop())
        stack.append([op, 0.0])
    while stack:
        close(stack.pop())
    return out


def label_gap(trace: Trace, s: float, e: float) -> str:
    """What the host did during most of the idle stretch [s, e): the
    benchmark span that covers the most of it, or ``host between calls``
    where the time outside every span is larger."""
    cover: dict = {}
    for sp in trace.spans:
        ov = min(e, sp.end) - max(s, sp.start)
        if ov > 0:
            cover[sp.name] = cover.get(sp.name, 0.0) + ov
    spans = [(sp.start, sp.end) for sp in trace.spans]
    cover["host between calls"] = (e - s) - busy_ns(spans, s, e)
    return max(cover, key=cover.get)


def short_name(hlo_text: str) -> str:
    """``%fusion.12 = f32[8,1024]{...} fusion(...)`` -> ``fusion.12
    f32[8,1024]``: the instruction and its first result shape."""
    head, _, rest = hlo_text.partition(" = ")
    ty = re.search(r"[a-z0-9]+\[[0-9,]*\]", rest)
    return f"{head.lstrip('%')} {ty.group(0) if ty else ''}".strip()


def tpu_custom_call(op_name: str) -> tuple | None:
    """(result count, first operand type) of a Pallas kernel event, or
    None for any other op. A Pallas call's HLO text carries no kernel
    name, only its calling convention: a ``tpu_custom_call`` whose first
    operand is the kernel's SMEM scalar array."""
    if 'custom_call_target="tpu_custom_call"' not in op_name:
        return None
    _, _, rest = op_name.partition(" = ")
    result, _, operands = rest.partition(" custom-call(")
    n_results = result.count("[") if result.startswith("(") else 1
    first = re.sub(r"\{[^}]*\}", "", operands.split(" ", 1)[0])
    return n_results, first


COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def is_collective(op_name: str) -> bool:
    head = op_name.partition(" = ")[0].lstrip("%")
    return any(head.startswith(c) for c in COLLECTIVES)


def exposed_ns(ops, is_target, lo: float, hi: float) -> float:
    """Time in [lo, hi] during which a ``is_target`` op runs on the
    chip while no other op does."""
    target = union([(o.start, o.end) for o in ops if is_target(o.name)],
                   lo, hi)
    other = union([(o.start, o.end) for o in ops if not is_target(o.name)],
                  lo, hi)
    total = sum(e - s for s, e in target)
    hidden = 0.0
    j = 0
    for s, e in target:
        while j < len(other) and other[j][1] <= s:
            j += 1
        k = j
        while k < len(other) and other[k][0] < e:
            hidden += min(e, other[k][1]) - max(s, other[k][0])
            k += 1
    return total - hidden
