"""What a profiler sees of a run: the trainer's host spans and the
process's compile log.

Host spans are ``jax.profiler.TraceAnnotation``s: free when no profile
is being taken, and a named interval on the profile's host plane
(which shares its clock with the device planes) when one is. The
trainer's spans all start with ``diloco.``:

  diloco.setup.build       parse the job, build model, configs, sampler
  diloco.setup.init        initial parameters (``arch.init``)
  diloco.setup.validation  the validation tokens (``sample_validation``)
  diloco.setup.state       the transport's state and its placement
  diloco.dispatch          one scanned chunk's call (compiles on first use)
  diloco.ingest.wait       block until the chunk's metrics are ready
  diloco.ingest.copy       their device-to-host copy
  diloco.emit              the chunk's records and console lines
  diloco.guard             the anomaly guard's verdict
  diloco.snapshot          a durable snapshot

The compiled program names its phases the same way
(``jax.named_scope``): ``diloco.sample``, ``diloco.inner``,
``diloco.adamw``, ``diloco.outer``, ``diloco.sync``, ``diloco.eval``.

Compile log. The first ``RunRecorder`` of a process registers one
``jax.monitoring`` listener (``install``), so the log holds every
compile from then on. Every ``/jax/core/compile/*`` span
becomes an entry: its ``stage`` (``jaxpr_trace``,
``jaxpr_to_mlir_module``, ``backend_compile``), the compiled
function's ``fun_name``, and its ``start``/``end`` on the
``time.perf_counter`` clock (the listener's wall-clock stamps, shifted
by the offset read at install). A backend compile also records
``cache``: ``"hit"`` when the executable came from the persistent
compilation cache, ``"miss"`` when it was compiled and written there,
None when no persistent cache took part. The log is process-wide, as
``jax.monitoring``'s listeners are; a ``RunRecorder`` keeps the mark
where its run began.
"""
from __future__ import annotations

import time

STAGE_PREFIX = "/jax/core/compile/"
BACKEND = "backend_compile"

_entries: list = []
_cache_events: list = []       # hits/misses inside the open compile
_offset: float | None = None   # time.time() - time.perf_counter()


def _on_span(event: str, start: float, end: float, **kw):
    if not event.startswith(STAGE_PREFIX):
        return
    stage = event[len(STAGE_PREFIX):].removesuffix("_duration")
    entry = {"stage": stage, "fun_name": kw.get("fun_name"),
             "start": start - _offset, "end": end - _offset}
    if stage == BACKEND:
        entry["cache"] = _cache_events[-1] if _cache_events else None
        _cache_events.clear()
    _entries.append(entry)


def _on_event(event: str, **_):
    if event == "/jax/compilation_cache/cache_hits":
        _cache_events.append("hit")
    elif event == "/jax/compilation_cache/cache_misses":
        _cache_events.append("miss")


def install() -> int:
    """Register the listener (once per process); returns the number of
    entries logged so far, the mark of a run that starts now."""
    global _offset
    if _offset is None:
        import jax

        _offset = time.time() - time.perf_counter()
        jax.monitoring.register_event_time_span_listener(_on_span)
        jax.monitoring.register_event_listener(_on_event)
    return len(_entries)


def entries(since: int = 0) -> list:
    """The log's entries from mark ``since`` on (copies)."""
    return [dict(e) for e in _entries[since:]]


def wall_to_perf(wall_s: float) -> float:
    """A ``time.time()`` stamp on the log's ``perf_counter`` clock."""
    return wall_s - _offset


def backend_seconds(log: list) -> float:
    """Seconds in backend compiles (persistent-cache loads included)."""
    return sum(e["end"] - e["start"] for e in log if e["stage"] == BACKEND)
