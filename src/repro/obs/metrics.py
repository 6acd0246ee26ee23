"""Unified run telemetry: one record schema for every transport.

The launch scripts used to keep five divergent history shapes
(pretrain / sync-round / async-event / gossip-round / benchmark rows),
each inventing its own keys and its own print lines. ``RunRecorder``
replaces them with one typed emitter per record kind:

  * ``pretrain(...)``   — single-worker warmup steps;
  * ``round(...)``      — one barrier-paced outer round (sync /
    streaming / sharded / gossip), fed from the scanned driver's
    stacked metrics at chunk boundaries;
  * ``async_event(...)``— one ``AsyncEngine`` event record (arrival /
    lost / leave / join), enriched in place.

Every record carries ``kind`` ("round" | "event"), ``phase``
("pretrain" | "diloco" | "diloco_async") and ``transport`` on top of
its measurement fields, so one consumer reads any run. Wire-byte
fields are accumulated into ``wire_bytes_total`` — the counter
``benchmarks/obs.py`` cross-checks against the HLO-measured cross-pod
bytes of the lowered round.

The recorder is HOST-ONLY by construction: it never launches device
work. The scanned driver hands it a stacked metrics tree once per
chunk via ``ingest_chunk`` (counted — the no-extra-device-syncs gate),
and every emitter takes already-materialized scalars. With the default
``log_format="text"`` the console lines are byte-identical to the
pre-recorder driver output; ``"json"`` emits one JSON object per line
instead.

``to_jsonable`` is the serialization audit: numpy scalars and (numpy
or jax) arrays in a record must not crash ``json.dump`` — they are
converted, not trusted to be Python types.

The recorder also keeps what a profile taken later cannot see: the
run's set-up spans (``setup``) and the compiles since it was made
(``compiles``, from ``obs/profile.py``'s log). Both go into the
dumped manifest, never to the console.
"""
from __future__ import annotations

import contextlib
import json
import time

import numpy as np

from repro.obs import profile

SCHEMA_VERSION = 1


def to_jsonable(obj):
    """Recursively convert ``obj`` into plain JSON-dumpable Python:
    numpy scalars -> int/float/bool, numpy/jax arrays -> nested lists,
    tuples -> lists, dict keys -> str. Values already plain pass
    through unchanged (floats keep their bits — NaN stays NaN, the
    divergence marker, exactly as ``json.dump`` has always written
    it)."""
    if isinstance(obj, np.generic):
        return obj.item()
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, dict):
        return {str(k): to_jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [to_jsonable(v) for v in obj]
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    if hasattr(obj, "__array__"):      # jax.Array and friends
        return to_jsonable(np.asarray(obj))
    return str(obj)                    # last resort: never crash a dump


def _round_text(rec, rounds) -> str:
    """The sync/streaming/sharded/gossip progress line — byte-identical
    to the pre-recorder driver's print."""
    vl = rec["val_loss"]
    val_s = "   skip" if vl is None else \
        f"{vl:.4f} ppl={np.exp(vl):.2f}"
    return (f"[round {rec['round']}/{rounds}] "
            f"inner={rec['inner_loss']:.4f} val={val_s} "
            f"active={rec['active']}")


def _async_text(rec) -> str:
    """The async event line — byte-identical to the pre-recorder
    driver's print (including the trailing space of an eval-less
    arrival)."""
    if rec["event"] == "arrival":
        vs = (f"val={rec['val_loss']:.4f} ppl={rec['ppl']:.2f}"
              if "val_loss" in rec else "")
        return (f"[tick {rec['tick']}] worker {rec['worker']} "
                f"stale={rec['staleness']} w={rec['weight']:.3f} "
                f"inner={rec['inner_loss']:.4f} {vs}")
    return (f"[tick {rec['tick']}] {rec['event']} "
            f"worker {rec['worker']}")


class RunRecorder:
    """One run's telemetry: manifest + typed records + console lines.

    manifest    run-level facts: schema version, transport, the CLI
                config, the static wire plan
                (``attach_wire_plan``), the HLO-measured wire profile
                (``attach_hlo_profile``), free-form notes.
    records     the unified history — what ``--out`` serializes and
                ``launch.train.run`` returns.
    log_format  "text" (default; byte-identical to the legacy console
                output) or "json" (one JSON object per line).
    printer     sink for console lines (tests/benchmarks pass a no-op).
    """

    def __init__(self, *, transport: str = "simulated",
                 log_format: str = "text", manifest: dict | None = None,
                 printer=print):
        if log_format not in ("text", "json"):
            raise ValueError(f"log_format must be 'text' or 'json', "
                             f"got {log_format!r}")
        self.transport = transport
        self.log_format = log_format
        self._print = printer
        self.manifest: dict = {"schema": SCHEMA_VERSION,
                               "transport": transport}
        if manifest:
            self.manifest.update(manifest)
        self.records: list = []
        self.wire_bytes_total: float = 0.0
        self.ingest_calls: int = 0
        self.setup_spans: list = []
        self._compile_mark = profile.install()

    # ---- console plumbing ----

    def _say(self, text: str, rec: dict | None = None):
        if self.log_format == "json":
            self._print(json.dumps(to_jsonable(
                rec if rec is not None else {"note": text})), flush=True)
        else:
            self._print(text, flush=True)

    def note(self, text: str, **fields):
        """A status line that is not a measurement (transport headers,
        output paths, timings). Printed, and kept in the manifest —
        NOT in the record history."""
        self.manifest.setdefault("notes", []).append(
            {"note": text, **fields} if fields else {"note": text})
        self._say(text, {"note": text, **fields})

    # ---- typed record emitters ----

    def _emit(self, rec: dict, text: str) -> dict:
        self.records.append(rec)
        self.wire_bytes_total += float(rec.get("wire_bytes") or 0.0)
        self._say(text, rec)
        return rec

    def pretrain(self, *, step: int, loss, val_loss) -> dict:
        rec = {"kind": "round", "phase": "pretrain",
               "transport": self.transport, "inner_steps": int(step),
               "inner_loss": float(loss), "val_loss": float(val_loss)}
        return self._emit(rec, f"[pretrain {step}] "
                               f"loss={float(loss):.4f} "
                               f"val={float(val_loss):.4f}")

    def round(self, *, round: int, rounds: int, inner_steps: int,
              inner_loss, val_loss, outer_gnorm, active: int,
              dropped: int | None = None, wire_bytes=None,
              gossip_edges=None, extras: dict | None = None,
              evaled: bool = True) -> dict:
        """One outer round of a barrier-paced transport. ``evaled``
        False marks a round the eval cadence skipped (val_loss is
        recorded as None, never as a stale number)."""
        rec = {"kind": "round", "phase": "diloco",
               "transport": self.transport, "round": int(round),
               "inner_steps": int(inner_steps),
               "inner_loss": float(inner_loss),
               "val_loss": None if not evaled else float(val_loss),
               "outer_gnorm": float(outer_gnorm), "active": int(active)}
        if dropped is not None:
            rec["dropped"] = int(dropped)
        if wire_bytes is not None:
            rec["wire_bytes"] = float(wire_bytes)
        if gossip_edges is not None:
            rec["gossip_edges"] = [list(e) for e in gossip_edges]
        if extras:
            rec.update({k: float(v) for k, v in extras.items()})
        return self._emit(rec, _round_text(rec, rounds))

    def guard_event(self, *, action: str, round: int,
                    **fields) -> dict:
        """One anomaly-guard verdict (``resilience.guard``): a spike /
        non-finite detection, a rollback, or a skipped round. Pure
        host-side bookkeeping — emitting it touches no device value."""
        rec = {"kind": "event", "phase": "guard",
               "transport": self.transport, "event": action,
               "round": int(round), **fields}
        detail = " ".join(f"{k}={v}" for k, v in fields.items())
        return self._emit(
            rec, f"[guard] {action} round={int(round)} {detail}".rstrip())

    def async_event(self, rec: dict) -> dict:
        """Ingest one ``AsyncEngine`` event record (already keyed by
        ``event``/``tick``/``worker``), stamping the unified kind /
        phase / transport fields in place."""
        rec = {"kind": "event", "phase": "diloco_async",
               "transport": self.transport, **rec}
        return self._emit(rec, _async_text(rec))

    # ---- device boundary ----

    def ingest_chunk(self, stacked_metrics):
        """Materialize one chunk's stacked device metrics as a numpy
        tree — the recorder's ONLY contact with device values. One call
        per scanned chunk; ``ingest_calls`` counts them, which is how
        ``benchmarks/obs.py`` gates that recording adds no device
        syncs beyond the chunk boundaries the scanned loop already pays.
        Host spans: ``diloco.ingest.wait`` while the device still works
        on the chunk, ``diloco.ingest.copy`` for the copy to the host."""
        import jax
        self.ingest_calls += 1
        with jax.profiler.TraceAnnotation("diloco.ingest.wait"):
            jax.block_until_ready(stacked_metrics)
        with jax.profiler.TraceAnnotation("diloco.ingest.copy"):
            return jax.tree.map(np.asarray, stacked_metrics)

    # ---- set-up and compiles ----

    @contextlib.contextmanager
    def setup(self, name: str):
        """Host span ``diloco.setup.<name>``, also kept in
        ``setup_spans`` with its ``perf_counter`` start and end."""
        import jax
        start = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"diloco.setup.{name}"):
            yield
        self.setup_spans.append({"span": f"diloco.setup.{name}",
                                 "start": start,
                                 "end": time.perf_counter()})

    def compiles(self) -> list:
        """The compile log's entries since this recorder was made."""
        return profile.entries(self._compile_mark)

    # ---- manifest attachments ----

    def attach_wire_plan(self, plan):
        """Static per-fragment outer-sync plan (see
        ``streaming.sync_plan`` / ``diloco.outer_wire_bytes``): what
        the transport is *scheduled* to ship each round."""
        self.manifest["wire_plan"] = [dict(p) for p in plan]

    def attach_hlo_profile(self, profile: dict, fn: str = "round"):
        """HLO-measured wire profile of the lowered program (see
        ``hlo_analysis.wire_profile``): what the compiled collective
        program REALLY ships — the trace's byte annotations are
        cross-checked against this."""
        self.manifest.setdefault("hlo_profile", {})[fn] = dict(profile)

    # ---- output ----

    @property
    def history(self) -> list:
        return self.records

    def round_records(self) -> list:
        return [r for r in self.records if r["kind"] == "round"
                and r["phase"] != "pretrain"]

    def event_records(self) -> list:
        return [r for r in self.records if r["kind"] == "event"]

    def payload(self, *, args: dict | None = None) -> dict:
        """The serializable run bundle: superset of the legacy
        ``{"args", "history"}`` shape plus the manifest (with the
        set-up spans and the compile log)."""
        manifest = dict(self.manifest, setup=self.setup_spans,
                        compiles=self.compiles())
        return to_jsonable({"args": args, "manifest": manifest,
                            "history": self.records})

    def dump(self, path: str, *, args: dict | None = None) -> str:
        with open(path, "w") as f:
            json.dump(self.payload(args=args), f, indent=1)
        return path
