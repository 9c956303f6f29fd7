"""Telemetry recorder — per-request lifecycle events and wall-clock spans
(DESIGN.md §2.9).

One :class:`Telemetry` instance is shared by every layer of a plane (or a
whole router): the control plane emits lifecycle events, the KV caches emit
hit/miss/evict events, the autoscaler emits scale events.  Events are plain
dicts with a virtual-clock timestamp ``t`` (ticks on the engine, simulated
seconds on the simulator) so the streams from both substrates are directly
diffable; an optional monotonic ``wall`` stamp rides along on the engine for
Chrome-trace wall-clock tracks and is excluded from equivalence diffs.

Spans time the host's own work on ``time.perf_counter`` (the clock of the
``wall`` stamps): ``span(name)`` nests like a call stack (each record names
its enclosing span), ``begin(name, key)`` / ``end(key)`` open in one call and
close in another (a request's ``queued`` → ``prefill`` → ``decode``
phases; such a span outlives the calls around it, so it names no parent).  They go to their own bounded ring, ``spans``, never to
``events``, so every reader of the event stream is unchanged.  With an
``annotate`` hook (``jax.profiler.TraceAnnotation`` in a traced run) each
nested span also opens ``annotate(name)``, which puts it in the profiler's
trace beside the device planes; keyed spans overlap freely and stay in
memory only.  A span that carries ``program=<name>`` times one launch of a
compiled program (its ``sync`` span the wait for the device); ``programs``
sums them per program as they are recorded, so the sums cover the whole
run whatever the ring has dropped.

The default recorder everywhere is :data:`NULL` — a no-op whose ``event()``
and spans do nothing and whose metrics sink discards writes.  Decision
code never *reads* telemetry, so attaching a real recorder is provably
zero-perturbation (tested in tests/test_obs.py by diffing decision traces
with telemetry on vs off).
"""

from __future__ import annotations

import time
from collections import deque

from .metrics import MetricsRegistry, NullMetrics

__all__ = ["Telemetry", "NullTelemetry", "NULL", "SPAN_CAPACITY"]

#: spans the ring keeps; older ones fall off its end (``spans_dropped``)
SPAN_CAPACITY = 1 << 16


class _Span:
    """One nested span: a context manager that records itself on exit.

    ``start`` — a clock reading the caller already holds (so a counter and
    the span share their reads); ``stop(end)`` likewise fixes the end."""

    __slots__ = ("tel", "rec", "end", "_ann")

    def __init__(self, tel: "Telemetry", rec: dict):
        self.tel, self.rec, self.end, self._ann = tel, rec, None, None

    def __enter__(self):
        tel, rec = self.tel, self.rec
        if rec["start"] is None:
            rec["start"] = tel.clock()
        rec["id"] = tel._new_id()
        rec["parent"] = tel._stack[-1] if tel._stack else None
        tel._stack.append(rec["id"])
        if tel.annotate is not None:
            self._ann = tel.annotate(rec["name"])
            self._ann.__enter__()
        return self

    def stop(self, end: float) -> None:
        self.end = end

    def __exit__(self, *exc):
        tel, rec = self.tel, self.rec
        rec["end"] = self.end if self.end is not None else tel.clock()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tel._stack.pop()
        tel._record(rec)
        return False

    @property
    def duration(self) -> float:
        return self.rec["end"] - self.rec["start"]


class Telemetry:
    """Append-only event recorder, span ring and metrics registry.

    ``wall_clock`` — optional zero-arg callable returning wall seconds
    (the engine passes ``time.perf_counter``); when set, every event also
    carries a ``wall`` key.  ``annotate`` — optional ``name -> context
    manager`` opened around every nested span.  ``spans_dropped`` counts
    what fell off the span ring's end.
    """

    enabled = True

    def __init__(self, metrics: MetricsRegistry | None = None,
                 wall_clock=None, annotate=None):
        self.events: list[dict] = []
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.wall_clock = wall_clock
        self.annotate = annotate
        self.clock = time.perf_counter
        self.spans: deque = deque(maxlen=SPAN_CAPACITY)
        self.spans_dropped = 0
        #: per program: launches, cold launches, dispatch and execute seconds
        self.programs: dict[str, dict] = {}
        self._stack: list[int] = []          # ids of the open nested spans
        self._open: dict = {}                # key -> open keyed span record
        self._ids = 0

    def event(self, t: float, kind: str, **attrs) -> None:
        ev = {"t": round(float(t), 9), "kind": kind}
        for k, v in attrs.items():
            if v is not None:
                ev[k] = v
        if self.wall_clock is not None and "wall" not in ev:
            ev["wall"] = self.wall_clock()
        self.events.append(ev)

    # -- spans ------------------------------------------------------------------
    def _new_id(self) -> int:
        self._ids += 1
        return self._ids

    def _record(self, rec: dict) -> None:
        if len(self.spans) == self.spans.maxlen:
            self.spans_dropped += 1
        self.spans.append(rec)
        prog = rec.get("program")
        if prog is not None:
            self._count_launch(prog, rec)

    def _count_launch(self, prog: str, rec: dict) -> None:
        e = self.programs.get(prog)
        if e is None:
            e = self.programs[prog] = {"launches": 0, "cold_launches": 0,
                                       "dispatch_s": 0.0, "execute_s": 0.0}
        if rec["name"] == "sync":
            e["execute_s"] += rec["end"] - rec["start"]
            return
        e["launches"] += 1
        e["cold_launches"] += int(bool(rec.get("cold")))
        e["dispatch_s"] += rec["end"] - rec["start"]

    def span(self, name: str, start: float | None = None, **attrs) -> _Span:
        """A nested span over a ``with`` block (``start`` defaults to the
        clock on entry, the end to the clock on exit)."""
        return _Span(self, {"name": name, "id": None, "parent": None,
                            "start": start, "end": None, **attrs})

    def begin(self, name: str, key, start: float | None = None,
              **attrs) -> dict | None:
        """Open a keyed span; a span still open under ``key`` ends at the
        same instant (a phase change), and its record is returned."""
        start = self.clock() if start is None else start
        left = self.end(key, end=start)
        self._open[key] = {"name": name, "id": self._new_id(),
                           "parent": None, "start": start, "end": None,
                           "keyed": True, **attrs}
        return left

    def end(self, key, end: float | None = None, **attrs) -> dict | None:
        """Close the span open under ``key`` (None when there is none);
        ``attrs`` join its record."""
        rec = self._open.pop(key, None)
        if rec is None:
            return None
        rec["end"] = self.clock() if end is None else end
        rec.update(attrs)
        self._record(rec)
        return rec

    def spans_of(self, *names: str) -> list[dict]:
        want = set(names)
        return [s for s in self.spans if s["name"] in want]

    # -- conveniences ---------------------------------------------------------
    def events_of(self, *kinds: str) -> list[dict]:
        want = set(kinds)
        return [e for e in self.events if e["kind"] in want]

    def comparable_events(self) -> list[dict]:
        """Events with substrate-only keys (``wall``) stripped — the stream
        the sim↔engine diff tests compare."""
        return [{k: v for k, v in e.items() if k != "wall"}
                for e in self.events]

    def clear(self) -> None:
        self.events.clear()
        self.spans.clear()
        self.programs.clear()


class _NullSpan:
    """The one shared no-op span of :class:`NullTelemetry`."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def stop(self, end: float) -> None:
        pass


_NULL_SPAN = _NullSpan()


class NullTelemetry:
    """Inert recorder: the default wired into every layer."""

    enabled = False
    events: list = []           # class-level, never written
    spans: tuple = ()
    spans_dropped = 0
    programs: dict = {}         # class-level, never written
    wall_clock = None
    annotate = None
    metrics = NullMetrics()

    def event(self, t, kind, **attrs):
        pass

    def span(self, name, start=None, **attrs):
        return _NULL_SPAN

    def begin(self, name, key, start=None, **attrs):
        pass

    def end(self, key, end=None, **attrs):
        return None

    def spans_of(self, *names):
        return []

    def events_of(self, *kinds):
        return []

    def comparable_events(self):
        return []

    def clear(self):
        pass


NULL = NullTelemetry()
