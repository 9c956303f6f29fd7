"""The program's own spans in a profiler trace, on the device's clock.

With ``Telemetry(annotate=jax.profiler.TraceAnnotation)`` every nested span
the program opens (``repro.obs.telemetry``) lands on a host plane of the
``.xplane.pb`` beside the harness's ``bench.*`` spans.  The functions here
take ``bench.trace.Event`` tuples, or ``(name, start, end)`` triples in any
one clock, so they are tested on hand-made lists:

* ``program_spans`` picks the program's and the harness's spans out of the
  host planes;
* ``clock_offset_ns`` estimates how far the host clock runs ahead of the
  device's: the median, over decode steps, of the host ``sync`` span's end
  minus the end of the ``jit_paged_decode_step`` run it waited on, matched
  in order (it holds the host's wake-up latency too, some microseconds);
  ``to_device_clock`` shifts host spans by it;
* ``innermost_pieces`` cuts nested spans into disjoint pieces, each owned
  by the innermost span open there, and ``attribute_innermost`` lays each
  device idle gap onto those pieces ("other" where no span is open);
* ``self_time`` and ``longest_uncovered`` find where a stall falls: the
  spans with the most time of their own, and the longest stretch that no
  span covers;
* ``wait_causes`` lays each request's wait (its ``request.queued`` span)
  on what ran meanwhile, at the level of whole steps and sequences.
"""

from __future__ import annotations

import statistics
from collections import defaultdict

#: the spans the program opens (``repro.serving``, ``repro.core``) and
#: the harness's own (``bench.harness``)
PROGRAM_SPANS = frozenset({
    "router.step", "cp.map", "cp.prune",
    "unit.step", "unit.prefill", "unit.decode", "unit.commit",
    "unit.complete", "kv.lookup", "kv.upload", "kv.download",
    "launch", "sync", "prepare", "readback"})
HARNESS_SPANS = frozenset({"bench.window", "bench.step", "bench.submit",
                           "bench.wait"})
#: what a request's wait is laid on: the harness's spans, the router's
#: step, the control plane's and the step loop's work per sequence
WAIT_SPANS = frozenset({"bench.step", "bench.submit", "bench.wait",
                        "router.step", "cp.map", "cp.prune", "unit.step",
                        "unit.prefill", "unit.decode", "unit.complete",
                        "kv.lookup"})
DECODE_PROGRAM = "jit_paged_decode_step"
PREFILL_PROGRAM = "jit_chunk_prefill"


def program_name(module: str) -> str:
    """An ``XLA Modules`` event's program: ``jit_chunk_prefill(123)`` ->
    ``jit_chunk_prefill``."""
    return module.split("(", 1)[0]


def program_spans(events, names=PROGRAM_SPANS | HARNESS_SPANS):
    """(name, start_ns, end_ns) of the host spans called one of ``names``,
    in order of start."""
    return sorted(((e.name, e.start_ns, e.start_ns + e.dur_ns)
                   for e in events
                   if not e.plane.startswith("/device:") and e.name in names),
                  key=lambda s: (s[1], -s[2]))


def _inside(spans, outer: str, inner: str):
    """``inner`` spans that lie inside some ``outer`` span."""
    outs = [(s, t) for n, s, t in spans if n == outer]
    out, i = [], 0
    for n, s, t in spans:
        if n != inner:
            continue
        while i < len(outs) and outs[i][1] < t:
            i += 1
        if i < len(outs) and outs[i][0] <= s:
            out.append((n, s, t))
    return out


def clock_offset_ns(spans, modules) -> float | None:
    """Host clock minus device clock, in ns (None without decode steps).

    The ``sync`` spans inside ``unit.decode`` spans and the
    ``jit_paged_decode_step`` module runs are matched in order.  Where the
    trace's ends cut a step from one list, the lists are matched at the
    shift (of up to two steps) whose median difference is smallest: the
    two clocks differ by far less than a step."""
    host = [t for _, _, t in _inside(spans, "unit.decode", "sync")]
    dev = sorted(m.start_ns + m.dur_ns for m in modules
                 if program_name(m.name) == DECODE_PROGRAM)
    if not host or not dev:
        return None
    meds = []
    for k in range(-2, 3):
        diffs = [h - dev[i + k] for i, h in enumerate(host)
                 if 0 <= i + k < len(dev)]
        if len(diffs) >= max(1, min(len(host), len(dev)) // 2):
            meds.append(statistics.median(diffs))
    return min(meds, key=abs) if meds else None


def to_device_clock(spans, offset_ns: float):
    return [(n, s - offset_ns, t - offset_ns) for n, s, t in spans]


def innermost_pieces(spans):
    """Disjoint (start, end, name) pieces, each owned by the innermost span
    open over it.  Spans must nest or be disjoint; one that overruns its
    parent is cut at the parent's end."""
    out: list[tuple] = []
    stack: list[list] = []               # [name, end, cursor]

    def close():
        name, end, cur = stack.pop()
        if end > cur:
            out.append((cur, end, name))
        if stack:
            stack[-1][2] = max(stack[-1][2], end)

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            close()
        if stack:
            top = stack[-1]
            if s > top[2]:
                out.append((top[2], s, top[0]))
            top[2] = max(top[2], s)
            e = min(e, top[1])
        stack.append([name, e, s])
    while stack:
        close()
    return sorted(out)


def attribute_innermost(gaps, spans) -> dict[str, float]:
    """Seconds of each (start, end) gap by the innermost span open over
    each instant of it ("other" where none is)."""
    pieces = innermost_pieces(spans)
    out: dict[str, float] = defaultdict(float)
    j = 0
    for s, t in sorted(gaps):
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < t:
            a, b, name = pieces[k]
            c = min(b, t) - max(a, s)
            if c > 0:
                out[name] += c * 1e-9
                covered += c
            k += 1
        if t - s - covered > 0:
            out["other"] += (t - s - covered) * 1e-9
    return dict(out)


def self_time(spans, lo: float, hi: float) -> dict[str, float]:
    """Each span name's own time inside [lo, hi), children excluded, in
    the spans' unit."""
    out: dict[str, float] = defaultdict(float)
    for a, b, name in innermost_pieces(spans):
        c = min(b, hi) - max(a, lo)
        if c > 0:
            out[name] += c
    return dict(out)


def longest_uncovered(spans, lo: float, hi: float) -> tuple[float, float]:
    """(start, length) of the longest stretch of [lo, hi) that no span
    covers."""
    best, cur = (lo, 0.0), lo
    for s, t in sorted((s, t) for _, s, t in spans):
        if s > cur and min(s, hi) - cur > best[1]:
            best = (cur, min(s, hi) - cur)
        cur = max(cur, t)
        if cur >= hi:
            break
    if hi - cur > best[1]:
        best = (cur, hi - cur)
    return best


def wait_causes(waits, spans) -> dict[str, float]:
    """Seconds of the (start, end) waits, in ns, by the innermost of the
    ``WAIT_SPANS`` open over each instant of them ("other" where none
    is)."""
    return attribute_innermost(waits, [x for x in spans
                                       if x[0] in WAIT_SPANS])
