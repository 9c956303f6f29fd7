"""Reduction of a profiler trace to the numbers the benchmark reports.

``load`` flattens the ``.xplane.pb`` that ``jax.profiler`` writes into plain
``Event`` tuples; the functions below work on those tuples alone, so they
are tested on small hand-made lists as well as on a recorded trace.

Device planes are named ``/device:<KIND>:<n>``.  Their ``XLA Ops`` line holds
one event per operation that ran on the chip, named by its HLO text; their
``XLA Modules`` line one event per run of a compiled program (``jit_f(<id>)``).
On a v5e the operations carry no module stat, so an operation is placed in
a module by time.  Host spans that the harness opens with
``jax.profiler.TraceAnnotation`` land on a host plane's ``python3`` line; on
a v5e that clock ran about 1.5 ms ahead of the device's (recorded trace),
which is nothing against a window of seconds.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from pathlib import Path
from typing import NamedTuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def find_xplane(root) -> Path:
    hits = sorted(Path(root).rglob("*.xplane.pb"))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return hits[-1]


def load(path) -> list[Event]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def device_ops(events, device: str | None = None) -> list[Event]:
    """Operations that ran on the device planes (or on one of them)."""
    return [e for e in events
            if e.plane.startswith("/device:") and e.line == OPS_LINE
            and (device is None or e.plane == device)]


def device_modules(events, device: str | None = None) -> list[Event]:
    """Runs of compiled programs on the device planes."""
    return [e for e in events
            if e.plane.startswith("/device:") and e.line == MODULES_LINE
            and (device is None or e.plane == device)]


def short_name(name: str) -> str:
    """An operation's HLO name without its shapes: ``%copy.22``."""
    return name.split(" = ", 1)[0]


def devices(events) -> list[str]:
    return sorted({e.plane for e in device_ops(events)})


def spans(events, name: str) -> list[tuple[float, float]]:
    """(start, end) of the host spans called ``name``."""
    return [(e.start_ns, e.start_ns + e.dur_ns) for e in events
            if not e.plane.startswith("/device:") and e.name == name]


def union(intervals) -> list[tuple[float, float]]:
    """Merge overlapping (start, end) intervals into disjoint ones."""
    out: list[list[float]] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(t, hi)) for s, t in intervals
            if min(t, hi) > max(s, lo)]


def busy_ns(ops, lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi) in which some operation ran."""
    iv = clip(union((e.start_ns, e.start_ns + e.dur_ns) for e in ops), lo, hi)
    return sum(t - s for s, t in iv)


def idle_gaps(ops, lo: float, hi: float) -> list[tuple[float, float]]:
    """The intervals of [lo, hi) in which no operation ran."""
    gaps, cur = [], lo
    for s, t in clip(union((e.start_ns, e.start_ns + e.dur_ns)
                           for e in ops), lo, hi):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def in_window(ops, lo: float, hi: float) -> list[Event]:
    return [e for e in ops if lo <= e.start_ns < hi]


def seconds_by_name(ops) -> dict[str, float]:
    out: dict[str, float] = defaultdict(float)
    for e in ops:
        out[short_name(e.name)] += e.dur_ns * 1e-9
    return dict(out)


def runs_holding(modules, ops, pred) -> list[Event]:
    """Module runs during which an operation satisfying ``pred`` started."""
    starts = sorted(e.start_ns for e in ops if pred(e.name))
    out = []
    for m in modules:
        i = bisect.bisect_left(starts, m.start_ns)
        if i < len(starts) and starts[i] <= m.start_ns + m.dur_ns:
            out.append(m)
    return out


def attribute_gaps(gaps, host: dict[str, list[tuple[float, float]]]):
    """Seconds of device idle time by the host span that covers most of
    each gap ("other" where none does)."""
    out: dict[str, float] = defaultdict(float)
    for s, t in gaps:
        best, cover = "other", 0.0
        for name, iv in host.items():
            c = sum(max(0.0, min(t, b) - max(s, a)) for a, b in iv)
            if c > cover:
                best, cover = name, c
        out[best] += (t - s) * 1e-9
    return dict(out)


#: the paged decode attention kernel's operation on the device: a
#: ``tpu_custom_call`` named after the jitted front door that launches it
PAGED_ATTN_KERNEL = "%_paged_decode_attention_jit"


def is_paged_attn(name: str) -> bool:
    return name.startswith(PAGED_ATTN_KERNEL) and "tpu_custom_call" in name
