"""The program's own spans (``repro.trace``) for the per-layer metrics.

The program records its spans on ``time.perf_counter``, the harness's
clock, so they clip to the measured window (``ctx.window``) as they are.
The device trace runs on the profiler's clock; ``bench.window`` opens just
before the harness reads the window's start, so a host time ``t`` lies at
``ctx.trace.window[0] + (t - ctx.window.start)`` on the trace's.

A program without ``repro.trace`` records no spans, and every reader here
returns None for it.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

from bench import trace_reduce

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Span:
    id: int
    parent: int
    name: str
    start: float  # seconds, host clock
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start


def clip(raw: Iterable, lo: float, hi: float) -> List[Span]:
    """Spans (``repro.trace.Span``-like: ``id``, ``parent``, ``name``,
    ``start_ns``, ``end_ns``) that overlap [lo, hi] seconds, clipped to it."""
    out = []
    for sp in raw:
        s, e = max(sp.start_ns * 1e-9, lo), min(sp.end_ns * 1e-9, hi)
        if e > s:
            out.append(Span(sp.id, sp.parent, sp.name, s, e))
    return out


def window_spans(window) -> Optional[List[Span]]:
    """The program's spans clipped to the measured window, or None if the
    program records none."""
    try:
        from repro import trace
    except ImportError:
        return None
    spans = clip(trace.spans(), window.start, window.end)
    return spans or None


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def covered(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    """Length of [lo, hi] that the intervals cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in union(intervals))


def children(spans: Sequence[Span]) -> Dict[int, List[Span]]:
    out: Dict[int, List[Span]] = {}
    for sp in spans:
        out.setdefault(sp.parent, []).append(sp)
    return out


def descendants(sp: Span, kids: Dict[int, List[Span]]) -> List[Span]:
    out, todo = [], list(kids.get(sp.id, ()))
    while todo:
        d = todo.pop()
        out.append(d)
        todo.extend(kids.get(d.id, ()))
    return out


def self_time(spans: Sequence[Span], name: str,
              inner: Callable[[Span], bool] = lambda d: True) -> Optional[float]:
    """Mean self time of the spans called ``name``: seconds less the part
    their descendants cover, counting only those for which ``inner``
    holds (all by default). None if there is no such span."""
    kids = children(spans)
    per = [sp.seconds - covered(((d.start, d.end) for d in descendants(sp, kids)
                                 if inner(d)), sp.start, sp.end)
           for sp in spans if sp.name == name]
    return sum(per) / len(per) if per else None


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length in which two sorted lists of disjoint intervals overlap."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def sched_host_ms_per_step(ctx) -> Optional[float]:
    """Scheduler: ``sched.step`` less the time the ``engine.*`` spans in it
    cover, in ms, averaged over the window's steps."""
    spans = window_spans(ctx.window)
    if spans is None:
        return None
    s = self_time(spans, "sched.step", lambda d: d.name.startswith("engine."))
    return None if s is None else 1e3 * s


def engine_decode_host_ms(ctx) -> Optional[float]:
    """Engine: ``engine.decode`` less its ``engine.decode.wait`` (the host
    blocked on the device), in ms, averaged over the window's calls."""
    spans = window_spans(ctx.window)
    if spans is None:
        return None
    s = self_time(spans, "engine.decode", lambda d: d.name == "engine.decode.wait")
    return None if s is None else 1e3 * s


def to_trace_ns(intervals: Iterable[Interval], host_start: float,
                trace_start_ns: float) -> List[Interval]:
    """Host-clock seconds to trace nanoseconds, by the window anchor."""
    return [((s - host_start) * 1e9 + trace_start_ns, (e - host_start) * 1e9 + trace_start_ns)
            for s, e in intervals]


def idle_in_program_pct(ctx) -> Optional[float]:
    """Device: the share of the traced window in which device 0 ran no
    operation while some program span was open, in %."""
    if ctx.trace is None or not ctx.trace.ops or ctx.trace.window_s <= 0:
        return None
    spans = window_spans(ctx.window)
    if spans is None:
        return None
    lo, hi = ctx.trace.window
    in_program = union(to_trace_ns(((sp.start, sp.end) for sp in spans),
                                  ctx.window.start, lo))
    idle = trace_reduce.gaps_ns(ctx.trace.ops[0], ctx.trace.window)
    return 100.0 * overlap(union(idle), in_program) / (hi - lo)
