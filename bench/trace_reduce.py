"""From a profiler trace (``.xplane.pb``) to device busy time, kernel time
and the idle gaps, with nothing but JAX's own reader.

Device operations are the events of each device plane's "XLA Ops" line;
programs are the events of its "XLA Modules" line. Host spans are the
``bench.*`` annotations the harness records around each call into a layer
(``jax.profiler.TraceAnnotation``); the one named ``bench.window`` marks
the measured window, and everything is clipped to it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Event = Tuple[str, float, float]  # (name, start_ns, end_ns)
# control flow whose event spans the operations it runs: not an operation
# of its own in the list of the costliest
PARENT_OPS = re.compile(r"^(while|conditional|call)(\.|$)")


@dataclasses.dataclass
class TraceSummary:
    window: Tuple[float, float]  # ns, the bench.window span
    ops: List[List[Event]]  # per device, clipped to the window
    modules: List[List[Event]]  # per device, clipped to the window
    spans: List[Event]  # bench.* host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(union_ns(d) for d in self.ops) / len(self.ops) * 1e-9

    def op_time_s(self, pattern: str) -> Tuple[float, int]:
        """Summed device seconds and count of the operations whose name
        matches ``pattern`` (a regular expression), over all devices."""
        rx = re.compile(pattern)
        total, n = 0.0, 0
        for dev in self.ops:
            for name, s, e in dev:
                if rx.search(name):
                    total += e - s
                    n += 1
        return total * 1e-9, n

    def module_times_s(self, pattern: str) -> List[float]:
        rx = re.compile(pattern)
        return [(e - s) * 1e-9 for dev in self.modules for name, s, e in dev
                if rx.search(name)]

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        acc: Dict[str, float] = {}
        for dev in self.ops:
            for name, s, e in dev:
                if not PARENT_OPS.match(name):
                    acc[name] = acc.get(name, 0.0) + (e - s) * 1e-9
        return sorted(acc.items(), key=lambda kv: -kv[1])[:k]

    def idle_gaps(self, k: int = 10) -> List[Tuple[str, float]]:
        """The longest gaps with no device operation, on the first device,
        each named by the innermost host span that covers its middle."""
        if not self.ops:
            return []
        gaps = gaps_ns(self.ops[0], self.window)
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:k]:
            out.append((covering_span(self.spans, (s + e) / 2), (e - s) * 1e-9))
        return out


def union_ns(events: Sequence[Event]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(events: Sequence[Event], window: Tuple[float, float]) -> List[Tuple[float, float]]:
    gaps, t = [], window[0]
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if window[1] > t:
        gaps.append((t, window[1]))
    return gaps


def covering_span(spans: Sequence[Event], t: float) -> str:
    best: Optional[Event] = None
    for sp in spans:
        if sp[0] != WINDOW_SPAN and sp[1] <= t <= sp[2]:
            if best is None or sp[2] - sp[1] < best[2] - best[1]:
                best = sp
    return best[0] if best else "none"


def clip(events: Sequence[Event], window: Tuple[float, float]) -> List[Event]:
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found {found}")
    return found[0]


def op_name(name: str) -> str:
    """An operation event's name is its HLO instruction ("%fusion.3 =
    f32[...] fusion(...)"); keep the instruction's own name."""
    if name.startswith("%") and " = " in name:
        return name[1:name.index(" = ")]
    return name


def _events(line) -> List[Event]:
    return [(op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def summarize_planes(planes) -> TraceSummary:
    """Reduce planes (objects with ``name`` and ``lines``, each line with
    ``name`` and ``events`` having ``name``/``start_ns``/``duration_ns``)."""
    spans: List[Event] = []
    devices = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                devices.append((_events(lines[OPS_LINE]),
                                _events(lines[MODULES_LINE]) if MODULES_LINE in lines
                                else []))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend(ev for ev in _events(ln) if ev[0].startswith("bench."))
    windows = [sp for sp in spans if sp[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"trace holds {len(windows)} {WINDOW_SPAN} spans, not 1")
    window = (windows[0][1], windows[0][2])
    return TraceSummary(window=window,
                        ops=[clip(o, window) for o, _ in devices],
                        modules=[clip(m, window) for _, m in devices],
                        spans=spans)


def load(log_dir: str) -> TraceSummary:
    from jax.profiler import ProfileData

    return summarize_planes(ProfileData.from_file(find_xplane(log_dir)).planes)

