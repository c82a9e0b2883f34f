"""Spans and counters of the serve path, kept in memory.

``span(name, **attrs)`` times a block on ``time.perf_counter_ns`` (the
clock ``StepStats.wall_s`` uses) and records ``Span(id, parent, name,
start_ns, end_ns, attrs)``, the parent being the span open around it on the
same thread. It also enters ``jax.profiler.TraceAnnotation(name)``, so
under a running profiler the same span lies on the trace's host line, on
the clock of the device operations. ``count(name, n)`` adds to a counter.

Finished spans go into a ring of ``RING_SIZE`` entries (the oldest drop
out); nothing is written while the program runs. Read ``spans()`` and
``counters()`` when it ends. The recorder is always on.

Two host pauses are recorded by this module itself, from its import on:

- ``jax.compile``: one span per backend compile (JAX's
  ``backend_compile_duration``, which also covers loading a program from
  the persistent compilation cache), ending when JAX reports it and
  lasting the duration JAX gives, under whatever span was open; counted
  as ``jax.compiles``;
- ``host.gc``: one span per generation-2 garbage collection. Every
  collection is counted, as ``host.gc.gen<g>`` and ``host.gc.gen<g>_ns``;
  generations 0 and 1 only so, lest their traffic flood the ring.
"""
from __future__ import annotations

import collections
import gc
import itertools
import threading
import time
from typing import Any, Deque, Dict, List, NamedTuple

import jax

RING_SIZE = 65_536
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    id: int
    parent: int  # 0: no span was open around it
    name: str
    start_ns: int
    end_ns: int
    attrs: Dict[str, Any]


class _Open:
    """A span while it is open (the context manager ``span`` returns)."""

    __slots__ = ("_rec", "name", "attrs", "id", "parent", "start_ns", "_annotation")

    def __init__(self, rec: "Recorder", name: str, attrs: Dict[str, Any]):
        self._rec, self.name, self.attrs = rec, name, attrs

    def __enter__(self) -> "_Open":
        stack = self._rec._stack()
        self.parent = stack[-1] if stack else 0
        self.id = next(self._rec._ids)
        stack.append(self.id)
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end_ns = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        self._rec._stack().pop()
        self._rec._ring.append(
            Span(self.id, self.parent, self.name, self.start_ns, end_ns, self.attrs))


class Recorder:
    """A ring of finished spans, the open spans of each thread, and counters."""

    def __init__(self, ring_size: int = RING_SIZE):
        self._ring: Deque[Span] = collections.deque(maxlen=ring_size)
        self._counters: Dict[str, int] = {}
        # re-entrant: a collection can start inside ``count``, and its
        # callback counts too
        self._lock = threading.RLock()
        self._ids = itertools.count(1)
        self._local = threading.local()

    def _stack(self) -> List[int]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def span(self, name: str, **attrs) -> _Open:
        return _Open(self, name, attrs)

    def record(self, name: str, start_ns: int, end_ns: int, **attrs) -> None:
        """A span that has already ended, under the span open now."""
        stack = self._stack()
        self._ring.append(Span(next(self._ids), stack[-1] if stack else 0, name,
                               start_ns, end_ns, attrs))

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def spans(self) -> List[Span]:
        """The finished spans still in the ring, oldest first."""
        return list(self._ring)

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counters)


RECORDER = Recorder()
span = RECORDER.span
count = RECORDER.count
spans = RECORDER.spans
counters = RECORDER.counters


def _on_duration(event: str, duration_secs: float, **_) -> None:
    if event == COMPILE_EVENT:
        end_ns = time.perf_counter_ns()
        RECORDER.record("jax.compile", end_ns - int(duration_secs * 1e9), end_ns)
        RECORDER.count("jax.compiles")


_GC_NAMES = [(f"host.gc.gen{g}", f"host.gc.gen{g}_ns") for g in range(3)]
_gc_start_ns = 0


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    global _gc_start_ns
    if phase == "start":
        _gc_start_ns = time.perf_counter_ns()
        return
    end_ns = time.perf_counter_ns()
    g = info["generation"]
    n_name, ns_name = _GC_NAMES[g]
    RECORDER.count(n_name)
    RECORDER.count(ns_name, end_ns - _gc_start_ns)
    if g == 2:
        RECORDER.record("host.gc", _gc_start_ns, end_ns, collected=info["collected"])


jax.monitoring.register_event_duration_secs_listener(_on_duration)
gc.callbacks.append(_on_gc)
