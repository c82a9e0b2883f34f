"""The readers of the program's own spans (bench/program_spans.py) on
synthetic spans and a synthetic trace summary: nothing here runs a model."""
import sys
from types import SimpleNamespace

import pytest

from bench import program_spans, spec, trace_reduce


def raw(i, parent, name, start_s, end_s):
    """A span as ``repro.trace`` records it (nanoseconds)."""
    return SimpleNamespace(id=i, parent=parent, name=name,
                           start_ns=int(start_s * 1e9), end_ns=int(end_s * 1e9), attrs={})


# a window [10, 20] s on the host clock: two scheduler steps, one of them
# straddling the close; an admission with its engine spans; decode calls
SPANS = [
    raw(1, 0, "sched.step", 9.0, 9.5),  # before the window: dropped
    raw(2, 0, "sched.step", 11.0, 15.0),
    raw(3, 2, "sched.admit", 11.0, 12.0),
    raw(4, 3, "engine.prefill", 11.2, 11.6),
    raw(5, 3, "engine.kv_write", 11.5, 11.8),  # overlaps the prefill
    raw(6, 2, "engine.decode", 12.5, 14.5),
    raw(7, 6, "engine.decode.wait", 12.6, 14.0),
    raw(8, 6, "engine.decode.account", 14.0, 14.4),
    raw(9, 0, "sched.step", 19.0, 21.0),  # clipped to [19, 20]
    raw(10, 9, "engine.decode", 19.0, 20.5),  # clipped to [19, 20]
    raw(11, 10, "engine.decode.wait", 19.2, 19.8),
]
WINDOW = SimpleNamespace(start=10.0, end=20.0)


@pytest.fixture
def recorded(monkeypatch):
    from repro import trace

    monkeypatch.setattr(trace, "spans", lambda: list(SPANS))


def test_spans_are_clipped_to_the_window():
    spans = program_spans.clip(SPANS, WINDOW.start, WINDOW.end)
    assert 1 not in {s.id for s in spans}
    last = {s.id: s for s in spans}[9]
    assert (last.start, last.end) == (19.0, 20.0)
    assert {s.id: s for s in spans}[10].end == 20.0


def test_self_time_counts_overlapping_children_once():
    spans = program_spans.clip(SPANS, WINDOW.start, WINDOW.end)
    # the admission's two children cover [11.2, 11.8] together
    assert program_spans.self_time(spans, "sched.admit") == pytest.approx(0.4)
    # the first step's descendants cover [11, 12] and [12.5, 14.5]; the
    # clipped second step is covered whole
    assert program_spans.self_time(spans, "sched.step") == pytest.approx((1.0 + 0.0) / 2)


def test_scheduler_and_engine_host_time(recorded):
    ctx = SimpleNamespace(window=WINDOW, trace=None)
    # step 2: 4 s less engine.* [11.2, 11.8] and [12.5, 14.5] = 1.4 s;
    # step 9: 1 s less its decode [19, 20] = 0
    assert program_spans.sched_host_ms_per_step(ctx) == pytest.approx(1e3 * 1.4 / 2)
    # decode 6: 2.0 - 1.4; decode 10 (clipped): 1.0 - 0.6
    assert program_spans.engine_decode_host_ms(ctx) == pytest.approx(1e3 * (0.6 + 0.4) / 2)


def test_the_window_anchor_maps_host_time_onto_the_trace():
    # bench.window opened at 5_000 ns on the trace's clock, at 10.0 s on the host's
    assert program_spans.to_trace_ns([(10.0, 10.5), (12.0, 12.25)], 10.0, 5_000) == [
        (5_000, pytest.approx(5_000 + 0.5e9)),
        (pytest.approx(5_000 + 2e9), pytest.approx(5_000 + 2.25e9))]


def summary(ops):
    lo = 5_000.0
    return trace_reduce.TraceSummary(
        window=(lo, lo + 10e9),
        ops=[[("op", lo + s * 1e9, lo + e * 1e9) for s, e in ops]],
        modules=[[]], spans=[])


def test_idle_inside_program_spans(recorded):
    # device busy [0, 1.5] and [3, 10] s into the window: idle [1.5, 3];
    # the program's spans cover [1, 5] and [9, 10] of it, so idle in a
    # program span is [1.5, 3] = 1.5 s of 10
    ctx = SimpleNamespace(window=WINDOW, trace=summary([(0.0, 1.5), (3.0, 10.0)]))
    assert program_spans.idle_in_program_pct(ctx) == pytest.approx(15.0)
    # an idle stretch outside every span does not count
    ctx = SimpleNamespace(window=WINDOW, trace=summary([(1.0, 10.0)]))
    assert program_spans.idle_in_program_pct(ctx) == pytest.approx(0.0)


def test_overlap_of_interval_lists():
    a = [(0.0, 2.0), (4.0, 6.0), (8.0, 9.0)]
    b = [(1.0, 5.0), (5.5, 8.5)]
    assert program_spans.overlap(a, b) == pytest.approx(1.0 + 1.0 + 0.5 + 0.5)
    assert program_spans.overlap(a, []) == 0.0


@pytest.mark.parametrize("name", ["sched.host_ms_per_step.batch", "sched.host_ms_per_step.chat",
                                  "engine.decode_host_ms.batch", "engine.decode_host_ms.chat",
                                  "device.idle_in_program_pct.batch",
                                  "device.idle_in_program_pct.chat"])
def test_readers_give_none_without_spans(monkeypatch, name):
    from repro import trace

    reader = spec.load_module(spec.BENCH_DIR / "metrics" / f"{name}.py")
    ctx = SimpleNamespace(window=WINDOW, trace=summary([(0.0, 1.0)]))
    monkeypatch.setattr(trace, "spans", lambda: [])
    assert reader.read(ctx) is None
    # a program without the recorder at all
    import repro

    monkeypatch.delattr(repro, "trace")
    monkeypatch.setitem(sys.modules, "repro.trace", None)
    with pytest.raises(ImportError):
        from repro import trace  # noqa: F401,F811
    monkeypatch.setattr(program_spans, "clip", None)  # never reached
    assert reader.read(ctx) is None


def test_the_idle_reader_gives_none_without_a_trace(recorded):
    assert program_spans.idle_in_program_pct(SimpleNamespace(window=WINDOW, trace=None)) is None
