"""The in-process span recorder (``repro.trace``), the serve path's spans
and counters, and the named scopes inside the decode programs."""
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trace
from repro.configs import get_config
from repro.models import build_model
from repro.serving import Request, Scheduler, ServeEngine

DECODE_SCOPES = ("decode.select", "decode.importance", "decode.attention",
                 "decode.gather", "decode.sample")


def _since(first_id):
    return [s for s in trace.spans() if s.id > first_id]


def _marker() -> int:
    with trace.span("test.marker") as m:
        pass
    return m.id


# -- the recorder ------------------------------------------------------------


def test_nested_spans_record_their_parents_and_attributes():
    rec = trace.Recorder()
    with rec.span("a", rid=7) as a:
        with rec.span("b") as b:
            with rec.span("c") as c:
                pass
        with rec.span("d") as d:
            pass
    with rec.span("e") as e:
        pass
    by = {s.name: s for s in rec.spans()}
    assert [s.name for s in rec.spans()] == ["c", "b", "d", "a", "e"]  # by end
    assert by["a"].parent == 0 and by["e"].parent == 0
    assert by["b"].parent == a.id and by["d"].parent == a.id
    assert by["c"].parent == b.id
    assert (by["a"].id, by["d"].id, by["e"].id) == (a.id, d.id, e.id)
    assert by["a"].attrs == {"rid": 7} and c.attrs == {}


def test_children_lie_inside_their_parent_and_leave_its_self_time():
    rec = trace.Recorder()
    with rec.span("parent"):
        with rec.span("child"):
            time.sleep(0.01)
        time.sleep(0.02)
        with rec.span("child"):
            time.sleep(0.01)
    spans = rec.spans()
    parent = spans[-1]
    kids = [s for s in spans if s.parent == parent.id]
    assert len(kids) == 2
    for k in kids:
        assert parent.start_ns <= k.start_ns <= k.end_ns <= parent.end_ns
    self_ns = (parent.end_ns - parent.start_ns) - sum(k.end_ns - k.start_ns for k in kids)
    assert 0.02e9 <= self_ns < 0.2e9


def test_ring_keeps_the_newest_spans():
    rec = trace.Recorder(ring_size=4)
    for i in range(10):
        with rec.span(f"s{i}"):
            pass
    assert [s.name for s in rec.spans()] == ["s6", "s7", "s8", "s9"]
    assert trace.RING_SIZE == 65_536


def test_counters_add_up():
    rec = trace.Recorder()
    rec.count("x")
    rec.count("x", 4)
    rec.count("y", 0)
    assert rec.counters() == {"x": 5, "y": 0}
    rec.counters()["x"] = 0  # a copy
    assert rec.counters()["x"] == 5


def test_a_backend_compile_lands_as_a_span_inside_the_open_span():
    n0 = trace.counters().get("jax.compiles", 0)
    # a constant of its own: nothing cached can stand in for this compile
    c = float(time.perf_counter_ns() % 1_000_003)
    with trace.span("test.outer") as outer:
        jax.jit(lambda x: x * c + 1.0)(jnp.ones((3, 5))).block_until_ready()
    compiles = [s for s in trace.spans() if s.name == "jax.compile"
                and s.parent == outer.id]
    assert compiles
    whole = next(s for s in trace.spans() if s.id == outer.id)
    for s in compiles:
        assert whole.start_ns <= s.start_ns <= s.end_ns <= whole.end_ns
    assert trace.counters()["jax.compiles"] >= n0 + len(compiles)


def test_a_full_collection_is_a_host_gc_span():
    n0 = trace.counters().get("host.gc.gen2", 0)
    with trace.span("test.outer") as outer:
        gc.collect()
    spans = [s for s in trace.spans() if s.name == "host.gc" and s.parent == outer.id]
    assert spans and all(s.end_ns >= s.start_ns and "collected" in s.attrs
                         for s in spans)
    cs = trace.counters()
    assert cs["host.gc.gen2"] >= n0 + 1 and cs["host.gc.gen2_ns"] > 0


# -- the serve path ----------------------------------------------------------


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("tinyllama-1.1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    eng = ServeEngine(model, params, max_seq=32, batch_size=2, device="nano",
                      sparsity=0.4, method="topk", seed=1)
    return cfg, eng


def test_a_request_nests_its_spans_through_scheduler_and_engine(engine):
    cfg, eng = engine
    sched = Scheduler(eng, round_tokens=1)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)
    sched.submit(Request(rid=41, prompt={"tokens": toks}, max_new_tokens=2))
    c0 = trace.counters()
    first = _marker()
    sched.run()
    spans = _since(first)
    by_id = {s.id: s for s in spans}
    named = lambda n: [s for s in spans if s.name == n]  # noqa: E731

    steps = named("sched.step")
    assert len(steps) == 2 and steps[0].attrs == {"running": 0, "waiting": 1}
    admit, = named("sched.admit")
    assert admit.attrs == {"rid": 41, "slot": 0} and admit.parent == steps[0].id
    prefill, = named("engine.prefill")
    kv_write, = named("engine.kv_write")
    assert prefill.parent == kv_write.parent == admit.id
    assert prefill.attrs == {"tokens": 8} and kv_write.attrs == {"slot": 0}
    decodes = named("engine.decode")
    assert [d.parent for d in decodes] == [s.id for s in steps]
    assert all(d.attrs == {"n_tokens": 1} for d in decodes)
    for name in ("engine.decode.wait", "engine.decode.account"):
        assert sorted(s.parent for s in named(name)) == sorted(d.id for d in decodes)
    evict, = named("sched.evict")
    assert evict.attrs == {"rid": 41, "slot": 0} and evict.parent == steps[1].id
    for s in spans:  # every child lies inside its parent
        p = by_id.get(s.parent)
        if p is not None:
            assert p.start_ns <= s.start_ns <= s.end_ns <= p.end_ns

    c1 = trace.counters()
    delta = {k: c1.get(k, 0) - c0.get(k, 0) for k in
             ("sched.admitted", "sched.evicted", "sched.tokens_delivered",
              "engine.decode_calls")}
    assert delta == {"sched.admitted": 1, "sched.evicted": 1,
                     "sched.tokens_delivered": 2, "engine.decode_calls": 2}


def test_decode_scan_carries_every_scope_and_prefill_keeps_its_module_name(engine):
    cfg, eng = engine
    eng.enable_slots()
    tokens = jnp.zeros((eng.batch_size, 1), jnp.int32)
    lowered = eng._decode_scan.lower(eng.params, tokens, eng.cache, 1, eng._init_plan())
    text = lowered.as_text(debug_info=True)
    for scope in DECODE_SCOPES:
        assert scope in text, scope
    assert "module @jit__decode_scan_impl" in lowered.as_text()
    batch = {"tokens": jnp.zeros((1, 8), jnp.int32)}
    assert "module @jit__lambda" in eng._prefill_one.lower(eng.params, batch).as_text()
