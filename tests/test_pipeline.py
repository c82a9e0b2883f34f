"""Overlapped I/O–compute pipeline + batched multi-site selection.

Timeline invariants (core/pipeline.py): zero compute ⇒ overlapped == serial;
compute-dominant ⇒ I/O fully hidden (steady-state critical path == compute);
overlapped ≤ serial always. Batched selection (core/chunking.py →
SparseExecution.refresh_layer): per-site mask identity vs the single-site
selector and the ``select_chunks_np`` numpy oracle, and ONE while_loop
greedy dispatch per layer (not one per site). Engine integration: both
charges logged per step, bytes threaded to IOEvents, selection overhead
populated.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.core.chunking import select_chunks_np
from repro.core.pipeline import PipelineModel
from repro.models import build_model
from repro.models.inputs import make_dummy_batch
from repro.serving import ServeEngine
from repro.serving.sparse_exec import SparseExecution


# -- timeline invariants ------------------------------------------------------


def test_zero_compute_overlapped_equals_serial():
    rng = np.random.default_rng(0)
    io = rng.random((6, 5))
    tl = PipelineModel().timeline(io, np.zeros(5))
    np.testing.assert_allclose(tl.overlap_s, tl.serial_s, rtol=1e-12)
    np.testing.assert_allclose(tl.serial_s, io.sum(axis=1), rtol=1e-12)
    assert tl.overlap_efficiency() == 1.0  # nothing hideable → trivially 1


def test_compute_dominant_io_fully_hidden():
    """When compute dwarfs I/O, every steady-state step's critical path is
    exactly Σ compute; step 0 additionally pays the cold first fetch."""
    n, n_layers = 5, 4
    io = np.full((n, n_layers), 1e-4)
    comp = np.full(n_layers, 1.0)
    tl = PipelineModel().timeline(io, comp)
    np.testing.assert_allclose(tl.overlap_s[1:], comp.sum(), rtol=1e-12)
    np.testing.assert_allclose(tl.overlap_s[0], comp.sum() + io[0, 0], rtol=1e-12)
    # everything hideable was hidden except the one cold fetch:
    # efficiency = (n·L − 1) / (n·L)
    np.testing.assert_allclose(
        tl.overlap_efficiency(), (n * n_layers - 1) / (n * n_layers), rtol=1e-9
    )


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("depth", [0, 1, 3])
def test_overlapped_never_exceeds_serial(seed, depth):
    rng = np.random.default_rng(seed)
    io = rng.random((8, 6)) * rng.choice([0.0, 1.0, 10.0], size=(8, 1))
    comp = rng.random((8, 6))
    tl = PipelineModel(prefetch_depth=depth).timeline(io, comp)
    assert np.all(tl.overlap_s <= tl.serial_s + 1e-12)
    assert np.all(tl.stall_s >= 0) and np.all(tl.bubble_s >= 0)
    assert 0.0 <= tl.overlap_efficiency() <= 1.0


def test_prefetch_depth_zero_is_serial():
    rng = np.random.default_rng(4)
    io, comp = rng.random((5, 3)), rng.random(3)
    tl = PipelineModel().serial_timeline(io, comp)
    np.testing.assert_allclose(tl.overlap_s, tl.serial_s, rtol=1e-12)


def test_reuse_steps_zero_io_charge_compute_only():
    """Plan-reuse-shaped input: refresh steps pay I/O, reuse steps are pure
    compute — cross-step prefetch may hide part of a refresh's I/O under
    the preceding reuse steps' compute, never the reverse."""
    io = np.zeros((6, 3))
    io[0] = io[3] = 0.01  # refresh every 3rd step
    comp = np.full(3, 1e-3)
    tl = PipelineModel().timeline(io, comp)
    reuse = [1, 2, 4, 5]
    np.testing.assert_allclose(tl.overlap_s[reuse], comp.sum(), rtol=1e-9)
    assert tl.overlap_total_s <= tl.serial_total_s + 1e-12


def test_pipeline_validation():
    with pytest.raises(ValueError):
        PipelineModel(prefetch_depth=-1)
    with pytest.raises(ValueError):
        PipelineModel().timeline(np.ones((2, 2)) * -1.0, np.ones(2))


# -- batched multi-site selection --------------------------------------------


@pytest.fixture(scope="module")
def ctx():
    cfg = get_config("tinyllama-1.1b").reduced()
    return SparseExecution(cfg, device="nano", sparsity=0.4, method="chunk")


@pytest.mark.parametrize("seed", [0, 7, 21])
def test_batched_selector_matches_single_site_and_oracle(ctx, seed):
    """Lane i of the batched (n_sites, K) problem must reproduce site i's
    single-site ``ChunkSelector.select`` AND the numpy oracle exactly —
    same utility, same stable tie-breaking, same budget rule — on the serve
    stack's real site shapes."""
    rng = np.random.default_rng(seed)
    batched = ctx.batched
    vs = np.zeros((batched.n_sites, batched.n_max), np.float32)
    budgets = []
    per_site = {}
    for i, kind in enumerate(ctx.site_order):
        site = ctx.sites[kind]
        v = rng.gamma(2.0, 1.0, site.n).astype(np.float32)
        vs[i, : site.n] = v
        budget = int(round((1.0 - site.sparsity) * site.n))
        budgets.append(budget)
        per_site[kind] = (v, budget, site)
    masks, selected = batched.select(
        jnp.asarray(vs), jnp.asarray(budgets, jnp.int32)
    )
    masks = np.asarray(masks)
    for i, kind in enumerate(ctx.site_order):
        v, budget, site = per_site[kind]
        m_single, n_single, _ = site.selector.select(
            jnp.asarray(v), jnp.int32(budget)
        )
        m_oracle = select_chunks_np(
            v, budget, site.selector.row_bytes, site.selector.table,
            site.selector.cfg,
        )
        np.testing.assert_array_equal(masks[i, : site.n], np.asarray(m_single))
        np.testing.assert_array_equal(masks[i, : site.n], m_oracle)
        assert int(selected[i]) == int(n_single) <= budget
        # padded rows are never selected
        assert not masks[i, site.n:].any()


def test_batched_selector_matches_oracle_with_residency(ctx):
    rng = np.random.default_rng(11)
    batched = ctx.batched
    vs = np.zeros((batched.n_sites, batched.n_max), np.float32)
    res = np.zeros((batched.n_sites, batched.n_max), bool)
    budgets, sites = [], []
    for i, kind in enumerate(ctx.site_order):
        site = ctx.sites[kind]
        vs[i, : site.n] = rng.gamma(2.0, 1.0, site.n).astype(np.float32)
        res[i, : site.n] = rng.random(site.n) < 0.3
        budgets.append(int(round((1.0 - site.sparsity) * site.n)))
        sites.append(site)
    masks, _ = batched.select(
        jnp.asarray(vs), jnp.asarray(budgets, jnp.int32), jnp.asarray(res)
    )
    masks = np.asarray(masks)
    for i, site in enumerate(sites):
        m_oracle = select_chunks_np(
            vs[i, : site.n], budgets[i], site.selector.row_bytes,
            site.selector.table, site.selector.cfg,
            resident=res[i, : site.n],
        )
        np.testing.assert_array_equal(masks[i, : site.n], m_oracle)


def test_batched_selector_prefilter_truncation_cannot_change_result(ctx):
    """Regression: the top-C prefilter must be a trip-count bound, never a
    truncation — with top_c far below K the completion segment has to take
    over and the masks must STILL match the oracle exactly (an earlier
    draft dropped candidates beyond C, under-filling the budget on
    full-size configs)."""
    from repro.core.chunking import BatchedChunkSelector

    sels = [ctx.sites[k].selector for k in ctx.site_order]
    tiny = BatchedChunkSelector.build(sels, top_c=16)
    assert tiny.top_c == 16  # prefilter genuinely engaged
    rng = np.random.default_rng(5)
    vs = np.zeros((tiny.n_sites, tiny.n_max), np.float32)
    budgets = []
    for i, kind in enumerate(ctx.site_order):
        site = ctx.sites[kind]
        vs[i, : site.n] = rng.gamma(2.0, 1.0, site.n).astype(np.float32)
        budgets.append(int(round((1.0 - site.sparsity) * site.n)))
    masks, selected = tiny.select(jnp.asarray(vs), jnp.asarray(budgets, jnp.int32))
    masks = np.asarray(masks)
    for i, kind in enumerate(ctx.site_order):
        site = ctx.sites[kind]
        m_oracle = select_chunks_np(
            vs[i, : site.n], budgets[i], site.selector.row_bytes,
            site.selector.table, site.selector.cfg,
        )
        np.testing.assert_array_equal(masks[i, : site.n], m_oracle)
        # and the budget is actually filled as far as the oracle fills it
        assert int(selected[i]) == int(m_oracle.sum())


def test_refresh_layer_honors_static_cached_without_residency_tier():
    """Legacy §5 contract on the PLANNED path with cache_mb == 0: static
    `cached` (memory-resident) neurons get zero selection importance —
    never streamed — and are always OR'd into the applied compute mask,
    exactly like the unplanned mask() path (a refactor once dropped this
    for the batched refresh)."""
    cfg = get_config("tinyllama-1.1b").reduced()
    n = cfg.d_model
    cached = jnp.zeros((n,), bool).at[jnp.arange(0, n, 4)].set(True)
    ctx_c = SparseExecution(cfg, device="nano", sparsity=0.4, method="chunk",
                            cached={"hidden_attn": cached})
    ctx_n = SparseExecution(cfg, device="nano", sparsity=0.4, method="chunk")
    rng = np.random.default_rng(2)

    def one_refresh(ctx):
        plan = jax.tree.map(lambda a: a[0], ctx.init_plan(1))
        for kind in ctx.site_order:
            v = rng.gamma(2.0, 1.0, (2, 4, ctx.sites[kind].n)).astype(np.float32)
            plan = ctx.record_importance(kind, jnp.asarray(v), plan)
        return ctx.refresh_layer(plan, jnp.bool_(True))

    plan_c, lat_c = one_refresh(ctx_c)
    plan_n, lat_n = one_refresh(ctx_n)
    # cached neurons always present in the applied mask
    assert bool(jnp.all(plan_c["hidden_attn"]["mask"][::4] == 1.0))
    # and caching never grows the I/O charge (cached rows stream nothing)
    assert float(lat_c) <= float(lat_n) * 1.2


def _count_while_eqns(jaxpr) -> int:
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            count += 1
        for v in eqn.params.values():
            objs = v if isinstance(v, (tuple, list)) else [v]
            for o in objs:
                inner = getattr(o, "jaxpr", None)
                if inner is not None:
                    count += _count_while_eqns(inner)
    return count


def test_refresh_is_one_batched_dispatch_per_layer(ctx):
    """The planned refresh path must run ONE vmapped while_loop greedy for
    all of a layer's sites — not one per site (the seed ran four)."""
    plan_full = ctx.init_plan(2)
    layer_plan = jax.tree.map(lambda a: a[0], plan_full)
    jaxpr = jax.make_jaxpr(
        lambda p: ctx.refresh_layer(p, jnp.bool_(True))
    )(layer_plan)
    assert _count_while_eqns(jaxpr.jaxpr) == 1


# -- engine integration -------------------------------------------------------


@pytest.fixture(scope="module")
def lm():
    cfg = get_config("tinyllama-1.1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = make_dummy_batch(cfg, InputShape("pipe", 8, 2, "train"))
    return cfg, model, params, batch


def _engine(lm, overlap=True, method="chunk", seed=3):
    cfg, model, params, batch = lm
    eng = ServeEngine(model, params, max_seq=64, batch_size=2, device="nano",
                      sparsity=0.4, method=method, seed=seed, overlap=overlap)
    eng.simulator.noise = 0.0
    tok0 = jnp.argmax(eng.prefill(batch), -1)[:, None].astype(jnp.int32)
    return eng, tok0


def test_engine_overlap_below_serial_tokens_identical(lm):
    eng_o, tok0 = _engine(lm, overlap=True)
    eng_s, _ = _engine(lm, overlap=False)
    out_o = eng_o.decode(tok0, 6)
    out_s = eng_s.decode(tok0, 6)
    assert bool(jnp.all(out_o == out_s)), "overlap mode must not change tokens"
    s = eng_o.io_summary()
    assert 0.0 < s["decode_overlap_s"] < s["decode_serial_s"]
    assert 0.5 <= s["overlap_efficiency"] <= 1.0
    for st in eng_o.stats:
        if st.kind == "decode":
            assert st.overlap_s <= st.serial_s + 1e-15
            assert st.compute_s > 0 and st.stall_s >= 0


def test_engine_bytes_threaded_to_simulator(lm):
    """total_bytes() must be meaningful on the estimate-driven scan path
    (it used to log nbytes=0): decode events carry miss-rows × row-bytes
    and the per-token loop agrees exactly."""
    eng, tok0 = _engine(lm)
    eng.decode(tok0, 5)
    dec_events = [e for e in eng.simulator.log if e.name.startswith("decode")]
    assert dec_events and all(e.nbytes > 0 for e in dec_events)
    assert eng.simulator.total_bytes() > 0
    decode_bytes = sum(e.nbytes for e in dec_events)
    assert decode_bytes == sum(
        s.nbytes for s in eng.stats if s.kind == "decode"
    )
    eng_p, tok0p = _engine(lm)
    eng_p.decode_per_token(tok0p, 5)
    assert sum(s.nbytes for s in eng_p.stats if s.kind == "decode") == decode_bytes


def test_engine_scan_and_per_token_pipeline_agree(lm):
    """With a deterministic simulator the two decode loops must agree on
    the pipeline charges, not just tokens and estimates."""
    eng_s, tok0 = _engine(lm)
    eng_p, _ = _engine(lm)
    eng_s.decode(tok0, 5)
    eng_p.decode_per_token(tok0, 5)
    for key in ("serial_s", "overlap_s", "stall_s"):
        a = [getattr(s, key) for s in eng_s.stats if s.kind == "decode"]
        b = [getattr(s, key) for s in eng_p.stats if s.kind == "decode"]
        np.testing.assert_allclose(a, b, rtol=1e-9, err_msg=key)


def test_dense_free_pipeline_is_compute_bound(lm):
    eng, tok0 = _engine(lm, method="dense_free")
    eng.decode(tok0, 4)
    s = eng.io_summary()
    assert s["io_sim_s"] == 0.0
    assert s["decode_compute_s"] > 0
    np.testing.assert_allclose(s["decode_overlap_s"], s["decode_serial_s"],
                               rtol=1e-12)
    assert s["overlap_efficiency"] == 1.0  # nothing hideable
