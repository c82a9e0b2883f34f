"""End-to-end chunk integrity (PR 9): checksum lane, data-plane corruption
injection, and the detection → re-read → recovery/degradation ladder.

The headline invariants:

  * every corruption RECOVERABLE (bit_rot: transient flips, p_stuck=0)
    ⇒ greedy tokens byte-identical to the corruption-off engine, across
    backends × wbits, with ``corruptions_detected == corruptions_recovered``
    and zero substitutions/drops — compute never sees a corrupt byte;
  * recovery OFF with the same (profile, seed) ⇒ the same injected damage
    reaches compute and measurably corrupts the tokens, yet the corrupted
    run itself replays bit-identically (and identically across backends:
    both apply the same ``corrupt_payload``);
  * corruptions that survive the re-read budget (degraded_nand) walk the
    deterministic ladder — resident-copy, substitute, drop — and every
    rung's counter in ``io_summary()`` replays exactly;
  * the checksum DMA lane itself is semantically inert: kernels with and
    without the third lane produce bit-identical outputs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.faults import (
    CORRUPTION_PROFILES,
    CorruptionModel,
    CorruptionProfile,
    corruption_key,
    get_corruption_profile,
)
from repro.core.offload import pack_checksums
from repro.kernels.chunk_gather_dma import (
    chunk_gather_matmul_dma,
    chunk_gather_mlp_dma,
)
from repro.kernels.quantize import (
    QUANT_SUFFIX_CHECKSUM,
    block_checksums,
    quantize_params,
    quantize_rows,
)
from repro.models import build_model
from repro.serving import DegradationController, ServeEngine

slow = pytest.mark.slow

COUNTER_KEYS = (
    "corruptions_detected",
    "corruptions_recovered",
    "corruptions_substituted",
    "corruptions_dropped",
    "integrity_reread_s",
)


@pytest.fixture(scope="module")
def lm():
    cfg = get_config("tinyllama-1.1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _engine(model, params, **kw):
    kw.setdefault("method", "chunk")
    return ServeEngine(model, params, max_seq=64, batch_size=2, device="nano",
                       sparsity=0.4, seed=1, **kw)


def _counters(eng):
    s = eng.io_summary()
    return {k: s[k] for k in COUNTER_KEYS}


# ---------------------------------------------------------------------------
# block_checksums: the pack-time integrity lane
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.float16, jnp.float32])
def test_checksum_shape_dtype_and_single_bit_detection(rng, dtype):
    """One uint32 per 8-row block, and ANY single-bit flip of the stored
    payload moves exactly the containing block's checksum — the property
    the odd position weights guarantee."""
    w = jnp.asarray(rng.normal(0, 1, (32, 16)) * 10, dtype)
    ck = block_checksums(w)
    assert ck.shape == (4,) and ck.dtype == jnp.uint32
    # flip the lowest bit of one element in block 2 via bitcast
    itemsize = jnp.dtype(dtype).itemsize
    uint = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[itemsize]
    u = np.array(jax.lax.bitcast_convert_type(w, uint))
    u[17, 3] ^= 1
    w2 = jax.lax.bitcast_convert_type(jnp.asarray(u), dtype)
    ck2 = block_checksums(w2)
    changed = np.asarray(ck != ck2)
    assert changed.tolist() == [False, False, True, False]


def test_checksum_detects_reordering_within_block(rng):
    """Equal-weight sums would miss a row swap inside a block; the
    position weighting must not."""
    w = np.asarray(rng.normal(0, 1, (8, 8)), np.float32)
    swapped = w[[1, 0, 2, 3, 4, 5, 6, 7]]
    c0 = block_checksums(jnp.asarray(w))
    c1 = block_checksums(jnp.asarray(swapped))
    assert int(c0[0]) != int(c1[0])


def test_checksum_rows_must_divide_block():
    with pytest.raises(ValueError, match="multiple of block_rows"):
        block_checksums(jnp.ones((12, 4)))


def test_quantize_params_emits_checksum_leaf(rng):
    """wbits=8 pack path: the ``_ck`` leaf checksums the int8 payload —
    exactly the bytes the DMA lane streams at that width."""
    layers = {"wq": jnp.asarray(rng.normal(0, 1, (3, 16, 8)), jnp.bfloat16)}
    out = quantize_params(layers, ("wq",), checksums=True)
    ck = out["wq" + QUANT_SUFFIX_CHECKSUM]
    assert ck.shape == (3, 2) and ck.dtype == jnp.uint32
    q0, _ = quantize_rows(layers["wq"][0], 8)
    np.testing.assert_array_equal(np.asarray(ck[0]),
                                  np.asarray(block_checksums(q0)))
    # default stays checksum-free: no silent storage growth at wbits=8
    assert "wq" + QUANT_SUFFIX_CHECKSUM not in quantize_params(layers, ("wq",))


def test_pack_checksums_fp_twin(rng):
    """wbits=16 pack path: ``pack_checksums`` checksums the fp weight
    itself (the bytes streamed unquantized); missing names are skipped."""
    layers = {"wo": jnp.asarray(rng.normal(0, 1, (2, 24, 4)), jnp.float32)}
    out = pack_checksums(layers, ("wo", "absent"))
    assert sorted(out) == ["wo" + QUANT_SUFFIX_CHECKSUM]
    assert out["wo_ck"].shape == (2, 3) and out["wo_ck"].dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(out["wo_ck"][1]),
                                  np.asarray(block_checksums(layers["wo"][1])))


# ---------------------------------------------------------------------------
# CorruptionModel: seeded draw / damage / re-read semantics
# ---------------------------------------------------------------------------


def test_corruption_profile_validation():
    with pytest.raises(ValueError, match="p_block"):
        CorruptionProfile("bad", p_block=1.0)
    with pytest.raises(ValueError, match="mode"):
        CorruptionProfile("bad", p_block=0.1, mode="scramble")
    with pytest.raises(ValueError, match="p_stuck"):
        CorruptionProfile("bad", p_block=0.1, p_stuck=1.0)
    with pytest.raises(KeyError, match="unknown corruption profile"):
        get_corruption_profile("nope")
    with pytest.raises(ValueError, match="max_reread"):
        CorruptionModel("bit_rot", max_reread=-1)
    assert not CorruptionModel("none").enabled
    assert CorruptionModel("bit_rot").enabled
    assert set(CORRUPTION_PROFILES) == {
        "none", "bit_rot", "torn_read", "degraded_nand"}


def test_draw_blocks_masked_to_fetched_and_deterministic():
    cm = CorruptionModel("degraded_nand", seed=11)
    fetched = jnp.asarray([True] * 200 + [False] * 200)
    k = corruption_key(cm.base_key(), 3, 1, 2, 0)
    c1 = np.asarray(cm.draw_blocks(k, fetched))
    c2 = np.asarray(cm.draw_blocks(k, fetched))
    np.testing.assert_array_equal(c1, c2)
    # resident blocks (not fetched) never corrupt
    assert not c1[200:].any()
    assert c1[:200].any()  # p=0.05 over 200 draws: essentially certain
    # a different (layer, epoch, site, matrix) gives a different pattern
    c3 = np.asarray(cm.draw_blocks(corruption_key(cm.base_key(), 3, 2, 2, 0),
                                   fetched))
    assert not np.array_equal(c1, c3)


def test_draw_rereads_transient_profile_always_recovers():
    """p_stuck=0 (bit_rot): the first re-read is clean, so every corrupt
    block charges exactly one re-read and recovers."""
    cm = CorruptionModel("bit_rot", max_reread=2)
    corrupt = jnp.asarray([True, False, True])
    rr, rec = cm.draw_rereads(cm.base_key(), corrupt)
    assert np.asarray(rr).tolist() == [1, 0, 1]
    assert np.asarray(rec).tolist() == [True, False, True]


def test_draw_rereads_recovery_off_and_budget_zero():
    corrupt = jnp.ones(4, bool)
    for cm in (CorruptionModel("degraded_nand", recover=False),
               CorruptionModel("degraded_nand", max_reread=0)):
        rr, rec = cm.draw_rereads(cm.base_key(), corrupt)
        assert not np.asarray(rr).any() and not np.asarray(rec).any()


def test_draw_rereads_sticky_profile_sometimes_exhausts_budget():
    """degraded_nand (p_stuck=0.65): across many corrupt blocks some recover
    within budget and some exhaust it; charged re-reads never exceed
    max_reread and recovery ⇔ fails < budget."""
    cm = CorruptionModel("degraded_nand", seed=5, max_reread=2)
    corrupt = jnp.ones(512, bool)
    k = corruption_key(cm.base_key(), 0, 0, 0, 0)
    rr = np.asarray(cm.draw_rereads(k, corrupt)[0])
    rec = np.asarray(cm.draw_rereads(k, corrupt)[1])
    assert rr.min() >= 1 and rr.max() == 2
    assert rec.any() and not rec.all()
    # a block that recovered needed < budget failures → charged ≤ budget;
    # an unrecovered block charged exactly the full budget
    assert (rr[~rec] == 2).all()


def test_backoff_seconds_geometric_ladder():
    cm = CorruptionModel("bit_rot")  # base 5e-5, mult 2.0
    r = jnp.asarray([0, 1, 2, 3], jnp.int32)
    out = np.asarray(cm.backoff_seconds(r))
    # base * (m^r - 1) / (m - 1): 0, 1, 3, 7 units
    np.testing.assert_allclose(out, 5e-5 * np.asarray([0, 1, 3, 7]),
                               rtol=1e-6)
    flat = CorruptionModel(CorruptionProfile(
        "flat", p_block=0.01, backoff_base_s=1e-4, backoff_mult=1.0))
    np.testing.assert_allclose(np.asarray(flat.backoff_seconds(r)),
                               1e-4 * np.asarray([0, 1, 2, 3]), rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.float16, jnp.float32])
def test_corrupt_payload_flip_touches_one_bit_per_block(rng, dtype):
    """mode="flip": exactly one element of one corrupted block differs, by
    exactly one bit; untouched blocks are bit-identical."""
    cm = CorruptionModel("bit_rot", seed=2)
    w = jnp.asarray(rng.normal(0, 1, (24, 8)) * 5, dtype)
    blocks = jnp.asarray([False, True, False])
    k = corruption_key(cm.base_key(), 0, 0, 0, 0)
    w2 = cm.corrupt_payload(w, blocks, k)
    itemsize = jnp.dtype(dtype).itemsize
    uint = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[itemsize]
    u0 = np.asarray(jax.lax.bitcast_convert_type(w, uint), np.uint32)
    u1 = np.asarray(jax.lax.bitcast_convert_type(w2, uint), np.uint32)
    diff = u0 ^ u1
    assert not diff[:8].any() and not diff[16:].any()
    nz = diff[8:16][diff[8:16] != 0]
    assert nz.size == 1  # one element
    assert bin(int(nz[0])).count("1") == 1  # one bit
    # the stored checksum flags exactly that block
    bad = np.asarray(block_checksums(w) != block_checksums(w2))
    assert bad.tolist() == [False, True, False]
    # deterministic in the key
    np.testing.assert_array_equal(
        np.asarray(w2), np.asarray(cm.corrupt_payload(w, blocks, k)))


def test_corrupt_payload_zero_mode_zeroes_whole_block(rng):
    cm = CorruptionModel("torn_read", seed=2)
    w = jnp.asarray(rng.normal(1, 0.1, (16, 4)), jnp.float32)
    blocks = jnp.asarray([True, False])
    w2 = np.asarray(cm.corrupt_payload(
        w, blocks, corruption_key(cm.base_key(), 0, 0, 0, 0)))
    assert (w2[:8] == 0.0).all()
    np.testing.assert_array_equal(w2[8:], np.asarray(w)[8:])


# ---------------------------------------------------------------------------
# the checksum DMA lane is semantically inert
# ---------------------------------------------------------------------------


def test_matmul_kernel_checksum_lane_bit_identical(rng):
    w = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 64)), jnp.float32)
    starts = jnp.asarray([0, 24, 0, 0], jnp.int32)
    sizes = jnp.asarray([16, 32, 0, 0], jnp.int32)
    y0 = chunk_gather_matmul_dma(w, x, starts, sizes, interpret=True)
    y1 = chunk_gather_matmul_dma(w, x, starts, sizes,
                                 checksums=block_checksums(w),
                                 interpret=True)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    # quantized + checksummed: both extra lanes ride the slot rotation
    q, s = quantize_rows(w)
    yq0 = chunk_gather_matmul_dma(q, x, starts, sizes, s, interpret=True)
    yq1 = chunk_gather_matmul_dma(q, x, starts, sizes, s, block_checksums(q),
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(yq0), np.asarray(yq1))


def test_mlp_kernel_checksum_lane_bit_identical(rng):
    wg = jnp.asarray(rng.normal(size=(64, 48)), jnp.float32)
    wu = jnp.asarray(rng.normal(size=(64, 48)), jnp.float32)
    wd = jnp.asarray(rng.normal(size=(48, 32)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 64)), jnp.float32)
    st = jnp.asarray([[0, 24, 0, 0], [8, 0, 0, 0]], jnp.int32)
    sz = jnp.asarray([[16, 32, 0, 0], [24, 0, 0, 0]], jnp.int32)
    z0 = chunk_gather_mlp_dma(wg, wu, wd, x, st, sz, interpret=True)
    z1 = chunk_gather_mlp_dma(
        wg, wu, wd, x, st, sz,
        checksums=(block_checksums(wg), block_checksums(wu),
                   block_checksums(wd)),
        interpret=True)
    np.testing.assert_array_equal(np.asarray(z0), np.asarray(z1))


def test_matmul_kernel_rejects_bad_checksum_shape(rng):
    w = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 64)), jnp.float32)
    starts = jnp.asarray([0, 0, 0, 0], jnp.int32)
    sizes = jnp.asarray([8, 0, 0, 0], jnp.int32)
    with pytest.raises(ValueError, match="checksums"):
        chunk_gather_matmul_dma(w, x, starts, sizes,
                                checksums=jnp.zeros(7, jnp.uint32),
                                interpret=True)


# ---------------------------------------------------------------------------
# engine: the headline byte-identity + ladder invariants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,wbits", [("reference", 16), ("kernel", 8)])
def test_engine_recovered_corruption_byte_identity(lm, backend, wbits):
    """bit_rot (every corruption recoverable) + recovery ⇒ tokens are
    byte-identical to the corruption-off engine; detected == recovered,
    nothing substituted or dropped, and the re-reads charged time."""
    cfg, model, params = lm
    tok0 = jnp.ones((2, 1), jnp.int32)
    base = _engine(model, params, backend=backend, wbits=wbits)
    t_base = base.decode(tok0, 6)
    eng = _engine(model, params, backend=backend, wbits=wbits,
                  corruption_profile="bit_rot", corruption_seed=7)
    t = eng.decode(tok0, 6)
    np.testing.assert_array_equal(np.asarray(t_base), np.asarray(t))
    c = _counters(eng)
    assert c["corruptions_detected"] > 0
    assert c["corruptions_detected"] == c["corruptions_recovered"]
    assert c["corruptions_substituted"] == 0 == c["corruptions_dropped"]
    assert c["integrity_reread_s"] > 0.0
    # the re-read time reached the simulated I/O clock
    assert eng.io_summary()["io_sim_s"] > base.io_summary()["io_sim_s"]
    # the corruption-off engine's new counters are all exactly zero
    assert all(v == 0.0 for v in _counters(base).values())


def _leaves_equal(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in
               zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


@pytest.mark.parametrize("backend,wbits,profile,moves_tokens", [
    pytest.param("reference", 16, "bit_rot", True, id="reference-16"),
    # one flipped bit of an int8 payload moves a weight by at most 128
    # quanta of its block's scale: with this seed the damage reaches the
    # KV cache but not the greedy tokens (on both backends alike)
    pytest.param("kernel", 8, "bit_rot", False, id="kernel-8"),
    # zeroed blocks move the 8-bit tokens too
    pytest.param("kernel", 8, "torn_read", True, id="kernel-8-torn_read"),
])
def test_engine_no_recover_corrupts_tokens_deterministically(
        lm, backend, wbits, profile, moves_tokens):
    """Recovery off: the same (profile, seed) reaches compute — the decode
    state written from the corrupted projections (the KV cache) differs
    bitwise from the clean run's, and the tokens too where stated — and
    the corrupted run replays bit-identically."""
    cfg, model, params = lm
    tok0 = jnp.ones((2, 1), jnp.int32)
    base = _engine(model, params, backend=backend, wbits=wbits)
    t_base = np.asarray(base.decode(tok0, 6))

    def run():
        e = _engine(model, params, backend=backend, wbits=wbits,
                    corruption_profile=profile, corruption_seed=7,
                    recover=False)
        return e, np.asarray(e.decode(tok0, 6))

    e1, t1 = run()
    e2, t2 = run()
    assert not _leaves_equal(base.cache, e1.cache)
    if moves_tokens:
        assert not np.array_equal(t_base, t1)
    np.testing.assert_array_equal(t1, t2)
    assert _leaves_equal(e1.cache, e2.cache)
    assert _counters(e1) == _counters(e2)
    c = _counters(e1)
    assert c["corruptions_detected"] > 0
    # nothing recovers, nothing is re-read, and the ladder never engages:
    # detection is observe-only when recovery is off
    assert c["corruptions_recovered"] == 0 == c["corruptions_substituted"]
    assert c["corruptions_dropped"] == 0 and c["integrity_reread_s"] == 0.0


def test_engine_corrupted_tokens_cross_backend_identical(lm):
    """Both backends apply the identical corrupt_payload damage, so even
    CORRUPTED tokens stay byte-identical across reference and kernel."""
    cfg, model, params = lm
    tok0 = jnp.ones((2, 1), jnp.int32)

    def run(backend):
        e = _engine(model, params, backend=backend,
                    corruption_profile="bit_rot", corruption_seed=7,
                    recover=False)
        return np.asarray(e.decode(tok0, 5))

    np.testing.assert_array_equal(run("reference"), run("kernel"))


def test_engine_degraded_nand_ladder_replays_exactly(lm):
    """Corruptions that survive the re-read budget walk the ladder:
    substitutions and/or drops appear and every counter replays exactly.
    Units differ by rung — detected/recovered count block-EVENTS per
    matrix, substituted/dropped count ROWS (an unreadable block takes its
    KERNEL_BLOCK_ROWS site rows with it), so rows ≤ 8 × unrecovered
    events bounds the ladder's tail."""
    cfg, model, params = lm
    tok0 = jnp.ones((2, 1), jnp.int32)

    def run():
        e = _engine(model, params, corruption_profile="degraded_nand",
                    corruption_seed=3, max_reread=1)
        t = np.asarray(e.decode(tok0, 6))
        return t, _counters(e)

    t1, c1 = run()
    t2, c2 = run()
    np.testing.assert_array_equal(t1, t2)
    assert c1 == c2
    assert c1["corruptions_detected"] > c1["corruptions_recovered"] > 0
    assert c1["corruptions_substituted"] > 0
    from repro.serving.sparse_exec import KERNEL_BLOCK_ROWS

    # only the FETCHED rows of an unreadable block are removed (resident
    # selected rows stay served from DRAM), so the bound is ≤, not ==
    assert (c1["corruptions_substituted"] + c1["corruptions_dropped"]
            <= KERNEL_BLOCK_ROWS
            * (c1["corruptions_detected"] - c1["corruptions_recovered"]))


def test_engine_corruption_requires_offloaded_plane(lm):
    cfg, model, params = lm
    with pytest.raises(ValueError, match="offloaded data plane"):
        _engine(model, params, method="dense_free",
                corruption_profile="bit_rot")
    with pytest.raises(ValueError, match="selecting method"):
        _engine(model, params, method="dense",
                corruption_profile="bit_rot")


def test_engine_per_token_path_matches_scan_counters(lm):
    """The per-token decode loop shares the plan-lane accounting: same
    seed, same number of steps ⇒ identical corruption counters and the
    identical recovered tokens as the scan path."""
    cfg, model, params = lm
    tok0 = jnp.ones((2, 1), jnp.int32)

    def run(per_token):
        e = _engine(model, params, corruption_profile="bit_rot",
                    corruption_seed=7)
        fn = e.decode_per_token if per_token else e.decode
        return np.asarray(fn(tok0, 5)), _counters(e)

    t_scan, c_scan = run(False)
    t_tok, c_tok = run(True)
    np.testing.assert_array_equal(t_scan, t_tok)
    assert c_scan == c_tok


def test_engine_corruption_feeds_degradation_controller(lm):
    """Sustained corruption is the controller's second degrade signal: a
    high-rate profile with recovery tightens the budget scale even though
    latency alone would not."""
    cfg, model, params = lm
    tok0 = jnp.ones((2, 1), jnp.int32)
    e = _engine(model, params, corruption_profile="degraded_nand",
                corruption_seed=3, degrade=True)
    # crank the corruption gain so the signal dominates the healthy
    # latency observations within a short test decode
    e.degrade_controller = DegradationController(corruption_ratio_gain=200.0)
    e.simulator.noise = 0.0
    for _ in range(6):
        e.decode(tok0, 3)
    assert e.fault_summary()["degrade_scale"] < 1.0
