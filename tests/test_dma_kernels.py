"""Interpret-mode parity suite for the double-buffered DMA gather kernels
(kernels/chunk_gather_dma.py) against the kernels/ref.py oracles, the
jit-safe batched-plan → kernel-table bridge, and the serve-stack wiring
(prefetch depth byte-identity, plan-routed fused MLP)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.offload import ComputeModel
from repro.core.pipeline import PipelineModel
from repro.kernels import (
    blocked_masked_matmul,
    chunk_gather_matmul_dma,
    chunk_gather_matmul_ref,
    chunk_gather_mlp_dma,
    chunk_gather_mlp_ref,
    chunk_table_to_mask,
    dequantize_rows,
    masks_to_block_tables,
    plan_to_kernel_table,
    quantize_rows,
    sparse_matmul_dma,
    sparse_mlp_fused,
)

DEPTHS = (0, 1, 2)


def _rel_err(a, b):
    denom = max(1.0, float(jnp.max(jnp.abs(b))))
    return float(jnp.max(jnp.abs(a - b))) / denom


def _stack_lanes(tables, k):
    """Pad per-lane (starts, sizes) pairs to a common K and stack (L, K)."""
    out_s = np.zeros((len(tables), k), np.int32)
    out_z = np.zeros((len(tables), k), np.int32)
    for i, (s, z) in enumerate(tables):
        out_s[i, : len(s)] = s
        out_z[i, : len(z)] = z
    return jnp.asarray(out_s), jnp.asarray(out_z)


# ---------------------------------------------------------------------------
# single-site DMA matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depth", DEPTHS)
@pytest.mark.parametrize("n,d,b", [(128, 128, 1), (256, 256, 4), (64, 128, 8)])
def test_matmul_dma_parity(n, d, b, depth, rng):
    w = jnp.asarray(rng.normal(0, 1, (n, d)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (b, n)), jnp.float32)
    mask = rng.random(n) < 0.5
    s, z = plan_to_kernel_table(mask, block_rows=8, max_chunks=max(n // 8, 1),
                                max_chunk_rows=64)
    y = sparse_matmul_dma(w, x, jnp.asarray(s), jnp.asarray(z),
                          prefetch_depth=depth)
    yref = chunk_gather_matmul_ref(w, x, s, z)
    assert _rel_err(y, yref) < 1e-5


def test_matmul_dma_bf16(rng):
    n, d, b = 128, 128, 2
    w = jnp.asarray(rng.normal(0, 1, (n, d)), jnp.bfloat16)
    x = jnp.asarray(rng.normal(0, 1, (b, n)), jnp.bfloat16)
    mask = rng.random(n) < 0.5
    s, z = plan_to_kernel_table(mask, block_rows=8, max_chunks=n // 8,
                                max_chunk_rows=64)
    y = sparse_matmul_dma(w, x, jnp.asarray(s), jnp.asarray(z))
    yref = chunk_gather_matmul_ref(w, x, s, z)
    assert _rel_err(y, yref) < 2e-2


@pytest.mark.parametrize("depth", DEPTHS)
def test_matmul_dma_all_padded(depth, rng):
    """Degenerate plan: every chunk padded (size 0) → exact zeros, and no
    slot is ever waited on (the rotation skips inactive steps)."""
    w = jnp.asarray(rng.normal(0, 1, (64, 128)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (2, 64)), jnp.float32)
    s = jnp.zeros((5,), jnp.int32)
    z = jnp.zeros((5,), jnp.int32)
    y = sparse_matmul_dma(w, x, s, z, prefetch_depth=depth)
    assert float(jnp.max(jnp.abs(y))) == 0.0


@pytest.mark.parametrize("depth", DEPTHS)
def test_matmul_dma_single_max_chunk(depth, rng):
    """One chunk of exactly max_chunk_rows (every block step active)."""
    n, d = 128, 128
    w = jnp.asarray(rng.normal(0, 1, (n, d)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (3, n)), jnp.float32)
    s = jnp.asarray([32], jnp.int32)
    z = jnp.asarray([64], jnp.int32)
    y = sparse_matmul_dma(w, x, s, z, prefetch_depth=depth)
    yref = chunk_gather_matmul_ref(w, x, s, z)
    assert _rel_err(y, yref) < 1e-5


def test_matmul_dma_k_exceeds_real_chunks(rng):
    """K far larger than the number of real chunks: the padded tail is
    pure no-op steps at every depth."""
    n, d = 64, 128
    w = jnp.asarray(rng.normal(0, 1, (n, d)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (2, n)), jnp.float32)
    s = np.zeros(32, np.int32)
    z = np.zeros(32, np.int32)
    s[0], z[0] = 8, 16
    outs = [
        sparse_matmul_dma(w, x, jnp.asarray(s), jnp.asarray(z),
                          prefetch_depth=depth)
        for depth in DEPTHS
    ]
    yref = chunk_gather_matmul_ref(w, x, s, z)
    for y in outs:
        assert _rel_err(y, yref) < 1e-5
    # the schedule is numerically identical at every depth, not just close
    for y in outs[1:]:
        assert bool(jnp.all(y == outs[0]))


def test_matmul_dma_depth_deeper_than_steps(rng):
    """prefetch_depth larger than the total step count: warm-up must guard
    against starting copies past the end."""
    w = jnp.asarray(rng.normal(0, 1, (16, 128)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (1, 16)), jnp.float32)
    s = jnp.asarray([0], jnp.int32)
    z = jnp.asarray([8], jnp.int32)
    y = sparse_matmul_dma(w, x, s, z, prefetch_depth=7)
    yref = chunk_gather_matmul_ref(w, x, s, z)
    assert _rel_err(y, yref) < 1e-5


# ---------------------------------------------------------------------------
# fused multi-site MLP
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depth", DEPTHS)
def test_mlp_fused_parity(depth, rng):
    n, f, d, b = 128, 256, 128, 2
    wg = jnp.asarray(rng.normal(0, 0.2, (n, f)), jnp.float32)
    wu = jnp.asarray(rng.normal(0, 0.2, (n, f)), jnp.float32)
    wd = jnp.asarray(rng.normal(0, 0.2, (f, d)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (b, n)), jnp.float32)
    # non-uniform site budgets: dense-ish hidden lane, sparse ffn lane
    th = plan_to_kernel_table(rng.random(n) < 0.7, 8, n // 8, 64)
    tf = plan_to_kernel_table(rng.random(f) < 0.3, 8, f // 8, 64)
    s2, z2 = _stack_lanes([th, tf], max(n, f) // 8)
    y = sparse_mlp_fused(wg, wu, wd, x, s2, z2, prefetch_depth=depth)
    yref = chunk_gather_mlp_ref(wg, wu, wd, x, s2, z2)
    assert _rel_err(y, yref) < 1e-5


@pytest.mark.parametrize("empty_lane", [0, 1])
def test_mlp_fused_empty_lane(empty_lane, rng):
    """Either lane fully padded → output exactly zero (empty hidden lane
    zeroes h; empty ffn lane gathers no down rows)."""
    n = f = d = 128
    wg = jnp.asarray(rng.normal(0, 0.2, (n, f)), jnp.float32)
    wu = jnp.asarray(rng.normal(0, 0.2, (n, f)), jnp.float32)
    wd = jnp.asarray(rng.normal(0, 0.2, (f, d)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (2, n)), jnp.float32)
    full = plan_to_kernel_table(np.ones(n, bool), 8, n // 8, 64)
    empty = (np.zeros(n // 8, np.int32), np.zeros(n // 8, np.int32))
    lanes = [full, full]
    lanes[empty_lane] = empty
    s2, z2 = _stack_lanes(lanes, n // 8)
    y = sparse_mlp_fused(wg, wu, wd, x, s2, z2)
    assert float(jnp.max(jnp.abs(y))) == 0.0


def test_mlp_fused_full_lanes_equal_dense(rng):
    n = f = d = 128
    wg = jnp.asarray(rng.normal(0, 0.2, (n, f)), jnp.float32)
    wu = jnp.asarray(rng.normal(0, 0.2, (n, f)), jnp.float32)
    wd = jnp.asarray(rng.normal(0, 0.2, (f, d)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (2, n)), jnp.float32)
    full = plan_to_kernel_table(np.ones(n, bool), 8, n // 8, 64)
    s2, z2 = _stack_lanes([full, full], n // 8)
    y = sparse_mlp_fused(wg, wu, wd, x, s2, z2)
    g = x @ wg
    dense = (g * (1.0 / (1.0 + jnp.exp(-g))) * (x @ wu)) @ wd
    assert _rel_err(y, dense) < 1e-5


# ---------------------------------------------------------------------------
# quantized chunk storage through the kernels (PR 6, satellite edge cases)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depth", DEPTHS)
def test_quantized_matmul_kernel_vs_twin_bitwise(depth, rng):
    """The in-kernel dequant (scales lane through the slot rotation) is
    bitwise the reference twin's per-block multiply — at every prefetch
    depth, on a chunk table covering the mask exactly."""
    n, d, b = 128, 128, 2
    w = jnp.asarray(rng.normal(0, 0.5, (n, d)), jnp.float32)
    q, s = quantize_rows(w, 8)
    x = jnp.asarray(rng.normal(0, 1, (b, n)), jnp.float32)
    mask = rng.random(n) < 0.5
    ks, kz = plan_to_kernel_table(mask, 8, n // 8, 64)
    # the twin sees the block-rounded mask (what the kernel actually gathers)
    cov = np.asarray(chunk_table_to_mask(jnp.asarray(ks), jnp.asarray(kz), n))
    y = chunk_gather_matmul_dma(q, x, jnp.asarray(ks), jnp.asarray(kz), s,
                                prefetch_depth=depth,
                                interpret=True)
    y_twin = blocked_masked_matmul(x * cov.astype(np.float32), q, 8, s)
    assert bool(jnp.all(y == y_twin))


@pytest.mark.parametrize("depth", DEPTHS)
def test_quantized_zero_magnitude_chunk(depth, rng):
    """A selected chunk whose rows are entirely zero: scale 0, payload 0 —
    the kernel's dequant multiply must yield exact zeros for that block's
    contribution (the scale=0 guard), with the other chunks unaffected."""
    n, d = 64, 128
    w = np.asarray(rng.normal(0, 0.5, (n, d)), np.float32)
    w[8:16] = 0.0  # one full block of zeros, selected below
    q, s = quantize_rows(jnp.asarray(w), 8)
    assert float(s[1]) == 0.0
    x = jnp.asarray(rng.normal(0, 1, (2, n)), jnp.float32)
    ks = jnp.asarray([0, 32], jnp.int32)  # covers rows 0..32 incl. the zeros
    kz = jnp.asarray([32, 16], jnp.int32)
    y = chunk_gather_matmul_dma(q, x, ks, kz, s, prefetch_depth=depth, interpret=True)
    yref = chunk_gather_matmul_ref(dequantize_rows(q, s), x, ks, kz)
    assert _rel_err(y, yref) < 1e-6
    assert bool(jnp.all(jnp.isfinite(y)))


@pytest.mark.parametrize("depth", DEPTHS)
def test_quantized_all_padded_plan(depth, rng):
    """Every quantized plan lane padded (size 0) → exact zeros; the scales
    lane is fetched through the same inactive-step skip."""
    w = np.asarray(rng.normal(0, 1, (64, 128)), np.float32)
    q, s = quantize_rows(jnp.asarray(w), 8)
    x = jnp.asarray(rng.normal(0, 1, (2, 64)), jnp.float32)
    z = jnp.zeros((5,), jnp.int32)
    y = chunk_gather_matmul_dma(q, x, z, z, s, prefetch_depth=depth, interpret=True)
    assert float(jnp.max(jnp.abs(y))) == 0.0


def test_quantized_k_exceeds_real_chunks(rng):
    """K far beyond the real chunk count: the padded tail must not fetch
    (or dequantize) anything, and the schedule stays depth-invariant."""
    n, d = 64, 128
    w = np.asarray(rng.normal(0, 1, (n, d)), np.float32)
    q, s = quantize_rows(jnp.asarray(w), 8)
    x = jnp.asarray(rng.normal(0, 1, (2, n)), jnp.float32)
    ks = np.zeros(32, np.int32)
    kz = np.zeros(32, np.int32)
    ks[0], kz[0] = 8, 16
    outs = [
        chunk_gather_matmul_dma(q, x, jnp.asarray(ks), jnp.asarray(kz), s,
                                prefetch_depth=depth,
                                interpret=True)
        for depth in DEPTHS
    ]
    yref = chunk_gather_matmul_ref(dequantize_rows(q, s), x, ks, kz)
    for y in outs:
        assert _rel_err(y, yref) < 1e-6
    for y in outs[1:]:
        assert bool(jnp.all(y == outs[0]))


@pytest.mark.parametrize("depth", DEPTHS)
def test_quantized_saturation_extremes(depth):
    """Blocks pinned at the int8 extremes (±127 payload): the dequant must
    reproduce the extreme values exactly — no overflow, no off-by-one in
    the clip."""
    n, d = 32, 128
    w = np.zeros((n, d), np.float32)
    w[:8] = 4.0
    w[8:16] = -4.0
    w[16:24, 0] = 1e-3  # tiny-magnitude block exercises small scales
    q, s = quantize_rows(jnp.asarray(w), 8)
    assert int(jnp.max(q)) == 127 and int(jnp.min(q)) == -127
    x = jnp.asarray(np.ones((1, n), np.float32))
    ks = jnp.asarray([0], jnp.int32)
    kz = jnp.asarray([32], jnp.int32)
    y = chunk_gather_matmul_dma(q, x, ks, kz, s, prefetch_depth=depth, interpret=True)
    yref = chunk_gather_matmul_ref(dequantize_rows(q, s), x, ks, kz)
    assert _rel_err(y, yref) < 1e-6


@pytest.mark.parametrize("depth", DEPTHS)
def test_quantized_mlp_fused_parity(depth, rng):
    """The fused MLP with all three weights quantized (three scale lanes
    riding the rotation) against the dequantized-weights oracle."""
    n, f, d, b = 128, 256, 128, 2
    wg = jnp.asarray(rng.normal(0, 0.2, (n, f)), jnp.float32)
    wu = jnp.asarray(rng.normal(0, 0.2, (n, f)), jnp.float32)
    wd = jnp.asarray(rng.normal(0, 0.2, (f, d)), jnp.float32)
    qg, sg = quantize_rows(wg, 8)
    qu, su = quantize_rows(wu, 8)
    qd, sd = quantize_rows(wd, 8)
    x = jnp.asarray(rng.normal(0, 1, (b, n)), jnp.float32)
    th = plan_to_kernel_table(rng.random(n) < 0.7, 8, n // 8, 64)
    tf = plan_to_kernel_table(rng.random(f) < 0.3, 8, f // 8, 64)
    s2, z2 = _stack_lanes([th, tf], max(n, f) // 8)
    y = chunk_gather_mlp_dma(qg, qu, qd, x, s2, z2, scales=(sg, su, sd),
                             prefetch_depth=depth,
                             interpret=True)
    yref = chunk_gather_mlp_ref(
        dequantize_rows(qg, sg), dequantize_rows(qu, su),
        dequantize_rows(qd, sd), x, s2, z2,
    )
    assert _rel_err(y, yref) < 1e-5


# ---------------------------------------------------------------------------
# the jit-safe batched-plan → kernel-table bridge
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("density", [0.1, 0.5, 0.9])
def test_masks_to_block_tables_matches_numpy_path(density, rng):
    n, br, mc = 256, 8, 64
    masks = np.stack([rng.random(n) < density for _ in range(3)])
    ks, kz = masks_to_block_tables(jnp.asarray(masks), br, mc)
    assert ks.shape == (3, n // br)
    for i in range(3):
        s0, z0 = plan_to_kernel_table(masks[i], block_rows=br,
                                      max_chunks=n // br, max_chunk_rows=mc)
        real = int((z0 > 0).sum())
        assert (np.asarray(ks[i])[:real] == s0[:real]).all()
        assert (np.asarray(kz[i])[:real] == z0[:real]).all()
        assert (np.asarray(kz[i])[real:] == 0).all()


def test_masks_to_block_tables_covers_block_rounded_mask(rng):
    n = 200  # deliberately not a multiple of block_rows (tail block)
    mask = rng.random(n) < 0.4
    ks, kz = masks_to_block_tables(jnp.asarray(mask[None]), 8, 32)
    n_pad = ((n + 7) // 8) * 8
    cov = np.asarray(chunk_table_to_mask(ks[0], kz[0], n_pad))
    rounded = np.repeat(
        np.pad(mask, (0, n_pad - n)).reshape(-1, 8).any(1), 8
    )
    assert (cov == rounded).all()
    assert (np.asarray(kz[0]) <= 32).all()


def test_masks_to_block_tables_empty_and_full():
    n = 64
    ks, kz = masks_to_block_tables(
        jnp.asarray(np.stack([np.zeros(n, bool), np.ones(n, bool)])), 8, 32
    )
    assert int(kz[0].sum()) == 0
    # full mask: one run split into max_chunk_rows pieces covering all rows
    assert int(kz[1].sum()) == n
    assert int((kz[1] > 0).sum()) == n // 32


@pytest.mark.parametrize("sb", [8, 128])
@pytest.mark.parametrize("density", [0.0, 0.3, 1.0])
def test_block_list_is_the_ascending_covered_blocks(density, sb, rng):
    """The kernels' step list: every super-block the table covers,
    ascending and once, padded, with its covered 8-row blocks as bits; the
    count says how many are real. 8-row super-blocks list single blocks."""
    from repro.kernels.chunk_gather_dma import block_list

    n = 256
    mask = rng.random(n) < density
    ks, kz = masks_to_block_tables(jnp.asarray(mask)[None], 8, 32)
    offs, bits, count = block_list(ks[0], kz[0], n, sb, 8)
    cov = np.asarray(chunk_table_to_mask(ks[0], kz[0], n)).reshape(-1, sb // 8, 8)
    blocks = cov.any(axis=2)  # (n // sb, blocks per super-block)
    want = np.flatnonzero(blocks.any(axis=1))
    want_bits = (blocks[want] * (1 << np.arange(sb // 8))).sum(axis=1)
    assert offs.shape == bits.shape == (n // sb,) and count.shape == (1,)
    assert int(count[0]) == len(want)
    np.testing.assert_array_equal(np.asarray(offs)[: len(want)], want * sb)
    np.testing.assert_array_equal(np.asarray(bits)[: len(want)], want_bits)


@pytest.mark.parametrize("depth", DEPTHS)
def test_matmul_dma_unsorted_table_matches_twin_bitwise(depth, rng):
    """Table entry order does not change the arithmetic: blocks accumulate
    in ascending row order, as in the reference twin, and padded entries
    anywhere in the table are skipped."""
    n, d, b = 128, 128, 2
    w = jnp.asarray(rng.normal(0, 0.5, (n, d)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (b, n)), jnp.float32)
    starts = jnp.asarray([96, 0, 0, 40, 16], jnp.int32)
    sizes = jnp.asarray([16, 8, 0, 24, 8], jnp.int32)
    cov = chunk_table_to_mask(starts, sizes, n).astype(jnp.float32)
    y = chunk_gather_matmul_dma(w, x, starts, sizes, prefetch_depth=depth,
                                interpret=True)
    y_twin = blocked_masked_matmul(x * cov, w, 8)
    assert bool(jnp.all(y == y_twin))


# ---------------------------------------------------------------------------
# super-block steps: the kernels against the reference twin, bitwise
# ---------------------------------------------------------------------------


def _sb_mask(n, sb, whole, partial):
    """Row mask over ``n`` rows: the super-blocks in ``whole`` selected
    entirely; in super-block k of ``partial``, one row of each listed
    8-row block (so the table covers the block, the exact mask one row)."""
    mask = np.zeros(n, bool)
    for k in whole:
        mask[k * sb:(k + 1) * sb] = True
    for k, blocks in partial.items():
        for j in blocks:
            mask[k * sb + j * 8 + 3] = True
    return mask


# (n, d, whole super-blocks, {super-block: covered blocks}); super-blocks
# 0-2 whole then 3 partial leaves the partial step's slot holding an
# earlier super-block's rows at depth 0 (slot 0), 1 and 2
SB_CASES = {
    "partial_after_whole": (512, 256, [0, 1, 2], {3: [1, 6, 15]}),
    "whole": (256, 128, [0, 1], {}),
    "partial_only": (384, 128, [], {0: [0], 2: list(range(15))}),
    "reduced_704": (704, 192, [0, 4, 9], {10: [2, 7]}),  # 64-row super-blocks
}


def _tables(mask):
    ks, kz = masks_to_block_tables(jnp.asarray(mask)[None], 8, 64)
    return ks[0], kz[0], chunk_table_to_mask(ks[0], kz[0], len(mask)).astype(jnp.float32)


def _mlp_lanes(mask_h, mask_f):
    """The (2, K) hidden/ffn plan lanes of two masks, and each coverage."""
    width = max(len(mask_h), len(mask_f))
    masks = np.zeros((2, width), bool)
    masks[0, :len(mask_h)], masks[1, :len(mask_f)] = mask_h, mask_f
    s2, z2 = masks_to_block_tables(jnp.asarray(masks), 8, 64)
    covs = [chunk_table_to_mask(s2[i], z2[i], len(m)).astype(jnp.float32)
            for i, m in enumerate((mask_h, mask_f))]
    return s2, z2, covs


@pytest.mark.parametrize(
    "depth,wbits", [(d, 16) for d in DEPTHS] + [(1, 8)])
@pytest.mark.parametrize("case", sorted(SB_CASES))
def test_superblock_matmul_matches_twin_bitwise(case, depth, wbits, rng):
    """One step per covered super-block — whole ones as one DMA, partial
    ones block by block into a slot that still holds an earlier
    super-block's rows — equals the twin's super-block accumulation
    bitwise. The input is NOT pre-masked: the kernel must zero the lanes of
    the rows it did not fetch itself."""
    from repro.kernels.chunk_gather_dma import superblock_rows

    n, d, whole, partial = SB_CASES[case]
    sb = superblock_rows(n)
    assert sb == (64 if n == 704 else 128)
    ks, kz, cov = _tables(_sb_mask(n, sb, whole, partial))
    w = jnp.asarray(rng.normal(0, 0.5, (n, d)), jnp.bfloat16)
    x = jnp.asarray(rng.normal(0, 1, (3, n)), jnp.float32)
    s = None
    if wbits == 8:
        w, s = quantize_rows(w.astype(jnp.float32), 8)
    y = chunk_gather_matmul_dma(w, x, ks, kz, s, prefetch_depth=depth,
                                interpret=True)
    y_twin = blocked_masked_matmul(x * cov, w, 8, s)
    assert bool(jnp.all(y == y_twin))
    assert float(jnp.max(jnp.abs(y))) > 0.0


def _mlp_twin(wg, wu, wd, x, cov_h, fm, scales=(None, None, None)):
    sg, su, sd = scales
    xm = x * cov_h
    g = blocked_masked_matmul(xm, wg, 8, sg)
    u = blocked_masked_matmul(xm, wu, 8, su)
    h = g * (1.0 / (1.0 + jnp.exp(-g))) * u
    return blocked_masked_matmul(h * fm[None, :], wd, 8, sd), h


def _pallas_grids(jaxpr):
    """The grids of every pallas_call in ``jaxpr`` and its sub-jaxprs."""
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(eqn.params["grid_mapping"].grid)
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                grids += _pallas_grids(sub)
    return grids


@pytest.mark.parametrize("depth", DEPTHS)
def test_column_tiled_kernels_match_twin_bitwise(depth, rng, monkeypatch):
    """Where the slots of a full-width step would overrun the VMEM budget
    (patched small here, as InternVL2's 28,672-wide gate/up does on the
    chip) the column tile narrows to a multiple of 128 dividing the width;
    the arithmetic per output column is unchanged."""
    from repro.kernels import chunk_gather_dma as cgd

    n, f, d = 256, 2048, 3072
    n_slots = depth + 1
    # bf16 slots hold 2 * 128 * n_slots bytes per column per matrix: room
    # for 1024 columns of gate + up, 2048 of down (or of one projection);
    # tiles are whole 512-column contraction pieces
    monkeypatch.setattr(cgd, "VMEM_SLOT_BUDGET", 2 * 256 * n_slots * 1024)
    assert cgd.column_tile(f, 2 * 256 * n_slots) == 1024
    assert cgd.column_tile(d, 256 * n_slots) == 1536
    mask_h = _sb_mask(n, 128, [0], {1: [0, 9]})
    mask_f = _sb_mask(f, 128, [0, 3, 4, 5, 6], {9: [2, 3], 15: [0]})
    s2, z2, (cov_h, cov_f) = _mlp_lanes(mask_h, mask_f)
    ks_h, kz_h = s2[0], z2[0]
    w = jnp.asarray(rng.normal(0, 0.2, (n, d)), jnp.bfloat16)
    x = jnp.asarray(rng.normal(0, 1, (2, n)), jnp.float32)
    jaxpr = jax.make_jaxpr(functools.partial(
        chunk_gather_matmul_dma, prefetch_depth=depth, interpret=True))(
        w, x, ks_h, kz_h)
    assert _pallas_grids(jaxpr.jaxpr) == [(2,)]  # two 1536-column tiles
    y = chunk_gather_matmul_dma(w, x, ks_h, kz_h, prefetch_depth=depth,
                                interpret=True)
    assert bool(jnp.all(y == blocked_masked_matmul(x * cov_h, w, 8)))
    ws = [jnp.asarray(rng.normal(0, 0.2, shape), jnp.bfloat16)
          for shape in ((n, f), (n, f), (f, d))]
    y, h = chunk_gather_mlp_dma(*ws, x, s2, z2, prefetch_depth=depth,
                                interpret=True, return_h=True)
    y_twin, h_twin = _mlp_twin(*ws, x, cov_h, cov_f)
    assert bool(jnp.all(h == h_twin))
    assert bool(jnp.all(y == y_twin))


def test_column_tile_engages_only_where_vmem_forces_it():
    """The cells' widths run at full width; InternVL2-76B's gate/up split
    in two; a width off the 128-lane grid is never split."""
    from repro.kernels.chunk_gather_dma import column_tile

    def tile(width, streams, depth=1, itemsize=2):
        return column_tile(width, streams * (depth + 1) * 128 * itemsize)

    assert tile(8192, 2) == 8192 and tile(2048, 1) == 2048  # granite
    assert tile(18432, 1) == 18432 and tile(18432, 1, depth=2) == 18432
    assert tile(4608, 1) == 4608  # starcoder2
    assert tile(28672, 2) == 14336 and tile(8192, 1) == 8192  # internvl2
    assert tile(28672, 2, itemsize=1) == 28672  # int8 slots fit whole
    assert tile(704, 2) == 704
    assert tile(1536, 1, itemsize=2**15) == 512  # one contraction piece


def test_superblock_counts_on_a_hand_built_mask():
    """Four super-blocks: one whole, one with blocks 0, 5 and 15, one
    empty, one with every block but 7 — one whole DMA, two partial
    super-blocks taking 3 + 15 block DMAs; 16 × whole + block DMAs is
    every covered block."""
    from repro.kernels.chunk_gather_dma import superblock_counts

    mask = _sb_mask(512, 128, [0], {1: [0, 5, 15],
                                    3: [j for j in range(16) if j != 7]})
    ks, kz, cov = _tables(mask)
    counts = superblock_counts(ks, kz, 512, 8)
    assert counts.tolist() == [1, 2, 18]
    assert 16 * 1 + 18 == int(cov.sum()) // 8


@pytest.mark.parametrize("density", [0.05, 0.6, 0.95])
def test_superblock_counts_read_only_covered_blocks(density, rng):
    from repro.kernels.chunk_gather_dma import superblock_counts

    n = 1024
    mask = rng.random(n) < density
    ks, kz, cov = _tables(mask)
    whole, partial, dmas = superblock_counts(ks, kz, n, 8).tolist()
    blocks = np.asarray(cov).reshape(-1, 16, 8).any(axis=2).sum(axis=1)
    assert whole == int((blocks == 16).sum())
    assert partial == int(((blocks > 0) & (blocks < 16)).sum())
    assert 16 * whole + dmas == int(blocks.sum())


# ---------------------------------------------------------------------------
# serve-stack wiring
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.models import build_model
    from repro.models.inputs import make_dummy_batch

    cfg = get_config("internvl2-76b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = make_dummy_batch(cfg, InputShape("t", 8, 2, "train"))
    return cfg, model, params, batch


def _decode(model, params, batch, n_tokens=5, **kw):
    from repro.serving import ServeEngine

    eng = ServeEngine(model, params, max_seq=64, batch_size=2, device="nano",
                      sparsity=0.4, method="chunk", seed=3, **kw)
    tok0 = jnp.argmax(eng.prefill(batch), -1)[:, None].astype(jnp.int32)
    out = eng.decode(tok0, n_tokens)
    return eng, out


def test_decode_tokens_identical_across_prefetch_depths(served):
    """The acceptance criterion: decode tokens byte-identical at
    prefetch_depth 0/1/2 (the pipeline only re-times the same masks)."""
    cfg, model, params, batch = served
    outs = [
        _decode(model, params, batch, prefetch_depth=depth)[1]
        for depth in DEPTHS
    ]
    for out in outs[1:]:
        assert bool(jnp.all(out == outs[0]))


def test_plan_routes_fused_mlp_tables(served):
    """End-to-end: the batched refresh's kernel tables, read straight off
    the decode-plan carry, drive the fused MLP kernel to the exact output
    of the oracle evaluated on the plan's own masks."""
    cfg, model, params, batch = served
    eng, _ = _decode(model, params, batch)
    sp = eng.sparse_ctx
    plan = eng._plan
    rng = np.random.default_rng(0)
    n, f, d = sp.sites["hidden_mlp"].n, sp.sites["ffn"].n, cfg.d_model
    wg = jnp.asarray(rng.normal(0, 0.1, (n, f)), jnp.float32)
    wu = jnp.asarray(rng.normal(0, 0.1, (n, f)), jnp.float32)
    wd = jnp.asarray(rng.normal(0, 0.1, (f, d)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (2, n)), jnp.float32)
    for layer in (0, cfg.n_layers - 1):
        s2, z2 = sp.mlp_kernel_plan(plan, layer=layer)
        y = sparse_mlp_fused(wg, wu, wd, x, s2, z2)
        yref = chunk_gather_mlp_ref(wg, wu, wd, x, s2, z2)
        assert _rel_err(y, yref) < 1e-5
        # tables cover exactly the block-rounded selection masks (no
        # reorderings in this engine, so selection order == storage order)
        for lane, kind in ((0, "hidden_mlp"), (1, "ffn")):
            m = np.asarray(plan[kind]["mask"][layer]) > 0
            n_pad = ((len(m) + 7) // 8) * 8
            cov = np.asarray(chunk_table_to_mask(s2[lane], z2[lane], n_pad))
            rounded = np.repeat(
                np.pad(m, (0, n_pad - len(m))).reshape(-1, 8).any(1), 8
            )
            assert (cov == rounded).all()


def test_plan_tables_survive_reuse_steps(served):
    """With plan_refresh_interval > 1 the reuse steps must carry the tables
    through unchanged (same lax.cond pytree both branches)."""
    cfg, model, params, batch = served
    eng, _ = _decode(model, params, batch, plan_refresh_interval=3)
    s2, z2 = eng.sparse_ctx.mlp_kernel_plan(eng._plan, layer=0)
    assert int(jnp.sum(z2)) > 0  # refreshed at least once, tables populated


@pytest.mark.parametrize("device", ["nano", "agx"])
def test_fused_mlp_from_batched_selection_per_device(served, device, rng):
    """Both shipped device profiles: a real batched selection (the device's
    own chunk-size schedule) → jit-side tables → fused kernel == oracle,
    and the tables reproduce the selection masks exactly after block
    rounding."""
    from repro.serving import SparseExecution
    from repro.serving.sparse_exec import KERNEL_BLOCK_ROWS, KERNEL_MAX_CHUNK_ROWS

    cfg = served[0]
    sp = SparseExecution(cfg, device=device, sparsity=0.4, method="chunk")
    vs = np.zeros((sp.batched.n_sites, sp.batched.n_max), np.float32)
    for i, kind in enumerate(sp.site_order):
        vs[i, : sp.sites[kind].n] = rng.random(sp.sites[kind].n)
    masks, _ = sp.batched.select(jnp.asarray(vs), sp._budgets)
    ks, kz = masks_to_block_tables(masks, KERNEL_BLOCK_ROWS, KERNEL_MAX_CHUNK_ROWS)
    order = list(sp.site_order)
    ih, i_f = order.index("hidden_mlp"), order.index("ffn")
    n, f, d = sp.sites["hidden_mlp"].n, sp.sites["ffn"].n, cfg.d_model
    wg = jnp.asarray(rng.normal(0, 0.1, (n, f)), jnp.float32)
    wu = jnp.asarray(rng.normal(0, 0.1, (n, f)), jnp.float32)
    wd = jnp.asarray(rng.normal(0, 0.1, (f, d)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (2, n)), jnp.float32)
    s2 = jnp.stack([ks[ih], ks[i_f]])
    z2 = jnp.stack([kz[ih], kz[i_f]])
    y = sparse_mlp_fused(wg, wu, wd, x, s2, z2)
    yref = chunk_gather_mlp_ref(wg, wu, wd, x, s2, z2)
    assert _rel_err(y, yref) < 1e-5
    for lane, idx, n_site in ((0, ih, n), (1, i_f, f)):
        m = np.asarray(masks[idx, :n_site])
        n_pad = ((n_site + 7) // 8) * 8
        cov = np.asarray(chunk_table_to_mask(s2[lane], z2[lane], n_pad))
        rounded = np.repeat(
            np.pad(m, (0, n_pad - n_site)).reshape(-1, 8).any(1), 8
        )
        assert (cov == rounded).all()


def test_kernel_tables_unknown_site_raises(served):
    cfg, model, params, batch = served
    eng, _ = _decode(model, params, batch)
    with pytest.raises(KeyError):
        eng.sparse_ctx.kernel_tables(eng._plan, "nope")


# ---------------------------------------------------------------------------
# pipeline depth generalization + per-layer compute calibration
# ---------------------------------------------------------------------------


def test_pipeline_latency_monotone_in_depth(rng):
    io = rng.random((6, 4))
    comp = rng.random((4,))
    totals = [
        PipelineModel(prefetch_depth=d).timeline(io, comp).overlap_total_s
        for d in range(5)
    ]
    for a, b in zip(totals, totals[1:]):
        assert b <= a + 1e-12
    # depth 0 == serial exactly
    tl0 = PipelineModel(prefetch_depth=0).timeline(io, comp)
    np.testing.assert_allclose(tl0.overlap_s, tl0.serial_s, rtol=0, atol=1e-12)


def test_pipeline_with_depth_helper():
    pm = PipelineModel(prefetch_depth=1)
    assert pm.with_depth(3).prefetch_depth == 3
    assert pm.prefetch_depth == 1  # frozen original untouched


def test_compute_model_layer_scale(served):
    cfg = served[0]
    cm = ComputeModel()
    uniform = cm.decode_layer_seconds(cfg, sparsity=0.4)
    scale = np.linspace(0.5, 1.5, cfg.n_layers)
    scaled = cm.decode_layer_seconds(cfg, sparsity=0.4, layer_scale=scale)
    np.testing.assert_allclose(scaled, uniform * scale)
    with pytest.raises(ValueError):
        cm.decode_layer_seconds(cfg, layer_scale=np.ones(cfg.n_layers + 1))
    with pytest.raises(ValueError):
        cm.decode_layer_seconds(cfg, layer_scale=-np.ones(cfg.n_layers))


def test_calibrate_layer_scale_mean_one():
    walls = np.array([1.0, 2.0, 3.0, 2.0])
    scale = ComputeModel.calibrate_layer_scale(walls)
    assert abs(scale.mean() - 1.0) < 1e-12
    np.testing.assert_allclose(scale * walls.mean(), walls)


def test_engine_nonuniform_compute_changes_timeline_not_tokens(served):
    cfg, model, params, batch = served
    scale = np.linspace(0.2, 1.8, cfg.n_layers)
    eng_u, out_u = _decode(model, params, batch)
    eng_n, out_n = _decode(model, params, batch, compute_layer_scale=scale)
    assert bool(jnp.all(out_u == out_n))  # calibration re-times, not re-masks
    assert not np.isclose(
        eng_u.io_summary()["decode_overlap_s"],
        eng_n.io_summary()["decode_overlap_s"],
    )


def test_reprice_timeline_matches_depth_engine(served):
    """reprice_timeline(d) must equal what an identically-seeded engine at
    prefetch_depth=d charges (the smoke benchmark relies on this) —
    including across MULTIPLE decode calls, each of which the real engine
    prices as its own cold pipeline."""
    cfg, model, params, batch = served
    eng1, _ = _decode(model, params, batch)
    eng2, _ = _decode(model, params, batch, prefetch_depth=2)
    for eng in (eng1, eng2):  # second decode call, same token streams
        tok = jnp.zeros((2, 1), jnp.int32)
        eng.decode(tok, 3)
    tl = eng1.reprice_timeline(2)
    assert len(eng1._layer_io_log) == 2
    assert np.isclose(tl.overlap_total_s, eng2.io_summary()["decode_overlap_s"])
    assert np.isclose(
        tl.overlap_efficiency(), eng2.io_summary()["overlap_efficiency"]
    )
    # and at the engine's own depth it reproduces the engine's own charge
    tl_same = eng1.reprice_timeline(1)
    assert np.isclose(tl_same.overlap_total_s,
                      eng1.io_summary()["decode_overlap_s"])
