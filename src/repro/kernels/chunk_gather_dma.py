"""Explicitly pipelined DMA chunk-gather kernels.

The BlockSpec-driven kernels (chunk_gather_matmul.py / chunk_gather_swiglu.py)
let the Pallas pipeline emitter fetch one HBM block per grid step — which the
compiler overlaps, but only one block deep and only in the schedule it picks.
These kernels drive the fetches themselves with ``pltpu.make_async_copy``:
``prefetch_depth + 1`` VMEM slots per streamed operand rotate through a
classic in-kernel pipeline —

    warm-up:  start copies for steps 0 .. depth-1
    step k:   start copy k+depth  →  wait copy k  →  MXU on slot k % (depth+1)

so step k+1's HBM→VMEM transfer is in flight while the MXU contracts step k
(depth 1 = double buffering; depth 0 degenerates to fetch-then-compute
serial, the baseline the overlap is benchmarked against). This is the kernel
realization of the host-side prefetch timeline in core/pipeline.py: the same
``prefetch_depth`` knob, the same hidden-fetch discipline.

**The step unit is a super-block**: an aligned group of ``superblock_rows``
input rows (128 at every real width — the MXU's contraction depth and the
lane width; the largest power of two ≤ 128 dividing N for the reduced CPU
configs) across the whole column tile. The steps are the super-blocks the
chunk table covers, in ascending row order (``block_list``), so padded table
entries and uncovered super-blocks cost nothing. One step:

  * **DMAs** — a super-block whose ``block_rows``-row blocks are all covered
    moves as ONE ``(superblock_rows, tile)`` copy per matrix (the common
    case at moderate sparsity); otherwise each covered block moves as one
    ``(block_rows, tile)`` copy into its rows of the slot. Uncovered blocks
    are never fetched: the bytes the plan selects are the bytes moved.
  * **Contraction** — one ``(B, superblock_rows) · (superblock_rows, tile)``
    dot per matrix on the lane-aligned ``x[:, off:off + superblock_rows]``
    slice (the down phase: h times the exact ffn mask), run in
    ``chunk_lanes``-column pieces (512 at real widths), f32 at
    ``Precision.HIGHEST``. The slice's lanes of uncovered blocks are zeroed
    (``_covered_lanes``), and the slots are zeroed once at kernel start, so
    the rows a partial super-block leaves stale (earlier super-blocks'
    finite weights) meet exact zeros and never uninitialised VMEM.
  * **Column tile** — the full output width unless VMEM forces a split:
    the widest multiple of the contraction piece dividing the width whose
    slots (every operand the step streams, all ``prefetch_depth + 1``
    slots) fit ``VMEM_SLOT_BUDGET`` (``column_tile``). Derived from the
    shapes, not an option; of the served models only InternVL2-76B's
    28,672-wide bf16 gate/up split (in two).

Two entry points:

  * ``chunk_gather_matmul_dma`` — drop-in for ``chunk_gather_matmul``: one
    weight matrix, one chunk table, same alignment contract
    (starts/sizes multiples of ``block_rows``, size 0 = padded entry).
  * ``chunk_gather_mlp_dma`` — the **fused multi-site** path: ONE
    ``pallas_call`` gathers gate, up AND down off the two MLP lanes of a
    ``BatchedChunkSelector`` ``(n_sites, K)`` plan. A hidden-lane
    super-block is fetched once and contracted against both W_gate and W_up
    while resident, and the SwiGLU intermediate h stays in VMEM for the
    down-lane gather — no per-site re-dispatch, no h HBM round-trip.

On TPU the kernels compile through Mosaic. Off-TPU (the CPU test platform)
``interpret=True`` executes the same slot rotation (make_async_copy is
emulated as a synchronous copy), which validates the schedule's *numerics* —
rotation never overwrites a live slot — while the overlap itself only exists
on the chip.

Layout rules Mosaic enforces, and how the kernels meet them:

  * a vector load's lane offset must be a multiple of the 128-lane tile:
    super-block offsets are, and carry a ``pl.multiple_of`` hint;
  * a DMA's row offset must provably sit on the HBM tiling, so block
    offsets carry a ``pl.multiple_of(…, block_rows)`` hint;
  * a one-word DMA is below a 1-D HBM array's tiling, so the per-block
    scales and checksums lanes are SMEM-resident for the call and read one
    word per block by scalar index.

``masks_to_block_tables`` is the jit-safe bridge from the batched selector's
``(n_sites, N)`` masks straight to these kernels' padded chunk tables (block
alignment + max_chunk_rows splitting), replacing the host-side per-site
numpy re-splitting of ``plan_to_kernel_table``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# jit-safe mask -> block-aligned chunk table (the batched-plan bridge)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block_rows", "max_chunk_rows"))
def masks_to_block_tables(
    masks: jnp.ndarray,  # (S, N) bool selection masks (selection row order)
    block_rows: int = 8,
    max_chunk_rows: int = 512,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched selection masks → padded kernel chunk tables, inside jit.

    Semantics match the numpy path ``plan_to_kernel_table`` exactly: each
    mask is rounded outward to the ``block_rows`` grid (any selected row
    claims its whole block — runs that merge after rounding merge here too),
    then maximal block runs are split at ``max_chunk_rows`` so every entry
    fits the kernel grid. Returns (starts, sizes) of shape (S, K) with
    K = ceil(N / block_rows) (the worst case: every block its own chunk);
    entries are row units, multiples of ``block_rows``, size 0 = padding.
    """
    if masks.ndim != 2:
        raise ValueError(f"masks must be (n_sites, N), got {masks.shape}")
    if max_chunk_rows % block_rows:
        raise ValueError("max_chunk_rows must be a multiple of block_rows")
    n = masks.shape[1]
    nb = -(-n // block_rows)  # ceil: tail partial block participates
    pad = nb * block_rows - n
    masks = jnp.pad(masks.astype(bool), ((0, 0), (0, pad)))
    maxb = max_chunk_rows // block_rows

    def one(mask):
        bm = mask.reshape(nb, block_rows).any(axis=1)
        idx = jnp.arange(nb, dtype=jnp.int32)
        prev = jnp.concatenate([jnp.zeros((1,), bool), bm[:-1]])
        run_start = bm & ~prev
        # index of the enclosing run's first block (cumulative max of starts)
        start_idx = jax.lax.associative_scan(
            jnp.maximum, jnp.where(run_start, idx, -1)
        )
        pos = idx - start_idx  # block position within the run (where bm)
        chunk_start = bm & (pos % maxb == 0)
        cid = jnp.cumsum(chunk_start.astype(jnp.int32)) - 1
        dump = jnp.where(bm, cid, nb)  # pad blocks scatter to a dropped slot
        sizes_b = (
            jnp.zeros((nb + 1,), jnp.int32).at[dump].add(bm.astype(jnp.int32))[:nb]
        )
        starts_b = (
            jnp.zeros((nb + 1,), jnp.int32)
            .at[jnp.where(chunk_start, cid, nb)]
            .max(idx)[:nb]
        )
        return starts_b * block_rows, sizes_b * block_rows

    starts, sizes = jax.vmap(one)(masks)
    return starts, sizes


SUPERBLOCK_ROWS = 128  # the MXU's contraction depth and the lane width
# VMEM the weight slots of one pipeline may hold: every operand a step
# streams, all prefetch_depth + 1 slots. Wider matrices split their columns.
VMEM_SLOT_BUDGET = 16 * 2**20
_CHUNK_LANES = 512  # output columns per dot inside a step
_VMEM_HEADROOM = 8 * 2**20  # Mosaic's own temporaries: f32 weight pieces, dots
_VMEM_CAP = 100 * 2**20  # of the v5e's 128 MiB per core


def pick_tile(dim: int, cap: int = SUPERBLOCK_ROWS) -> int:
    """Largest power-of-two tile ≤ ``cap`` dividing ``dim`` — the
    super-block height of a matrix with ``dim`` input rows: 128, the MXU's
    contraction depth, at every real model width; reduced-config widths
    like d_ff 704 take 64."""
    t = cap
    while t >= 8:
        if dim % t == 0:
            return t
        t //= 2
    raise ValueError(
        f"dim {dim} has no power-of-two tile divisor >= 8 — the kernel "
        "backend needs dims divisible by 8"
    )


def superblock_rows(n_rows: int, block_rows: int = 8) -> int:
    """Rows per pipeline step of a matrix with ``n_rows`` input rows."""
    sb = pick_tile(n_rows)
    if sb % block_rows or block_rows & (block_rows - 1):
        raise ValueError(
            f"super-block of {sb} rows is not a whole number of "
            f"power-of-two blocks of {block_rows} rows"
        )
    return sb


def chunk_lanes(width: int) -> int:
    """Columns per dot inside a step, for a matrix ``width`` wide: the
    widest multiple of 128 up to ``_CHUNK_LANES`` dividing it, or the whole
    width off the 128-lane grid. A property of the matrix, not of its
    column tile, so the reference twin contracts the same pieces."""
    for c in range(_CHUNK_LANES, 0, -128):
        if width % c == 0:
            return c
    return width


def column_tile(width: int, bytes_per_column: int) -> int:
    """Columns per pipeline of a matrix ``width`` wide whose slots hold
    ``bytes_per_column`` bytes per column: the full width when they fit
    ``VMEM_SLOT_BUDGET``, else the widest multiple of its contraction piece
    (``chunk_lanes``, a multiple of 128) dividing it that fits, one piece
    at least. A width off the 128-lane grid — only the reduced CPU
    configs, interpreted — is never split."""
    chunk = chunk_lanes(width)
    for t in range(width, chunk, -chunk):
        if width % t == 0 and t * bytes_per_column <= VMEM_SLOT_BUDGET:
            return t
    return chunk


def _rows8(b: int) -> int:
    return -(-b // 8) * 8


def _vmem_limit(nbytes: int) -> int:
    return min(nbytes + _VMEM_HEADROOM, _VMEM_CAP)


# ---------------------------------------------------------------------------
# the step list and what it moves
# ---------------------------------------------------------------------------


def _block_coverage(starts, sizes, n_rows: int, block_rows: int):
    """(n_rows // block_rows,) bool: the blocks the chunk table covers, by a
    difference array (+1 where a chunk starts, -1 past its end; a size-0
    entry cancels itself)."""
    nb = n_rows // block_rows
    b0 = jnp.clip(starts // block_rows, 0, nb)
    b1 = jnp.clip((starts + sizes) // block_rows, 0, nb)
    diff = jnp.zeros((nb + 1,), jnp.int32).at[b0].add(1).at[b1].add(-1)
    return jnp.cumsum(diff[:nb]) > 0


@functools.partial(jax.jit, static_argnames=("n_rows", "block_rows", "sb_rows"))
def block_list(starts, sizes, n_rows: int, sb_rows: int, block_rows: int = 8):
    """Chunk table → (offsets, bits, count): the row offsets of the
    ``sb_rows``-row super-blocks the table covers, ascending and each once,
    padded to ``n_rows // sb_rows`` entries; beside each, its covered
    ``block_rows`` blocks as a bitmask (bit j: rows ``off + j·block_rows``
    on); and how many entries are real (a (1,) int32). The kernels'
    pipelines walk this list, so a padded table entry costs no loop step,
    and super-blocks accumulate in the ascending order of the reference
    twin whatever the table's order."""
    per, n_sb = sb_rows // block_rows, n_rows // sb_rows
    cov = _block_coverage(starts, sizes, n_rows, block_rows).reshape(n_sb, per)
    bits = jnp.sum(cov.astype(jnp.int32) << jnp.arange(per, dtype=jnp.int32),
                   axis=1, dtype=jnp.int32)
    real = bits != 0
    rank = jnp.where(real, jnp.cumsum(real) - 1, n_sb)

    def compact(v):
        return jnp.zeros((n_sb + 1,), jnp.int32).at[rank].set(v)[:n_sb]

    offs = compact(jnp.arange(n_sb, dtype=jnp.int32) * sb_rows)
    return offs, compact(bits), jnp.sum(real, dtype=jnp.int32)[None]


@functools.partial(jax.jit, static_argnames=("n_rows", "block_rows"))
def superblock_counts(starts, sizes, n_rows: int, block_rows: int = 8):
    """What one walk of the table by a kernel moves per matrix, (3,) int32:
    super-blocks moved whole (one DMA each), super-blocks moved by blocks,
    and the ``block_rows``-row DMAs those take. ``per × whole + block
    DMAs`` is the number of covered blocks (``per`` = blocks per
    super-block, 16 at real widths): no unselected block is read."""
    per = superblock_rows(n_rows, block_rows) // block_rows
    n_cov = jnp.sum(
        _block_coverage(starts, sizes, n_rows, block_rows).reshape(-1, per),
        axis=1, dtype=jnp.int32)
    whole = n_cov == per
    partial = (n_cov > 0) & ~whole
    return jnp.stack([jnp.sum(whole, dtype=jnp.int32),
                      jnp.sum(partial, dtype=jnp.int32),
                      jnp.sum(jnp.where(partial, n_cov, 0), dtype=jnp.int32)])


# ---------------------------------------------------------------------------
# the slot-rotation pipeline (shared by both kernels)
# ---------------------------------------------------------------------------


def _superblock_copies(streams, off, bits, slot, col, width: int,
                       sb_rows: int, block_rows: int, wait: bool):
    """Start — or, with ``wait``, wait on — one step's copies into slot
    ``slot`` of each stream ``(hbm ref, slots, semaphores)``: the
    super-block at row ``off`` as ONE ``(sb_rows, width)`` copy when every
    block is covered (``bits`` all ones), else one ``(block_rows, width)``
    copy per covered block into the same rows of the slot. A slot's copies
    all signal its semaphore; the wait side rebuilds the same descriptors,
    so each wait matches one copy."""
    per = sb_rows // block_rows

    def copy(r0: int, rows: int):
        src = pl.ds(pl.multiple_of(off + r0, block_rows), rows)
        for hbm, slots, sems in streams:
            c = pltpu.make_async_copy(hbm.at[src, pl.ds(col, width)],
                                      slots.at[slot, pl.ds(r0, rows)],
                                      sems.at[slot])
            c.wait() if wait else c.start()

    whole = bits == (1 << per) - 1
    pl.when(whole)(functools.partial(copy, 0, sb_rows))

    @pl.when(jnp.logical_not(whole))
    def _():
        for j in range(per):
            pl.when(((bits >> j) & 1) == 1)(
                functools.partial(copy, j * block_rows, block_rows))


def _lane_blocks(sb_rows: int, block_rows: int):
    """(1, sb_rows) int32: each lane's block within the super-block."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, sb_rows), 1)
    return lanes >> (block_rows.bit_length() - 1)


def _covered_lanes(bits, lane_blk):
    return ((bits >> lane_blk) & 1) == 1


def _lane_scales(s_ref, blk0, lane_blk, per: int):
    """(1, sb_rows) f32: each lane's block scale, read from SMEM."""
    v = jnp.zeros(lane_blk.shape, jnp.float32)
    for j in range(per):
        v = jnp.where(lane_blk == j, s_ref[blk0 + j], v)
    return v


def _dot(xb, wb):
    """(B, rows) · (rows, T) in f32 at full f32 precision — the reference
    twin's contraction asks for the same."""
    return jnp.dot(xb, wb, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _contract(terms, slot, width: int, chunk: int, acc_col=0):
    """``acc[:, acc_col + c] += xb · slots[slot][:, c]`` for each
    ``(xb, slots, acc)`` of ``terms``, over the slot's ``width`` columns in
    ``chunk``-column pieces."""

    def body(c, carry):
        lo = pl.multiple_of(c * chunk, chunk)
        dst = pl.ds(pl.multiple_of(acc_col + lo, chunk), chunk)
        for xb, slots, acc in terms:
            acc[:, dst] += _dot(xb, slots[slot, :, pl.ds(lo, chunk)].astype(jnp.float32))
        return carry

    jax.lax.fori_loop(0, width // chunk, body, None)


def _zero_slots(slots, chunk: int):
    """Zero every slot, ``chunk`` columns at a time: a partially covered
    super-block leaves rows of its slot unfetched, and zeroed ``x`` lanes
    meet them; ``0 · garbage`` could be NaN, ``0 · weight`` is 0."""
    n_slots, rows, width = slots.shape

    def body(c, carry):
        lo = pl.multiple_of(c * chunk, chunk)
        slots[:, :, pl.ds(lo, chunk)] = jnp.zeros((n_slots, rows, chunk), slots.dtype)
        return carry

    jax.lax.fori_loop(0, width // chunk, body, None)


def _pipelined_steps(total, n_slots, start_copy, wait_and_compute):
    """Run the slot-rotation schedule over ``total`` (traced) steps: start
    copies ``n_slots - 1`` steps ahead (prefetch_depth = n_slots - 1),
    wait + compute in order. With n_slots == 1 (depth 0) each step starts
    its own copy then immediately waits on it — the serial baseline
    schedule."""
    depth = n_slots - 1
    for s in range(depth):  # warm-up (static: depth is a python int)

        @pl.when(s < total)
        def _():
            start_copy(jnp.int32(s), s % n_slots)

    def body(step, carry):
        nxt = step + depth

        @pl.when(nxt < total)
        def _():
            start_copy(nxt, nxt % n_slots)

        wait_and_compute(step, step % n_slots)
        return carry

    jax.lax.fori_loop(0, total, body, None)


# ---------------------------------------------------------------------------
# single-site DMA matmul
# ---------------------------------------------------------------------------


def _matmul_dma_kernel(
    offs_ref,  # scalar prefetch (N // sb_rows,): covered super-block offsets
    bits_ref,  # scalar prefetch (N // sb_rows,): their covered blocks
    count_ref,  # scalar prefetch (1,): how many of them are real
    x_ref,  # (B, N) f32 VMEM
    w_hbm,  # (N, D) ANY/HBM — fetched by explicit DMA only
    *rest,  # [s_smem,] [c_smem,] out_ref, wslots, sems
    block_rows: int,
    sb_rows: int,
    tile_d: int,
    chunk: int,
    n_slots: int,
    quantized: bool,
    checksummed: bool,
):
    s_smem = rest[0] if quantized else None
    out_ref, wslots, sems = rest[quantized + checksummed:]
    col = pl.multiple_of(pl.program_id(0) * tile_d, tile_d)
    lane_blk = _lane_blocks(sb_rows, block_rows)
    streams = ((w_hbm, wslots, sems),)

    def step_args(step):
        return pl.multiple_of(offs_ref[step], sb_rows), bits_ref[step]

    def start_copy(step, slot, wait=False):
        off, bits = step_args(step)
        _superblock_copies(streams, off, bits, slot, col, tile_d, sb_rows,
                           block_rows, wait)

    def wait_and_compute(step, slot):
        start_copy(step, slot, wait=True)
        off, bits = step_args(step)
        xb = jnp.where(_covered_lanes(bits, lane_blk),
                       x_ref[:, pl.ds(off, sb_rows)], 0.0)
        if quantized:
            # dequantize on the input side: each lane carries its block's
            # scale, the reference twin's exact expression
            xb = xb * _lane_scales(s_smem, off // block_rows, lane_blk,
                                   sb_rows // block_rows)
        _contract(((xb, wslots, out_ref),), slot, tile_d, chunk)

    @pl.when(pl.program_id(0) == 0)
    def _():
        _zero_slots(wslots, chunk)

    out_ref[...] = jnp.zeros_like(out_ref)
    _pipelined_steps(count_ref[0], n_slots, start_copy, wait_and_compute)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "prefetch_depth", "interpret")
)
def chunk_gather_matmul_dma(
    w: jnp.ndarray,  # (N, D); int8 payload when scales is given
    x: jnp.ndarray,  # (B, N)
    starts: jnp.ndarray,  # (K,) int32, multiples of block_rows
    sizes: jnp.ndarray,  # (K,) int32, multiples of block_rows (0 = padded)
    scales: jnp.ndarray | None = None,  # (N // block_rows,) f32 per-block
    checksums: jnp.ndarray | None = None,  # (N // block_rows,) u32 per-block
    *,
    block_rows: int = 8,
    prefetch_depth: int = 1,
    interpret: bool = False,
) -> jnp.ndarray:
    """y (B, D) f32 = Σ_chunks x_chunk @ W_chunk, fetched by an explicitly
    ``prefetch_depth``-deep DMA pipeline over the table's covered
    super-blocks (``block_list``), in ascending row order. Numerically
    identical at every depth (the schedule only re-times the same fetches) —
    matches ``chunk_gather_matmul_ref`` like the BlockSpec kernel, and
    ``blocked_masked_matmul`` on an input masked to the table bitwise.

    With ``scales`` (the quantized chunk format, ``kernels/quantize.py``):
    ``w`` is the int8 payload, the per-block f32 scales sit in SMEM for the
    whole call, and each input lane is multiplied by its block's scale
    before the contraction — matching
    ``blocked_masked_matmul(..., scales=...)`` bitwise.

    With ``checksums`` (``kernels/quantize.block_checksums`` /
    ``core/offload.pack_checksums``): the per-block uint32 integrity words
    travel into SMEM with the call, next to the payload they cover. They
    are not verified here; the verification happens at the selection
    boundary (``serving/sparse_exec.refresh_layer``), identically on both
    backends. Output is bit-identical with and without the lane."""
    n, d = w.shape
    b = x.shape[0]
    if prefetch_depth < 0:
        raise ValueError(f"prefetch_depth must be >= 0, got {prefetch_depth}")
    if n % block_rows:
        raise ValueError(f"N={n} must be a multiple of block_rows={block_rows}")
    quantized = scales is not None
    if quantized and scales.shape != (n // block_rows,):
        raise ValueError(
            f"scales must be ({n // block_rows},), got {scales.shape}"
        )
    checksummed = checksums is not None
    if checksummed and checksums.shape != (n // block_rows,):
        raise ValueError(
            f"checksums must be ({n // block_rows},), got {checksums.shape}"
        )
    n_slots = prefetch_depth + 1
    sb = superblock_rows(n, block_rows)
    slot_col = n_slots * sb * jnp.dtype(w.dtype).itemsize
    tile_d = column_tile(d, slot_col)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [
        pl.BlockSpec((b, n), lambda dj, *_: (0, 0)),  # x resident in VMEM
        pl.BlockSpec(memory_space=pl.ANY),  # w stays in HBM; DMA'd manually
    ]
    offs, bits, count = block_list(starts, sizes, n, sb, block_rows)
    operands = [offs, bits, count, x.astype(jnp.float32), w]
    if quantized:
        in_specs.append(smem)
        operands.append(scales.astype(jnp.float32))
    if checksummed:
        in_specs.append(smem)
        operands.append(checksums.astype(jnp.uint32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(d // tile_d,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((b, tile_d), lambda dj, *_: (0, dj)),
        scratch_shapes=[
            pltpu.VMEM((n_slots, sb, tile_d), w.dtype),
            pltpu.SemaphoreType.DMA((n_slots,)),
        ],
    )
    # x and the output block are double-buffered by the grid pipeline
    vmem = slot_col * tile_d + 2 * 4 * _rows8(b) * (n + tile_d)
    return pl.pallas_call(
        functools.partial(
            _matmul_dma_kernel,
            block_rows=block_rows,
            sb_rows=sb,
            tile_d=tile_d,
            chunk=chunk_lanes(d),
            n_slots=n_slots,
            quantized=quantized,
            checksummed=checksummed,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(vmem)),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# fused multi-site MLP (gate/up off the hidden lane, down off the ffn lane)
# ---------------------------------------------------------------------------


def _mlp_dma_kernel(
    offs_ref,  # scalar prefetch (2, L): super-block lists, 0 = hidden_mlp, 1 = ffn
    bits_ref,  # scalar prefetch (2, L): their covered blocks
    count_ref,  # scalar prefetch (2,): real entries per lane
    x_ref,  # (B, N) f32 VMEM
    wg_hbm,  # (N, F) ANY
    wu_hbm,  # (N, F) ANY
    wd_hbm,  # (F, D) ANY
    fmask_ref,  # (1, F) VMEM f32 — exact ffn row mask (all-ones = table only)
    *rest,  # [sg, su, sd SMEM,] [cg, cu, cd SMEM,] out_ref, h, slots, accs, sems
    block_rows: int,
    sb_x: int,
    sb_h: int,
    tile_f: int,
    tile_d: int,
    chunk_f: int,
    chunk_d: int,
    n_slots: int,
    n_f_tiles: int,
    n_d_tiles: int,
    quantized: bool,
    checksummed: bool,
):
    sg, su, sd = rest[:3] if quantized else (None, None, None)
    (out_ref, h_ref, gslots, uslots, dslots, acc_g, acc_u,
     sems_g, sems_u, sems_d) = rest[3 * (quantized + checksummed):]
    lane_x = _lane_blocks(sb_x, block_rows)
    lane_h = _lane_blocks(sb_h, block_rows)

    def step_args(lane, step, sb):
        return pl.multiple_of(offs_ref[lane, step], sb), bits_ref[lane, step]

    _zero_slots(gslots, chunk_f)
    _zero_slots(uslots, chunk_f)
    _zero_slots(dslots, chunk_d)

    # -- phase 1: gate/up over the hidden lane, one f-tile at a time --------
    def gate_up_tile(fj, carry):
        col = pl.multiple_of(fj * tile_f, tile_f)
        # one super-block, fetched once, feeds BOTH gate and up
        streams = ((wg_hbm, gslots, sems_g), (wu_hbm, uslots, sems_u))

        def start_copy(step, slot, wait=False):
            off, bits = step_args(0, step, sb_x)
            _superblock_copies(streams, off, bits, slot, col, tile_f, sb_x,
                               block_rows, wait)

        def wait_and_compute(step, slot):
            start_copy(step, slot, wait=True)
            off, bits = step_args(0, step, sb_x)
            xg = xu = jnp.where(_covered_lanes(bits, lane_x),
                                x_ref[:, pl.ds(off, sb_x)], 0.0)
            if quantized:
                blk0, per = off // block_rows, sb_x // block_rows
                xg = xg * _lane_scales(sg, blk0, lane_x, per)
                xu = xu * _lane_scales(su, blk0, lane_x, per)
            _contract(((xg, gslots, acc_g), (xu, uslots, acc_u)), slot,
                      tile_f, chunk_f)

        acc_g[...] = jnp.zeros_like(acc_g)
        acc_u[...] = jnp.zeros_like(acc_u)
        _pipelined_steps(count_ref[0], n_slots, start_copy, wait_and_compute)

        def swiglu(c, carry):
            lo = pl.multiple_of(c * chunk_f, chunk_f)
            g = acc_g[:, pl.ds(lo, chunk_f)]
            h_ref[:, pl.ds(pl.multiple_of(col + lo, chunk_f), chunk_f)] = (
                g * (1.0 / (1.0 + jnp.exp(-g))) * acc_u[:, pl.ds(lo, chunk_f)])
            return carry

        jax.lax.fori_loop(0, tile_f // chunk_f, swiglu, None)
        return carry

    jax.lax.fori_loop(0, n_f_tiles, gate_up_tile, None)

    # -- phase 2: down over the ffn lane, consuming h straight from VMEM ----
    def down_tile(dj, carry):
        col = pl.multiple_of(dj * tile_d, tile_d)
        streams = ((wd_hbm, dslots, sems_d),)

        def start_copy(step, slot, wait=False):
            off, bits = step_args(1, step, sb_h)
            _superblock_copies(streams, off, bits, slot, col, tile_d, sb_h,
                               block_rows, wait)

        def wait_and_compute(step, slot):
            start_copy(step, slot, wait=True)
            off, bits = step_args(1, step, sb_h)
            rows = pl.ds(off, sb_h)
            # the exact ffn mask applies at the gather, NOT to the h output:
            # block-rounding pulls in rows outside the selected mask, and
            # those must contribute zero for the kernel to equal the
            # masked-matmul reference exactly
            hb = jnp.where(_covered_lanes(bits, lane_h),
                           h_ref[:, rows] * fmask_ref[:, rows], 0.0)
            if quantized:
                hb = hb * _lane_scales(sd, off // block_rows, lane_h,
                                       sb_h // block_rows)
            _contract(((hb, dslots, out_ref),), slot, tile_d, chunk_d,
                      acc_col=col)

        _pipelined_steps(count_ref[1], n_slots, start_copy, wait_and_compute)
        return carry

    out_ref[...] = jnp.zeros_like(out_ref)
    jax.lax.fori_loop(0, n_d_tiles, down_tile, None)


@functools.partial(
    jax.jit,
    static_argnames=("block_rows", "prefetch_depth", "interpret", "return_h"),
)
def chunk_gather_mlp_dma(
    w_gate: jnp.ndarray,  # (N, F); int8 payloads when scales is given
    w_up: jnp.ndarray,  # (N, F)
    w_down: jnp.ndarray,  # (F, D)
    x: jnp.ndarray,  # (B, N)
    starts: jnp.ndarray,  # (2, K): lane 0 = hidden_mlp plan, lane 1 = ffn plan
    sizes: jnp.ndarray,  # (2, K)
    ffn_mask: jnp.ndarray | None = None,  # (F,) exact down-input row mask
    scales: tuple | None = None,  # (sg (N//br,), su (N//br,), sd (F//br,)) f32
    checksums: tuple | None = None,  # (cg (N//br,), cu (N//br,), cd (F//br,)) u32
    *,
    block_rows: int = 8,
    prefetch_depth: int = 1,
    interpret: bool = False,
    return_h: bool = False,
) -> jnp.ndarray:
    """Fused sparse MLP: y (B, D) f32 = SwiGLU-masked down projection where
    gate/up gather off ``starts[0]`` (the hidden_mlp lane of the batched
    plan) and down gathers off ``starts[1]`` (the ffn lane) — one
    ``pallas_call`` for what the per-site path dispatches as three. Matches
    ``chunk_gather_mlp_ref`` exactly.

    ``ffn_mask`` (optional, (F,)): the exact selected row mask of the down
    projection's input. The block tables round masks outward to the
    ``block_rows`` grid; multiplying the gathered h block by the exact mask
    restores masked-matmul semantics on the over-fetched rows, which is what
    the decode execution backend needs for byte-identical parity with the
    reference path. None keeps pure chunk-table semantics (every gathered
    row contributes), the contract the standalone oracles test.

    ``return_h=True`` additionally returns the **unmasked** SwiGLU
    intermediate h (B, F) f32 — the decode path records its |·| importance
    for the next refresh's ffn-lane selection, so it must see h before the
    mask zeroes the unselected rows. With ``return_h=False`` h stays a VMEM
    scratch buffer that never round-trips HBM (the fused kernel's whole
    point); the kernel body is identical either way because outputs and
    scratch occupy the same positional slot.

    With ``scales = (sg, su, sd)`` the three weights are int8 payloads of
    the quantized chunk format; the scale lanes sit in SMEM for the call
    and each input lane is multiplied by its block's scale before the
    contraction (bitwise equal to the reference backend's quantized
    schedule twin).

    With ``checksums = (cg, cu, cd)`` the three lanes' per-block uint32
    integrity words travel into SMEM with the call — verified at the
    selection boundary (see ``chunk_gather_matmul_dma``); output is
    bit-identical either way."""
    n, f = w_gate.shape
    fd, d = w_down.shape
    b = x.shape[0]
    if w_up.shape != (n, f):
        raise ValueError("w_gate/w_up shape mismatch")
    if fd != f:
        raise ValueError(f"w_down rows {fd} must equal d_ff {f}")
    if starts.shape[0] != 2 or starts.shape != sizes.shape:
        raise ValueError(
            f"starts/sizes must be (2, K) plan lanes, got {starts.shape}/{sizes.shape}"
        )
    if prefetch_depth < 0:
        raise ValueError(f"prefetch_depth must be >= 0, got {prefetch_depth}")
    if n % block_rows or f % block_rows:
        raise ValueError("alignment violation")
    if ffn_mask is None:
        fmask = jnp.ones((1, f), jnp.float32)
    else:
        if ffn_mask.shape != (f,):
            raise ValueError(f"ffn_mask must be ({f},), got {ffn_mask.shape}")
        fmask = ffn_mask.astype(jnp.float32)[None, :]
    quantized = scales is not None
    if quantized:
        sg, su, sd = scales
        if sg.shape != (n // block_rows,) or su.shape != (n // block_rows,):
            raise ValueError(
                f"gate/up scales must be ({n // block_rows},), "
                f"got {sg.shape}/{su.shape}"
            )
        if sd.shape != (f // block_rows,):
            raise ValueError(
                f"down scales must be ({f // block_rows},), got {sd.shape}"
            )
    checksummed = checksums is not None
    if checksummed:
        cg, cu, cd = checksums
        if cg.shape != (n // block_rows,) or cu.shape != (n // block_rows,):
            raise ValueError(
                f"gate/up checksums must be ({n // block_rows},), "
                f"got {cg.shape}/{cu.shape}"
            )
        if cd.shape != (f // block_rows,):
            raise ValueError(
                f"down checksums must be ({f // block_rows},), got {cd.shape}"
            )
    n_slots = prefetch_depth + 1
    sb_x, sb_h = superblock_rows(n, block_rows), superblock_rows(f, block_rows)
    gu_col = 2 * n_slots * sb_x * jnp.dtype(w_gate.dtype).itemsize
    d_col = n_slots * sb_h * jnp.dtype(w_down.dtype).itemsize
    tile_f, tile_d = column_tile(f, gu_col), column_tile(d, d_col)
    # h (B, F) occupies the same positional kernel-ref slot either way:
    # second OUTPUT when the caller wants it, first SCRATCH when not (so a
    # return_h=False dispatch never writes the intermediate back to HBM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_specs = (vmem, vmem) if return_h else vmem
    out_shape = jax.ShapeDtypeStruct((b, d), jnp.float32)
    if return_h:
        out_shape = (out_shape, jax.ShapeDtypeStruct((b, f), jnp.float32))
    h_scratch = [] if return_h else [pltpu.VMEM((b, f), jnp.float32)]
    in_specs = [
        vmem,  # x
        pl.BlockSpec(memory_space=pl.ANY),  # w_gate
        pl.BlockSpec(memory_space=pl.ANY),  # w_up
        pl.BlockSpec(memory_space=pl.ANY),  # w_down
        vmem,  # ffn mask
    ]
    lanes = [block_list(starts[i], sizes[i], m, sb, block_rows)
             for i, (m, sb) in enumerate(((n, sb_x), (f, sb_h)))]
    n_sb = max(n // sb_x, f // sb_h)
    offs, bits = (jnp.stack([jnp.pad(lane[k], (0, n_sb - lane[k].shape[0]))
                             for lane in lanes]) for k in (0, 1))
    count = jnp.concatenate([lane[2] for lane in lanes])
    operands = [offs, bits, count, x.astype(jnp.float32), w_gate, w_up, w_down,
                fmask]
    if quantized:
        in_specs += [smem] * 3  # scales lanes
        operands += [s.astype(jnp.float32) for s in (sg, su, sd)]
    if checksummed:
        in_specs += [smem] * 3  # checksum lanes
        operands += [c.astype(jnp.uint32) for c in (cg, cu, cd)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=h_scratch + [
            pltpu.VMEM((n_slots, sb_x, tile_f), w_gate.dtype),
            pltpu.VMEM((n_slots, sb_x, tile_f), w_up.dtype),
            pltpu.VMEM((n_slots, sb_h, tile_d), w_down.dtype),
            pltpu.VMEM((b, tile_f), jnp.float32),
            pltpu.VMEM((b, tile_f), jnp.float32),
            pltpu.SemaphoreType.DMA((n_slots,)),
            pltpu.SemaphoreType.DMA((n_slots,)),
            pltpu.SemaphoreType.DMA((n_slots,)),
        ],
    )
    # slots; x, h, the two accumulators and y; the (1, F) mask pads to 8 rows
    vmem_bytes = (gu_col * tile_f + d_col * tile_d
                  + 4 * _rows8(b) * (n + f + 2 * tile_f + d) + 4 * 8 * f)
    return pl.pallas_call(
        functools.partial(
            _mlp_dma_kernel,
            block_rows=block_rows,
            sb_x=sb_x,
            sb_h=sb_h,
            tile_f=tile_f,
            tile_d=tile_d,
            chunk_f=chunk_lanes(f),
            chunk_d=chunk_lanes(d),
            n_slots=n_slots,
            n_f_tiles=f // tile_f,
            n_d_tiles=d // tile_d,
            quantized=quantized,
            checksummed=checksummed,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(vmem_bytes)),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# routed experts, addressed in place in the stacked weights
# ---------------------------------------------------------------------------
#
# An expert layer of the latent-attention family holds its experts stacked
# over layers: gate and up (L, E, d, F), down (L, E, F, d). Both phases take
# the whole stack as an HBM operand with the layer index (and, for gate/up,
# the routed experts' ids) scalar-prefetched, so the DMAs read the bytes in
# place: no layer's slice is ever copied out of the stack.
#
#   * up phase — one step per routed expert: its whole gate and up matrices
#     (every row of a routed expert is a candidate, and the rows' importance
#     is only known once both have run) as one DMA each, then
#     act = silu(x · gate) * (x · up) into the expert's columns of the
#     (B, E·F) output; unrouted experts stay zero and cost nothing;
#   * down phase — the selected rows of down, (L, E·F, d) viewed as one
#     row space, gathered by 128-row super-blocks off the block table
#     exactly as ``chunk_gather_matmul_dma`` does, for the layer given.
#
# Both contract bf16 operands into f32 (the MXU's single pass); the
# reference twin (``kernels/backend.py``) contracts the same pieces.


def _expert_up_kernel(
    ids_ref,  # scalar prefetch (E,): routed held experts first
    count_ref,  # scalar prefetch (1,): how many are routed
    layer_ref,  # scalar prefetch (1,): the layer within the stack
    x_ref,  # (B, d) bf16 VMEM
    wg_hbm,  # (L, E, d, F) ANY
    wu_hbm,  # (L, E, d, F) ANY
    out_ref,  # (B, E·F) f32 VMEM
    gslots,  # (n_slots, d, F)
    uslots,
    sems,
    *,
    d_ff: int,
    n_slots: int,
):
    layer = layer_ref[0]

    def copies(step, slot, wait):
        e = ids_ref[step]
        for hbm, slots in ((wg_hbm, gslots), (wu_hbm, uslots)):
            c = pltpu.make_async_copy(hbm.at[layer, e], slots.at[slot], sems.at[slot])
            c.wait() if wait else c.start()

    def wait_and_compute(step, slot):
        copies(step, slot, True)
        x = x_ref[...]
        g = jnp.dot(x, gslots[slot], preferred_element_type=jnp.float32)
        u = jnp.dot(x, uslots[slot], preferred_element_type=jnp.float32)
        col = pl.multiple_of(ids_ref[step] * d_ff, 128 if d_ff % 128 == 0 else 8)
        out_ref[:, pl.ds(col, d_ff)] = g * (1.0 / (1.0 + jnp.exp(-g))) * u

    out_ref[...] = jnp.zeros_like(out_ref)
    _pipelined_steps(count_ref[0], n_slots,
                     lambda step, slot: copies(step, slot, False), wait_and_compute)


def _expert_down_kernel(
    offs_ref,  # scalar prefetch (E·F // sb_rows,): covered super-blocks
    bits_ref,  # scalar prefetch: their covered blocks
    count_ref,  # scalar prefetch (1,)
    layer_ref,  # scalar prefetch (1,)
    x_ref,  # (B, E·F) f32 VMEM, pre-masked
    w_hbm,  # (L, E·F, d) ANY
    out_ref,  # (B, tile_d) f32
    wslots,
    sems,
    *,
    block_rows: int,
    sb_rows: int,
    tile_d: int,
    chunk: int,
    n_slots: int,
):
    col = pl.multiple_of(pl.program_id(0) * tile_d, tile_d)
    lane_blk = _lane_blocks(sb_rows, block_rows)
    streams = ((w_hbm.at[layer_ref[0]], wslots, sems),)

    def step_args(step):
        return pl.multiple_of(offs_ref[step], sb_rows), bits_ref[step]

    def start_copy(step, slot, wait=False):
        off, bits = step_args(step)
        _superblock_copies(streams, off, bits, slot, col, tile_d, sb_rows,
                           block_rows, wait)

    def wait_and_compute(step, slot):
        start_copy(step, slot, wait=True)
        off, bits = step_args(step)
        xb = jnp.where(_covered_lanes(bits, lane_blk),
                       x_ref[:, pl.ds(off, sb_rows)], 0.0).astype(wslots.dtype)

        def piece(c, carry):
            lo = pl.multiple_of(c * chunk, chunk)
            out_ref[:, pl.ds(lo, chunk)] += jnp.dot(
                xb, wslots[slot, :, pl.ds(lo, chunk)],
                preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, tile_d // chunk, piece, None)

    @pl.when(pl.program_id(0) == 0)
    def _():
        _zero_slots(wslots, chunk)

    out_ref[...] = jnp.zeros_like(out_ref)
    _pipelined_steps(count_ref[0], n_slots, start_copy, wait_and_compute)


@functools.partial(jax.jit, static_argnames=("prefetch_depth", "interpret"))
def chunk_gather_expert_up_dma(
    w_gate: jnp.ndarray,  # (L, E, d, F) the whole stack
    w_up: jnp.ndarray,  # (L, E, d, F)
    layer: jnp.ndarray,  # () int32
    ids: jnp.ndarray,  # (E,) int32: routed held experts first
    count: jnp.ndarray,  # () int32: how many are routed
    x: jnp.ndarray,  # (B, d)
    *,
    prefetch_depth: int = 1,
    interpret: bool = False,
) -> jnp.ndarray:
    """The expert gather's up phase: act (B, E·F) f32, expert ``e``'s
    columns silu(x · gate[layer, e]) * (x · up[layer, e]) for the first
    ``count`` experts of ``ids``, zero for the rest. One pipeline step per
    routed expert; its gate and up come in whole, read in place."""
    n_layers, e, d, f = w_gate.shape
    b = x.shape[0]
    if w_up.shape != w_gate.shape:
        raise ValueError("w_gate/w_up shape mismatch")
    if prefetch_depth < 0:
        raise ValueError(f"prefetch_depth must be >= 0, got {prefetch_depth}")
    n_slots = prefetch_depth + 1
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(),
        in_specs=[vmem, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=vmem,
        scratch_shapes=[
            pltpu.VMEM((n_slots, d, f), w_gate.dtype),
            pltpu.VMEM((n_slots, d, f), w_up.dtype),
            pltpu.SemaphoreType.DMA((n_slots,)),
        ],
    )
    slot_bytes = 2 * n_slots * d * f * jnp.dtype(w_gate.dtype).itemsize
    vmem_bytes = slot_bytes + 4 * _rows8(b) * (d + e * f + 2 * f)
    return pl.pallas_call(
        functools.partial(_expert_up_kernel, d_ff=f, n_slots=n_slots),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, e * f), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(vmem_bytes)),
        interpret=interpret,
        name="chunk_gather_expert_dma",
    )(ids.astype(jnp.int32), count.reshape(1).astype(jnp.int32),
      layer.reshape(1).astype(jnp.int32), x.astype(w_gate.dtype), w_gate, w_up)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "prefetch_depth", "interpret")
)
def chunk_gather_expert_down_dma(
    w_down: jnp.ndarray,  # (L, E, F, d) the whole stack
    layer: jnp.ndarray,  # () int32
    x: jnp.ndarray,  # (B, E·F) pre-masked intermediate
    starts: jnp.ndarray,  # (K,) block table over the E·F rows
    sizes: jnp.ndarray,  # (K,)
    *,
    block_rows: int = 8,
    prefetch_depth: int = 1,
    interpret: bool = False,
) -> jnp.ndarray:
    """The expert gather's down phase: y (B, d) f32 = Σ over the covered
    super-blocks of x_sb · down[layer] rows, the (E, F) rows of the layer
    taken as one row space (a view of the stack, no copy), fetched as
    ``chunk_gather_matmul_dma`` fetches and contracted in bf16 into f32."""
    n_layers, e, f, d = w_down.shape
    n = e * f
    b = x.shape[0]
    if prefetch_depth < 0:
        raise ValueError(f"prefetch_depth must be >= 0, got {prefetch_depth}")
    if f % block_rows:
        raise ValueError(f"F={f} must be a multiple of block_rows={block_rows}")
    n_slots = prefetch_depth + 1
    sb = superblock_rows(n, block_rows)
    if f % sb:
        raise ValueError(f"a {sb}-row super-block must not straddle experts of {f} rows")
    slot_col = n_slots * sb * jnp.dtype(w_down.dtype).itemsize
    tile_d = column_tile(d, slot_col)
    offs, bits, count = block_list(starts, sizes, n, sb, block_rows)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(d // tile_d,),
        in_specs=[pl.BlockSpec((b, n), lambda dj, *_: (0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((b, tile_d), lambda dj, *_: (0, dj)),
        scratch_shapes=[
            pltpu.VMEM((n_slots, sb, tile_d), w_down.dtype),
            pltpu.SemaphoreType.DMA((n_slots,)),
        ],
    )
    vmem_bytes = slot_col * tile_d + 2 * 4 * _rows8(b) * (n + tile_d)
    return pl.pallas_call(
        functools.partial(
            _expert_down_kernel,
            block_rows=block_rows,
            sb_rows=sb,
            tile_d=tile_d,
            chunk=chunk_lanes(d),
            n_slots=n_slots,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(vmem_bytes)),
        interpret=interpret,
        name="chunk_gather_expert_dma",
    )(offs, bits, count, layer.reshape(1).astype(jnp.int32),
      x.astype(jnp.float32), w_down.reshape(n_layers, n, d))
