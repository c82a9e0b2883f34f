"""Explicitly double-buffered DMA chunk-gather kernels.

The BlockSpec-driven kernels (chunk_gather_matmul.py / chunk_gather_swiglu.py)
let the Pallas pipeline emitter fetch one HBM block per grid step — which the
compiler overlaps, but only one block deep and only in the schedule it picks.
These kernels drive the fetches themselves with ``pltpu.make_async_copy``:
``prefetch_depth + 1`` VMEM slots per streamed operand rotate through a
classic in-kernel pipeline —

    warm-up:  start copies for steps 0 .. depth-1
    step k:   start copy k+depth  →  wait copy k  →  MXU on slot k % (depth+1)

so chunk-block k+1's HBM→VMEM transfer is in flight while the MXU contracts
block k (depth 1 = double buffering; depth 0 degenerates to fetch-then-compute
serial, the baseline the overlap is benchmarked against). The steps are the
blocks the chunk table covers, in ascending row order (``block_list``), so
padded table entries cost nothing. This is the kernel
realization of the host-side prefetch timeline in core/pipeline.py: the same
``prefetch_depth`` knob, the same hidden-fetch discipline, so the model and
the kernel agree on what is hidden.

Two entry points:

  * ``chunk_gather_matmul_dma`` — drop-in for ``chunk_gather_matmul``: one
    weight matrix, one chunk table, same alignment contract
    (starts/sizes multiples of ``block_rows``, size 0 = padded entry).
  * ``chunk_gather_mlp_dma`` — the **fused multi-site** path: ONE
    ``pallas_call`` gathers gate, up AND down off the two MLP lanes of a
    ``BatchedChunkSelector`` ``(n_sites, K)`` plan. A hidden-lane chunk
    block is fetched once and contracted against both W_gate and W_up
    while resident, and the SwiGLU intermediate h stays in VMEM for the
    down-lane gather — no per-site re-dispatch, no h HBM round-trip.

On TPU the kernels compile through Mosaic. Off-TPU (the CPU test platform)
``interpret=True`` executes the same slot rotation (make_async_copy is
emulated as a synchronous copy), which validates the schedule's *numerics* —
rotation never overwrites a live slot — while the overlap itself only exists
on the chip.

Layout rules Mosaic enforces, and how the kernels meet them:

  * a vector load's lane offset must be a multiple of the 128-lane tile, so
    the ``block_rows``-wide column block of ``x`` / ``h`` / the ffn mask is
    read through its aligned lane window and rotated into place
    (``_column_block``);
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


# ---------------------------------------------------------------------------
# the slot-rotation pipeline (shared by both kernels)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_rows", "block_rows"))
def block_list(starts, sizes, n_rows: int, block_rows: int = 8):
    """Chunk table → (offsets, count): the row offsets of the ``block_rows``
    blocks the table covers, ascending and each once, padded to
    ``n_rows // block_rows`` entries, and how many of them are real (a
    (1,) int32). The kernels' pipelines walk this list, so a padded table
    entry (size 0) costs no loop step, and blocks accumulate in the
    ascending order of the reference twin whatever the table's order."""
    nb = n_rows // block_rows
    b0 = jnp.clip(starts // block_rows, 0, nb)
    b1 = jnp.clip((starts + sizes) // block_rows, 0, nb)
    # coverage by a difference array: +1 where a chunk starts, -1 past its
    # end (a size-0 entry cancels itself)
    diff = jnp.zeros((nb + 1,), jnp.int32).at[b0].add(1).at[b1].add(-1)
    covered = jnp.cumsum(diff[:nb]) > 0
    rank = jnp.where(covered, jnp.cumsum(covered) - 1, nb)
    offs = (jnp.zeros((nb + 1,), jnp.int32)
            .at[rank].set(jnp.arange(nb, dtype=jnp.int32) * block_rows))
    return offs[:nb], jnp.sum(covered, dtype=jnp.int32)[None]


def _lane_window(n: int, block_rows: int, cap: int = 128) -> int:
    """Width of the aligned lane window a ``block_rows``-wide column block
    is read through: the largest power of two ≤ ``cap`` dividing ``n``
    (128, the lane width, at every real model width; smaller only for the
    reduced CPU configs, which run in interpret mode)."""
    w = cap
    while n % w:
        w //= 2
    if w < block_rows:
        raise ValueError(f"dim {n} has no lane window >= block_rows={block_rows}")
    return w


def _column_block(ref, off, block_rows: int, window: int):
    """``ref[:, off:off + block_rows]`` for an ``off`` that is only
    ``block_rows``-aligned. A TPU vector load needs a lane offset aligned to
    the 128-lane tile, so load the aligned window holding the block, rotate
    the block down to lane 0 and slice it statically — pure data movement,
    bit-exact."""
    base = pl.multiple_of((off // window) * window, window)
    v = ref[:, pl.ds(base, window)]
    v = pltpu.roll(v, (window - (off - base)) % window, axis=1)
    return v[:, :block_rows]


def _block_dot(xb, wb):
    """One block's contribution: (B, block_rows) · (block_rows, T) in f32 at
    full f32 precision — the reference twin's einsum asks for the same (on
    the v5e both compile to the same bits, checked on the chip)."""
    return jnp.dot(xb, wb, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _pipelined_steps(total, n_slots, start_copy, wait_and_compute):
    """Run the slot-rotation schedule over ``total`` (traced) block steps:
    start copies ``n_slots - 1`` steps ahead (prefetch_depth = n_slots - 1),
    wait + compute in order. With n_slots == 1 (depth 0) each step starts
    its own copy then immediately waits on it — the serial baseline
    schedule."""
    depth = n_slots - 1
    for s in range(depth):  # warm-up (static: depth is a python int)

        @pl.when(s < total)
        def _():
            start_copy(jnp.int32(s), s % n_slots)

    def body(step, _):
        nxt = step + depth

        @pl.when(nxt < total)
        def _():
            start_copy(nxt, nxt % n_slots)

        wait_and_compute(step, step % n_slots)
        return _

    jax.lax.fori_loop(0, total, body, None)


# ---------------------------------------------------------------------------
# single-site DMA matmul
# ---------------------------------------------------------------------------


def _matmul_dma_kernel(
    offs_ref,  # scalar prefetch (N // block_rows,): covered block row offsets
    count_ref,  # scalar prefetch (1,): how many of them are real
    x_ref,  # (B, N) f32 VMEM
    w_hbm,  # (N, D) ANY/HBM — fetched by explicit DMA only
    *rest,  # [s_smem,] [c_smem,] out_ref, wslots, sems
    block_rows: int,
    tile_d: int,
    n_slots: int,
    window: int,
    quantized: bool,
    checksummed: bool,
):
    s_smem = rest[0] if quantized else None
    out_ref, wslots, sems = rest[quantized + checksummed:]
    col = pl.multiple_of(pl.program_id(0) * tile_d, tile_d)

    def offset(step):
        # table entries are block multiples by contract; Mosaic must see
        # that to tile the block_rows-row DMA
        return pl.multiple_of(offs_ref[step], block_rows)

    def copy(off, slot):
        return pltpu.make_async_copy(
            w_hbm.at[pl.ds(off, block_rows), pl.ds(col, tile_d)],
            wslots.at[slot],
            sems.at[slot],
        )

    def start_copy(step, slot):
        copy(offset(step), slot).start()

    def wait_and_compute(step, slot):
        off = offset(step)
        copy(off, slot).wait()
        xb = _column_block(x_ref, off, block_rows, window)
        part = _block_dot(xb, wslots[slot].astype(jnp.float32))
        if quantized:
            # dequantize after the contraction: one f32 multiply per output
            # element by the block's scale, the same order the reference
            # twin uses, so the two stay bitwise equal
            part = part * s_smem[off // block_rows]
        out_ref[...] += part

    out_ref[...] = jnp.zeros_like(out_ref)
    _pipelined_steps(count_ref[0], n_slots, start_copy, wait_and_compute)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_rows", "tile_d", "prefetch_depth", "interpret"
    ),
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
    tile_d: int = 128,
    prefetch_depth: int = 1,
    interpret: bool = False,
) -> jnp.ndarray:
    """y (B, D) f32 = Σ_chunks x_chunk @ W_chunk, fetched by an explicitly
    ``prefetch_depth``-deep double-buffered DMA pipeline over the table's
    covered blocks (``block_list``), in ascending row order. Numerically
    identical at every depth (the schedule only re-times the same fetches) —
    matches ``chunk_gather_matmul_ref`` exactly like the BlockSpec kernel.

    With ``scales`` (the quantized chunk format, ``kernels/quantize.py``):
    ``w`` is the int8 payload, the per-block f32 scales sit in SMEM for the
    whole call, and each block's f32 partial product is multiplied by its
    scale before accumulation — matching
    ``blocked_masked_matmul(..., scales=...)`` bitwise.

    With ``checksums`` (``kernels/quantize.block_checksums`` /
    ``core/offload.pack_checksums``): the per-block uint32 integrity words
    travel into SMEM with the call, next to the payload they cover. They
    are not verified here — a checksum covers the full (block_rows, D)
    storage block while the kernel fetches (block_rows, tile_d) tiles; the
    verification happens at the selection boundary
    (``serving/sparse_exec.refresh_layer``), identically on both backends.
    Output is bit-identical with and without the lane."""
    n, d = w.shape
    b = x.shape[0]
    if prefetch_depth < 0:
        raise ValueError(f"prefetch_depth must be >= 0, got {prefetch_depth}")
    if d % tile_d:
        raise ValueError(f"D={d} must be a multiple of tile_d={tile_d}")
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
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [
        pl.BlockSpec((b, n), lambda dj, *_: (0, 0)),  # x resident in VMEM
        pl.BlockSpec(memory_space=pl.ANY),  # w stays in HBM; DMA'd manually
    ]
    offs, count = block_list(starts, sizes, n, block_rows)
    operands = [offs, count, x.astype(jnp.float32), w]
    if quantized:
        in_specs.append(smem)
        operands.append(scales.astype(jnp.float32))
    if checksummed:
        in_specs.append(smem)
        operands.append(checksums.astype(jnp.uint32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(d // tile_d,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((b, tile_d), lambda dj, *_: (0, dj)),
        scratch_shapes=[
            pltpu.VMEM((n_slots, block_rows, tile_d), w.dtype),
            pltpu.SemaphoreType.DMA((n_slots,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _matmul_dma_kernel,
            block_rows=block_rows,
            tile_d=tile_d,
            n_slots=n_slots,
            window=_lane_window(n, block_rows),
            quantized=quantized,
            checksummed=checksummed,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# fused multi-site MLP (gate/up off the hidden lane, down off the ffn lane)
# ---------------------------------------------------------------------------


def _mlp_dma_kernel(
    offs_ref,  # scalar prefetch (2, nb): block lists, 0 = hidden_mlp, 1 = ffn
    count_ref,  # scalar prefetch (2,): real entries per lane
    x_ref,  # (B, N) f32 VMEM
    wg_hbm,  # (N, F) ANY
    wu_hbm,  # (N, F) ANY
    wd_hbm,  # (F, D) ANY
    fmask_ref,  # (1, F) VMEM f32 — exact ffn row mask (all-ones = table only)
    *rest,  # [sg, su, sd SMEM,] [cg, cu, cd SMEM,] out_ref, h, slots, accs, sems
    block_rows: int,
    tile_f: int,
    tile_d: int,
    n_slots: int,
    n_f_tiles: int,
    n_d_tiles: int,
    x_window: int,
    h_window: int,
    quantized: bool,
    checksummed: bool,
):
    sg, su, sd = rest[:3] if quantized else (None, None, None)
    (out_ref, h_ref, gslots, uslots, dslots, acc_g, acc_u,
     sems_g, sems_u, sems_d) = rest[3 * (quantized + checksummed):]

    def offset(lane, step):
        return pl.multiple_of(offs_ref[lane, step], block_rows)

    # -- phase 1: gate/up over the hidden lane, one f-tile at a time --------
    def gate_up_tile(fj):
        col = pl.multiple_of(fj * tile_f, tile_f)

        def copies(off, slot):
            # one chunk block, fetched once, feeds BOTH gate and up
            rows, cols = pl.ds(off, block_rows), pl.ds(col, tile_f)
            return (
                pltpu.make_async_copy(
                    wg_hbm.at[rows, cols], gslots.at[slot], sems_g.at[slot]),
                pltpu.make_async_copy(
                    wu_hbm.at[rows, cols], uslots.at[slot], sems_u.at[slot]),
            )

        def start_copy(step, slot):
            for c in copies(offset(0, step), slot):
                c.start()

        def wait_and_compute(step, slot):
            off = offset(0, step)
            for c in copies(off, slot):
                c.wait()
            xb = _column_block(x_ref, off, block_rows, x_window)
            g = _block_dot(xb, gslots[slot].astype(jnp.float32))
            u = _block_dot(xb, uslots[slot].astype(jnp.float32))
            if quantized:
                bk = off // block_rows
                g = g * sg[bk]
                u = u * su[bk]
            acc_g[...] += g
            acc_u[...] += u

        acc_g[...] = jnp.zeros_like(acc_g)
        acc_u[...] = jnp.zeros_like(acc_u)
        _pipelined_steps(count_ref[0], n_slots, start_copy, wait_and_compute)
        g = acc_g[...]
        h_ref[:, pl.ds(col, tile_f)] = g * (1.0 / (1.0 + jnp.exp(-g))) * acc_u[...]

    def f_body(fj, _):
        gate_up_tile(fj)
        return _

    jax.lax.fori_loop(0, n_f_tiles, f_body, None)

    # -- phase 2: down over the ffn lane, consuming h straight from VMEM ----
    def down_tile(dj):
        col = pl.multiple_of(dj * tile_d, tile_d)

        def copy(off, slot):
            return pltpu.make_async_copy(
                wd_hbm.at[pl.ds(off, block_rows), pl.ds(col, tile_d)],
                dslots.at[slot],
                sems_d.at[slot],
            )

        def start_copy(step, slot):
            copy(offset(1, step), slot).start()

        def wait_and_compute(step, slot):
            off = offset(1, step)
            copy(off, slot).wait()
            # the exact ffn mask applies at the gather, NOT to the h output:
            # block-rounding may pull in rows outside the selected mask, and
            # those must contribute zero for the kernel to equal the
            # masked-matmul reference exactly
            hb = _column_block(h_ref, off, block_rows, h_window)
            hb = hb * _column_block(fmask_ref, off, block_rows, h_window)
            part = _block_dot(hb, dslots[slot].astype(jnp.float32))
            if quantized:
                part = part * sd[off // block_rows]
            out_ref[:, pl.ds(col, tile_d)] += part

        _pipelined_steps(count_ref[1], n_slots, start_copy, wait_and_compute)

    out_ref[...] = jnp.zeros_like(out_ref)

    def d_body(dj, _):
        down_tile(dj)
        return _

    jax.lax.fori_loop(0, n_d_tiles, d_body, None)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_rows", "tile_f", "tile_d", "prefetch_depth", "interpret",
        "return_h",
    ),
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
    tile_f: int = 128,
    tile_d: int = 128,
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
    and each block's f32 partial product is multiplied by its scale before
    accumulation (bitwise equal to the reference backend's quantized
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
    if f % tile_f or d % tile_d or n % block_rows or f % block_rows:
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
    nb = max(n, f) // block_rows
    lanes = [block_list(starts[i], sizes[i], m, block_rows)
             for i, m in enumerate((n, f))]
    offs = jnp.stack([jnp.pad(o, (0, nb - o.shape[0])) for o, _ in lanes])
    count = jnp.concatenate([c for _, c in lanes])
    operands = [offs, count, x.astype(jnp.float32), w_gate, w_up, w_down, fmask]
    if quantized:
        in_specs += [smem] * 3  # scales lanes
        operands += [s.astype(jnp.float32) for s in (sg, su, sd)]
    if checksummed:
        in_specs += [smem] * 3  # checksum lanes
        operands += [c.astype(jnp.uint32) for c in (cg, cu, cd)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=h_scratch + [
            pltpu.VMEM((n_slots, block_rows, tile_f), w_gate.dtype),
            pltpu.VMEM((n_slots, block_rows, tile_f), w_up.dtype),
            pltpu.VMEM((n_slots, block_rows, tile_d), w_down.dtype),
            pltpu.VMEM((b, tile_f), jnp.float32),
            pltpu.VMEM((b, tile_f), jnp.float32),
            pltpu.SemaphoreType.DMA((n_slots,)),
            pltpu.SemaphoreType.DMA((n_slots,)),
            pltpu.SemaphoreType.DMA((n_slots,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _mlp_dma_kernel,
            block_rows=block_rows,
            tile_f=tile_f,
            tile_d=tile_d,
            n_slots=n_slots,
            n_f_tiles=f // tile_f,
            n_d_tiles=d // tile_d,
            x_window=_lane_window(n, block_rows),
            h_window=_lane_window(f, block_rows),
            quantized=quantized,
            checksummed=checksummed,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
