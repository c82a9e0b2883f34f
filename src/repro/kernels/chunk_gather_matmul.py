"""Pallas TPU kernel: chunk-gathered sparse matmul.

The TPU-native realization of the paper's contiguous-chunk loads
(DESIGN.md §2): the utility-guided selector emits a chunk table
(starts, sizes); each selected chunk of weight rows becomes a sequence of
contiguous HBM→VMEM block fetches driven by a scalar-prefetched BlockSpec
index_map, and the MXU accumulates x_chunk · W_chunk into the output tile.
Rows NOT in any chunk are never read from HBM — the kernel's HBM traffic is
exactly the chunk plan's byte count, which is what the latency model scores.

Alignment contract (TPU adaptation of the paper's KB-granular chunks):
  starts % block_rows == 0 and sizes % block_rows == 0 (padded entries have
  size 0). The selection layer guarantees this by generating candidates on a
  block_rows grid — analogous to the paper aligning chunk sizes to the SSD's
  saturation granularity.

Grid: (D/tile_d, n_chunks, max_chunk/block_rows) — output tiles outermost so
each out tile's accumulation visits are consecutive; dimension semantics all
"arbitrary".
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.contiguity import mask_to_chunks_np


def _kernel(
    starts_ref,  # scalar prefetch: (K,) block-aligned row starts
    sizes_ref,  # scalar prefetch: (K,) block-aligned chunk sizes (0 = pad)
    x_ref,  # (B, block_rows) VMEM
    w_ref,  # (block_rows, tile_d) VMEM
    out_ref,  # (B, tile_d) VMEM, f32
    *,
    block_rows: int,
):
    ci = pl.program_id(1)  # chunk index
    bk = pl.program_id(2)  # block index within the chunk

    @pl.when((ci == 0) & (bk == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # Blocks past this chunk's size contribute nothing (padded chunks: size 0)
    # — and DO nothing: the accumulate is predicated off entirely, instead of
    # the old lax.cond that still paid a zeros add into out_ref per pad step.
    active = bk * block_rows < sizes_ref[ci]

    @pl.when(active)
    def _acc():
        x = x_ref[...].astype(jnp.float32)
        w = w_ref[...].astype(jnp.float32)
        out_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "tile_d", "max_chunk_rows", "interpret")
)
def chunk_gather_matmul(
    w: jnp.ndarray,  # (N, D) weights (rows = neurons)
    x: jnp.ndarray,  # (B, N) activations
    starts: jnp.ndarray,  # (K,) int32, multiples of block_rows
    sizes: jnp.ndarray,  # (K,) int32, multiples of block_rows (0 = padded)
    *,
    block_rows: int = 8,
    tile_d: int = 128,
    max_chunk_rows: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns y (B, D) f32 = Σ_chunks x_chunk @ W_chunk."""
    n, d = w.shape
    b = x.shape[0]
    k = starts.shape[0]
    if d % tile_d:
        raise ValueError(f"D={d} must be a multiple of tile_d={tile_d}")
    if n % block_rows:
        raise ValueError(f"N={n} must be a multiple of block_rows={block_rows}")
    if max_chunk_rows % block_rows:
        raise ValueError("max_chunk_rows must be a multiple of block_rows")
    # output-tile dim OUTERMOST so the accumulated out block stays resident
    # across its consecutive (chunk, block) visits
    grid = (d // tile_d, k, max_chunk_rows // block_rows)

    def x_index(dj, ci, bk, starts_ref, sizes_ref):
        return (0, starts_ref[ci] // block_rows + bk)

    def w_index(dj, ci, bk, starts_ref, sizes_ref):
        return (starts_ref[ci] // block_rows + bk, dj)

    def out_index(dj, ci, bk, starts_ref, sizes_ref):
        return (0, dj)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, block_rows), x_index),
            pl.BlockSpec((block_rows, tile_d), w_index),
        ],
        out_specs=pl.BlockSpec((b, tile_d), out_index),
    )
    return pl.pallas_call(
        functools.partial(_kernel, block_rows=block_rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(starts, sizes, x, w)


def align_chunk_table(
    starts: np.ndarray,
    sizes: np.ndarray,
    block_rows: int,
    n: int,
    max_chunk_rows: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Round an arbitrary chunk table outward to block_rows alignment
    (start down, end up), clamped to [0, n). Overlapping/adjacent coverage is
    merged, then runs longer than ``max_chunk_rows`` are split so every entry
    fits the kernel grid (splitting a contiguous run costs nothing: the
    fetches stay back-to-back)."""
    def _as_rows(name, arr):
        """Validate/cast ONCE up front: row counts must be integral — a
        float table that survived by accident used to floor silently in the
        index arithmetic below."""
        arr = np.asarray(arr)
        if arr.ndim != 1:
            raise ValueError(f"{name} must be 1-D, got shape {arr.shape}")
        cast = arr.astype(np.int64)
        if not np.issubdtype(arr.dtype, np.integer) and not np.array_equal(cast, arr):
            raise TypeError(
                f"{name} must hold integral row values, got dtype {arr.dtype} "
                "with non-integer entries"
            )
        return cast

    starts = _as_rows("starts", starts)
    sizes = _as_rows("sizes", sizes)
    if starts.shape != sizes.shape:
        raise ValueError(
            f"starts/sizes length mismatch: {starts.shape} vs {sizes.shape}"
        )
    mask = np.zeros(n, bool)
    for s, z in zip(starts, sizes):
        if z <= 0:
            continue
        lo = (s // block_rows) * block_rows
        hi = min(n, ((s + z + block_rows - 1) // block_rows) * block_rows)
        mask[lo:hi] = True

    out_s, out_z = [], []
    for c in mask_to_chunks_np(mask):
        s, z = c.start, c.size
        if max_chunk_rows:
            while z > max_chunk_rows:
                out_s.append(s)
                out_z.append(max_chunk_rows)
                s += max_chunk_rows
                z -= max_chunk_rows
        out_s.append(s)
        out_z.append(z)
    return np.asarray(out_s, np.int32), np.asarray(out_z, np.int32)
