"""Jit'd public wrappers for the Pallas kernels.

``sparse_matmul`` / ``sparse_swiglu`` dispatch to the compiled kernel on TPU
and to interpret mode on the CPU test platform (``resolve_interpret``:
interpret executes the kernel body in Python for correctness validation —
the BlockSpec tiling and scalar-prefetch structure are identical).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..core.contiguity import mask_to_chunks_np
from .backend import resolve_interpret
from .chunk_gather_dma import chunk_gather_matmul_dma, chunk_gather_mlp_dma
from .chunk_gather_matmul import align_chunk_table, chunk_gather_matmul
from .chunk_gather_swiglu import chunk_gather_swiglu


def sparse_matmul(
    w: jnp.ndarray,
    x: jnp.ndarray,
    starts: jnp.ndarray,
    sizes: jnp.ndarray,
    *,
    block_rows: int = 8,
    tile_d: int = 128,
    max_chunk_rows: int = 512,
) -> jnp.ndarray:
    """y (B, D) f32 — rows outside the chunk plan are never read from HBM."""
    return chunk_gather_matmul(
        w,
        x,
        starts,
        sizes,
        block_rows=block_rows,
        tile_d=tile_d,
        max_chunk_rows=max_chunk_rows,
        interpret=resolve_interpret(),
    )


def sparse_swiglu(
    w_gate: jnp.ndarray,
    w_up: jnp.ndarray,
    x: jnp.ndarray,
    starts: jnp.ndarray,
    sizes: jnp.ndarray,
    *,
    block_rows: int = 8,
    tile_f: int = 128,
    max_chunk_rows: int = 512,
) -> jnp.ndarray:
    return chunk_gather_swiglu(
        w_gate,
        w_up,
        x,
        starts,
        sizes,
        block_rows=block_rows,
        tile_f=tile_f,
        max_chunk_rows=max_chunk_rows,
        interpret=resolve_interpret(),
    )


def sparse_matmul_dma(
    w: jnp.ndarray,
    x: jnp.ndarray,
    starts: jnp.ndarray,
    sizes: jnp.ndarray,
    *,
    block_rows: int = 8,
    prefetch_depth: int = 1,
) -> jnp.ndarray:
    """``sparse_matmul`` through the explicitly double-buffered DMA kernel:
    ``prefetch_depth + 1`` VMEM slots rotate so super-block k+1 streams from
    HBM while the MXU contracts super-block k. Interpret mode off-TPU validates
    the identical slot-rotation schedule synchronously."""
    return chunk_gather_matmul_dma(
        w,
        x,
        starts,
        sizes,
        block_rows=block_rows,
        prefetch_depth=prefetch_depth,
        interpret=resolve_interpret(),
    )


def sparse_mlp_fused(
    w_gate: jnp.ndarray,
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    x: jnp.ndarray,
    starts: jnp.ndarray,  # (2, K): hidden_mlp and ffn lanes of a batched plan
    sizes: jnp.ndarray,
    ffn_mask: Optional[jnp.ndarray] = None,
    *,
    block_rows: int = 8,
    prefetch_depth: int = 1,
    return_h: bool = False,
) -> jnp.ndarray:
    """The fused multi-site MLP: ONE dispatch gathers gate/up off the
    hidden_mlp plan lane and down off the ffn lane, with the SwiGLU
    intermediate kept in VMEM (no per-site re-dispatch, no h round-trip).
    ``ffn_mask``/``return_h`` as in ``chunk_gather_mlp_dma`` (the decode
    execution backend's exact-mask / importance-recording plumbing)."""
    return chunk_gather_mlp_dma(
        w_gate,
        w_up,
        w_down,
        x,
        starts,
        sizes,
        ffn_mask,
        block_rows=block_rows,
        prefetch_depth=prefetch_depth,
        interpret=resolve_interpret(),
        return_h=return_h,
    )


def plan_to_kernel_table(
    mask: np.ndarray,
    block_rows: int = 8,
    max_chunks: Optional[int] = None,
    max_chunk_rows: int = 512,
) -> Tuple[np.ndarray, np.ndarray]:
    """Selection mask → block-aligned padded chunk table for the kernels."""
    chunks = mask_to_chunks_np(np.asarray(mask))
    starts = np.asarray([c.start for c in chunks], np.int32)
    sizes = np.asarray([c.size for c in chunks], np.int32)
    starts, sizes = align_chunk_table(
        starts, sizes, block_rows, len(mask), max_chunk_rows=max_chunk_rows
    )
    k = max_chunks or max(len(starts), 1)
    out_s = np.zeros(k, np.int32)
    out_z = np.zeros(k, np.int32)
    out_s[: len(starts)] = starts[:k]
    out_z[: len(sizes)] = sizes[:k]
    return out_s, out_z
