"""Fused chunk-gathered SwiGLU gate/up kernel.

gate and up projections share the hidden-state chunk plan (paper App. A), so
a fused kernel fetches each (block_rows × tile_f) block of W_gate and W_up
back-to-back while the x block is already resident, and applies SiLU·mul on
the final block step — halving VMEM x-traffic and eliding the intermediate
gate/up HBM round-trip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    starts_ref,
    sizes_ref,
    x_ref,  # (B, block_rows)
    wg_ref,  # (block_rows, tile_f)
    wu_ref,  # (block_rows, tile_f)
    out_ref,  # (B, tile_f) f32
    acc_g,  # scratch (B, tile_f) f32
    acc_u,  # scratch (B, tile_f) f32
    *,
    block_rows: int,
):
    ci = pl.program_id(1)
    bk = pl.program_id(2)
    n_chunks = pl.num_programs(1)
    n_blocks = pl.num_programs(2)

    @pl.when((ci == 0) & (bk == 0))
    def _init():
        acc_g[...] = jnp.zeros_like(acc_g)
        acc_u[...] = jnp.zeros_like(acc_u)

    active = bk * block_rows < sizes_ref[ci]

    @pl.when(active)
    def _acc():
        x = x_ref[...].astype(jnp.float32)
        acc_g[...] += jnp.dot(x, wg_ref[...].astype(jnp.float32),
                              preferred_element_type=jnp.float32)
        acc_u[...] += jnp.dot(x, wu_ref[...].astype(jnp.float32),
                              preferred_element_type=jnp.float32)

    @pl.when((ci == n_chunks - 1) & (bk == n_blocks - 1))
    def _finish():
        g = acc_g[...]
        out_ref[...] = g * (1.0 / (1.0 + jnp.exp(-g))) * acc_u[...]


@functools.partial(
    jax.jit, static_argnames=("block_rows", "tile_f", "max_chunk_rows", "interpret")
)
def chunk_gather_swiglu(
    w_gate: jnp.ndarray,  # (N, F)
    w_up: jnp.ndarray,  # (N, F)
    x: jnp.ndarray,  # (B, N)
    starts: jnp.ndarray,
    sizes: jnp.ndarray,
    *,
    block_rows: int = 8,
    tile_f: int = 128,
    max_chunk_rows: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    n, f = w_gate.shape
    b = x.shape[0]
    k = starts.shape[0]
    if w_up.shape != (n, f):
        raise ValueError("w_gate/w_up shape mismatch")
    if f % tile_f or n % block_rows or max_chunk_rows % block_rows:
        raise ValueError("alignment violation")
    # f-tile outermost: per out tile, accumulate over all (chunk, block) steps
    grid = (f // tile_f, k, max_chunk_rows // block_rows)

    def x_index(fj, ci, bk, starts_ref, sizes_ref):
        return (0, starts_ref[ci] // block_rows + bk)

    def w_index(fj, ci, bk, starts_ref, sizes_ref):
        return (starts_ref[ci] // block_rows + bk, fj)

    def out_index(fj, ci, bk, starts_ref, sizes_ref):
        return (0, fj)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, block_rows), x_index),
            pl.BlockSpec((block_rows, tile_f), w_index),
            pl.BlockSpec((block_rows, tile_f), w_index),
        ],
        out_specs=pl.BlockSpec((b, tile_f), out_index),
        scratch_shapes=[
            pltpu.VMEM((b, tile_f), jnp.float32),
            pltpu.VMEM((b, tile_f), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, block_rows=block_rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, f), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(starts, sizes, x, w_gate, w_up)
