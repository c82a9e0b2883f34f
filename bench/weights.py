"""Seeded weights for a configuration file, made by the benchmark itself.

The served model and the reference both get their weights from here, from
``--seed`` alone: the reference never reads a weight the program holds.
What the tree holds is the configuration's reference module's to say:
``leaves(dims)`` gives ``{"top": {name: (shape, kind)}, "stacks": {stack:
(depth, {name: (shape, kind)})}}``, and each stack's leaves get its depth
as a leading axis. The harness checks the tree against the program's own
parameter tree before serving, so a drift fails loudly.

Every leaf has its own key (from the crc32 of its name), and every layer
of a stacked leaf its own key under it (its index in the whole model,
stacks counted in the order declared), so one layer can be made alone (the
reference walks the model layer by layer) and equals that layer of the
whole tree bit for bit.
"""
from __future__ import annotations

import functools
import zlib
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

SERVED_DTYPE = jnp.bfloat16

# leaf kinds: how a leaf is drawn
MATRIX, EMBED, NORM_W, SMALL = "matrix", "embed", "norm_w", "small"

Leaves = Callable[[object], dict]  # a reference module's ``leaves``


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (folded in 32 bits at a time)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(0)
    while True:
        key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
        seed >>= 32
        if not seed:
            return key


def _leaf_key(root, name: str):
    return jax.random.fold_in(root, np.uint32(zlib.crc32(name.encode())))


def _draw(key, shape, kind: str, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == MATRIX:
        v = z / np.sqrt(shape[-2])  # the fan-in; a leading axis stacks experts
    elif kind == EMBED:
        v = z * 0.02
    elif kind == NORM_W:
        v = 1.0 + 0.05 * z
    else:
        v = 0.02 * z
    # rounded to the served type first, so every dtype sees the same values
    return v.astype(SERVED_DTYPE).astype(dtype)


def _stacks(layout: dict) -> Dict[str, Tuple[int, int, dict]]:
    """stack -> (index of its first layer in the model, depth, leaves)."""
    out, first = {}, 0
    for stack, (depth, leaves) in layout["stacks"].items():
        out[stack] = (first, int(depth), leaves)
        first += int(depth)
    return out


def _layer(root, layer, leaves: dict, dtype):
    return {name: _draw(jax.random.fold_in(_leaf_key(root, name), layer), shape,
                        kind, dtype)
            for name, (shape, kind) in leaves.items()}


def _top(root, layout: dict, dtype):
    return {name: _draw(_leaf_key(root, name), shape, kind, dtype)
            for name, (shape, kind) in layout["top"].items()}


@functools.partial(jax.jit, static_argnums=(1, 2))
def _all(root, leaves_of: Leaves, dm):
    layout = leaves_of(dm)
    params = _top(root, layout, SERVED_DTYPE)
    for stack, (first, depth, leaves) in _stacks(layout).items():
        layers = jnp.arange(first, first + depth, dtype=jnp.uint32)
        params[stack] = jax.vmap(lambda l, lv=leaves: _layer(root, l, lv, SERVED_DTYPE))(
            layers)
    return params


def served_params(leaves_of: Leaves, dm, seed: int):
    """The whole tree, in bfloat16 on the default device, in one call."""
    return _all(seed_key(seed), leaves_of, dm)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _one_layer(root, layer, leaves_of: Leaves, dm, stack: str, dtype):
    return _layer(root, layer, leaves_of(dm)["stacks"][stack][1], dtype)


def layer_params(leaves_of: Leaves, dm, seed: int, stack: str, layer: int,
                 dtype=jnp.float32):
    """Layer ``layer`` of ``stack``'s leaves (equal to that slice of the
    served tree)."""
    first, depth, _ = _stacks(leaves_of(dm))[stack]
    if not 0 <= layer < depth:
        raise IndexError(f"stack {stack!r} has {depth} layers, not {layer}")
    return _one_layer(seed_key(seed), jnp.uint32(first + layer), leaves_of, dm, stack,
                      dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _top_params(root, leaves_of: Leaves, dm, dtype):
    return _top(root, leaves_of(dm), dtype)


def top_params(leaves_of: Leaves, dm, seed: int, dtype=jnp.float32):
    return _top_params(seed_key(seed), leaves_of, dm, dtype)
