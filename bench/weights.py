"""Seeded weights for a configuration file, made by the benchmark itself.

The served model and the reference both get their weights from here, from
``--seed`` alone: the reference never reads a weight the program holds.
Leaf names and shapes follow the decoder layout the program serves
(embedding, norms, q/k/v/o, SwiGLU gate/up/down or GELU fc/proj with
biases); the harness checks them against the program's own parameter tree
before serving, so a drift fails loudly.

Every leaf has its own key, and every layer of a stacked leaf its own key
under it, so one layer can be made alone (the reference walks the model
layer by layer) and equals that layer of the whole tree bit for bit.
"""
from __future__ import annotations

import dataclasses
import functools
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

SERVED_DTYPE = jnp.bfloat16


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    f: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    rope_theta: float
    norm: str  # rms | layer
    norm_eps: float
    mlp: str  # swiglu | gelu
    tied: bool


def dims_of(cfg: dict) -> Dims:
    """Shape facts of a configuration file (Hugging Face key names)."""
    act = cfg["hidden_act"]
    if act == "silu":
        mlp = "swiglu"
    elif act.startswith("gelu"):
        mlp = "gelu"
    else:
        raise ValueError(f"unsupported hidden_act {act!r}")
    if "rms_norm_eps" in cfg:
        norm, eps = "rms", float(cfg["rms_norm_eps"])
    else:
        norm, eps = "layer", float(cfg["norm_epsilon"])
    # a served model that departs from the published epsilon says so
    eps = float(cfg.get("served_norm_eps", eps))
    heads = int(cfg["num_attention_heads"])
    return Dims(
        d=int(cfg["hidden_size"]), f=int(cfg["intermediate_size"]),
        layers=int(cfg["num_hidden_layers"]), heads=heads,
        kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg.get("head_dim") or cfg["hidden_size"] // heads),
        vocab=int(cfg["vocab_size"]), rope_theta=float(cfg["rope_theta"]),
        norm=norm, norm_eps=eps, mlp=mlp, tied=bool(cfg["tie_word_embeddings"]),
    )


# leaf kinds: how a leaf is drawn
_MATRIX, _EMBED, _NORM_W, _SMALL = "matrix", "embed", "norm_w", "small"


def top_leaves(dm: Dims) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    leaves = {"embed": ((dm.vocab, dm.d), _EMBED),
              "final_norm_w": ((dm.d,), _NORM_W)}
    if dm.norm == "layer":
        leaves["final_norm_b"] = ((dm.d,), _SMALL)
    if not dm.tied:
        leaves["head"] = ((dm.d, dm.vocab), _EMBED)
    return leaves


def layer_leaves(dm: Dims) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """One layer's leaves (the stacked tree prepends the layer axis)."""
    q, kv = dm.heads * dm.head_dim, dm.kv_heads * dm.head_dim
    leaves = {"ln1_w": ((dm.d,), _NORM_W), "ln2_w": ((dm.d,), _NORM_W),
              "wq": ((dm.d, q), _MATRIX), "wk": ((dm.d, kv), _MATRIX),
              "wv": ((dm.d, kv), _MATRIX), "wo": ((q, dm.d), _MATRIX)}
    if dm.norm == "layer":
        leaves.update(ln1_b=((dm.d,), _SMALL), ln2_b=((dm.d,), _SMALL))
    if dm.mlp == "swiglu":
        leaves.update(w_gate=((dm.d, dm.f), _MATRIX), w_up=((dm.d, dm.f), _MATRIX),
                      w_down=((dm.f, dm.d), _MATRIX))
    else:
        leaves.update(w_fc=((dm.d, dm.f), _MATRIX), b_fc=((dm.f,), _SMALL),
                      w_proj=((dm.f, dm.d), _MATRIX), b_proj=((dm.d,), _SMALL))
    return leaves


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
    if kind == _MATRIX:
        v = z / np.sqrt(shape[0])
    elif kind == _EMBED:
        v = z * 0.02
    elif kind == _NORM_W:
        v = 1.0 + 0.05 * z
    else:
        v = 0.02 * z
    # rounded to the served type first, so every dtype sees the same values
    return v.astype(SERVED_DTYPE).astype(dtype)


def _layer(root, layer, dm: Dims, dtype):
    return {name: _draw(jax.random.fold_in(_leaf_key(root, name), layer), shape,
                        kind, dtype)
            for name, (shape, kind) in layer_leaves(dm).items()}


@functools.partial(jax.jit, static_argnums=(1,))
def _all(root, dm: Dims):
    params = {name: _draw(_leaf_key(root, name), shape, kind, SERVED_DTYPE)
              for name, (shape, kind) in top_leaves(dm).items()}
    layers = jnp.arange(dm.layers, dtype=jnp.uint32)
    params["layers"] = jax.vmap(lambda l: _layer(root, l, dm, SERVED_DTYPE))(layers)
    return params


def served_params(dm: Dims, seed: int):
    """The whole tree, in bfloat16 on the default device, in one call."""
    return _all(seed_key(seed), dm)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _one_layer(root, layer, dm: Dims, dtype):
    return _layer(root, layer, dm, dtype)


def layer_params(dm: Dims, seed: int, layer: int, dtype=jnp.float32):
    """Layer ``layer``'s leaves (equal to that slice of the served tree)."""
    return _one_layer(seed_key(seed), jnp.uint32(layer), dm, dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _top(root, dm: Dims, dtype):
    return {name: _draw(_leaf_key(root, name), shape, kind, dtype)
            for name, (shape, kind) in top_leaves(dm).items()}


def top_params(dm: Dims, seed: int, dtype=jnp.float32):
    return _top(seed_key(seed), dm, dtype)
