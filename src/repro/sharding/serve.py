"""ServeMesh: the serve stack's (data, model) mesh context.

The sharded decode hot path partitions WHERE bytes live and stream, never
WHAT arithmetic runs — greedy tokens must stay byte-identical between the
1×1 mesh and any (data, model) mesh at both wbits 16 and 8 (the PR-5/6
invariant extended). Three rules make that hold by construction:

  * **Selection is replicated, storage and I/O are sharded.** Chunk
    selection must produce the same masks on every shard, so importance
    vectors are replicated (``replicate`` — an explicit
    ``with_sharding_constraint`` to ``P()``) BEFORE any cross-batch
    reduction; an unconstrained mean over a data-sharded batch would let
    GSPMD reassociate the sum and change low bits.
  * **Only decode-streamed leaves shard over ``model``.** At wbits=8 the
    ``_q8``/``_sc`` chunk leaves shard; at wbits=16 a ``<name>_dec`` fp
    copy is created and sharded while the original stays replicated —
    prefill / frame-append matmuls over a row-sharded weight would
    psum-partial the contraction and perturb the KV cache. The decode
    path's ``blocked_masked_matmul`` is immune: its f32 accumulation is an
    explicit sequential ``fori_loop`` over 8-row blocks, which GSPMD
    gathers and sums in the written order (this gather IS the all-reduce
    at the SwiGLU down-projection boundary).
  * **Row slices own whole quantization blocks.** Row-sharded matrices
    (``wo``/``w_down``/``w_proj`` — the streamed dim of the ``attn_out``
    and ``ffn`` sites) require rows % (model × QUANT_BLOCK_ROWS) == 0 so
    each shard's slice is a whole number of 8-row scale blocks and the
    per-shard block tables / byte counters align with storage.

Weight specs are derived through ``MeshRules`` from the same logical axes
the ParamDefs declare (heads/kv_heads/ffn → 'model'; embed replicated), so
the serve mesh can never drift from the training-side sharding vocabulary.

Serve slots partition over ``data``: the batch dim of activations, tokens
and the KV cache shards over the data axis (validated divisible), so
``--streams`` scales with ``data`` × the per-shard slot count.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels.quantize import (
    DECODE_COPY_SUFFIX,
    QUANT_BLOCK_ROWS,
    QUANT_SUFFIX_PAYLOAD,
    QUANT_SUFFIX_SCALE,
)
from .specs import MeshRules, auto_mesh

MESH_AXES = ("data", "model")

# logical axes of the offloaded per-layer matrices (mirrors the ParamDefs in
# models/attention.py and models/mlp.py; the leading dim is the stacked layer
# axis). MeshRules maps heads/kv_heads/ffn → 'model' and embed → replicated,
# so matrices whose STREAMED row dim carries a model-mapped axis shard by
# rows (wo, w_down, w_proj) and the rest shard by output columns.
DECODE_WEIGHT_AXES: Dict[str, Tuple[Optional[str], ...]] = {
    "wq": ("layer", "embed", "heads"),
    "wk": ("layer", "embed", "kv_heads"),
    "wv": ("layer", "embed", "kv_heads"),
    "wo": ("layer", "heads", "embed"),
    "w_gate": ("layer", "embed", "ffn"),
    "w_up": ("layer", "embed", "ffn"),
    "w_fc": ("layer", "embed", "ffn"),
    "w_down": ("layer", "ffn", "embed"),
    "w_proj": ("layer", "ffn", "embed"),
}

# weights whose ROW (streamed) dim shards over 'model' — these carry the
# per-shard block tables and the data-dependent per-shard miss counters of
# their sites ('attn_out' streams wo rows, 'ffn' streams w_down/w_proj rows)
ROW_SHARDED_WEIGHTS = ("wo", "w_down", "w_proj")


def validate_serve_mesh(data: int, model: int, *, batch: int = 0,
                        streams: int = 0, d_ff: int = 0,
                        n_devices: Optional[int] = None) -> None:
    """The sharded serve path's static preconditions, with actionable
    messages — ``launch.serve`` calls this at parse time so a bad ``--mesh``
    fails before any model is built. Zero-valued optional dims skip their
    check (callers validate what they know)."""
    if data < 1 or model < 1:
        raise ValueError(
            f"--mesh axes must be >= 1, got data={data} model={model}"
        )
    if n_devices is not None and data * model > n_devices:
        raise ValueError(
            f"--mesh {data},{model} needs {data * model} devices but only "
            f"{n_devices} are visible; shrink the mesh or launch with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={data * model} "
            "(host-device simulation)"
        )
    if batch and batch % data != 0:
        raise ValueError(
            f"--batch {batch} must be divisible by the mesh data axis "
            f"({data}) — each data shard serves batch/data slot rows; use "
            f"--batch {((batch + data - 1) // data) * data} or shrink data"
        )
    if streams and streams % data != 0:
        raise ValueError(
            f"--streams {streams} must be divisible by the mesh data axis "
            f"({data}) so every data shard serves the same number of "
            f"streams; use --streams {((streams + data - 1) // data) * data} "
            "or shrink data"
        )
    if d_ff and d_ff % (model * QUANT_BLOCK_ROWS) != 0:
        raise ValueError(
            f"ffn rows ({d_ff}) must be divisible by model × the "
            f"{QUANT_BLOCK_ROWS}-row quant block ({model} × "
            f"{QUANT_BLOCK_ROWS} = {model * QUANT_BLOCK_ROWS}) so each "
            "model shard owns whole quantization blocks of w_down; pick a "
            "mesh whose model axis divides d_ff/8"
        )


@dataclasses.dataclass(frozen=True, eq=False)
class ServeMesh:
    """The serve engine's mesh context. ``mesh is None`` ⇔ the unsharded
    1×1 path: every method degrades to a no-op, so single-device code pays
    nothing and the engine never branches on device count."""

    data: int
    model: int
    mesh: Optional[Mesh] = None
    rules: Optional[MeshRules] = None

    @staticmethod
    def single() -> "ServeMesh":
        return ServeMesh(1, 1, None, None)

    @staticmethod
    def create(data: int = 1, model: int = 1) -> "ServeMesh":
        validate_serve_mesh(data, model, n_devices=len(jax.devices()))
        if data * model == 1:
            return ServeMesh.single()
        mesh = auto_mesh((data, model), MESH_AXES)
        return ServeMesh(data, model, mesh, MeshRules.for_mesh(mesh))

    @staticmethod
    def from_spec(spec: str) -> "ServeMesh":
        """Parse a ``--mesh data,model`` string (e.g. "2,2")."""
        parts = spec.split(",")
        if len(parts) != 2:
            raise ValueError(
                f"--mesh must be 'data,model' (e.g. 2,2), got {spec!r}"
            )
        try:
            data, model = (int(p) for p in parts)
        except ValueError:
            raise ValueError(
                f"--mesh axes must be integers, got {spec!r}"
            ) from None
        return ServeMesh.create(data, model)

    @property
    def is_sharded(self) -> bool:
        return self.mesh is not None

    @property
    def size(self) -> int:
        return self.data * self.model

    # -- placement helpers ---------------------------------------------------
    def _sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def replicate(self, x: jax.Array) -> jax.Array:
        """Constrain an in-jit value to full replication. THE bitwise
        linchpin: applied to activations before any cross-batch reduction
        (importance recording), so the reduction's operand layout — hence
        its f32 summation order — is independent of the mesh shape."""
        if not self.is_sharded:
            return x
        return jax.lax.with_sharding_constraint(x, self._sharding(P()))

    def put_replicated(self, tree: Any) -> Any:
        if not self.is_sharded:
            return tree
        s = self._sharding(P())
        return jax.tree.map(lambda x: jax.device_put(x, s), tree)

    def batch_spec(self, shape: Tuple[int, ...], axis: int = 0) -> P:
        spec: list = [None] * len(shape)
        if self.is_sharded and shape[axis] % self.data == 0:
            spec[axis] = "data"
        return P(*spec)

    def put_batch(self, x: jax.Array, axis: int = 0) -> jax.Array:
        """Commit an array to the mesh sharded over ``data`` on its batch
        dim (replicated when indivisible) — serve slots partition over the
        data axis."""
        if not self.is_sharded:
            return x
        return jax.device_put(x, self._sharding(self.batch_spec(x.shape, axis)))

    def place_cache(self, cache: Any, axes: Any) -> Any:
        """Commit a KV/state cache to the mesh: the 'batch' logical dim
        shards over ``data`` (slot rows are per-data-shard), as does the
        paged pools' 'kv_page' dim (pages partition over data shards the
        same way the slot rows that own them do); everything else
        replicates. ``axes`` is the model's ``cache_axes()`` /
        ``paged_cache_axes()`` pytree (dicts / tuples mirroring the cache
        structure; leaves are logical-axis tuples)."""
        if not self.is_sharded:
            return cache

        def rec(c, a):
            if isinstance(c, dict):
                return {
                    k: rec(v, a.get(k) if isinstance(a, dict) else None)
                    for k, v in c.items()
                }
            if isinstance(c, (tuple, list)) and not hasattr(c, "shape"):
                sub = a if isinstance(a, (tuple, list)) else (None,) * len(c)
                return type(c)(rec(v, sa) for v, sa in zip(c, sub))
            if not hasattr(c, "shape"):
                return c
            names = tuple(a) if isinstance(a, (tuple, list)) else ()
            spec = [None] * c.ndim
            for i, name in enumerate(names[: c.ndim]):
                if name in ("batch", "kv_page") and c.shape[i] % self.data == 0:
                    spec[i] = "data"
            return jax.device_put(c, self._sharding(P(*spec)))

        return rec(cache, axes)

    # -- decode-weight sharding ----------------------------------------------
    def weight_spec(self, name: str, shape: Tuple[int, ...]) -> P:
        """PartitionSpec of one stacked (L, N, D) decode-streamed leaf,
        derived through MeshRules from the matrix's declared logical axes —
        with the extra serve-side constraint that a ROW-sharded slice must
        be a whole number of QUANT_BLOCK_ROWS blocks (per-shard chunk
        tables and scale lanes align with storage). Returns ``P()``
        (replicate) for unknown names or indivisible dims."""
        axes = DECODE_WEIGHT_AXES.get(name)
        if axes is None or self.rules is None:
            return P()
        spec = self.rules.spec(axes, shape)
        if name in ROW_SHARDED_WEIGHTS:
            if shape[1] % (self.model * QUANT_BLOCK_ROWS) != 0:
                return P()
        return spec

    def scale_spec(self, weight_spec: P) -> P:
        """Spec of a weight's (L, N // QUANT_BLOCK_ROWS) per-block scale
        lane: rows shard with the payload's row dim (whole blocks per shard
        by the weight_spec constraint), otherwise replicated."""
        parts = tuple(weight_spec)
        if len(parts) >= 2 and parts[1] is not None:
            return P(None, parts[1])
        return P()

    def place_params(self, params: Dict[str, Any], wbits: int,
                     sparse_names: Tuple[str, ...]) -> Dict[str, Any]:
        """Commit a model's params to the mesh. Decode-streamed leaves of
        the stacked layer dict shard over ``model`` (the ``_q8``/``_sc``
        chunk leaves at wbits=8; freshly created ``<name>_dec`` fp copies
        at wbits=16 — see module docstring for why the originals stay
        replicated); every other leaf replicates. No-op when unsharded."""
        if not self.is_sharded:
            return params
        rep = self._sharding(P())
        layers = dict(params["layers"])
        placed: Dict[str, jax.Array] = {}
        for name in sparse_names:
            if name not in layers:
                continue
            if wbits == 8:
                qn = name + QUANT_SUFFIX_PAYLOAD
                sn = name + QUANT_SUFFIX_SCALE
                if qn not in layers:
                    continue
                wspec = self.weight_spec(name, layers[qn].shape)
                placed[qn] = jax.device_put(layers[qn], self._sharding(wspec))
                placed[sn] = jax.device_put(
                    layers[sn], self._sharding(self.scale_spec(wspec))
                )
            else:
                wspec = self.weight_spec(name, layers[name].shape)
                if tuple(wspec):  # only materialize a copy that shards
                    placed[name + DECODE_COPY_SUFFIX] = jax.device_put(
                        layers[name], self._sharding(wspec)
                    )
        new_layers = {
            k: placed.get(k, None) if k in placed else jax.device_put(v, rep)
            for k, v in layers.items()
        }
        new_layers.update(placed)
        return {
            k: (new_layers if k == "layers"
                else jax.tree.map(lambda x: jax.device_put(x, rep), v))
            for k, v in params.items()
        }

    # -- per-shard accounting geometry ---------------------------------------
    def row_shard_count(self, n_rows: int) -> int:
        """How many model-axis row slices an ``n_rows``-row site splits
        into: ``model`` when each slice is whole quantization blocks, else
        1 (the site replicates and its bytes split evenly instead)."""
        if not self.is_sharded:
            return 1
        if n_rows % (self.model * QUANT_BLOCK_ROWS) != 0:
            return 1
        return self.model


def shard_block_tables(starts, sizes, n_rows: int, n_shards: int):
    """Intersect a site's block-aligned chunk table with each model shard's
    contiguous row range ``[s·n_rows/n_shards, (s+1)·n_rows/n_shards)``.

    Returns per-shard (starts, sizes) of shape (n_shards, K) — same padded
    K as the global table, entries outside a shard's range clipped to size
    0 (the DMA kernels already skip zero-size chunks). Invariants (pinned
    by tests/test_sharded_serving.py): per-shard sizes sum to the global
    sum (the ranges partition the rows), every surviving chunk lies inside
    its shard's range, and chunk starts stay QUANT_BLOCK_ROWS-aligned
    because the range boundaries are (n_rows divisible by
    n_shards × QUANT_BLOCK_ROWS by construction). Works on jnp or np
    arrays; jit-safe."""
    import jax.numpy as jnp

    if n_rows % (n_shards * QUANT_BLOCK_ROWS) != 0:
        raise ValueError(
            f"n_rows={n_rows} must divide into {n_shards} shards of whole "
            f"{QUANT_BLOCK_ROWS}-row blocks"
        )
    seg = n_rows // n_shards
    lo = jnp.arange(n_shards)[:, None] * seg  # (S, 1)
    hi = lo + seg
    s = jnp.asarray(starts)[None, :]  # (1, K)
    e = s + jnp.asarray(sizes)[None, :]
    cs = jnp.clip(s, lo, hi)
    ce = jnp.clip(e, lo, hi)
    return cs.astype(jnp.int32), jnp.maximum(ce - cs, 0).astype(jnp.int32)
