"""Logical-axis sharding rules (MaxText-style).

Model code annotates tensors with *logical* axis names; a MeshRules object
(installed by the launcher for the active mesh) maps logical names to mesh
axes and applies ``with_sharding_constraint``. With no rules installed (unit
tests, CPU smoke runs) every annotation is a no-op, so model code never
depends on a mesh being present.

Resolution is two-pass with divisibility + used-axis tracking:
  pass 1: each dim gets its primary mesh axis if the axis divides the dim
          and is not already used in this spec;
  pass 2: unassigned dims may pick up a fallback axis (e.g. a KV cache whose
          8 kv-heads can't split 16-way model-parallel instead shards its
          sequence dim over 'model' — without this, a 32k-decode cache for
          internvl2-76b would replicate ~43 GB per chip).

Logical axes:
  batch            → ('pod','data')      act_seq        → 'model' (seq-parallel)
  heads/kv_heads   → 'model'             ffn/vocab      → 'model'
  embed (weights)  → 'data' iff FSDP     expert         → 'model'
  expert_capacity  → ('pod','data')      ssm_heads      → 'model'
  cache_kv_heads   → 'model'             cache_seq      → fallback 'model'
  conv_dim         → 'model'             layer/state/…  → replicated
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

Axis = Union[str, Tuple[str, ...], None]



def auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the compiler propagates
    shardings from the constraints these rules place (``jax.make_mesh``
    defaults to ``Explicit`` axes, under which ops such as a gather over a
    data-sharded index must name their output sharding)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))

@dataclasses.dataclass(frozen=True, eq=False)
class MeshRules:
    """Maps logical axis names → mesh axes (primary + optional fallback)."""

    mesh: Mesh
    rules: Dict[str, Axis]
    fallbacks: Dict[str, Axis]
    fsdp: bool = False
    # §Perf iteration C: allow shard_map sequence-sharded attention when the
    # head count doesn't divide the model axis (beyond-paper optimization;
    # False reproduces the baseline GSPMD behaviour).
    seq_shard_attention: bool = False

    @staticmethod
    def for_mesh(mesh: Mesh, fsdp: bool = False) -> "MeshRules":
        names = mesh.axis_names
        dp: Axis = tuple(a for a in ("pod", "data") if a in names) or None
        if isinstance(dp, tuple) and len(dp) == 1:
            dp = dp[0]
        tp: Axis = "model" if "model" in names else None
        rules: Dict[str, Axis] = {
            "batch": dp,
            "act_seq": tp,  # sequence parallelism between blocks
            "act_embed": None,
            "heads": tp,
            "kv_heads": tp,
            "head_dim": None,
            "ffn": tp,
            "embed": ("data" if (fsdp and "data" in names) else None),
            "vocab": tp,
            "expert": tp,
            "expert_capacity": dp,
            "ssm_heads": tp,
            "conv_dim": tp,
            "state": None,
            "layer": None,
            "cache_seq": None,
            "cache_kv_heads": tp,
        }
        fallbacks: Dict[str, Axis] = {
            "cache_seq": tp,  # when kv-heads can't take the model axis
            "expert": "data",  # tiny expert counts at decode time
            "act_seq": tp,  # attention seq when head counts don't divide tp
        }
        return MeshRules(mesh=mesh, rules=rules, fallbacks=fallbacks, fsdp=fsdp)

    def axis_size(self, axis: Axis) -> int:
        if axis is None:
            return 1
        if isinstance(axis, tuple):
            return int(np.prod([self.mesh.shape[a] for a in axis]))
        return int(self.mesh.shape[axis])

    def _axis_names(self, axis: Axis) -> Tuple[str, ...]:
        if axis is None:
            return ()
        return axis if isinstance(axis, tuple) else (axis,)

    def spec(
        self, logical: Sequence[Optional[str]], shape: Optional[Sequence[int]] = None
    ) -> P:
        n = len(logical)
        out: list = [None] * n
        used: set = set()

        def fits(axis: Axis, dim: Optional[int]) -> bool:
            if axis is None:
                return False
            parts = self._axis_names(axis)
            if any(a in used for a in parts):
                return False
            if dim is not None and dim % self.axis_size(axis) != 0:
                return False
            return True

        # model-parallel "structure" dims claim their axis before generic
        # activation dims (a 36-head tensor must not lose 'model' to the
        # sequence dim just because seq comes first in the shape)
        priority = {"heads": 0, "kv_heads": 0, "cache_kv_heads": 0, "ffn": 0,
                    "vocab": 0, "expert": 0, "ssm_heads": 0, "conv_dim": 0,
                    "batch": 1, "expert_capacity": 1}
        order = sorted(range(n), key=lambda i: priority.get(logical[i] or "", 2))
        for i in order:
            name = logical[i]
            axis = self.rules.get(name) if name else None
            dim = shape[i] if shape is not None else None
            if fits(axis, dim):
                out[i] = axis
                used.update(self._axis_names(axis))
        for i, name in enumerate(logical):
            if out[i] is not None or not name:
                continue
            axis = self.fallbacks.get(name)
            dim = shape[i] if shape is not None else None
            if fits(axis, dim):
                out[i] = axis
                used.update(self._axis_names(axis))
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def sharding(self, logical: Sequence[Optional[str]], shape=None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical, shape))


_state = threading.local()


def set_rules(rules: Optional[MeshRules]) -> None:
    _state.rules = rules


def current_rules() -> Optional[MeshRules]:
    return getattr(_state, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Optional[MeshRules]):
    prev = current_rules()
    set_rules(rules)
    try:
        yield rules
    finally:
        set_rules(prev)


def logical_to_spec(logical: Sequence[Optional[str]], shape=None) -> P:
    rules = current_rules()
    if rules is None:
        return P()
    return rules.spec(logical, shape)


def shard_act(x: jax.Array, logical: Sequence[Optional[str]]) -> jax.Array:
    """Annotate an activation with logical axes (no-op without rules)."""
    rules = current_rules()
    if rules is None:
        return x
    spec = rules.spec(logical, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, spec))
