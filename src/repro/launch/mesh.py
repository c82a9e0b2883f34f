"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before the
first jax initialization.

Production topology (assignment): TPU v5e, 256 chips/pod.
  single-pod: (data=16, model=16)                    = 256 devices
  multi-pod:  (pod=2, data=16, model=16)             = 512 devices
"""
from __future__ import annotations

from typing import Tuple

from jax.sharding import Mesh

from ..sharding import auto_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Arbitrary mesh (tests use small host-device meshes like (2,2))."""
    return auto_mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 2) -> Mesh:
    """Small mesh over host devices for CI-scale sharding tests."""
    return auto_mesh((n_data, n_model), ("data", "model"))
