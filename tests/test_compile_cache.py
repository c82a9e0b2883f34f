"""The persistent-compilation-cache helper every entry point calls first
(repro/compile_cache.py): JAX's own env var wins; otherwise the cache goes
to the fixed in-checkout directory."""
from pathlib import Path

import jax
import pytest

from repro.compile_cache import CACHE_ENV, enable_compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_leaves_config_alone(monkeypatch, restore_cache_dir):
    monkeypatch.setenv(CACHE_ENV, "/some/dir")
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_checkout_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    path = enable_compile_cache()
    assert path == str(CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # fixed: a second call (another process, another day) lands in the same place
    assert enable_compile_cache() == path
