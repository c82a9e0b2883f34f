"""Where JAX keeps its persistent compilation cache.

Every entry point (``launch/serve.py``, ``launch/train.py``, the benchmark
CLIs, ``chip_smoke.py``) calls :func:`enable_compile_cache` before its first
compile, so processes of one checkout reuse each other's executables.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (gitignored): fixed, so every process finds it again
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself
    and nothing is set here. Otherwise the cache goes to the checkout's
    ``.jax_cache`` — never a temp, pid- or time-derived path, which no later
    process would look in."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
