"""Compile the DMA gather kernels for a described TPU v5e, ahead of time.

Mosaic refuses at compile time what interpret mode never checks: lane
offsets off the 128-lane tile, DMA row offsets it cannot prove aligned,
one-word DMAs below an HBM array's tiling, more VMEM than a kernel may use.
These tests compile both decode-path kernels at the served models' real
widths (TinyLlama-1.1B, InternVL2-76B, whose gate/up split into column
tiles, and the benchmark cells' Granite-3.0-2B and StarCoder2-7B at their
batch of 16), bf16 and int8 with scales, plus the checksum lane, and
Kimi-VL-A3B's expert gather over its whole stacked experts and its latent
projection — a chip is described, not attached, so nothing runs. Decode
attention is compiled at the cells' cache shapes too, to hold it to the
grouped-query form: no float32 copy of the cache repeated to every head.

The topology is described inside module fixtures, never at import: only
one process may hold the TPU library, and pytest-xdist workers all import
this file.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (
    chunk_gather_expert_down_dma,
    chunk_gather_expert_up_dma,
    chunk_gather_matmul_dma,
    chunk_gather_mlp_dma,
)
from repro.models.attention import decode_attention

# (name, d_model, d_ff, batch) at published widths: the serve CLI's
# default 2 slots, the benchmark cells' 16
WIDTHS = (("tinyllama", 2048, 5632, 2), ("internvl2", 8192, 28672, 2),
          ("granite", 2048, 8192, 16), ("starcoder2", 4608, 18432, 16))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's executables cannot be read back from the persistent
    # cache without one attached; keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _lanes(sharding, sizes, dtype):
    return tuple(_spec(sharding, (m // 8,), dtype) for m in sizes)


def _assert_kernel(compiled):
    assert compiled.as_text().count("tpu_custom_call") >= 1


CASES = [(w, wbits, False) for w in WIDTHS[:2] for wbits in (16, 8)]
CASES.append((WIDTHS[1], 8, True))  # InternVL2 int8 with the checksum lane
CASES += [(w, 16, False) for w in WIDTHS[2:]]  # the cells serve bf16


def _case_id(case):
    (name, *_), wbits, ck = case
    return f"{name}-w{wbits}" + ("-checksums" if ck else "")


def _compile_matmul(sharding, case, rows, cols):
    (_, n, f, batch), wbits, checksummed = case
    r, c = ({"d": n, "f": f}[a] for a in (rows, cols))
    wdt = jnp.bfloat16 if wbits == 16 else jnp.int8
    k = max(n, f) // 8  # the serve plan's table width (n_max / block_rows)
    args = (
        _spec(sharding, (r, c), wdt),
        _spec(sharding, (batch, r), jnp.float32),
        _spec(sharding, (k,), jnp.int32),
        _spec(sharding, (k,), jnp.int32),
        _lanes(sharding, (r,), jnp.float32)[0] if wbits == 8 else None,
        _lanes(sharding, (r,), jnp.uint32)[0] if checksummed else None,
    )
    _assert_kernel(jax.jit(chunk_gather_matmul_dma).lower(*args).compile())


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_matmul_dma_compiles_for_v5e(one_chip, case):
    _compile_matmul(one_chip, case, "d", "d")  # attn_out wo at d_model width


@pytest.mark.parametrize("rows,cols", [("d", "f"), ("f", "d")], ids=["fc", "proj"])
@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_gelu_mlp_matmul_dma_compiles_for_v5e(one_chip, case, rows, cols):
    """The non-gated MLP's fc and proj, each one projection kernel."""
    _compile_matmul(one_chip, case, rows, cols)


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_mlp_dma_compiles_for_v5e(one_chip, case):
    (_, n, f, batch), wbits, checksummed = case
    wdt = jnp.bfloat16 if wbits == 16 else jnp.int8
    k = max(n, f) // 8
    args = (
        _spec(one_chip, (n, f), wdt),
        _spec(one_chip, (n, f), wdt),
        _spec(one_chip, (f, n), wdt),
        _spec(one_chip, (batch, n), jnp.float32),
        _spec(one_chip, (2, k), jnp.int32),
        _spec(one_chip, (2, k), jnp.int32),
        _spec(one_chip, (f,), jnp.float32),  # exact ffn mask
        _lanes(one_chip, (n, n, f), jnp.float32) if wbits == 8 else None,
        _lanes(one_chip, (n, n, f), jnp.uint32) if checksummed else None,
    )
    fn = jax.jit(lambda *a: chunk_gather_mlp_dma(*a, return_h=True))
    _assert_kernel(fn.lower(*args).compile())


# Kimi-VL-A3B at published widths: 26 expert layers of 8 held experts,
# 2048 wide, 1408 per expert, the benchmark cell's batch of 16
KIMI_EXPERTS = (26, 8, 2048, 1408, 16)


@pytest.mark.parametrize("phase", ["up", "down"])
def test_expert_gather_compiles_for_v5e_over_the_whole_stack(one_chip, phase):
    n_layers, e, d, f, batch = KIMI_EXPERTS
    i32 = jnp.int32
    if phase == "up":
        args = (_spec(one_chip, (n_layers, e, d, f), jnp.bfloat16),
                _spec(one_chip, (n_layers, e, d, f), jnp.bfloat16),
                _spec(one_chip, (), i32), _spec(one_chip, (e,), i32),
                _spec(one_chip, (), i32), _spec(one_chip, (batch, d), jnp.bfloat16))
        fn = jax.jit(chunk_gather_expert_up_dma)
    else:
        k = e * f // 8
        args = (_spec(one_chip, (n_layers, e, f, d), jnp.bfloat16),
                _spec(one_chip, (), i32), _spec(one_chip, (batch, e * f), jnp.float32),
                _spec(one_chip, (k,), i32), _spec(one_chip, (k,), i32))
        fn = jax.jit(chunk_gather_expert_down_dma)
    _assert_kernel(fn.lower(*args).compile())


@pytest.mark.parametrize("cols", [3072, 640], ids=["wq", "wkv_a_lane_padded"])
def test_latent_projections_compile_for_v5e(one_chip, cols):
    """``wq`` and ``wkv_a`` (576 wide, padded to 640 on the lane grid) off
    the hidden_attn table; the plan's tables are 11264 / 8 long."""
    k = 8 * 1408 // 8
    args = (_spec(one_chip, (2048, cols), jnp.bfloat16),
            _spec(one_chip, (16, 2048), jnp.float32),
            _spec(one_chip, (k,), jnp.int32), _spec(one_chip, (k,), jnp.int32))
    _assert_kernel(jax.jit(chunk_gather_matmul_dma).lower(*args).compile())


# (batch, cache slots, query heads, KV heads, head_dim): the chat cell's
# Granite-3.0-2B and the topk cell's StarCoder2-7B
DECODE_ATTENTION = {"granite-chat": (16, 1216, 32, 8, 64),
                    "starcoder2-topk": (16, 352, 36, 4, 128)}


@pytest.mark.parametrize("shape", DECODE_ATTENTION.values(), ids=DECODE_ATTENTION)
def test_decode_attention_reads_the_cache_in_place(one_chip, shape):
    """Each query-head group contracts against its stored KV head: the
    optimised program holds no float32 array as large as the cache repeated
    to every query head, inside a fusion or out of one."""
    b, phys, h, kv, hd = shape
    d = h * hd
    bf16 = jnp.bfloat16
    params = {"wq": _spec(one_chip, (d, d), bf16), "wo": _spec(one_chip, (d, d), bf16)}
    cache = _spec(one_chip, (b, phys, kv, hd), bf16)
    fn = jax.jit(lambda x, p, k, v, n: decode_attention(x, p, k, v, n, h, kv, hd,
                                                        10000.0, None))
    text = fn.lower(_spec(one_chip, (b, 1, d), bf16), params, cache, cache,
                    _spec(one_chip, (b,), jnp.int32)).compile().as_text()
    sizes = [math.prod(int(n) for n in dims.split(",") if n)
             for dims in re.findall(r"\bf32\[([\d,]*)\]", text)]
    assert sizes and max(sizes) < b * phys * h * hd, max(sizes)
