"""Chunk-gather kernel microbench: fused multi-site DMA dispatch vs the
per-site kernel inventory.

Interpret-mode on CPU (this container and CI have no TPU), so wall numbers
measure the *schedule's Python emulation*, not MXU throughput — the rows
that matter for the perf trajectory are structural and deterministic:

  * ``kernel/dispatches_*`` — pallas_call dispatches one decode layer's
    refresh step costs per path. Per-site: one ``sparse_matmul`` per matrix
    that doesn't share a fetch (q, k, v, o, down) plus one
    ``sparse_swiglu`` (gate/up fused); fused: the MLP collapses to ONE
    ``chunk_gather_mlp_dma`` call (gate/up/down off the batched
    ``(n_sites, K)`` plan lanes, SwiGLU intermediate never leaves VMEM).
  * ``kernel/bytes_*`` — modeled HBM traffic of the two paths from the SAME
    batched chunk plan: weight bytes are identical by construction (the fused
    kernel fetches the same chunk tables); the saving is the SwiGLU
    intermediate h (B × d_ff f32) that the per-site path writes then re-reads
    between the swiglu and down dispatches.
  * parity assertions — the fused kernel and the per-site kernels reproduce
    the ``chunk_gather_mlp_ref`` oracle on the plan actually produced by
    ``SparseExecution``'s batched refresh (tables routed straight from the
    plan carry, no host re-splitting).
  * ``kernel/quant_*`` — the quantized chunk format (PR 6): int8 payloads
    + per-block scale lanes fetched through the same DMA slot rotation and
    dequantized in VMEM, parity-checked against the dequantized-weights
    oracle, plus the same chunk plan's modeled row bytes priced at
    wbits=16 vs wbits=8 per site (ratio asserted under the serve smoke's
    ceiling).
  * ``kernel/decode_backend_*`` — end-to-end serve-engine decode through
    ``backend='kernel'`` vs ``backend='reference'``: byte-identical tokens
    asserted, wall tokens/s recorded for both.

Standalone:  PYTHONPATH=src python -m benchmarks.kernel_gather
CI artifact: PYTHONPATH=src python -m benchmarks.kernel_gather \
                 --smoke --out BENCH_kernel.json
(uploaded as the ``BENCH_kernel`` perf-trajectory artifact next to
``BENCH_serve.json`` by .github/workflows/ci.yml)
"""
from __future__ import annotations

import argparse
import json
import time

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.kernels import (
    chunk_gather_matmul_dma,
    chunk_gather_matmul_ref,
    chunk_gather_mlp_dma,
    chunk_gather_mlp_ref,
    dequantize_rows,
    masks_to_block_tables,
    quantize_rows,
    sparse_matmul_dma,
    sparse_mlp_fused,
    sparse_swiglu,
)
from repro.serving import SparseExecution
from repro.serving.sparse_exec import KERNEL_BLOCK_ROWS, KERNEL_MAX_CHUNK_ROWS

from .common import Rows, decode_backend_pair, llm_importance

ARCH = "internvl2-76b"
H_BYTES = 4  # the per-site path's SwiGLU intermediate round-trips as f32


def _layer_plan(sparse: SparseExecution, rng: np.random.Generator):
    """One layer's batched selection + kernel chunk tables, exactly the way
    a refresh step produces them: importance per site → ONE vmapped greedy →
    ONE vmapped mask→table conversion."""
    vs = np.zeros((sparse.batched.n_sites, sparse.batched.n_max), np.float32)
    for i, kind in enumerate(sparse.site_order):
        n = sparse.sites[kind].n
        vs[i, :n] = llm_importance(rng, n)
    masks, _ = sparse.batched.select(jnp.asarray(vs), sparse._budgets)
    kstarts, ksizes = masks_to_block_tables(
        masks, KERNEL_BLOCK_ROWS, KERNEL_MAX_CHUNK_ROWS
    )
    return masks, kstarts, ksizes


def _dispatch_and_bytes(sparse: SparseExecution, ksizes, batch: int):
    """(dispatches, modeled bytes) per layer refresh for both paths."""
    per_site_dispatch = 0
    weight_bytes = 0.0
    sizes = np.asarray(ksizes)
    for i, kind in enumerate(sparse.site_order):
        site = sparse.sites[kind]
        rows = float(sizes[i].sum())
        weight_bytes += rows * sparse.site_row_bytes(kind)
        if kind == "hidden_mlp":
            per_site_dispatch += 1  # gate/up already fuse (sparse_swiglu)
        else:
            per_site_dispatch += len(site.tables)  # one matmul per matrix
    d_ff = sparse.sites["ffn"].n if "ffn" in sparse.sites else 0
    h_round_trip = 2.0 * batch * d_ff * H_BYTES  # write + read between calls
    fused_dispatch = per_site_dispatch - 1  # swiglu + down matmul → one call
    return (
        per_site_dispatch,
        fused_dispatch,
        weight_bytes + h_round_trip,
        weight_bytes,
    )


def run(rows: Rows, smoke: bool = False) -> None:
    cfg = get_config(ARCH).reduced()
    rng = np.random.default_rng(7)
    sparse = SparseExecution(cfg, device="nano", sparsity=0.4, method="chunk")
    _masks, kstarts, ksizes = _layer_plan(sparse, rng)
    batch = 2

    per_site, fused, bytes_per_site, bytes_fused = _dispatch_and_bytes(
        sparse, ksizes, batch
    )
    assert fused < per_site, "fused path must issue fewer dispatches"
    assert bytes_fused < bytes_per_site, (
        "fused path must move fewer modeled bytes (no h round-trip)"
    )
    rows.add("kernel/dispatches_per_site", 0.0, f"count={per_site}")
    rows.add("kernel/dispatches_fused", 0.0,
             f"count={fused} saving={per_site - fused}")
    rows.add("kernel/bytes_per_site", 0.0, f"bytes={bytes_per_site:.0f}")
    rows.add("kernel/bytes_fused", 0.0,
             f"bytes={bytes_fused:.0f} "
             f"h_round_trip_saved={bytes_per_site - bytes_fused:.0f}")

    # -- interpret-mode execution: the fused kernel on the REAL plan lanes --
    order = list(sparse.site_order)
    ih, i_f = order.index("hidden_mlp"), order.index("ffn")
    n, f = sparse.sites["hidden_mlp"].n, sparse.sites["ffn"].n
    d = cfg.d_model
    wg = jnp.asarray(rng.normal(0, 0.05, (n, f)), jnp.float32)
    wu = jnp.asarray(rng.normal(0, 0.05, (n, f)), jnp.float32)
    wd = jnp.asarray(rng.normal(0, 0.05, (f, d)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (batch, n)), jnp.float32)
    lanes_s = jnp.stack([kstarts[ih], kstarts[i_f]])
    lanes_z = jnp.stack([ksizes[ih], ksizes[i_f]])

    depths = (1,) if smoke else (0, 1, 2)
    yref = chunk_gather_mlp_ref(wg, wu, wd, x, lanes_s, lanes_z)
    scale = float(jnp.max(jnp.abs(yref))) + 1.0
    for depth in depths:
        t0 = time.perf_counter()
        y = sparse_mlp_fused(wg, wu, wd, x, lanes_s, lanes_z,
                             prefetch_depth=depth)
        y.block_until_ready()
        wall = time.perf_counter() - t0
        err = float(jnp.max(jnp.abs(y - yref))) / scale
        assert err < 1e-5, f"fused kernel diverged from oracle at depth {depth}: {err}"
        rows.add(f"kernel/fused_mlp_depth{depth}", wall * 1e6,
                 f"rel_err={err:.2e} interpret=cpu")

    # per-site path on the same plan: swiglu + down matmul, against the
    # same oracle (the two paths must agree bit-for-policy)
    h = sparse_swiglu(wg, wu, x, lanes_s[0], lanes_z[0],
                      max_chunk_rows=KERNEL_MAX_CHUNK_ROWS)
    y_ps = sparse_matmul_dma(wd, h, lanes_s[1], lanes_z[1])
    err_ps = float(jnp.max(jnp.abs(y_ps - yref))) / scale
    assert err_ps < 1e-5, f"per-site path diverged from oracle: {err_ps}"
    rows.add("kernel/per_site_mlp_parity", 0.0, f"rel_err={err_ps:.2e}")

    if not smoke:
        # single-site DMA matmul parity across depths on the attn_out lane
        io_ = order.index("attn_out")
        n_o = sparse.sites["attn_out"].n
        w_o = jnp.asarray(rng.normal(0, 0.05, (n_o, d)), jnp.float32)
        x_o = jnp.asarray(rng.normal(0, 1, (batch, n_o)), jnp.float32)
        y0 = chunk_gather_matmul_ref(w_o, x_o, kstarts[io_], ksizes[io_])
        for depth in (0, 1, 2):
            y = sparse_matmul_dma(w_o, x_o, kstarts[io_], ksizes[io_],
                                  prefetch_depth=depth)
            err = float(jnp.max(jnp.abs(y - y0))) / (float(jnp.max(jnp.abs(y0))) + 1.0)
            assert err < 1e-5
            rows.add(f"kernel/matmul_dma_depth{depth}", 0.0, f"rel_err={err:.2e}")

    bench_quantized_gather(rows, sparse, kstarts, ksizes, rng, batch,
                           smoke=smoke)
    bench_decode_backends(rows, smoke=smoke)


def bench_quantized_gather(rows: Rows, sparse, kstarts, ksizes, rng,
                           batch: int, smoke: bool = False) -> None:
    """The quantized chunk format through the DMA gather kernels (PR 6):
    int8 payloads + per-block f32 scale lanes ride the same async-copy slot
    rotation and are dequantized in VMEM before the f32 accumulation.
    Parity is asserted against the dequantized-weights reference oracle at
    every swept prefetch depth for BOTH kernels (single-site matmul on the
    attn_out lane, fused MLP on the hidden_mlp/ffn lanes); the bytes sweep
    prices the SAME chunk plan at wbits=16 vs 8 via two SparseExecution
    instances and asserts the per-site ratio stays under the serve smoke's
    QUANTIZED_BYTES_RATIO_MAX ceiling."""
    from .serve_throughput import QUANTIZED_BYTES_RATIO_MAX

    order = list(sparse.site_order)
    d = sparse.cfg.d_model

    # -- single-site quantized matmul on the attn_out lane -------------------
    io_ = order.index("attn_out")
    n_o = sparse.sites["attn_out"].n
    w_o = jnp.asarray(rng.normal(0, 0.05, (n_o, d)), jnp.float32)
    x_o = jnp.asarray(rng.normal(0, 1, (batch, n_o)), jnp.float32)
    q_o, s_o = quantize_rows(w_o, KERNEL_BLOCK_ROWS)
    yref = chunk_gather_matmul_ref(
        dequantize_rows(q_o, s_o, KERNEL_BLOCK_ROWS), x_o,
        kstarts[io_], ksizes[io_],
    )
    scale = float(jnp.max(jnp.abs(yref))) + 1.0
    depths = (1,) if smoke else (0, 1, 2)
    for depth in depths:
        t0 = time.perf_counter()
        y = chunk_gather_matmul_dma(q_o, x_o, kstarts[io_], ksizes[io_], s_o,
                                    block_rows=KERNEL_BLOCK_ROWS,
                                    prefetch_depth=depth, interpret=True)
        y.block_until_ready()
        wall = time.perf_counter() - t0
        err = float(jnp.max(jnp.abs(y - yref))) / scale
        assert err < 1e-5, (
            f"quantized matmul diverged from dequantized oracle at depth "
            f"{depth}: {err}"
        )
        rows.add(f"kernel/quant_matmul_depth{depth}", wall * 1e6,
                 f"rel_err={err:.2e} interpret=cpu")

    # -- fused quantized MLP on the real hidden_mlp/ffn plan lanes -----------
    ih, i_f = order.index("hidden_mlp"), order.index("ffn")
    n, f = sparse.sites["hidden_mlp"].n, sparse.sites["ffn"].n
    wg = jnp.asarray(rng.normal(0, 0.05, (n, f)), jnp.float32)
    wu = jnp.asarray(rng.normal(0, 0.05, (n, f)), jnp.float32)
    wd = jnp.asarray(rng.normal(0, 0.05, (f, d)), jnp.float32)
    x = jnp.asarray(rng.normal(0, 1, (batch, n)), jnp.float32)
    qg, sg = quantize_rows(wg, KERNEL_BLOCK_ROWS)
    qu, su = quantize_rows(wu, KERNEL_BLOCK_ROWS)
    qd, sd = quantize_rows(wd, KERNEL_BLOCK_ROWS)
    lanes_s = jnp.stack([kstarts[ih], kstarts[i_f]])
    lanes_z = jnp.stack([ksizes[ih], ksizes[i_f]])
    yref_m = chunk_gather_mlp_ref(
        dequantize_rows(qg, sg, KERNEL_BLOCK_ROWS),
        dequantize_rows(qu, su, KERNEL_BLOCK_ROWS),
        dequantize_rows(qd, sd, KERNEL_BLOCK_ROWS),
        x, lanes_s, lanes_z,
    )
    scale_m = float(jnp.max(jnp.abs(yref_m))) + 1.0
    for depth in depths:
        t0 = time.perf_counter()
        y = chunk_gather_mlp_dma(qg, qu, qd, x, lanes_s, lanes_z,
                                 scales=(sg, su, sd),
                                 block_rows=KERNEL_BLOCK_ROWS,
                                 prefetch_depth=depth, interpret=True)
        y.block_until_ready()
        wall = time.perf_counter() - t0
        err = float(jnp.max(jnp.abs(y - yref_m))) / scale_m
        assert err < 1e-5, (
            f"quantized fused MLP diverged from dequantized oracle at depth "
            f"{depth}: {err}"
        )
        rows.add(f"kernel/quant_mlp_depth{depth}", wall * 1e6,
                 f"rel_err={err:.2e} interpret=cpu")

    # -- bytes sweep: the same chunk plan priced at 16 vs 8 bits -------------
    sparse8 = SparseExecution(sparse.cfg, device="nano", sparsity=0.4,
                              method="chunk", wbits=8)
    sizes = np.asarray(ksizes)
    total16 = total8 = 0.0
    for i, kind in enumerate(order):
        rows_sel = float(sizes[i].sum())
        b16 = rows_sel * sparse.site_row_bytes(kind)
        b8 = rows_sel * sparse8.site_row_bytes(kind)
        total16 += b16
        total8 += b8
        ratio = b8 / b16
        assert ratio <= QUANTIZED_BYTES_RATIO_MAX, (
            f"site {kind}: quantized row bytes ratio {ratio:.3f} exceeds "
            f"{QUANTIZED_BYTES_RATIO_MAX}"
        )
        rows.add(f"kernel/quant_bytes_{kind}", 0.0,
                 f"bytes_w16={b16:.0f} bytes_w8={b8:.0f} ratio={ratio:.3f}")
    rows.add("kernel/quant_bytes_total", 0.0,
             f"bytes_w16={total16:.0f} bytes_w8={total8:.0f} "
             f"ratio={total8 / total16:.3f} "
             f"ceiling={QUANTIZED_BYTES_RATIO_MAX}")


def bench_decode_backends(rows: Rows, smoke: bool = False) -> None:
    """End-to-end decode through the execution backends: the serve engine's
    fused scan with ``backend='kernel'`` (the DMA kernels consuming the
    decode plan inside the scan) vs ``backend='reference'`` (the pure-jnp
    schedule twin), byte-identical tokens asserted
    (``common.decode_backend_pair`` — the same helper the serve smoke
    pins), wall tokens/s for both recorded into BENCH_kernel.json.
    Interpret-mode kernels on CPU — the kernel row tracks emulation
    overhead, the parity bit is the invariant."""
    import jax

    from repro.configs.base import InputShape
    from repro.models import build_model
    from repro.models.inputs import make_dummy_batch

    cfg = get_config(ARCH).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = make_dummy_batch(cfg, InputShape("kb", 16, 2, "train"))
    n_tokens = 4 if smoke else 16
    results = decode_backend_pair(model, params, batch, max_seq=64,
                                  batch_size=2, n_tokens=n_tokens, seed=7)
    for backend, (_eng, _out, wall) in results.items():
        rows.add(f"kernel/decode_backend_{backend}",
                 wall / n_tokens * 1e6,
                 f"tokens_per_s={n_tokens * 2 / wall:.1f} "
                 "identical_tokens=True")


def _emit_json(rows: Rows, path: str, smoke: bool) -> None:
    payload = {
        "bench": "kernel_gather",
        "arch": ARCH,
        "smoke": smoke,
        "rows": [
            {"name": name, "us_per_call": us, "derived": derived}
            for name, us, derived in rows.rows
        ],
    }
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")


def build_parser() -> argparse.ArgumentParser:
    """Exposed for tests/test_docs.py's docs-vs-CLI drift check."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI mode: one depth, still asserts parity")
    ap.add_argument("--out", default=None,
                    help="also write rows as JSON (e.g. BENCH_kernel.json)")
    return ap


if __name__ == "__main__":
    enable_compile_cache()
    args = build_parser().parse_args()
    rows = Rows()
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    run(rows, smoke=args.smoke)
    rows.emit()
    print(f"# total {time.perf_counter() - t0:.1f}s")
    if args.out:
        _emit_json(rows, args.out, args.smoke)
