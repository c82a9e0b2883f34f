"""Serving throughput: fused-scan decode vs the legacy per-token loop, the
overlapped I/O–compute pipeline vs the serial charge, the chunk-plan reuse
knob, the residency-cache budget sweep, and continuous-batching request
latency per policy.

Eight sections (reduced InternVL2 under the flash simulator):

  * serve/fused_vs_loop — equal batch, equal policy: wall tokens/s of the
    one-jit ``lax.scan`` decode vs the seed's one-jit-call-per-token loop,
    asserting byte-identical greedy tokens (the acceptance criterion);
  * serve/backend_* — the kernel-backed decode execution path
    (``--backend kernel``: the Pallas DMA gather kernels consume the decode
    plan's chunk tables inside the scan) vs the reference schedule twin,
    asserting byte-identical greedy tokens across backends at wbits 16 AND
    8 (in-kernel dequantization vs the twin's identical per-block multiply)
    and emitting both wall tokens/s (interpret-mode kernels on CPU CI);
  * serve/sharded_<d>x<m>_* — multi-chip sharded serving (``--mesh``, a
    (data, model) host-device mesh simulated via
    XLA_FLAGS=--xla_force_host_platform_device_count=8): per mesh shape
    and wbits 16/8, asserts greedy tokens byte-identical to the 1×1
    engine, total modeled I/O bytes equal, and the per-shard byte lanes
    summing to the unsharded total; emits wall tokens/s per shape (rows
    degrade to an explicit skipped marker below data×model devices);
  * serve/quantized_* — int8 chunk storage (``--wbits 8``) vs fp16 on BOTH
    the nano and agx profiles at equal settings (deterministic sim):
    asserts total modeled I/O bytes at 8 bits strictly below fp16 and the
    ratio at or under QUANTIZED_BYTES_RATIO_MAX (payload halves;
    per-block scales add 4/8 bytes per row) — the PR-6 byte-trajectory
    floor CI gates on;
  * serve/overlap_<device> — the two-stage prefetch pipeline on BOTH the
    nano and agx profiles, swept over prefetch depth: asserts overlapped
    per-step decode latency strictly below the serial charge for
    method=chunk, byte-identical tokens across --overlap/--no-overlap AND
    prefetch_depth 0/1/2, efficiency(depth 2) ≥ efficiency(depth 1) ≥ the
    floor, and that the chunk-vs-topk latency advantage survives in both
    charging modes; emits serial and overlapped simulated tokens/s +
    overlap_efficiency per depth;
  * serve/admission_* — bubble-aware scheduler admission: a request backlog
    admitted against banked decode-stall credit vs the admission-at-cost
    baseline; asserts the feature fires (admitted_during_stall ≥ 1,
    positive bubble utilization — the smoke floor) and never slows the
    simulated clock;
  * serve/paged_kv_* — the paged KV cache (``--kv-page-tokens``): slot-mode
    greedy tokens byte-identical to the dense per-slot cache across
    backend × wbits and on the 2×2 mesh, strictly more concurrent
    shared-prefix streams than the dense slot cap at equal KV memory, and
    the shared-prefix resident-byte reduction at or above
    PAGED_KV_SHARING_FLOOR — the PR-10 acceptance rows CI gates on;
  * serve/plan_reuse — I/O per token as ``plan_refresh_interval`` grows
    (selection reruns every k steps, resident chunks are free in between);
  * serve/cache_sweep — steady-state decode I/O vs DRAM residency budget
    (``cache_mb``) for chunk AND topk at fixed sparsity: the serve-stack
    reproduction of the paper's §5 claim — more cache → strictly less
    flash I/O, and the chunk-vs-topk advantage persists (indeed grows) at
    every swept budget because the remaining misses are more scattered;
  * serve/batch_<method> — chunk vs topk vs dense vs dense_free under
    concurrent Poisson-arriving streams: simulated tokens/s and p50/p95
    request latency from the continuous-batching scheduler;
  * serve/fault_* — storage-fault robustness (docs/robustness.md):
    fault-off byte-identity (tokens + io_summary), then sustained thermal
    throttle with per-request deadlines, DegradationController off vs on —
    asserts controller-on attainment strictly higher, p99 strictly lower,
    the degraded baseline preempting a deadline-blown request, and the
    degraded tokens/s above FAULT_DEGRADED_TPS_FLOOR; fully deterministic
    under the fixed fault seed.

Standalone:  PYTHONPATH=src python -m benchmarks.serve_throughput
CI artifact: PYTHONPATH=src python -m benchmarks.serve_throughput \
                 --smoke --out BENCH_serve.json
(--smoke runs the first four sections shrunk to a minute or two on CPU —
continuous batching is covered by tier-1 tests — and skips the
wall-clock speedup assertion, which is noise-prone on shared CI runners;
the byte-identity, I/O-ordering and overlap assertions always run, and the
smoke FAILS if overlap_efficiency drops below OVERLAP_EFFICIENCY_FLOOR —
the perf-trajectory guard for the prefetch pipeline.)
"""
from __future__ import annotations

import os

# Must land before jax initializes: the sharded-mesh section needs >= 4
# devices, simulated as host CPU devices on CI runners and laptops alike.
# setdefault keeps a caller's own XLA_FLAGS intact, and when the module is
# imported after jax already initialized (e.g. from a test) the section
# simply skips below 4 devices.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.configs.base import InputShape
from repro.models import build_model
from repro.models.inputs import make_dummy_batch
from repro.serving import (
    PoissonArrivalDriver,
    Request,
    Scheduler,
    ServeEngine,
    SparseExecution,
)
from repro.sharding.serve import ServeMesh

from .common import Rows, decode_backend_pair

ARCH = "internvl2-76b"
BATCH = 2
DECODE_TOKENS = 32
PROMPT_LEN = 32
MAX_SEQ = 128
# conservative floor for the prefetch pipeline's overlap efficiency (the
# fraction of hideable time actually hidden; ~0.92+ at current settings) —
# the CI smoke fails below it to guard the perf trajectory
OVERLAP_EFFICIENCY_FLOOR = 0.5
# ceiling for int8-vs-fp16 total modeled I/O bytes at matched settings
# (~0.49 at current geometry: payload exactly halves, the per-block scale
# lane adds 4 bytes per 8 rows) — the CI smoke fails above it so quantized
# storage can never silently stop paying for itself
QUANTIZED_BYTES_RATIO_MAX = 0.55
# the fault-robustness scenario (sustained thermal throttle + deadlines):
# per-request SLO and arrival spacing picked so the throttled baseline
# blows deadlines (and preempts) while the degradation controller keeps
# the same workload inside SLO; the tokens/s floor is ~half the current
# controller-on throughput so the CI smoke fails if adaptive degradation
# regresses badly
FAULT_DEADLINE_S = 0.03
FAULT_ARRIVAL_GAP_S = 0.002
FAULT_DEGRADED_TPS_FLOOR = 200.0
# floor for the shared-prefix KV-byte reduction (resident pages, 4 streams
# sharing a 4-page prefix vs 4 unique same-length prompts: 20/8 = 2.5x at
# current geometry) — the CI smoke fails below 2x, the PR-10 acceptance
# criterion for prefix sharing
PAGED_KV_SHARING_FLOOR = 2.0


def _setup():
    cfg = get_config(ARCH).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = make_dummy_batch(cfg, InputShape("bench", PROMPT_LEN, BATCH, "train"))
    return cfg, model, params, batch


def _engine(model, params, method="chunk", refresh=1, seed=5, cache_mb=0.0,
            device="nano", overlap=True, prefetch_depth=1, backend="reference",
            wbits=16, mesh=None):
    return ServeEngine(model, params, max_seq=MAX_SEQ, batch_size=BATCH,
                       device=device, sparsity=0.4, method=method, seed=seed,
                       plan_refresh_interval=refresh, cache_mb=cache_mb,
                       overlap=overlap, prefetch_depth=prefetch_depth,
                       backend=backend, wbits=wbits, mesh=mesh)


def _timed_decode(eng, decode_fn, tok0, n, repeats=3):
    """Median wall seconds; the first run's tokens are returned for the
    identity check (later repeats mutate the cache, which doesn't change
    the per-step cost being measured)."""
    out = None
    walls = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        o = decode_fn(tok0, n)
        jax.block_until_ready(o)
        walls.append(time.perf_counter() - t0)
        out = o if out is None else out
    return out, float(np.median(walls))


def bench_fused_vs_loop(rows: Rows, model, params, batch,
                        decode_tokens=DECODE_TOKENS, repeats=3,
                        assert_speedup=True) -> None:
    eng_f = _engine(model, params)
    eng_l = _engine(model, params)
    tok0 = jnp.argmax(eng_f.prefill(batch), -1)[:, None].astype(jnp.int32)
    eng_l.prefill(batch)
    # warm up both compiled paths, then measure from identical cache state
    eng_f.decode(tok0, decode_tokens)
    eng_l.decode_per_token(tok0, decode_tokens)
    eng_f.prefill(batch)
    eng_l.prefill(batch)
    out_f, wall_f = _timed_decode(eng_f, eng_f.decode, tok0, decode_tokens,
                                  repeats=repeats)
    eng_l.prefill(batch)
    out_l, wall_l = _timed_decode(eng_l, eng_l.decode_per_token, tok0,
                                  decode_tokens, repeats=repeats)
    identical = bool(jnp.all(out_f == out_l))
    tps_f = decode_tokens * BATCH / wall_f
    tps_l = decode_tokens * BATCH / wall_l
    assert identical, "fused scan and per-token loop diverged"
    if assert_speedup:
        assert tps_f > tps_l, (
            f"fused decode must beat the per-token loop: {tps_f:.1f} vs {tps_l:.1f} tok/s"
        )
    rows.add("serve/fused_scan", wall_f / decode_tokens * 1e6,
             f"tokens_per_s={tps_f:.1f}")
    rows.add("serve/per_token_loop", wall_l / decode_tokens * 1e6,
             f"tokens_per_s={tps_l:.1f}")
    rows.add("serve/fused_vs_loop", 0.0,
             f"speedup={tps_f / tps_l:.2f}x identical_tokens={identical}")


def bench_backend_parity(rows: Rows, model, params, batch,
                         decode_tokens=DECODE_TOKENS, repeats=1) -> None:
    """The kernel-backed decode execution path vs the reference backend:
    equal settings, byte-identical greedy tokens (the PR-5 acceptance
    invariant — the backend switch changes how the masked arithmetic is
    realized, never which neurons participate), wall tokens/s for both —
    at wbits=16 AND wbits=8 (PR 6: the kernels dequantize int8 chunk
    payloads in VMEM; the reference twin performs the elementwise-identical
    per-block multiply, so the parity invariant extends to the quantized
    path unchanged). The kernel backend runs the Pallas DMA gather kernels
    in interpret mode here (CPU CI), so its wall number measures the
    schedule's emulation, not MXU throughput — the rows that matter for
    the perf trajectory are the parity bits plus the reference-backend
    tokens/s."""
    for wbits in (16, 8):
        results = decode_backend_pair(model, params, batch, max_seq=MAX_SEQ,
                                      batch_size=BATCH, n_tokens=decode_tokens,
                                      seed=5, repeats=repeats, wbits=wbits)
        suffix = "" if wbits == 16 else "_w8"
        for backend, (_eng, _out, wall) in results.items():
            tps = decode_tokens * BATCH / wall
            rows.add(f"serve/backend_{backend}{suffix}",
                     wall / decode_tokens * 1e6,
                     f"tokens_per_s={tps:.1f} identical_tokens=True "
                     f"wbits={wbits}")


def bench_sharded_mesh(rows: Rows, model, params, batch,
                       decode_tokens=DECODE_TOKENS,
                       shapes=((2, 2),)) -> None:
    """Multi-chip sharded serving vs the single-device engine: per mesh
    shape (data, model) and wbits 16/8, prefill + fused-scan decode at
    equal settings, asserting (1) byte-identical greedy tokens — the
    sharded-serving acceptance invariant: storage and I/O shard over the
    model axis but every fold's operands are gathered and summed in
    single-device block order (kernels/backend.py), so the mesh can never
    change a token; (2) equal total modeled I/O bytes; (3) the per-shard
    byte lanes (``shard_summary``) summing to that total. Emits wall
    tokens/s per shape. Below data×model devices (the CI smoke sets
    XLA_FLAGS=--xla_force_host_platform_device_count=8 before jax init,
    as does this module when imported first) the shape degrades to an
    explicit skipped row rather than silently vanishing."""
    ndev = len(jax.devices())
    for wbits in (16, 8):
        suffix = f"w{wbits}"
        eng0 = _engine(model, params, refresh=2, cache_mb=2.0, wbits=wbits)
        tok0 = jnp.argmax(eng0.prefill(batch), -1)[:, None].astype(jnp.int32)
        out0, _ = _timed_decode(eng0, eng0.decode, tok0, decode_tokens,
                                repeats=1)
        bytes0 = eng0.io_summary()["io_bytes"]
        for d, m in shapes:
            name = f"serve/sharded_{d}x{m}_{suffix}"
            if ndev < d * m:
                rows.add(name, 0.0,
                         f"skipped=True devices={ndev} needed={d * m}")
                continue
            eng = _engine(model, params, refresh=2, cache_mb=2.0,
                          wbits=wbits, mesh=ServeMesh.create(d, m))
            tok = jnp.argmax(eng.prefill(batch), -1)[:, None].astype(jnp.int32)
            out, wall = _timed_decode(eng, eng.decode, tok, decode_tokens,
                                      repeats=1)
            identical = bool(jnp.array_equal(out0, out))
            assert identical, (
                f"{name}: sharded greedy tokens diverged from the 1x1 mesh "
                f"at wbits={wbits} — the operand-gather constraint in "
                "kernels/backend.py is the byte-identity mechanism"
            )
            total = eng.io_summary()["io_bytes"]
            assert abs(total - bytes0) <= 1e-6 * max(bytes0, 1.0), (
                f"{name}: sharded total I/O bytes {total} != unsharded "
                f"{bytes0} — per-shard accounting must repartition, never "
                "rescale, the modeled traffic"
            )
            ss = eng.shard_summary()
            per = ss["io_bytes_per_shard"]
            assert abs(sum(per) - total) <= 1e-6 * max(total, 1.0), (
                f"{name}: per-shard byte lanes {per} do not sum to the "
                f"engine total {total}"
            )
            tps = decode_tokens * BATCH / wall
            rows.add(name, wall / decode_tokens * 1e6,
                     f"tokens_per_s={tps:.1f} identical_tokens={identical} "
                     f"io_bytes_eq=True shards={ss['n_shards']} "
                     f"wbits={wbits}")


def bench_overlap_pipeline(rows: Rows, model, params, batch,
                           devices=("nano", "agx"),
                           decode_tokens=DECODE_TOKENS,
                           depth_engines=True) -> None:
    """The overlapped I/O–compute prefetch pipeline vs the serial charge,
    swept over prefetch depth.

    Per device profile: (1) --overlap / --no-overlap chunk engines AND
    engines at prefetch_depth 0/1/2 at identical settings must all emit
    byte-identical tokens (the pipeline only re-times the same masks);
    (2) the overlapped per-step decode latency must be STRICTLY below the
    serial Σio+Σcompute charge (deterministic sim); (3) a deeper pipeline
    never hides less: efficiency(depth=2) ≥ efficiency(depth=1) ≥ the
    OVERLAP_EFFICIENCY_FLOOR; (4) the chunk-vs-topk latency advantage must
    survive under BOTH charging modes. Emits serial/overlapped simulated
    tokens/s per depth.

    ``depth_engines=False`` (the smoke mode) gets the depth sweep from
    ``ServeEngine.reprice_timeline`` — the pipeline is a host-side timeline
    over recorded per-layer I/O, so repricing the depth-1 engine's decode at
    other depths yields exactly what identically-seeded engines would charge
    — skipping two full engine compiles on CI; the engine-level byte
    identity across real depth-0/1/2 engines stays pinned by the full run
    and by tests/test_dma_kernels.py."""
    for device in devices:
        eng_o = _engine(model, params, device=device, overlap=True)
        eng_2 = (
            _engine(model, params, device=device, overlap=True, prefetch_depth=2)
            if depth_engines else None
        )
        eng_0 = (
            _engine(model, params, device=device, overlap=True, prefetch_depth=0)
            if depth_engines else None
        )
        eng_s = _engine(model, params, device=device, overlap=False)
        eng_t = _engine(model, params, device=device, method="topk")
        identity_engines = [e for e in (eng_o, eng_2, eng_0, eng_s) if e is not None]
        for eng in identity_engines + [eng_t]:
            eng.simulator.noise = 0.0  # deterministic for the assertions
        tok0 = jnp.argmax(eng_o.prefill(batch), -1)[:, None].astype(jnp.int32)
        for eng in identity_engines[1:] + [eng_t]:
            eng.prefill(batch)
        outs = [eng.decode(tok0, decode_tokens) for eng in identity_engines]
        for out in outs[1:]:
            assert bool(jnp.all(outs[0] == out)), (
                f"[{device}] tokens must be byte-identical across "
                "--overlap modes and prefetch depths 0/1/2"
            )
        eng_t.decode(tok0, decode_tokens)

        so = eng_o.io_summary()
        st = eng_t.io_summary()
        if eng_2 is not None:
            s2 = eng_2.io_summary()
            overlap2, eff2 = s2["decode_overlap_s"], s2["overlap_efficiency"]
        else:
            tl2 = eng_o.reprice_timeline(2)
            overlap2, eff2 = tl2.overlap_total_s, tl2.overlap_efficiency()
        serial, overlapped = so["decode_serial_s"], so["decode_overlap_s"]
        assert overlapped < serial, (
            f"[{device}] overlapped decode must be strictly below serial: "
            f"{overlapped:.3e} vs {serial:.3e}"
        )
        # per-step too, not just in aggregate
        steps = [s for s in eng_o.stats if s.kind == "decode"]
        assert all(s.overlap_s <= s.serial_s + 1e-15 for s in steps)
        # depth 0 degenerates to the serial schedule exactly; a deeper
        # pipeline is monotone: depth 2 hides at least as much as depth 1
        if eng_0 is not None:
            s0 = eng_0.io_summary()
            assert abs(s0["decode_overlap_s"] - s0["decode_serial_s"]) < 1e-12
        assert overlap2 <= overlapped + 1e-15, (
            f"[{device}] depth-2 pipeline must not be slower than depth-1"
        )
        # the chunk-vs-topk advantage survives both charging modes
        assert st["decode_overlap_s"] > overlapped, (
            f"[{device}] chunk must beat topk under the overlapped charge"
        )
        assert st["decode_serial_s"] > serial, (
            f"[{device}] chunk must beat topk under the serial charge"
        )
        eff = so["overlap_efficiency"]
        assert eff2 >= eff >= OVERLAP_EFFICIENCY_FLOOR, (
            f"[{device}] overlap_efficiency must satisfy depth2 {eff2:.3f} "
            f">= depth1 {eff:.3f} >= {OVERLAP_EFFICIENCY_FLOOR}"
        )
        n_tok = decode_tokens * BATCH
        rows.add(f"serve/overlap_{device}",
                 overlapped / decode_tokens * 1e6,
                 f"sim_tokens_per_s={n_tok / overlapped:.1f} "
                 f"overlap_efficiency={eff:.3f} "
                 f"stall_ms={so['decode_stall_s']*1e3:.2f}")
        rows.add(f"serve/overlap_depth2_{device}",
                 overlap2 / decode_tokens * 1e6,
                 f"sim_tokens_per_s={n_tok / overlap2:.1f} "
                 f"overlap_efficiency={eff2:.3f}")
        rows.add(f"serve/serial_{device}",
                 serial / decode_tokens * 1e6,
                 f"sim_tokens_per_s={n_tok / serial:.1f} "
                 f"speedup={serial / overlapped:.3f}x")


def bench_quantized_io(rows: Rows, model, params, batch,
                       devices=("nano", "agx"),
                       decode_tokens=DECODE_TOKENS) -> None:
    """int8 chunk storage vs fp16 on both device profiles (PR 6): identical
    settings and seed, deterministic sim, the same quality proxy (selection
    budget = (1-sparsity)·N rows per site either way) — total modeled I/O
    bytes at wbits=8 must come in strictly below fp16 AND at or under the
    QUANTIZED_BYTES_RATIO_MAX ceiling (the payload halves; the per-block
    scale lane costs 4 bytes per 8 rows). Emits per-width bytes plus the
    ratio row the CI artifact tracks."""
    for device in devices:
        total_bytes = {}
        for wbits in (16, 8):
            eng = _engine(model, params, device=device, wbits=wbits)
            eng.simulator.noise = 0.0  # deterministic sim for the assertions
            tok0 = jnp.argmax(eng.prefill(batch), -1)[:, None].astype(jnp.int32)
            eng.decode(tok0, decode_tokens)
            s = eng.io_summary()
            total_bytes[wbits] = float(s["io_bytes"])
            rows.add(f"serve/quantized_w{wbits}_{device}",
                     s["io_sim_s"] / decode_tokens * 1e6,
                     f"io_bytes={s['io_bytes']:.0f} wbits={wbits}")
        ratio = total_bytes[8] / total_bytes[16]
        assert total_bytes[8] < total_bytes[16], (
            f"[{device}] wbits=8 total I/O bytes must be strictly below "
            f"fp16: {total_bytes[8]:.0f} vs {total_bytes[16]:.0f}"
        )
        assert ratio <= QUANTIZED_BYTES_RATIO_MAX, (
            f"[{device}] quantized_bytes_ratio {ratio:.3f} exceeds the "
            f"{QUANTIZED_BYTES_RATIO_MAX} ceiling — int8 chunk storage "
            "stopped paying for itself"
        )
        rows.add(f"serve/quantized_bytes_ratio_{device}", 0.0,
                 f"ratio={ratio:.3f} ceiling={QUANTIZED_BYTES_RATIO_MAX}")


def bench_plan_reuse(rows: Rows, model, params, batch,
                     intervals=(1, 2, 4, 8), decode_tokens=DECODE_TOKENS) -> None:
    for k in intervals:
        eng = _engine(model, params, refresh=k)
        tok0 = jnp.argmax(eng.prefill(batch), -1)[:, None].astype(jnp.int32)
        eng.decode(tok0, decode_tokens)
        steps = [s for s in eng.stats if s.kind == "decode"]
        io_tok = float(np.mean([s.io_est_s for s in steps]))
        refreshes = sum(1 for s in steps if s.io_est_s > 0)
        rows.add(f"serve/plan_reuse_k{k}", io_tok * 1e6,
                 f"refresh_steps={refreshes}/{decode_tokens}")


def bench_cache_sweep(rows: Rows, model, params, batch, cfg,
                      fractions=(0.0, 0.15, 0.35, 0.7),
                      decode_tokens=DECODE_TOKENS) -> None:
    """§5 end-to-end: sweep the residency-cache byte budget at fixed 0.4
    sparsity and record steady-state decode I/O + hit rate for chunk and
    topk. Asserts the acceptance criteria: chunk I/O is monotone
    non-increasing in budget (strictly below cache-0 whenever the budget is
    > 0) and the chunk-vs-topk advantage persists at every point."""
    sizing = SparseExecution(cfg, device="nano", sparsity=0.4)  # sizes the sweep
    total_mb = sizing.sparsifiable_bytes(cfg.n_layers) / (1024.0 * 1024.0)
    budgets = [round(f * total_mb, 3) for f in fractions]
    steady = {}
    for method in ("chunk", "topk"):
        for mb in budgets:
            eng = _engine(model, params, method=method, refresh=2, cache_mb=mb)
            eng.simulator.noise = 0.0  # deterministic sim for the assertions
            tok0 = jnp.argmax(eng.prefill(batch), -1)[:, None].astype(jnp.int32)
            eng.decode(tok0, decode_tokens)
            steps = [s for s in eng.stats if s.kind == "decode"]
            # steady state: drop the warm-up half where the tier is filling
            tail = steps[len(steps) // 2:]
            io_tok = float(np.mean([s.io_sim_s for s in tail]))
            hit = sum(s.hit_rows for s in tail)
            miss = sum(s.miss_rows for s in tail)
            rate = hit / (hit + miss) if (hit + miss) > 0 else 0.0
            steady[(method, mb)] = io_tok
            rows.add(f"serve/cache_sweep_{method}_mb{mb:g}", io_tok * 1e6,
                     f"hit_rate={rate:.3f} cache_frac_of_weights="
                     f"{mb / total_mb if total_mb else 0:.2f}")
    chunk_ios = [steady[("chunk", mb)] for mb in budgets]
    for prev, cur, mb in zip(chunk_ios, chunk_ios[1:], budgets[1:]):
        assert cur <= prev * (1 + 1e-9), (
            f"chunk I/O must be monotone non-increasing in cache budget; "
            f"rose to {cur:.3e} at {mb} MB"
        )
        assert cur < chunk_ios[0], (
            f"cache_mb={mb} > 0 must beat the cache-0 run strictly "
            f"({cur:.3e} vs {chunk_ios[0]:.3e})"
        )
    for mb in budgets:
        ratio = steady[("topk", mb)] / max(steady[("chunk", mb)], 1e-30)
        assert ratio > 1.0, (
            f"chunk-vs-topk I/O advantage must persist at cache_mb={mb} "
            f"(ratio {ratio:.2f})"
        )
        rows.add(f"serve/cache_topk_vs_chunk_mb{mb:g}", 0.0, f"ratio={ratio:.2f}x")


def bench_scheduler_admission(rows: Rows, cfg, model, params,
                              n_requests: int = 6, smoke: bool = False) -> None:
    """Bubble-aware scheduler admission: with more requests than slots, the
    backlog is admitted at round boundaries AFTER decode rounds have banked
    measured stall seconds — so their prefill charge rides the pipeline's
    I/O bubbles instead of extending the clock. Asserts (deterministic sim)
    that at least one admission was hidden and that realized bubble
    utilization is positive — the smoke-mode floor guarding the feature —
    and that the bubble-aware clock never exceeds the admission-at-cost
    baseline. Emits both schedulers' tokens/s plus the admission stats."""
    rng = np.random.default_rng(13)
    prompts = []
    for _ in range(n_requests):
        p = dict(make_dummy_batch(cfg, InputShape("req", PROMPT_LEN, 1, "train")))
        p["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, p["tokens"].shape), jnp.int32
        )
        prompts.append(p)

    results = {}
    # smoke keeps only the bubble-aware engine (the floor below is what CI
    # gates on); the full run also prices the admission-at-cost baseline
    for mode in ("bubble",) if smoke else ("bubble", "boundary"):
        eng = _engine(model, params, refresh=2)
        eng.simulator.noise = 0.0
        sched = Scheduler(eng, round_tokens=2,
                          admit_in_bubbles=(mode == "bubble"))
        # all requests arrive at t=0: slots fill, the rest wait through
        # decode rounds and are admitted against the banked stall credit
        sched.submit([
            Request(rid=i, prompt=prompts[i], max_new_tokens=4, arrival_s=0.0)
            for i in range(n_requests)
        ])
        st = sched.run()
        s = eng.io_summary()
        results[mode] = (st, s)
        rows.add(
            f"serve/admission_{mode}",
            st.latency_p50_s * 1e6,
            f"tokens_per_s={st.tokens_per_s:.1f} "
            f"admitted_during_stall={s['admitted_during_stall']} "
            f"bubble_utilization={s['bubble_utilization']:.3f} "
            f"stall_hidden_ms={s['stall_hidden_s']*1e3:.2f}",
        )

    st_b, s_b = results["bubble"]
    # the smoke-mode floor: the feature must demonstrably fire
    assert s_b["admitted_during_stall"] >= 1, (
        "bubble-aware admission never fired despite a request backlog"
    )
    assert s_b["bubble_utilization"] > 0.0
    if "boundary" in results:
        st_0, s_0 = results["boundary"]
        assert s_0["admitted_during_stall"] == 0  # baseline: no hiding
        assert st_b.sim_time_s <= st_0.sim_time_s + 1e-12, (
            "hiding admissions in stall bubbles must not slow the clock: "
            f"{st_b.sim_time_s:.4f} vs {st_0.sim_time_s:.4f}"
        )
        rows.add("serve/admission_speedup", 0.0,
                 f"sim_time_ratio="
                 f"{st_0.sim_time_s / max(st_b.sim_time_s, 1e-12):.3f}x "
                 f"finished={st_b.finished}/{n_requests}")


def bench_continuous_batching(rows: Rows, cfg, model, params,
                              n_requests: int = 8, rate_rps: float = 500.0) -> None:
    rng = np.random.default_rng(11)
    prompts = []
    for _ in range(n_requests):
        p = dict(make_dummy_batch(cfg, InputShape("req", PROMPT_LEN, 1, "train")))
        p["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, p["tokens"].shape), jnp.int32
        )
        prompts.append(p)

    # extra per-token host/dispatch constant on top of the engine's modeled
    # compute lane (the pipeline already gives dense_free a finite
    # compute-bound latency) — kept equal across policies
    compute_s = 1e-4
    for method in ("chunk", "topk", "dense", "dense_free"):
        eng = _engine(model, params, method=method, refresh=2)
        sched = Scheduler(eng, round_tokens=4, compute_s_per_token=compute_s)
        driver = PoissonArrivalDriver(
            rate_rps,
            lambda rid: Request(rid=rid, prompt=prompts[rid % n_requests],
                                max_new_tokens=8),
            seed=3,
        )
        sched.submit(driver.generate(n_requests))
        st = sched.run()
        rows.add(
            f"serve/batch_{method}",
            st.latency_p50_s * 1e6,
            f"tokens_per_s={st.tokens_per_s:.1f} "
            f"p95_ms={st.latency_p95_s*1e3:.2f} finished={st.finished}",
        )


def bench_fault_robustness(rows: Rows, cfg, model, params,
                           n_requests: int = 8) -> None:
    """Storage-fault robustness (ISSUE 8 acceptance rows, deterministic —
    fixed fault seed, simulator noise 0):

      * serve/fault_identity — fault machinery attached but disabled must
        be FREE: greedy tokens AND io_summary() byte-identical to an
        engine without it;
      * serve/fault_off|on — sustained thermal throttle + per-request
        deadlines, DegradationController off vs on: asserts attainment_on
        strictly above attainment_off, p99_on strictly below p99_off, the
        degraded baseline preempting >= 1 deadline-blown request, and the
        controller-on degraded tokens/s above FAULT_DEGRADED_TPS_FLOOR —
        the floor CI gates on so adaptive degradation can never silently
        stop paying for itself."""
    tok0 = jnp.ones((BATCH, 1), jnp.int32)
    base = _engine(model, params)
    t_base = base.decode(tok0, 6)
    eng_none = ServeEngine(model, params, max_seq=MAX_SEQ, batch_size=BATCH,
                           device="nano", sparsity=0.4, method="chunk",
                           seed=5, plan_refresh_interval=1, cache_mb=0.0,
                           fault_profile="none", fault_seed=123)
    t_none = eng_none.decode(tok0, 6)
    assert bool(jnp.all(t_base == t_none)), (
        "fault-off engine changed greedy tokens — injection must be free "
        "when disabled"
    )
    s_base, s_none = base.io_summary(), eng_none.io_summary()
    assert s_base == s_none, (
        f"fault-off engine perturbed io_summary: "
        f"{ {k: (s_base[k], s_none[k]) for k in s_base if s_base[k] != s_none[k]} }"
    )
    rows.add("serve/fault_identity", 0.0,
             f"tokens_and_io_identical=True events="
             f"{eng_none.fault_summary()['fault_events']}")

    rng = np.random.default_rng(17)
    prompts = []
    for _ in range(n_requests):
        p = dict(make_dummy_batch(cfg, InputShape("req", PROMPT_LEN, 1, "train")))
        p["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, p["tokens"].shape), jnp.int32
        )
        prompts.append(p)

    results = {}
    for mode in ("off", "on"):
        eng = ServeEngine(model, params, max_seq=MAX_SEQ, batch_size=BATCH,
                          device="nano", sparsity=0.4, method="chunk",
                          seed=5, plan_refresh_interval=1, cache_mb=0.0,
                          fault_profile="thermal_throttle", fault_seed=0,
                          degrade=(mode == "on"))
        eng.simulator.noise = 0.0
        sched = Scheduler(eng, round_tokens=2)
        sched.submit([
            Request(rid=i, prompt=prompts[i], max_new_tokens=6,
                    arrival_s=FAULT_ARRIVAL_GAP_S * i,
                    deadline_s=FAULT_DEADLINE_S)
            for i in range(n_requests)
        ])
        st = sched.run()
        fs = eng.fault_summary()
        results[mode] = st
        rows.add(
            f"serve/fault_{mode}",
            st.latency_p50_s * 1e6,
            f"tokens_per_s={st.tokens_per_s:.1f} "
            f"p99_ms={st.latency_p99_s*1e3:.2f} "
            f"slo_attainment={st.slo_attainment:.3f} "
            f"preempted={st.preempted} "
            f"degrade_scale={fs['degrade_scale']:.2f} "
            f"min_throttle_scale={fs['min_throttle_scale']:.2f}",
        )

    st_off, st_on = results["off"], results["on"]
    assert st_off.finished == st_on.finished == n_requests
    assert st_on.slo_attainment > st_off.slo_attainment, (
        f"degradation controller must lift SLO attainment under throttle: "
        f"on={st_on.slo_attainment:.3f} off={st_off.slo_attainment:.3f}"
    )
    assert st_on.latency_p99_s < st_off.latency_p99_s, (
        f"controller-on p99 must drop: on={st_on.latency_p99_s:.4f} "
        f"off={st_off.latency_p99_s:.4f}"
    )
    assert st_off.preempted >= 1, (
        "the degraded baseline must preempt >= 1 deadline-blown request"
    )
    assert st_on.tokens_per_s > st_off.tokens_per_s
    assert st_on.tokens_per_s >= FAULT_DEGRADED_TPS_FLOOR, (
        f"degraded throughput {st_on.tokens_per_s:.1f} tok/s under the "
        f"{FAULT_DEGRADED_TPS_FLOOR} floor — adaptive degradation stopped "
        "paying for itself"
    )


def bench_integrity(rows: Rows, cfg, model, params,
                    decode_tokens: int = 6) -> None:
    """End-to-end chunk integrity (PR 9 acceptance rows, deterministic —
    seeded corruption, simulator noise irrelevant to tokens):

      * serve/integrity_recovered_w{16,8} — bit_rot (every corruption
        transient, hence recoverable) + the recovery ladder: greedy tokens
        must be BYTE-IDENTICAL to the corruption-off engine and the
        detection rate must be exactly 1.0 (detected == recovered, nothing
        substituted or dropped) — the CI smoke fails if a corrupted block
        ever slips past the checksum or a recovery rung leaks into compute;
      * serve/integrity_norecover — same seed with recovery off must
        CHANGE the tokens (the injection is real, not a counter);
      * serve/integrity_ladder — degraded_nand retention errors exhaust
        the re-read budget and walk the substitute/drop rungs; the counters
        land in the artifact so the ladder's mix is tracked over time."""
    tok0 = jnp.ones((BATCH, 1), jnp.int32)
    for wbits, backend in ((16, "reference"), (8, "kernel")):
        base = _engine(model, params, backend=backend, wbits=wbits)
        t_base = np.asarray(base.decode(tok0, decode_tokens))
        eng = ServeEngine(model, params, max_seq=MAX_SEQ, batch_size=BATCH,
                          device="nano", sparsity=0.4, method="chunk",
                          seed=5, plan_refresh_interval=1, cache_mb=0.0,
                          backend=backend, wbits=wbits,
                          corruption_profile="bit_rot", corruption_seed=7)
        t = np.asarray(eng.decode(tok0, decode_tokens))
        s = eng.io_summary()
        det, rec = s["corruptions_detected"], s["corruptions_recovered"]
        assert det > 0, (
            f"wbits={wbits}: bit_rot drew no corruption — the integrity "
            "rows are vacuous; raise decode_tokens or change the seed"
        )
        assert det == rec and not s["corruptions_substituted"] \
            and not s["corruptions_dropped"], (
            f"wbits={wbits}: bit_rot recovery rate must be exactly 1.0 "
            f"(detected={det} recovered={rec})"
        )
        assert np.array_equal(t_base, t), (
            f"wbits={wbits}: recovered corruption changed greedy tokens — "
            "a damaged block reached compute"
        )
        rows.add(f"serve/integrity_recovered_w{wbits}",
                 s["integrity_reread_s"] * 1e6,
                 f"backend={backend} detected={det:.0f} recovered={rec:.0f} "
                 f"detection_rate=1.0 tokens_identical=True")
    # recovery off: the same seed must measurably corrupt the output
    eng_off = ServeEngine(model, params, max_seq=MAX_SEQ, batch_size=BATCH,
                          device="nano", sparsity=0.4, method="chunk",
                          seed=5, plan_refresh_interval=1, cache_mb=0.0,
                          corruption_profile="bit_rot", corruption_seed=7,
                          recover=False)
    t_off = np.asarray(eng_off.decode(tok0, decode_tokens))
    base16 = _engine(model, params)
    assert not np.array_equal(
        np.asarray(base16.decode(tok0, decode_tokens)), t_off
    ), "recovery-off corruption left tokens untouched — injection inert?"
    s_off = eng_off.io_summary()
    rows.add("serve/integrity_norecover", 0.0,
             f"detected={s_off['corruptions_detected']:.0f} "
             f"tokens_corrupted=True")
    # the full ladder: persistent retention errors → substitute/drop rungs
    eng_nand = ServeEngine(model, params, max_seq=MAX_SEQ, batch_size=BATCH,
                           device="nano", sparsity=0.4, method="chunk",
                           seed=5, plan_refresh_interval=1, cache_mb=0.0,
                           corruption_profile="degraded_nand",
                           corruption_seed=3, max_reread=1)
    eng_nand.decode(tok0, decode_tokens)
    s_n = eng_nand.io_summary()
    assert s_n["corruptions_substituted"] > 0, (
        "degraded_nand never reached the substitution rung — the ladder "
        "below re-read is untested"
    )
    rows.add("serve/integrity_ladder", s_n["integrity_reread_s"] * 1e6,
             f"detected={s_n['corruptions_detected']:.0f} "
             f"recovered={s_n['corruptions_recovered']:.0f} "
             f"substituted={s_n['corruptions_substituted']:.0f} "
             f"dropped={s_n['corruptions_dropped']:.0f}")


def bench_paged_kv(rows: Rows, cfg, model, params, decode_tokens: int = 6,
                   combos=(("reference", 16), ("kernel", 8))) -> None:
    """Paged KV cache (PR 10 acceptance rows, fully deterministic):

      * serve/paged_kv_identity_<backend>_w<wbits> — slot-mode decode with
        the paged pool vs the dense per-slot cache at equal settings must
        produce BYTE-IDENTICAL greedy tokens (every slot admitted, no
        eviction — the workload class the identity criterion covers);
      * serve/paged_kv_2x2 — the same identity on a 2×2 (data, model)
        mesh, with the per-shard page lanes summing to the global count
        (skipped-row idiom below 4 devices, like serve/sharded_*);
      * serve/paged_kv_concurrency — at EQUAL KV memory (16 pages of 8
        tokens, max_seq 64), the dense layout caps at 16//8 = 2 resident
        slots while the paged engine serves 4 shared-prefix streams
        concurrently — the smoke fails unless strictly more streams than
        the dense slot cap fit;
      * serve/paged_kv_sharing — 4 shared-prefix streams vs 4 unique
        same-length prompts: resident KV pages (= bytes) must shrink by
        at least PAGED_KV_SHARING_FLOOR×.
    """
    rng = np.random.default_rng(11)
    # per-slot VLM prompts (frontend rows + tokens fuse to PROMPT_LEN
    # positions); distinct seeds -> fully distinct streams
    prompts = [
        make_dummy_batch(cfg, InputShape("req", PROMPT_LEN, 1, "train"),
                         seed=100 + i)
        for i in range(BATCH)
    ]

    def _slot_decode(eng):
        eng.enable_slots()
        lasts = []
        for slot, p in enumerate(prompts):
            last, _ = eng.admit_slot(slot, p)
            lasts.append(jnp.argmax(last, -1)[:, None])
        tok0 = jnp.concatenate(lasts).astype(jnp.int32)
        t0 = time.perf_counter()
        out, _ = eng.decode_slots(tok0, decode_tokens)
        jax.block_until_ready(out)
        return np.asarray(out), time.perf_counter() - t0

    for backend, wbits in combos:
        dense = _engine(model, params, backend=backend, wbits=wbits)
        paged = ServeEngine(model, params, max_seq=MAX_SEQ, batch_size=BATCH,
                            device="nano", sparsity=0.4, method="chunk",
                            seed=5, plan_refresh_interval=1, cache_mb=0.0,
                            backend=backend, wbits=wbits, kv_page_tokens=8)
        out_d, _ = _slot_decode(dense)
        out_p, wall = _slot_decode(paged)
        name = f"serve/paged_kv_identity_{backend}_w{wbits}"
        assert np.array_equal(out_d, out_p), (
            f"{name}: paged greedy tokens diverged from the dense KV cache "
            "— the gathered page view must reproduce the dense reduction "
            "tree exactly (models/attention.py gather_paged_kv)"
        )
        paged.kv_pool.check()
        tps = decode_tokens * BATCH / wall
        rows.add(name, wall / decode_tokens * 1e6,
                 f"tokens_per_s={tps:.1f} identical_tokens=True "
                 f"pages_in_use={paged.kv_pool.pages_in_use} wbits={wbits}")

    # 2x2 mesh identity (skipped-row idiom below 4 devices)
    if len(jax.devices()) < 4:
        rows.add("serve/paged_kv_2x2", 0.0,
                 f"skipped=True devices={len(jax.devices())} needed=4")
    else:
        dense = _engine(model, params, mesh=ServeMesh.create(2, 2))
        paged = ServeEngine(model, params, max_seq=MAX_SEQ, batch_size=BATCH,
                            device="nano", sparsity=0.4, method="chunk",
                            seed=5, plan_refresh_interval=1, cache_mb=0.0,
                            kv_page_tokens=8, mesh=ServeMesh.create(2, 2))
        out_d, _ = _slot_decode(dense)
        out_p, wall = _slot_decode(paged)
        assert np.array_equal(out_d, out_p), (
            "serve/paged_kv_2x2: paged tokens diverged on the 2x2 mesh"
        )
        per = paged.shard_summary()["kv_pages_per_shard"]
        assert sum(per) == paged.kv_pool.pages_in_use, (
            f"serve/paged_kv_2x2: per-shard page lanes {per} do not sum to "
            f"the global count {paged.kv_pool.pages_in_use}"
        )
        rows.add("serve/paged_kv_2x2", wall / decode_tokens * 1e6,
                 f"identical_tokens=True pages_per_shard={per}")

    # concurrency at equal KV memory: 16 usable pages of 8 tokens. The
    # dense layout must reserve max_seq (64 positions = 8 pages) per slot
    # up front -> 2 slots. Paged: 4 streams share a 4-page prefix (the
    # frontend rows + the first token span) and add a private tail page +
    # one decode-grown page each.
    pt, kv_pages, max_seq = 8, 17, 64
    dense_slot_cap = (kv_pages - 1) * pt // max_seq
    base = dict(make_dummy_batch(cfg, InputShape("req", 5 * pt, 1, "train"),
                                 seed=7))
    n_tok = base["tokens"].shape[1]
    streams = []
    for _ in range(4):
        p = dict(base)  # same frontend + leading tokens = shared prefix
        toks = np.asarray(p["tokens"]).copy()
        toks[0, n_tok - pt:] = rng.integers(0, cfg.vocab_size, pt)
        p["tokens"] = jnp.asarray(toks, jnp.int32)
        streams.append(p)
    eng = ServeEngine(model, params, max_seq=max_seq, batch_size=4,
                      device="nano", sparsity=0.4, method="chunk", seed=5,
                      plan_refresh_interval=1, cache_mb=0.0,
                      kv_page_tokens=pt, kv_pages=kv_pages)
    eng.enable_slots()
    lasts = []
    for slot, p in enumerate(streams):
        assert eng.kv_can_admit(p), (
            f"serve/paged_kv_concurrency: stream {slot} did not fit — "
            "prefix sharing must stretch the fixed page budget"
        )
        last, _ = eng.admit_slot(slot, p)
        lasts.append(jnp.argmax(last, -1)[:, None])
    tok0 = jnp.concatenate(lasts).astype(jnp.int32)
    out, _ = eng.decode_slots(tok0, decode_tokens)
    assert out.shape == (4, decode_tokens)
    eng.kv_pool.check()
    concurrent = sum(1 for s in range(4) if eng.kv_pool.slot_pages(s))
    assert concurrent > dense_slot_cap, (
        f"serve/paged_kv_concurrency: {concurrent} paged streams vs dense "
        f"slot cap {dense_slot_cap} at equal KV memory — the acceptance "
        "criterion requires strictly more"
    )
    rows.add("serve/paged_kv_concurrency", 0.0,
             f"streams={concurrent} dense_slot_cap={dense_slot_cap} "
             f"pages={eng.kv_pool.pages_in_use}/{kv_pages - 1} "
             f"shared_hits={eng.kv_pool.shared_pages_hit}")

    # sharing: resident KV bytes, shared-prefix vs unique same-length
    def _admit_all(prompt_list):
        e = ServeEngine(model, params, max_seq=max_seq, batch_size=4,
                        device="nano", sparsity=0.4, method="chunk", seed=5,
                        plan_refresh_interval=1, cache_mb=0.0,
                        kv_page_tokens=pt, kv_pages=41)
        e.enable_slots()
        for slot, p in enumerate(prompt_list):
            e.admit_slot(slot, p)
        return e.kv_pool.pages_in_use

    # distinct seeds: distinct frontend rows too, so nothing can share
    unique = [
        make_dummy_batch(cfg, InputShape("req", 5 * pt, 1, "train"),
                         seed=200 + i)
        for i in range(4)
    ]
    shared_pages = _admit_all(streams)
    unique_pages = _admit_all(unique)
    ratio = unique_pages / shared_pages
    assert ratio >= PAGED_KV_SHARING_FLOOR, (
        f"serve/paged_kv_sharing: page reduction {ratio:.2f}x below the "
        f"{PAGED_KV_SHARING_FLOOR}x floor ({unique_pages} unique vs "
        f"{shared_pages} shared)"
    )
    rows.add("serve/paged_kv_sharing", 0.0,
             f"kv_byte_reduction={ratio:.2f}x shared_pages={shared_pages} "
             f"unique_pages={unique_pages}")


def run(rows: Rows, smoke: bool = False) -> None:
    cfg, model, params, batch = _setup()
    if smoke:
        # tiny everything: identity + I/O-ordering + overlap assertions
        # (incl. the efficiency floor and the bubble-admission floor) still
        # run, wall-clock speedup (noisy on shared CI runners) does not;
        # the continuous-batching policy comparison is exercised by tier-1
        # tests instead
        bench_fused_vs_loop(rows, model, params, batch, decode_tokens=8,
                            repeats=1, assert_speedup=False)
        bench_backend_parity(rows, model, params, batch, decode_tokens=8)
        bench_sharded_mesh(rows, model, params, batch, decode_tokens=8)
        bench_overlap_pipeline(rows, model, params, batch, devices=("nano",),
                               decode_tokens=8, depth_engines=False)
        # both device profiles even in smoke: the int8-below-fp16 byte
        # ordering is a per-profile acceptance criterion
        bench_quantized_io(rows, model, params, batch, decode_tokens=8)
        bench_plan_reuse(rows, model, params, batch, intervals=(1, 4),
                         decode_tokens=8)
        bench_cache_sweep(rows, model, params, batch, cfg,
                          fractions=(0.0, 0.35), decode_tokens=8)
        bench_scheduler_admission(rows, cfg, model, params, n_requests=4,
                                  smoke=True)
        bench_fault_robustness(rows, cfg, model, params)
        bench_integrity(rows, cfg, model, params)
        bench_paged_kv(rows, cfg, model, params)
        return
    bench_fused_vs_loop(rows, model, params, batch)
    bench_backend_parity(rows, model, params, batch, repeats=3)
    bench_sharded_mesh(rows, model, params, batch)
    bench_overlap_pipeline(rows, model, params, batch)
    bench_quantized_io(rows, model, params, batch)
    bench_plan_reuse(rows, model, params, batch)
    bench_cache_sweep(rows, model, params, batch, cfg)
    bench_scheduler_admission(rows, cfg, model, params)
    bench_continuous_batching(rows, cfg, model, params)
    bench_fault_robustness(rows, cfg, model, params)
    bench_integrity(rows, cfg, model, params, decode_tokens=8)
    bench_paged_kv(rows, cfg, model, params, decode_tokens=8,
                   combos=(("reference", 16), ("reference", 8),
                           ("kernel", 16), ("kernel", 8)))


def _emit_json(rows: Rows, path: str, smoke: bool) -> None:
    payload = {
        "bench": "serve_throughput",
        "arch": ARCH,
        "smoke": smoke,
        "rows": [
            {"name": name, "us_per_call": us, "derived": derived}
            for name, us, derived in rows.rows
        ],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")


def build_parser() -> argparse.ArgumentParser:
    """Exposed for tests/test_docs.py's docs-vs-CLI drift check."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config for CI: every section, a minute or two on CPU")
    ap.add_argument("--out", default=None,
                    help="also write the rows as JSON (the CI perf artifact, "
                         "e.g. BENCH_serve.json)")
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
