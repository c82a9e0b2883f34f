"""ServeEngine: streaming-VLM serving with flash-offload simulation.

Pipeline per the paper (§2.1): prefill(prompt) → append_frame(frame)* →
decode(n)*. Prefill and frame-append run as one jit-compiled step each; the
decode path is a **fused ``lax.scan`` multi-token loop** — the whole n-token
generation is one jit call that accumulates per-step PER-LAYER additive-model
I/O estimates on device and returns (tokens, io_estimates) once, eliminating
the per-token ``float(io)`` host round-trip the seed engine paid. The legacy
one-python-iteration-per-token loop survives as ``decode_per_token`` for
A/B comparison (benchmarks/serve_throughput.py) and regression tests.

Step latency is charged through the **overlapped I/O–compute pipeline**
(core/pipeline.py): per-layer simulated I/O and the ComputeModel's per-layer
compute seconds run through a two-stage prefetch timeline (layer l+1's
chunks stream while layer l computes — double buffering), so the default
per-step latency is the pipeline's critical path, not Σ io + Σ compute.
``overlap=False`` retains the serial charge as the baseline; token outputs
are byte-identical across the two modes (the pipeline only re-times the same
masks). ``StepStats`` carries both charges plus stall/bubble accounting and
``io_summary()`` reports ``overlap_efficiency``.

Inside the scan, ``plan_refresh_interval`` enables temporal chunk-plan
reuse: utility-guided selection reruns every k steps — ONE batched dispatch
per layer over all sites (SparseExecution.refresh_layer), consuming the
importances recorded on the previous step — and the cached masks are reused
(at zero I/O — their chunks are still resident) in between. ``cache_mb``
adds the dynamic chunk residency cache (paper §5): a byte-budgeted DRAM tier
whose per-(layer, site) score state rides the same plan carry — selection
becomes marginal-cost aware, refresh steps insert / evict, and only
cache-miss rows are charged (hit rate lands in ``io_summary``). See
docs/serving.md for the full decode contract and the residency-state
lifecycle.

Two operating modes share the engine:

  * classic single-stream mode: prefill / append_frame / decode drive one
    batch of lockstep requests through a scalar-length KV cache;
  * slot mode (``enable_slots`` + Scheduler): each batch row is an
    independent request slot with its own cache length; ``admit_slot``
    prefills one request into a free slot and ``decode_slots`` runs the
    fused loop over all slots at once (continuous batching).

``method`` ∈ SERVE_METHODS: "chunk" | "topk" | "dense" stream weights from
simulated flash through SparseExecution; "dense_free" means fully
memory-resident weights (no flash tier, zero I/O, no SparseExecution).

Works with any dense/moe/vlm architecture; recurrent archs serve through
decode_step only (their state is the cache).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.faults import (
    CorruptionModel,
    CorruptionProfile,
    FaultModel,
    FaultProfile,
)
from ..core.latency_model import MB
from ..core.offload import ComputeModel, FlashOffloadSimulator, pack_checksums
from ..core.pipeline import PipelineModel, PipelineTimeline, overlap_efficiency
from .. import trace
from ..models.model import Model
from ..models.transformer import SPARSE_WEIGHT_NAMES
from ..kernels.backend import validate_backend
from ..kernels.quantize import quantize_params
from ..sharding.serve import ServeMesh, validate_serve_mesh
from .degrade import DegradationController
from .kv_pool import KVPagePool, KVPoolExhausted, prompt_prefix_hashes
from .sparse_exec import (
    INTEGRITY_COUNTER_KEYS,
    MOE_COUNTER_KEYS,
    KERNEL_BLOCK_ROWS,
    WBITS_CHOICES,
    SparseExecution,
    plan_hit_miss,
    plan_integrity_counters,
    plan_kernel_counters,
    plan_moe_counters,
    plan_transfer_bytes,
    reset_plan_counters,
    set_plan_budget_scale,
    validate_method,
)


@dataclasses.dataclass
class StepStats:
    kind: str  # prefill | frame | decode
    tokens: int
    io_est_s: float
    io_sim_s: float
    wall_s: float
    # residency-tier accounting: selected rows served from the DRAM cache
    # (free) vs streamed from flash this step; 0/0 when the tier is off
    hit_rows: float = 0.0
    miss_rows: float = 0.0
    # estimated flash→DRAM transfer volume of the step (miss rows × row
    # bytes from the plan counters; also stamped on the IOEvent)
    nbytes: float = 0.0
    # overlapped-pipeline accounting (decode steps; core/pipeline.py):
    # serial charge Σ(io+compute), critical-path charge with prefetch,
    # compute lane total, compute-waiting-on-fetch stall, and
    # fetch-engine-idle bubble (the window scheduler admission hides in)
    compute_s: float = 0.0
    serial_s: float = 0.0
    overlap_s: float = 0.0
    stall_s: float = 0.0
    bubble_s: float = 0.0


# the trace counters (repro.trace) of the gather kernels' DMA plans, in
# KERNEL_COUNTER_KEYS order: per matrix walk of a site's block table,
# super-blocks moved whole, super-blocks moved by blocks, block DMAs
KERNEL_TRACE_COUNTERS = ("kernel.superblocks_whole",
                         "kernel.superblocks_partial", "kernel.block_dmas")

# the trace counters of the expert site (MOE_COUNTER_KEYS order): held
# experts whose rows the expert gather streamed, token-expert picks that
# landed on held experts, and expert layer steps
MOE_TRACE_COUNTERS = ("moe.experts_streamed", "moe.routed_pairs_held",
                      "moe.layer_steps")


# the exact key set io_summary() returns — the docstring table and
# tests/test_serving.py both pin against this, so docs, code and tests
# cannot drift independently
IO_SUMMARY_KEYS = (
    "io_est_s",
    "io_sim_s",
    "steps",
    "hit_rows",
    "miss_rows",
    "cache_hit_rate",
    "io_bytes",
    "decode_compute_s",
    "decode_serial_s",
    "decode_overlap_s",
    "decode_stall_s",
    "decode_bubble_s",
    "overlap_efficiency",
    "admitted_during_stall",
    "stall_hidden_s",
    "bubble_utilization",
    "fault_events",
    "fault_spikes",
    "fault_retries",
    "fault_backoff_s",
    "fault_extra_s",
    "min_throttle_scale",
    "corruptions_detected",
    "corruptions_recovered",
    "corruptions_substituted",
    "corruptions_dropped",
    "integrity_reread_s",
    "kv_cache_mb",
    "weight_cache_mb",
    "kv_pages_in_use",
    "kv_shared_pages",
)


class ServeEngine:
    # retention bound of the per-layer I/O log behind reprice_timeline
    _LAYER_IO_LOG_MAX_STEPS = 4096

    def __init__(
        self,
        model: Model,
        params: Any,
        max_seq: int,
        batch_size: int,
        device: str = "nano",
        sparsity: float | Dict[str, float] = 0.4,
        method: str = "chunk",  # see SERVE_METHODS
        reorderings: Optional[dict] = None,
        seed: int = 0,
        plan_refresh_interval: int = 1,
        cache_mb: Optional[float] = None,
        overlap: bool = True,
        prefetch_depth: int = 1,
        compute_layer_scale=None,
        backend: str = "reference",
        wbits: int = 16,
        mesh: Optional[ServeMesh] = None,
        fault_profile: Optional[str | FaultProfile] = None,
        fault_seed: int = 0,
        degrade: bool = False,
        corruption_profile: Optional[str | CorruptionProfile] = None,
        corruption_seed: int = 0,
        max_reread: int = 2,
        recover: bool = True,
        kv_page_tokens: Optional[int] = None,
        kv_pages: Optional[int] = None,
    ):
        """``backend``: the decode execution backend ("reference" |
        "kernel", see kernels/backend.py). "reference" computes the planned
        decode path's sparse projections as the DMA kernels' pure-jnp
        schedule twin; "kernel" dispatches the Pallas chunk-gather kernels
        off the decode plan's ``kstarts``/``ksizes``/``mlp_kernel_plan``
        lanes (interpret mode off-TPU, compiled on real TPU). Decode tokens
        are byte-identical across backends — the switch changes how the
        masked arithmetic is realized, never which neurons participate.
        Ignored by ``dense_free`` (no sparse execution at all).

        ``cache_mb``: DRAM budget (MB) of the dynamic chunk residency
        cache (paper §5). None → the device profile's ``dram_cache_mb``
        default; 0 disables the tier.

        ``overlap``: charge decode steps through the two-stage prefetch
        pipeline (default) instead of the serial Σ io + Σ compute baseline.
        Token outputs are identical either way — the flag only selects
        which timeline prices the step (StepStats keeps both).

        ``prefetch_depth``: how many layers the pipeline's fetch engine may
        run ahead of compute — the same knob as the DMA gather kernels' slot
        count (kernels/chunk_gather_dma.py). 1 = double buffering; 0
        degenerates the timeline to the serial schedule. Tokens are
        byte-identical at every depth.

        ``compute_layer_scale``: optional (n_layers,) per-layer calibration
        multipliers for the pipeline's compute lane
        (``ComputeModel.decode_layer_seconds``); None = uniform.

        ``wbits``: offloaded chunk storage width (16 = fp16, 8 = int8
        payload + per-block f32 scales, kernels/quantize.py). At 8 the
        engine quantizes the sparsifiable layer matrices once at
        construction (the ``_q8``/``_sc`` leaves ride the decode scan next
        to the fp originals) and every byte/latency figure prices the
        quantized rows; decode tokens stay byte-identical across backends
        at fixed wbits. Ignored by ``dense_free`` (nothing streams).

        ``mesh``: the (data, model) serve mesh (sharding/serve.py). Serve
        slots partition over ``data`` (batch must divide); the offloaded
        decode-streamed weights, chunk payloads/scales and per-shard block
        tables partition over ``model``; selection stays replicated so
        greedy tokens are byte-identical between the 1×1 mesh and any
        (d, m) mesh at both wbits. None → unsharded (the default).

        ``fault_profile`` / ``fault_seed``: storage fault injection
        (core/faults.py) — a named ``FAULT_PROFILES`` entry (or a
        ``FaultProfile``) attached to the simulator's MEASUREMENT boundary
        with its own seeded RNG. Selection keeps planning against the
        clean latency table; faults only perturb the charged time of each
        I/O event, never which neurons are selected or which tokens come
        out. None (default) or "none" ⇒ bit-identical behavior to an
        engine without the fault machinery.

        ``corruption_profile`` / ``corruption_seed`` / ``max_reread`` /
        ``recover``: data-plane corruption injection (core/faults.py
        ``CORRUPTION_PROFILES``). Unlike ``fault_profile`` (time-only),
        corruption damages the BYTES of fetched chunk blocks; plan
        refreshes verify them against pack-time checksum lanes the engine
        emits at construction (``_ck`` leaves — ``quantize_params`` over
        the int8 payload at wbits=8, ``pack_checksums`` over the fp leaves
        at 16). With ``recover=True`` (default) the detection/recovery
        ladder keeps greedy tokens byte-identical to a fault-off engine
        whenever every corruption is recoverable; re-read + backoff
        seconds are charged through ``IOEvent.integrity_s``. With
        ``recover=False`` the corruption flows into the gather and tokens
        CAN change (identically on both backends). Counters surface in
        ``io_summary()``. Requires a selecting method, no reorderings and
        the unsharded mesh; None/"none" ⇒ bit-identical to a build
        without the integrity subsystem.

        ``kv_page_tokens`` / ``kv_pages``: paged KV cache (PR 10). None
        (default) keeps the dense per-slot cache. Set, the KV cache becomes
        a pool of ``kv_pages`` fixed-size pages of ``kv_page_tokens``
        tokens each (page 0 reserved as the garbage page), per-slot page
        tables riding the decode scan carry, and copy-on-write prefix
        sharing keyed on chained token-prefix hashes (serving/kv_pool.py).
        ``kv_pages`` defaults to the dense-equivalent capacity
        (batch·max_pages + the garbage page, rounded up to the data-shard
        count) so every dense workload still fits. The pool's byte
        capacity is carved out of the unified ``--cache-mb`` DRAM budget:
        ``io_summary()`` surfaces the ``kv_cache_mb`` / ``weight_cache_mb``
        split and the chunk residency cache gets only the weight share.
        Paged mode is slot-mode only (continuous batching via
        ``admit_slot`` / ``decode_slots``); greedy tokens are
        byte-identical to the dense-KV engine at both wbits, on both
        backends and on any serve mesh.

        ``degrade``: enable the adaptive ``DegradationController``
        (serving/degrade.py): at every decode-call boundary the engine
        observes the measured/estimated step-latency ratio (normalized by
        the deterministic interleave lift, so healthy ≈ 1.0) and tightens
        the selector's chunk I/O budget through the plan-carried "bscale"
        multiplier while the device looks degraded, relaxing on recovery.
        Requires a selecting method ("chunk" | "topk")."""
        validate_method(method, allow_dense_free=True)
        validate_backend(backend)
        if wbits not in WBITS_CHOICES:
            raise ValueError(
                f"wbits must be one of {WBITS_CHOICES}, got {wbits!r}"
            )
        if plan_refresh_interval < 1:
            raise ValueError("plan_refresh_interval must be >= 1")
        if model.cfg.is_latent and (wbits != 16 or kv_page_tokens is not None
                                    or (mesh is not None and mesh.is_sharded)):
            raise NotImplementedError(
                "the latent-attention family serves at wbits 16 on one device "
                "with the dense latent cache (no int8 chunks, paged KV or mesh)")
        self.mesh = mesh if mesh is not None else ServeMesh.single()
        if self.mesh.is_sharded:
            validate_serve_mesh(
                self.mesh.data, self.mesh.model, batch=batch_size,
                d_ff=(model.cfg.d_ff
                      if (self.mesh.model > 1 and model.cfg.d_ff
                          and not model.cfg.has_moe) else 0),
            )
        if degrade and method not in ("chunk", "topk"):
            raise ValueError(
                f"degrade=True needs a selecting method ('chunk' | 'topk') "
                f"whose budget the controller can tighten, got {method!r}"
            )
        # data-plane corruption injection (PR 9): resolve/validate the
        # profile up front — dense_free has no flash data plane to corrupt
        # (SparseExecution validates the sparse-method constraints itself)
        _corruption_probe = (
            CorruptionModel(corruption_profile, seed=corruption_seed,
                            max_reread=max_reread, recover=recover)
            if corruption_profile is not None else None
        )
        if (method == "dense_free" and _corruption_probe is not None
                and _corruption_probe.enabled):
            raise ValueError(
                "corruption injection needs an offloaded data plane — "
                "method='dense_free' streams nothing from flash"
            )
        self.backend = backend
        self.model = model
        self.params = params
        self.max_seq = max_seq
        self.batch_size = batch_size
        # PipelineModel validates prefetch_depth >= 0
        self.prefetch_depth = prefetch_depth
        # storage turbulence: a seeded FaultModel on the simulator's
        # measurement boundary (None ⇒ the clean pre-fault simulator)
        self.faults = (
            FaultModel(fault_profile, seed=fault_seed)
            if fault_profile is not None else None
        )
        self.simulator = FlashOffloadSimulator(
            device, seed=seed,
            pipeline=PipelineModel(prefetch_depth=prefetch_depth),
            faults=self.faults,
        )
        self.degrade_controller = DegradationController() if degrade else None
        self.compute_model = ComputeModel()
        self.method = method
        self.plan_refresh_interval = plan_refresh_interval
        self.overlap = overlap
        # scheduler-admission-during-stall accounting (Scheduler reports
        # hidden prefill time back through note_stall_admission)
        self.admitted_during_stall = 0
        self.stall_hidden_s = 0.0
        # profile-default resolution + >= 0 validation live on the profile
        self.cache_mb = self.simulator.profile.cache_capacity_bytes(cache_mb) / MB
        # paged KV (PR 10): resolve the pool geometry and carve its bytes
        # out of the unified DRAM budget BEFORE SparseExecution is built —
        # the chunk residency cache only ever sees the weight share
        self.kv_page_tokens = kv_page_tokens
        if kv_page_tokens is not None:
            if not model.supports_paged_kv:
                raise ValueError(
                    f"paged KV is only supported for decoder families "
                    f"(dense/moe/vlm), not {model.family!r}"
                )
            if kv_page_tokens < 1 or max_seq % kv_page_tokens != 0:
                raise ValueError(
                    f"kv_page_tokens ({kv_page_tokens}) must be >= 1 and "
                    f"divide max_seq ({max_seq})"
                )
            max_pages = max_seq // kv_page_tokens
            if kv_pages is None:
                # dense-equivalent capacity + the reserved garbage page,
                # rounded up so the pool's page axis shards over 'data'
                kv_pages = batch_size * max_pages + 1
                d = self.mesh.data if self.mesh.is_sharded else 1
                kv_pages += -kv_pages % d
            if kv_pages < 2:
                raise ValueError(f"kv_pages must be >= 2, got {kv_pages}")
            cfg = model.cfg
            # bf16 K + V entries per page position, summed over layers
            self.kv_page_bytes = float(
                2 * 2 * cfg.n_layers * kv_page_tokens
                * cfg.n_cache_kv_heads * cfg.resolved_head_dim
            )
            self.kv_pages = kv_pages
            self.kv_cache_mb = kv_pages * self.kv_page_bytes / MB
        else:
            if kv_pages is not None:
                raise ValueError("kv_pages requires kv_page_tokens")
            self.kv_pages = 0
            self.kv_page_bytes = 0.0
            self.kv_cache_mb = 0.0
        self.weight_cache_mb = max(0.0, self.cache_mb - self.kv_cache_mb)
        self.sparse_ctx = (
            None
            if method == "dense_free"
            else SparseExecution(model.cfg, device=device, sparsity=sparsity,
                                 method=method, reorderings=reorderings,
                                 cache_mb=self.weight_cache_mb, backend=backend,
                                 kernel_prefetch_depth=prefetch_depth,
                                 wbits=wbits, mesh=self.mesh,
                                 degradable=degrade,
                                 corruption_profile=corruption_profile,
                                 corruption_seed=corruption_seed,
                                 max_reread=max_reread,
                                 corruption_recover=recover)
        )
        self.wbits = wbits
        # the resolved corruption model (None when off) + engine-lifetime
        # integrity counter totals, ordered like INTEGRITY_COUNTER_KEYS
        self.corruption = (
            self.sparse_ctx.corruption if self.sparse_ctx is not None else None
        )
        self._integrity_totals = np.zeros(len(INTEGRITY_COUNTER_KEYS))
        # per-shard I/O accounting width (1 on the unsharded path — the
        # shard lanes stay out of the logs entirely so single-device
        # StepStats/IOEvents are byte-identical to pre-mesh engines)
        self.n_shards = (
            self.sparse_ctx.n_shards if self.sparse_ctx is not None
            else (self.mesh.model if self.mesh.is_sharded else 1)
        )
        integrity_on = self.corruption is not None
        if self.sparse_ctx is not None and wbits == 8:
            # quantize the offloaded matrices once: the int8 payload +
            # per-block scale leaves (leading L dim preserved) join the
            # stacked layer params so they ride the decode scan unchanged;
            # prefill / append / the unplanned paths keep the fp originals.
            # Corruption injection adds the pack-time checksum lane (_ck)
            # over the int8 payload — the exact bytes the DMA lane streams
            layers = dict(self.params["layers"])
            layers.update(quantize_params(layers, SPARSE_WEIGHT_NAMES,
                                          checksums=integrity_on))
            self.params = {**self.params, "layers": layers}
        elif self.sparse_ctx is not None and integrity_on:
            # fp pack path (wbits=16): checksum the fp payload leaves
            layers = dict(self.params["layers"])
            layers.update(pack_checksums(layers, SPARSE_WEIGHT_NAMES))
            self.params = {**self.params, "layers": layers}
        if self.mesh.is_sharded:
            # commit params to the mesh: decode-streamed leaves shard over
            # 'model' (the _q8/_sc chunk leaves at wbits=8; fresh <name>_dec
            # fp copies at 16 — originals stay replicated for prefill), the
            # rest replicates. dense_free has nothing decode-streamed.
            if self.sparse_ctx is not None:
                self.params = self.mesh.place_params(
                    self.params, wbits, SPARSE_WEIGHT_NAMES
                )
            else:
                self.params = self.mesh.put_replicated(self.params)
        # per-layer compute lane of the overlap pipeline: selecting methods
        # compute over their kept rows, dense/dense_free over everything
        eff_sparsity = sparsity if method in ("chunk", "topk") else 0.0
        self.compute_layer_s = self.compute_model.decode_layer_seconds(
            model.cfg, sparsity=eff_sparsity, tokens=batch_size,
            layer_scale=compute_layer_scale,
        )
        if self.kv_page_tokens is not None:
            # paged engines are slot-mode from birth: pool + page table +
            # per-slot lengths (prefill/append_frame raise; use admit_slot)
            self.kv_pool: Optional[KVPagePool] = None
            self._init_paged_state()
        else:
            self.kv_pool = None
            self.cache = self.mesh.place_cache(
                model.init_cache(batch_size, max_seq), self._cache_axes()
            )
        self.stats: List[StepStats] = []
        self._plan = None  # chunk-plan carry, persists across decode calls
        # per-decode-call (n_steps, n_layers) simulated-I/O matrices, kept so
        # the host-side timeline can be repriced at other prefetch depths;
        # bounded to the most recent _LAYER_IO_LOG_MAX_STEPS decode steps so
        # a long-lived serving engine doesn't grow without bound
        self._layer_io_log: List[np.ndarray] = []

        # per-token baseline shares the fused loop's step function (the
        # planned path), so the two decode modes differ ONLY in host-loop
        # structure — that's what makes their outputs byte-identical
        def _decode_one_impl(p, t, c, plan, i):
            logits, cache, io, new_plan = model.decode_step_planned(
                p, t, c, self.sparse_ctx, plan,
                (i % self.plan_refresh_interval) == 0,
            )
            h0, m0 = plan_hit_miss(plan)
            h1, m1 = plan_hit_miss(new_plan)
            db = plan_transfer_bytes(new_plan) - plan_transfer_bytes(plan)
            dsb = self._plan_shard_bytes(new_plan) - self._plan_shard_bytes(plan)
            # per-step integrity counter deltas ((6,) zeros with
            # corruption off — see INTEGRITY_COUNTER_KEYS)
            dci = (plan_integrity_counters(new_plan)
                   - plan_integrity_counters(plan))
            dk = plan_kernel_counters(new_plan) - plan_kernel_counters(plan)
            dmoe = plan_moe_counters(new_plan) - plan_moe_counters(plan)
            return (logits, cache, io, new_plan, h1 - h0, m1 - m0, db, dsb, dci, dk,
                    dmoe)

        self._decode_one = jax.jit(_decode_one_impl)
        self._append = jax.jit(
            lambda p, f, c: model.append_frame(p, f, c, self.sparse_ctx)
        )
        self._decode_scan = jax.jit(self._decode_scan_impl, static_argnums=3)
        self._prefill_one = jax.jit(
            lambda p, b: model.prefill(p, b, self.max_seq)
        )

    def _cache_axes(self):
        """The model's logical cache-axes pytree for mesh placement, or
        None (→ fully replicated cache) for families that don't expose
        one."""
        try:
            return self.model.cache_axes()
        except (AttributeError, NotImplementedError):
            return None

    # -- fused decode loop ----------------------------------------------------
    def _init_plan(self):
        if self.sparse_ctx is None:
            return {}
        return self.sparse_ctx.init_plan(self.model.cfg.n_layers)

    def _plan_shard_bytes(self, plan) -> jnp.ndarray:
        """Per-model-shard transfer bytes accumulated in ``plan``, shape
        (n_shards,) — (0,)-summing zeros when there is no sparse context.
        jit-safe (rides the decode step functions)."""
        if self.sparse_ctx is None:
            return jnp.zeros((self.n_shards,), jnp.float32)
        return self.sparse_ctx.plan_shard_bytes(plan)

    def _decode_scan_impl(self, params, token, cache, n_tokens: int, plan):
        """One jit: scan ``decode_step_planned`` over n_tokens greedy steps.

        Returns (tokens (b, n), final cache, final plan, io (n, n_layers),
        hits (n,), misses (n,), bytes (n,), shard_bytes (n, n_shards),
        integrity (n, 6), kernel DMAs (n, 3), expert counters (n, 3)) —
        per-step per-layer I/O
        estimates plus residency-cache row/byte counters, per-model-shard
        byte splits, integrity-counter deltas (INTEGRITY_COUNTER_KEYS order;
        zeros with corruption off) and the gather kernels' DMA-plan deltas
        (KERNEL_COUNTER_KEYS order; zeros on the reference backend) ride
        along. Everything stays on device until the
        caller syncs once. The plan's counters are zeroed here, inside the
        program: an eager reset costs one host dispatch per counter lane.
        """
        k = self.plan_refresh_interval
        plan = reset_plan_counters(plan)

        def step(carry, i):
            tok, cache, plan = carry
            refresh = (i % k) == 0
            logits, cache, io, new_plan = self.model.decode_step_planned(
                params, tok, cache, self.sparse_ctx, plan, refresh
            )
            h0, m0 = plan_hit_miss(plan)
            h1, m1 = plan_hit_miss(new_plan)
            db = plan_transfer_bytes(new_plan) - plan_transfer_bytes(plan)
            dsb = self._plan_shard_bytes(new_plan) - self._plan_shard_bytes(plan)
            dci = (plan_integrity_counters(new_plan)
                   - plan_integrity_counters(plan))
            dk = plan_kernel_counters(new_plan) - plan_kernel_counters(plan)
            dmoe = plan_moe_counters(new_plan) - plan_moe_counters(plan)
            with jax.named_scope("decode.sample"):
                nxt = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            return (nxt, cache, new_plan), (
                nxt[:, 0], io, h1 - h0, m1 - m0, db, dsb, dci, dk, dmoe
            )

        (_, cache, plan), (toks, ios, hits, misses, byts, sbyts,
                           civ, kdma, moe) = jax.lax.scan(
            step, (token, cache, plan), jnp.arange(n_tokens)
        )
        # toks: (n, b) -> (b, n)
        return toks.T, cache, plan, ios, hits, misses, byts, sbyts, civ, kdma, moe

    def _dense_step_bytes(self) -> float:
        """Per-decode-step transfer volume of the dense streaming policy
        (everything re-streams every step; the plan carries no counters)."""
        if self.sparse_ctx is None or self.method != "dense":
            return 0.0
        return float(self.sparse_ctx.sparsifiable_bytes(self.model.cfg.n_layers))

    def _run_decode_scan(self, tokens: jnp.ndarray, n_tokens: int):
        """Shared fused-loop body: run the scan, sync the estimate arrays
        once, convert them to simulated measurements, run the overlap
        pipeline, log per-step stats. Returns (new_tokens (b, n), per-step
        charged latency (n,) — overlapped or serial per ``self.overlap``).
        Spans: ``engine.decode.wait`` while the host waits on the device,
        ``engine.decode.account`` for the host-side accounting after it."""
        if self._plan is None:
            self._plan = self._init_plan()
        if self.degrade_controller is not None:
            # the controller acts only at decode-call boundaries: write its
            # current budget scale into the plan's traced "bscale" leaf so
            # the jitted refresh sees it (mutating engine state after jit
            # compilation would be a silent no-op)
            self._plan = set_plan_budget_scale(
                self._plan, self.degrade_controller.scale
            )
        tokens = self.mesh.put_batch(tokens)
        t0 = time.perf_counter()
        (toks, self.cache, self._plan, ios, hits, misses, byts,
         sbyts, civ, kdma, moe) = self._decode_scan(
            self.params, tokens, self.cache, n_tokens, self._plan
        )
        # ONE blocking host transfer for the whole scan (per-layer estimates
        # + residency/integrity/kernel counters), after the tokens and cache
        # are ready so the wall below covers the whole decode
        with trace.span("engine.decode.wait"):
            jax.block_until_ready((toks, self.cache))
            ios, hits, misses, byts, sbyts, civ, kdma, moe = jax.device_get(
                (ios, hits, misses, byts, sbyts, civ, kdma, moe)
            )
        with trace.span("engine.decode.account"):
            self._count_kernel_dmas(kdma)
            self._count_moe(moe)
            ios = np.asarray(ios, np.float64)  # (n, n_layers)
            hits, misses = np.asarray(hits, np.float64), np.asarray(misses, np.float64)
            byts = np.asarray(byts, np.float64)
            sbyts = np.asarray(sbyts, np.float64)  # (n, n_shards)
            civ = np.asarray(civ, np.float64)  # (n, len(INTEGRITY_COUNTER_KEYS))
            self._integrity_totals += civ.sum(axis=0)
            if self.method == "dense":
                byts = np.full_like(byts, self._dense_step_bytes())
                sbyts = np.full_like(sbyts, self._dense_step_bytes() / self.n_shards)
            wall = time.perf_counter() - t0
            io_steps = ios.sum(axis=1)
            rows = hits + misses
            hit_rates = np.where(rows > 0, hits / np.maximum(rows, 1.0), 0.0)
            sims = self.simulator.measure_from_estimate_batch(
                io_steps, name="decode", hit_rates=hit_rates, nbytes=byts,
                shard_bytes=sbyts if self.n_shards > 1 else None,
                integrity_s=civ[:, 5],
            )
            # the simulator's lift+jitter applies per step; spread it over the
            # step's layers proportionally so the pipeline sees simulated time
            scale = np.where(io_steps > 0, sims / np.maximum(io_steps, 1e-30), 1.0)
            layer_io = ios * scale[:, None]
            self._log_layer_io(layer_io)
            tl = self.simulator.pipeline.timeline(layer_io, self.compute_layer_s)
            per_step_wall = wall / max(n_tokens, 1)
            compute_step = float(np.asarray(self.compute_layer_s).sum())
            for i, (est, sim, h, m) in enumerate(zip(io_steps, sims, hits, misses)):
                self.stats.append(
                    StepStats("decode", 1, float(est), float(sim), per_step_wall,
                              hit_rows=float(h), miss_rows=float(m),
                              nbytes=float(byts[i]), compute_s=compute_step,
                              serial_s=float(tl.serial_s[i]),
                              overlap_s=float(tl.overlap_s[i]),
                              stall_s=float(tl.stall_s[i]),
                              bubble_s=float(tl.bubble_s[i]))
                )
            self._observe_degradation(io_steps, sims)
            self._observe_corruption(float(civ[:, 0].sum()), float(misses.sum()))
        return toks, (tl.overlap_s if self.overlap else tl.serial_s)

    @staticmethod
    def _count_kernel_dmas(kdma) -> None:
        """Add a decode call's kernel DMA-plan deltas ((..., 3), ordered
        like KERNEL_COUNTER_KEYS, already on the host) to the trace's
        counters; nothing on the reference backend, whose plans carry none."""
        total = np.asarray(kdma, np.float64).reshape(-1, 3).sum(axis=0)
        if total.any():
            for name, v in zip(KERNEL_TRACE_COUNTERS, total):
                trace.count(name, int(round(v)))

    @staticmethod
    def _count_moe(moe) -> None:
        """Add a decode call's expert-site counter deltas ((..., 3), ordered
        like MOE_COUNTER_KEYS, on the host) to the trace's counters;
        nothing for a model without routed experts on the planned path."""
        total = np.asarray(moe, np.float64).reshape(-1, len(MOE_COUNTER_KEYS)).sum(axis=0)
        if total[-1] > 0:
            for name, v in zip(MOE_TRACE_COUNTERS, total):
                trace.count(name, int(round(v)))

    def _decode_lift(self) -> float:
        """The deterministic lift decode measurements carry
        (``measure_from_estimate``'s diversity-0.5 factor) — the healthy
        measured/estimated ratio is jitter-centred at 1.0 after dividing
        it out, which is the DegradationController's reference point."""
        return self.simulator.profile.interleave_lift * 1.05

    def _observe_degradation(self, io_est, io_sim) -> None:
        """Feed one decode call's per-step (estimate, measurement) pairs to
        the degradation controller (no-op when ``degrade`` is off)."""
        if self.degrade_controller is None:
            return
        est = np.asarray(io_est, np.float64).reshape(-1)
        sim = np.asarray(io_sim, np.float64).reshape(-1)
        pos = est > 0.0
        if not np.any(pos):
            return
        self.degrade_controller.observe(sim[pos] / (est[pos] * self._decode_lift()))

    def _observe_corruption(self, detected: float, miss_rows: float) -> None:
        """Feed one decode call's corruption rate — detected corrupt blocks
        per fetched block (miss rows / KERNEL_BLOCK_ROWS) — to the
        degradation controller as its second degrade signal. No-op when
        degradation control or corruption injection is off."""
        if self.degrade_controller is None or self.corruption is None:
            return
        blocks = max(miss_rows / KERNEL_BLOCK_ROWS, 1.0)
        self.degrade_controller.observe_corruption(detected / blocks)

    @staticmethod
    def _validate_greedy(greedy: bool) -> None:
        """Both decode loops are argmax-only; the ``greedy`` kwarg used to
        be silently ignored — now a ``greedy=False`` request fails loudly
        instead of quietly returning greedy tokens."""
        if not greedy:
            raise NotImplementedError(
                "sampled decoding is not implemented: ServeEngine.decode / "
                "decode_per_token always take the argmax. Pass greedy=True "
                "(the default) or implement a sampling step function."
            )

    def decode(self, first_token: jnp.ndarray, n_tokens: int, greedy: bool = True):
        """Greedy-decode n_tokens with the fused scan loop. Returns
        (b, n_tokens+1) including ``first_token`` — same contract (and, at
        equal settings, byte-identical output) as the legacy
        ``decode_per_token`` loop."""
        self._validate_greedy(greedy)
        trace.count("engine.decode_calls")
        with trace.span("engine.decode", n_tokens=n_tokens):
            toks, _ = self._run_decode_scan(first_token, n_tokens)
        return jnp.concatenate([first_token, toks], axis=1)

    def decode_per_token(self, first_token: jnp.ndarray, n_tokens: int,
                         greedy: bool = True):
        """The seed engine's decode loop: one jit call + one ``float(io)``
        host sync per python iteration. Runs the same step function as the
        fused scan (including plan reuse and residency-cache updates), so at
        equal settings the two modes produce byte-identical tokens — the
        only difference is the per-token host round-trip the scan
        eliminates. Pipeline accounting is backfilled once the loop ends
        (the overlap timeline needs every step's per-layer I/O)."""
        self._validate_greedy(greedy)
        trace.count("engine.decode_calls")
        with trace.span("engine.decode", n_tokens=n_tokens):
            return self._decode_per_token(first_token, n_tokens)

    def _decode_per_token(self, first_token: jnp.ndarray, n_tokens: int):
        if self._plan is None:
            self._plan = self._init_plan()
        self._plan = reset_plan_counters(self._plan)
        if self.degrade_controller is not None:
            # same call-boundary contract as the fused path: one scale for
            # the whole call, observations folded in once at the end — the
            # two decode modes see identical control behaviour
            self._plan = set_plan_budget_scale(
                self._plan, self.degrade_controller.scale
            )
        token = self.mesh.put_batch(first_token)
        out = [token]
        start_idx = len(self.stats)
        io_rows = []
        det_call = 0.0
        for i in range(n_tokens):
            t0 = time.perf_counter()
            (logits, self.cache, io_vec, self._plan, dh, dm, db,
             dsb, dci, dk, dmoe) = self._decode_one(
                self.params, token, self.cache, self._plan, jnp.int32(i)
            )
            io_vec = np.asarray(io_vec, np.float64)  # the per-token host sync
            self._count_kernel_dmas(dk)
            self._count_moe(dmoe)
            dci = np.asarray(dci, np.float64)
            self._integrity_totals += dci
            det_call += float(dci[0])
            io = float(io_vec.sum())
            hit, miss = float(dh), float(dm)
            nbytes = self._dense_step_bytes() if self.method == "dense" else float(db)
            if self.n_shards > 1:
                if self.method == "dense":
                    sb = (nbytes / self.n_shards,) * self.n_shards
                else:
                    sb = tuple(float(x) for x in np.asarray(dsb, np.float64))
            else:
                sb = None
            wall = time.perf_counter() - t0
            token = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            out.append(token)
            rate = hit / (hit + miss) if (hit + miss) > 0 else 0.0
            sim = self.simulator.measure_from_estimate(
                io, name="decode", hit_rate=rate, nbytes=nbytes, shard_bytes=sb,
                integrity_s=float(dci[5]),
            )
            io_rows.append(io_vec * (sim / io if io > 0 else 1.0))
            self.stats.append(StepStats("decode", 1, io, sim, wall,
                                        hit_rows=hit, miss_rows=miss,
                                        nbytes=nbytes))
        if not io_rows:  # n_tokens == 0: nothing to time
            return jnp.concatenate(out, axis=1)
        recent = self.stats[start_idx:]
        self._observe_degradation(
            [s.io_est_s for s in recent], [s.io_sim_s for s in recent]
        )
        self._observe_corruption(
            det_call, float(sum(s.miss_rows for s in recent))
        )
        # backfill the overlap-pipeline accounting for the whole loop
        self._log_layer_io(np.asarray(io_rows))
        tl = self.simulator.pipeline.timeline(
            np.asarray(io_rows), self.compute_layer_s
        )
        compute_step = float(np.asarray(self.compute_layer_s).sum())
        for j, st in enumerate(self.stats[start_idx:]):
            st.compute_s = compute_step
            st.serial_s = float(tl.serial_s[j])
            st.overlap_s = float(tl.overlap_s[j])
            st.stall_s = float(tl.stall_s[j])
            st.bubble_s = float(tl.bubble_s[j])
        return jnp.concatenate(out, axis=1)

    # -- classic single-stream stages ----------------------------------------
    def prefill(self, batch: Dict[str, jnp.ndarray]):
        if self.kv_pool is not None:
            raise NotImplementedError(
                "paged KV is slot-mode only: admit requests with admit_slot "
                "(single-stream prefill would bypass the page allocator)"
            )
        n = int(batch["tokens"].shape[1])
        t0 = time.perf_counter()
        with trace.span("engine.prefill", tokens=n):
            last, self.cache = self.model.prefill(self.params, batch, self.max_seq)
            jax.block_until_ready((last, self.cache))
        wall = time.perf_counter() - t0
        # prefill loads every matrix once, contiguously (weights streamed)
        est = self._dense_io() if self.sparse_ctx else 0.0
        nbytes = (
            self.sparse_ctx.sparsifiable_bytes(self.model.cfg.n_layers)
            if self.sparse_ctx else 0.0
        )
        sim = self.simulator.measure_from_estimate(
            est, name="prefill", nbytes=nbytes,
            shard_bytes=self._even_shard_bytes(nbytes),
        )
        self.stats.append(StepStats("prefill", n, est, sim, wall,
                                    nbytes=float(nbytes)))
        self._plan = None  # new sequence → stale plan
        return last

    def append_frame(self, frame_embeds: jnp.ndarray):
        """One video frame's patch embeddings → KV cache extension."""
        if self.kv_pool is not None:
            raise NotImplementedError(
                "paged KV is slot-mode only: append_frame extends the "
                "single-stream linear cache, which paged engines don't keep"
            )
        n = int(frame_embeds.shape[1])
        t0 = time.perf_counter()
        with trace.span("engine.prefill", tokens=n):
            hidden, self.cache, io = self._append(self.params, frame_embeds, self.cache)
            jax.block_until_ready((hidden, self.cache))
        io = float(io)
        wall = time.perf_counter() - t0
        sim = self.simulator.measure_from_estimate(io, name="frame")
        self.stats.append(StepStats("frame", n, io, sim, wall))
        return hidden

    # -- slot mode (continuous batching; used by serving.scheduler) ----------
    def _init_paged_state(self):
        """(Re)build the paged-KV pool, page pools and table from scratch."""
        self.kv_pool = KVPagePool(
            self.batch_size, self.max_seq, self.kv_page_tokens,
            self.kv_pages, self.kv_page_bytes,
            n_data_shards=self.mesh.data if self.mesh.is_sharded else 1,
        )
        cache = self.model.init_paged_cache(
            self.batch_size, self.max_seq, self.kv_page_tokens, self.kv_pages
        )
        self.cache = self.mesh.place_cache(cache, self.model.paged_cache_axes())

    def _push_table(self) -> jnp.ndarray:
        """Commit the pool's host page table to the device/mesh."""
        return self.mesh.put_batch(jnp.asarray(self.kv_pool.table))

    def enable_slots(self):
        """Switch the cache to per-slot lengths: each batch row becomes an
        independent request slot (empty until ``admit_slot``)."""
        if self.kv_pool is not None:
            self._init_paged_state()
        else:
            cache = self.model.init_cache(self.batch_size, self.max_seq)
            cache["length"] = jnp.zeros((self.batch_size,), jnp.int32)
            self.cache = self.mesh.place_cache(cache, self._cache_axes())
        self._plan = None

    def kv_can_admit(self, batch: Dict[str, jnp.ndarray]) -> bool:
        """Admission check against FREE PAGES, not free slots: True when
        the pool can cover this prompt's unshared pages (always True on
        the dense path — there a free slot is the only requirement)."""
        if self.kv_pool is None:
            return True
        seq_len, hashes = prompt_prefix_hashes(batch, self.kv_page_tokens)
        return self.kv_pool.can_admit(seq_len, hashes)

    def release_slot(self, slot: int):
        """Free a slot's KV storage — the single funnel every scheduler
        release path (eviction, PR-8 preemption, PR-9 drop rungs) must go
        through. Paged: drop the slot's page references (shared prefix
        pages go cold, private pages return to the free list) and push the
        cleared table row. Dense: zero the slot's length so ``slot_lengths``
        / byte accounting stop counting the dead occupant's KV."""
        if not (0 <= slot < self.batch_size):
            raise ValueError(f"slot {slot} out of range [0, {self.batch_size})")
        if self.kv_pool is not None and self.kv_pool.release(slot):
            self.cache["page_table"] = self._push_table()
        self.cache["length"] = self.cache["length"].at[slot].set(0)

    def admit_slot(self, slot: int, batch: Dict[str, jnp.ndarray]):
        """Prefill one request (leading batch dim 1) into ``slot``,
        overwriting whatever a previous occupant left there. Returns the
        request's last-position logits (1, vocab) and the prefill I/O
        estimate (the request's weights stream in once, contiguously).

        Paged mode: the prompt's full pages are content-addressed — pages
        already resident (live or cold) are shared by reference and their
        KV bytes are NOT rewritten; only fresh pages receive the batch-1
        prefill's cache slices. Raises ``KVPoolExhausted`` when the pool
        cannot cover the unshared pages (``kv_can_admit`` pre-checks)."""
        if not (0 <= slot < self.batch_size):
            raise ValueError(f"slot {slot} out of range [0, {self.batch_size})")
        n = int(batch["tokens"].shape[1])
        with trace.span("engine.prefill", tokens=n):
            last, cache1 = self._prefill_one(self.params, batch)
        with trace.span("engine.kv_write", slot=slot):
            self._write_slot(slot, batch, cache1)
        est = self._dense_io() if self.sparse_ctx else 0.0
        nbytes = (
            self.sparse_ctx.sparsifiable_bytes(self.model.cfg.n_layers)
            if self.sparse_ctx else 0.0
        )
        sim = self.simulator.measure_from_estimate(
            est, name=f"admit[{slot}]", nbytes=nbytes,
            shard_bytes=self._even_shard_bytes(nbytes),
        )
        self.stats.append(
            StepStats("prefill", n, est, sim, 0.0, nbytes=float(nbytes))
        )
        return last, sim

    def _write_slot(self, slot: int, batch: Dict[str, jnp.ndarray], cache1):
        """Write a batch-1 prefill's cache into ``slot``: its fresh pages
        and the pushed page table (paged), or its row (dense), and its
        length."""
        if self.kv_pool is not None:
            seq_len, hashes = prompt_prefix_hashes(batch, self.kv_page_tokens)
            entries = self.kv_pool.admit(slot, seq_len, hashes)
            fresh = [(j, page) for j, (page, is_fresh) in enumerate(entries)
                     if is_fresh]
            if fresh:
                pages = jnp.asarray([page for _, page in fresh])
                srcs = jnp.asarray([j for j, _ in fresh])
                pt, mp = self.kv_page_tokens, self.max_seq // self.kv_page_tokens
                for key in ("k", "v"):
                    n_layers = cache1[key].shape[0]
                    view = cache1[key][:, 0].reshape(
                        n_layers, mp, pt, *cache1[key].shape[3:]
                    )
                    self.cache[key] = self.cache[key].at[:, pages].set(
                        view[:, srcs]
                    )
            self.cache["page_table"] = self._push_table()
            self.cache["length"] = self.cache["length"].at[slot].set(seq_len)
        else:
            for key in cache1:
                if key == "length":
                    continue
                self.cache[key] = jax.lax.dynamic_update_slice_in_dim(
                    self.cache[key], cache1[key], slot, axis=1
                )
            self.cache["length"] = (
                self.cache["length"].at[slot].set(cache1["length"].astype(jnp.int32))
            )

    def decode_slots(self, tokens: jnp.ndarray, n_tokens: int):
        """Fused decode round over all slots. ``tokens``: (batch, 1) current
        input token per slot (free slots decode garbage that callers drop).
        Returns (new_tokens (batch, n), per-step charged latency (n,) —
        the overlapped-pipeline critical path by default, the serial
        Σ io + Σ compute charge with ``overlap=False``)."""
        trace.count("engine.decode_calls")
        with trace.span("engine.decode", n_tokens=n_tokens):
            self._grow_pages(n_tokens)
            return self._run_decode_scan(tokens, n_tokens)

    def _grow_pages(self, n_tokens: int) -> None:
        """Paged KV: cover each occupied slot's writes of the coming round
        before the table rides the scan carry."""
        if self.kv_pool is not None and n_tokens > 0:
            # grow each occupied slot's page table to cover this round's
            # write positions [length, length + n_tokens) before the table
            # rides the scan carry (free slots scatter to the garbage page).
            # The whole round's growth is checked up front so exhaustion
            # raises BEFORE any host table mutates or page allocates —
            # recoverable: the scheduler preempts a slot and retries.
            lengths = self.slot_lengths()
            occupied = [s for s in range(self.batch_size)
                        if self.kv_pool.slot_pages(s)]
            need = {
                s: self.kv_pool.pages_needed(s, int(lengths[s]) + n_tokens - 1)
                for s in occupied
            }
            total = sum(need.values())
            if total > self.kv_pool.reclaimable_pages:
                raise KVPoolExhausted(
                    f"decode round needs {total} new KV pages but only "
                    f"{self.kv_pool.reclaimable_pages} are free or "
                    "cold-evictable — release or preempt a slot first "
                    "(no page was allocated; engine state is unchanged)"
                )
            grew = False
            for slot in occupied:
                if need[slot] and self.kv_pool.ensure(
                    slot, int(lengths[slot]) + n_tokens - 1
                ):
                    grew = True
            if grew:
                self.cache["page_table"] = self._push_table()

    def slot_lengths(self) -> np.ndarray:
        return np.asarray(self.cache["length"]).reshape(-1)

    # -- accounting ----------------------------------------------------------
    def _even_shard_bytes(self, nbytes: float):
        """Even per-model-shard split of a transfer that streams every
        matrix contiguously (prefill / slot admission load ALL weights, so
        each shard streams exactly its slice); None on the unsharded path
        so single-device IOEvents are unchanged."""
        if self.n_shards == 1:
            return None
        return (float(nbytes) / self.n_shards,) * self.n_shards

    def _dense_io(self) -> float:
        per_layer = self.sparse_ctx.dense_total_latency()
        return per_layer * self.model.cfg.n_layers

    def _log_layer_io(self, layer_io: np.ndarray) -> None:
        """Append one decode call's (n_steps, n_layers) simulated-I/O matrix
        and trim the oldest WHOLE calls past the retention bound (whole
        calls, because each logged call is repriced as its own cold
        pipeline)."""
        self._layer_io_log.append(layer_io)
        total = sum(m.shape[0] for m in self._layer_io_log)
        while len(self._layer_io_log) > 1 and total > self._LAYER_IO_LOG_MAX_STEPS:
            total -= self._layer_io_log.pop(0).shape[0]

    def reprice_timeline(self, prefetch_depth: int):
        """Re-run the prefetch timeline over the retained decode calls'
        recorded per-layer simulated I/O at a different depth. Each logged
        call is priced as its own cold pipeline — exactly how the engine
        charges a decode call — so the result matches what an
        identically-seeded engine constructed with
        ``prefetch_depth=depth`` would log for those calls: a free depth
        sweep without re-decoding (benchmarks use it to assert depth
        monotonicity). Covers the most recent ``_LAYER_IO_LOG_MAX_STEPS``
        decode steps (whole calls). Returns a combined ``PipelineTimeline``
        whose per-step arrays are the per-call timelines concatenated."""
        if not self._layer_io_log:
            raise RuntimeError("no decode steps logged yet — nothing to reprice")
        model = self.simulator.pipeline.with_depth(prefetch_depth)
        tls = [model.timeline(ios, self.compute_layer_s) for ios in self._layer_io_log]
        if len(tls) == 1:
            return tls[0]
        return PipelineTimeline(
            io_s=np.concatenate([t.io_s for t in tls]),
            compute_s=np.concatenate([t.compute_s for t in tls]),
            serial_s=np.concatenate([t.serial_s for t in tls]),
            overlap_s=np.concatenate([t.overlap_s for t in tls]),
            stall_s=np.concatenate([t.stall_s for t in tls]),
            bubble_s=np.concatenate([t.bubble_s for t in tls]),
        )

    def note_stall_admission(self, hidden_s: float) -> None:
        """Record one scheduler admission whose prefill was (partially)
        hidden inside measured decode stall windows — the Scheduler reports
        it here so ``io_summary`` can expose realized bubble utilization
        next to the stall totals the windows came from."""
        if hidden_s < 0:
            raise ValueError(f"hidden_s must be >= 0, got {hidden_s}")
        self.admitted_during_stall += 1
        self.stall_hidden_s += float(hidden_s)

    def shard_summary(self) -> Dict[str, Any]:
        """Per-shard rollup of the sharded serve path (mesh geometry,
        per-model-shard transfer bytes, per-shard residency budget, slots
        per data shard). Lives NEXT TO ``io_summary`` — whose key set is
        pinned — rather than inside it; on the 1×1 mesh everything
        degrades to one shard holding the unsharded totals.

        ``io_bytes_per_shard`` sums exactly to ``io_summary()['io_bytes']``
        (the ISSUE's accounting invariant): row-sharded sites split by each
        shard's actual miss rows, everything else splits evenly.
        ``cache_mb_per_shard`` is the uniform capacity split — resident
        rows partition across model shards with the weights, so each shard
        provisions 1/n_shards of the residency budget."""
        per_shard = self.simulator.total_bytes_by_shard(self.n_shards)
        n_data = self.mesh.data
        return {
            "mesh_data": self.mesh.data,
            "mesh_model": self.mesh.model,
            "n_shards": self.n_shards,
            "io_bytes": float(sum(per_shard)),
            "io_bytes_per_shard": [float(b) for b in per_shard],
            "cache_mb_per_shard": self.cache_mb / self.n_shards,
            "slots_per_data_shard": self.batch_size // self.mesh.data,
            # paged-KV occupancy by data shard (page "home" = the shard of
            # the slot that first allocated it); sums to kv_pages_in_use —
            # the same sum-to-global invariant as io_bytes_per_shard
            "kv_pages_in_use": (
                self.kv_pool.pages_in_use if self.kv_pool is not None else 0
            ),
            "kv_pages_per_shard": (
                self.kv_pool.pages_per_shard(n_data)
                if self.kv_pool is not None else [0] * n_data
            ),
        }

    def fault_summary(self) -> Dict[str, Any]:
        """Fault-injection + degradation rollup. Lives NEXT TO
        ``io_summary`` — whose key set is pinned bit-identical across the
        fault-off/on switch — exactly like ``shard_summary``. With no
        fault model and no controller it reports the quiescent defaults
        (profile "none", scale 1.0), so callers can read it
        unconditionally.

        Fault lanes (core/faults.py): the profile/seed, perturbed event
        count, tail-spike count, transient-failure retries and their total
        backoff seconds, the total extra charged seconds, and the deepest
        thermal-throttle derate seen. ``device_time_s`` is the simulator's
        cumulative charged I/O clock (the throttle trajectory's input).
        Degradation lanes (serving/degrade.py, "degrade_" prefix): current
        budget scale, EWMA ratio, observation/tighten/relax counters."""
        out: Dict[str, Any] = {
            "fault_profile": "none",
            "fault_seed": 0,
            "fault_enabled": False,
            "device_time_s": self.simulator.device_time_s,
            "fault_events": 0,
            "fault_spikes": 0,
            "fault_retries": 0,
            "fault_backoff_s": 0.0,
            "fault_extra_s": 0.0,
            "min_throttle_scale": 1.0,
            "degrade_enabled": self.degrade_controller is not None,
            "degrade_scale": 1.0,
            "degrade_ewma_ratio": 1.0,
            "degrade_observations": 0,
            "degrade_tighten_steps": 0,
            "degrade_relax_steps": 0,
            "degrade_calls_degraded": 0,
        }
        if self.faults is not None:
            fs = self.faults.summary()
            out.update({
                "fault_profile": fs["profile"],
                "fault_seed": fs["seed"],
                "fault_enabled": self.faults.enabled,
                "fault_events": fs["events"],
                "fault_spikes": fs["spikes"],
                "fault_retries": fs["retries"],
                "fault_backoff_s": fs["backoff_s"],
                "fault_extra_s": fs["fault_extra_s"],
                "min_throttle_scale": fs["min_throttle_scale"],
            })
        if self.degrade_controller is not None:
            ds = self.degrade_controller.summary()
            out.update({f"degrade_{k}": v for k, v in ds.items()})
        return out

    def io_summary(self) -> Dict[str, float]:
        """Engine-lifetime I/O / pipeline / cache / admission rollup.

        The returned dict carries EXACTLY the keys below (pinned against
        ``IO_SUMMARY_KEYS`` by ``tests/test_serving.py`` so the table can't
        drift from the implementation):

        | field                  | meaning                                          | since |
        |------------------------|--------------------------------------------------|-------|
        | ``io_est_s``           | Σ additive-model I/O estimate over all steps     | PR 0  |
        | ``io_sim_s``           | Σ simulator-measured I/O (lift + jitter applied) | PR 0  |
        | ``steps``              | number of logged StepStats entries               | PR 0  |
        | ``hit_rows``           | residency-cache rows served from DRAM (free)     | PR 2  |
        | ``miss_rows``          | selected rows streamed from flash                | PR 2  |
        | ``cache_hit_rate``     | hit_rows / (hit_rows + miss_rows), 0 when idle   | PR 2  |
        | ``io_bytes``           | Σ estimated flash→DRAM transfer volume (nbytes)  | PR 3  |
        | ``decode_compute_s``   | Σ compute-lane seconds over decode steps         | PR 3  |
        | ``decode_serial_s``    | Σ serial Σio+Σcompute charge (decode steps)      | PR 3  |
        | ``decode_overlap_s``   | Σ prefetch-pipeline critical-path charge         | PR 3  |
        | ``decode_stall_s``     | Σ compute-idle seconds (waiting on a fetch)      | PR 3  |
        | ``decode_bubble_s``    | Σ fetch-engine-idle seconds (no free buffer)     | PR 4  |
        | ``overlap_efficiency`` | hidden time / hideable time, clipped to [0, 1]   | PR 3  |
        | ``admitted_during_stall`` | scheduler admissions hidden in idle windows   | PR 4  |
        | ``stall_hidden_s``     | Σ prefill seconds those admissions hid           | PR 4  |
        | ``bubble_utilization`` | stall_hidden_s / (stall + bubble), ≤ 1           | PR 4  |
        | ``fault_events``       | I/O events the fault model perturbed             | PR 9  |
        | ``fault_spikes``       | tail-latency spikes the fault model injected     | PR 9  |
        | ``fault_retries``      | transient-failure re-reads (fault model)         | PR 9  |
        | ``fault_backoff_s``    | Σ retry backoff seconds charged                  | PR 9  |
        | ``fault_extra_s``      | Σ extra charged seconds vs the clean clock       | PR 9  |
        | ``min_throttle_scale`` | deepest thermal-throttle derate seen (≤ 1)       | PR 9  |
        | ``corruptions_detected``    | checksum-mismatched (matrix, block) fetches | PR 9  |
        | ``corruptions_recovered``   | detections healed by re-read or DRAM copy   | PR 9  |
        | ``corruptions_substituted`` | unreadable rows swapped for next-best rows  | PR 9  |
        | ``corruptions_dropped``     | unreadable rows dropped (no substitute)     | PR 9  |
        | ``integrity_reread_s``      | Σ re-read + backoff seconds charged         | PR 9  |
        | ``kv_cache_mb``        | paged-KV pool share of the unified DRAM budget   | PR 10 |
        | ``weight_cache_mb``    | chunk-residency share (cache_mb − kv_cache_mb)   | PR 10 |
        | ``kv_pages_in_use``    | live (referenced) KV pages right now             | PR 10 |
        | ``kv_shared_pages``    | live pages referenced by more than one slot      | PR 10 |

        The fault lanes mirror ``fault_summary()`` (quiescent defaults —
        0 counts, throttle scale 1.0 — with no fault model); the corruption
        lanes total the plan's INTEGRITY_COUNTER_KEYS accumulators over the
        engine lifetime (all zero with corruption injection off). The
        paged-KV lanes read the live pool (dense engines report
        ``kv_cache_mb`` 0, ``weight_cache_mb`` == the full ``cache_mb``,
        and zero page counts).
        """
        tot_est = sum(s.io_est_s for s in self.stats)
        tot_sim = sum(s.io_sim_s for s in self.stats)
        hit = sum(s.hit_rows for s in self.stats)
        miss = sum(s.miss_rows for s in self.stats)
        dec = [s for s in self.stats if s.kind == "decode"]
        serial = sum(s.serial_s for s in dec)
        overlap = sum(s.overlap_s for s in dec)
        stall = sum(s.stall_s for s in dec)
        bubble = sum(s.bubble_s for s in dec)
        fs = self.fault_summary()
        it = self._integrity_totals
        return {
            "io_est_s": tot_est,
            "io_sim_s": tot_sim,
            "steps": len(self.stats),
            "hit_rows": hit,
            "miss_rows": miss,
            "cache_hit_rate": hit / (hit + miss) if (hit + miss) > 0 else 0.0,
            "io_bytes": sum(s.nbytes for s in self.stats),
            # overlapped-pipeline rollup (decode steps)
            "decode_compute_s": sum(s.compute_s for s in dec),
            "decode_serial_s": serial,
            "decode_overlap_s": overlap,
            "decode_stall_s": stall,
            "decode_bubble_s": bubble,
            "overlap_efficiency": overlap_efficiency(
                [s.serial_s for s in dec],
                [s.overlap_s for s in dec],
                [s.io_sim_s for s in dec],
                [s.compute_s for s in dec],
            ),
            # scheduler admissions landed inside measured idle windows
            # (stall + bubble) and the fraction of those windows their
            # hidden prefill time realized
            "admitted_during_stall": self.admitted_during_stall,
            "stall_hidden_s": self.stall_hidden_s,
            "bubble_utilization": (
                min(self.stall_hidden_s / (stall + bubble), 1.0)
                if (stall + bubble) > 0 else 0.0
            ),
            # storage-fault + corruption-integrity lanes (PR 9): numeric
            # fault_summary() mirrors + lifetime integrity-counter totals
            "fault_events": fs["fault_events"],
            "fault_spikes": fs["fault_spikes"],
            "fault_retries": fs["fault_retries"],
            "fault_backoff_s": fs["fault_backoff_s"],
            "fault_extra_s": fs["fault_extra_s"],
            "min_throttle_scale": fs["min_throttle_scale"],
            "corruptions_detected": float(it[0]),
            "corruptions_recovered": float(it[1]),
            "corruptions_substituted": float(it[2]),
            "corruptions_dropped": float(it[3]),
            "integrity_reread_s": float(it[5]),
            # unified-budget split + live paged-KV pool occupancy (PR 10)
            "kv_cache_mb": self.kv_cache_mb,
            "weight_cache_mb": self.weight_cache_mb,
            "kv_pages_in_use": (
                self.kv_pool.pages_in_use if self.kv_pool is not None else 0
            ),
            "kv_shared_pages": (
                self.kv_pool.shared_pages if self.kv_pool is not None else 0
            ),
        }
