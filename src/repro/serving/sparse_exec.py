"""SparseExecution: the paper's runtime policy wired into the model blocks.

One instance per (model config × device × policy). Model blocks call
``mask(kind, acts)`` once per sparsifiable projection input —
kind ∈ {hidden_attn, hidden_mlp, ffn, attn_out} mirroring the paper's
q / gate / down / o sites (k, v, up share masks with q and gate, App. A).

Everything runs inside jit: importance → utility-guided chunk selection
(jit-compiled ``lax.while_loop`` greedy) → mask + additive-model latency.
Latency accounts for every matrix sharing the mask (q+k+v for hidden_attn,
gate+up for hidden_mlp) with per-matrix row sizes.

Methods: "chunk" (ours), "topk" (TEAL/LLMFlash-style baseline),
"dense" (no sparsification — full contiguous load).

The planned decode path (the engine's scan/per-token loops) batches all of
a layer's sites into ONE selection dispatch per refresh step
(``refresh_layer`` → core.chunking.BatchedChunkSelector, a single vmapped
greedy instead of four sequential while_loops). To make that possible —
and to make the overlapped prefetch pipeline physically realizable, since
layer l+1's chunks must be known while layer l computes — refresh-step
selection consumes the importance vectors *recorded on the previous decode
step* (``record_importance`` stashes each site's importance into the plan
carry as the step runs; the first refresh bootstraps from uniform
importance). The unplanned paths (prefill / frame append / plain
``decode_step``) keep the original in-step per-site selection.

With ``cache_mb > 0`` a dynamic chunk residency cache (paper §5) rides the
decode-plan carry: per-(layer, site) score state whose top-``cap_rows``
entries are DRAM-resident, marginal-cost selection, miss-only I/O charging,
and hit/miss accounting — see docs/serving.md for the lifecycle.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..core.baselines import topk_mask
from ..core.chunking import BatchedChunkSelector, ChunkConfig, ChunkSelector
from ..core.faults import CorruptionModel, CorruptionProfile, corruption_key
from ..kernels.backend import ExecutionBackend
from ..kernels.chunk_gather_dma import masks_to_block_tables, pick_tile, superblock_counts
from ..kernels.quantize import block_checksums
from ..core.latency_model import (
    DeviceProfile,
    LatencyTable,
    get_profile,
    profile_table,
    row_stream_bytes,
)
from ..core.offload import decode_site_shapes, normalize_site_sparsity, site_layers
from ..core.reorder import Reordering
from ..sharding.serve import ServeMesh

DTYPE_BYTES = 2  # offloaded weights stored bf16/fp16 at wbits=16 (paper: fp16)

# Offloaded chunk storage widths (kernels/quantize.py): 16 = fp16 payload,
# 8 = int8 payload + one f32 scale per KERNEL_BLOCK_ROWS rows. All byte
# pricing (selector utilities, residency budget, IOEvent.nbytes) goes
# through core.latency_model.row_stream_bytes so every consumer sees the
# same per-row cost including the amortized scale overhead.
WBITS_CHOICES = (16, 8)

# Kernel chunk-table geometry for the DMA gather kernels
# (kernels/chunk_gather_dma.py): refresh steps convert each site's selected
# mask into a block-aligned padded (starts, sizes) table INSIDE jit
# (masks_to_block_tables — one vmapped call per layer over all sites, no
# per-site host re-splitting), so the plan carry always holds tables the
# kernels can consume directly.
KERNEL_BLOCK_ROWS = 8
KERNEL_MAX_CHUNK_ROWS = 512

# Dynamic residency-cache policy constants (paper §5, applied temporally):
# scores decay by RESIDENCY_DECAY per refresh step (recency) and grow by the
# row's importance when selected (frequency×magnitude) — a jit-friendly
# LFU/LRU hybrid. Pinned (pre-warmed) rows get PIN_SCORE so rank-based
# eviction never removes them.
RESIDENCY_DECAY = 0.9
PIN_SCORE = 1e30

# The single source of truth for serving policy names (ServeEngine and
# SparseExecution both validate against these):
#   * SPARSE_METHODS run through SparseExecution (selection + I/O accounting);
#   * "dense_free" means fully memory-resident weights — dense compute with
#     NO flash tier at all, so no SparseExecution instance and zero I/O.
SPARSE_METHODS = ("chunk", "topk", "dense")
SERVE_METHODS = SPARSE_METHODS + ("dense_free",)


def validate_method(method: str, allow_dense_free: bool = False) -> str:
    allowed = SERVE_METHODS if allow_dense_free else SPARSE_METHODS
    if method not in allowed:
        raise ValueError(f"unknown sparse method {method!r}; expected one of {allowed}")
    return method


def residency_from_score(score: jnp.ndarray, cap: int) -> jnp.ndarray:
    """Derive the resident set from a residency score vector: the top-``cap``
    rows by score (``topk_mask``'s stable rank — never exceeds ``cap`` rows
    even under score ties, so the byte budget holds by construction),
    excluding never-inserted rows (score <= 0). jit-safe."""
    return topk_mask(score, cap) & (score > 0.0)


def plan_hit_miss(plan) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Total residency-cache (hit_rows, miss_rows) accumulated in a decode
    plan/state pytree, summed over sites and layers. Counters accumulate
    within one engine decode call (``reset_plan_counters`` zeroes them at
    the start of each, bounding float32 round-off). Returns (0, 0) for
    empty plans. Without the residency tier ``hit`` is always 0 and
    ``miss`` counts every selected (streamed) row. jit-safe."""
    hit = jnp.float32(0.0)
    miss = jnp.float32(0.0)
    if not plan:
        return hit, miss
    for state in plan.values():
        if isinstance(state, dict) and "hit" in state:
            hit += jnp.sum(state["hit"])
            miss += jnp.sum(state["miss"])
    return hit, miss


def plan_transfer_bytes(plan) -> jnp.ndarray:
    """Total estimated flash→DRAM transfer volume accumulated in a decode
    plan pytree (cache-miss rows × per-site row bytes, summed over sites
    and layers) — the quantity the engine threads into ``IOEvent.nbytes``
    so ``FlashOffloadSimulator.total_bytes()`` is meaningful on the
    estimate-driven decode paths. jit-safe."""
    total = jnp.float32(0.0)
    if not plan:
        return total
    for state in plan.values():
        if isinstance(state, dict) and "bytes" in state:
            total += jnp.sum(state["bytes"])
    return total


def set_plan_budget_scale(plan, scale: float):
    """Rewrite the decode plan's carried budget-scale leaf ("bscale",
    present only on degradable plans — see ``SparseExecution.init_plan``)
    to ``scale`` for every layer and site. Host-side helper the engine
    calls between decode invocations with the DegradationController's
    current scale: because the scale rides the plan pytree it reaches the
    jitted refresh as a TRACED value — mutating a closed-over array on the
    SparseExecution instance would be a silent no-op once the scan is
    compiled. No-op (returns ``plan`` unchanged) on non-degradable plans."""
    if not plan:
        return plan
    s = float(scale)
    if not (0.0 < s <= 1.0):
        raise ValueError(f"budget scale must be in (0, 1], got {scale}")
    out = {}
    changed = False
    for kind, state in plan.items():
        if isinstance(state, dict) and "bscale" in state:
            state = dict(state)
            state["bscale"] = jnp.full_like(state["bscale"], s)
            changed = True
        out[kind] = state
    return out if changed else plan


def plan_budget_scale(plan) -> Optional[float]:
    """The (uniform) budget scale currently carried by a degradable plan,
    or None for plans without the "bscale" leaf. Host-side accessor."""
    for state in (plan or {}).values():
        if isinstance(state, dict) and "bscale" in state:
            return float(np.asarray(state["bscale"]).reshape(-1)[0])
    return None


# per-(layer, site) integrity counter lanes carried by the decode plan when
# corruption injection is on (PR 9): detected corrupt block-events, events
# recovered (clean re-read or rung-1 resident DRAM copy), substituted rows
# (rung 2), dropped rows (rung 3), re-reads charged, and the re-read +
# backoff seconds the engine routes through IOEvent.integrity_s
INTEGRITY_COUNTER_KEYS = ("cdet", "crec", "csub", "cdrop", "crr", "crr_s")


def plan_integrity_counters(plan) -> jnp.ndarray:
    """Total integrity counters accumulated in a decode plan pytree, as one
    (6,) float32 vector ordered like ``INTEGRITY_COUNTER_KEYS``. All-zero
    when the plan carries no integrity lanes (corruption off), so the
    engine can emit the vector unconditionally. jit-safe."""
    out = jnp.zeros((len(INTEGRITY_COUNTER_KEYS),), jnp.float32)
    if not plan:
        return out
    for state in plan.values():
        if isinstance(state, dict) and "cdet" in state:
            out = out + jnp.stack(
                [jnp.sum(state[k]) for k in INTEGRITY_COUNTER_KEYS]
            )
    return out


# per-(layer, site) lanes of what the gather kernels' DMA plans move,
# carried by kernel-backend plans and added every decode step from the
# site's block table (``superblock_counts``, per matrix the table feeds):
# super-blocks moved whole (one DMA), super-blocks moved by blocks, and the
# block DMAs those take
KERNEL_COUNTER_KEYS = ("sb_whole", "sb_partial", "block_dmas")


# sites selected inside their own step rather than by the batched refresh:
# the expert site's rows are ranked once the router has chosen this step's
# experts and they have run (``SparseExecution.run_experts``)
IN_STEP_SITES = ("expert_ffn",)

# per-layer lanes of the expert site, added every decode step: held experts
# whose rows the kernel streamed, token-expert picks that landed on held
# experts, and layer steps
MOE_COUNTER_KEYS = ("experts", "pairs", "steps")


def plan_moe_counters(plan) -> jnp.ndarray:
    """Totals of the expert site's lanes, (3,) float32 ordered like
    ``MOE_COUNTER_KEYS``; zeros for a plan without an expert site."""
    out = jnp.zeros((len(MOE_COUNTER_KEYS),), jnp.float32)
    for state in (plan or {}).values():
        if isinstance(state, dict) and MOE_COUNTER_KEYS[0] in state:
            out = out + jnp.stack([jnp.sum(state[k]) for k in MOE_COUNTER_KEYS])
    return out


def plan_kernel_counters(plan) -> jnp.ndarray:
    """Totals of the kernel DMA-plan lanes in a decode plan pytree, (3,)
    float32 ordered like ``KERNEL_COUNTER_KEYS``; zeros when the plan
    carries none (reference backend). jit-safe."""
    out = jnp.zeros((len(KERNEL_COUNTER_KEYS),), jnp.float32)
    for state in (plan or {}).values():
        if isinstance(state, dict) and KERNEL_COUNTER_KEYS[0] in state:
            out = out + jnp.stack([jnp.sum(state[k]) for k in KERNEL_COUNTER_KEYS])
    return out


def reset_plan_counters(plan):
    """Zero the hit/miss/bytes (and integrity and kernel-counter)
    accumulators of a decode plan state. Run at the start of each engine
    decode invocation (inside the fused scan's program) so the float32
    counters only ever accumulate one call's rows — exact far beyond any
    realistic n_tokens. jit-safe."""
    if not plan:
        return plan
    out = {}
    for kind, state in plan.items():
        if isinstance(state, dict):
            state = dict(state)
            for key in (("hit", "miss", "bytes", "hit_shard", "miss_shard")
                        + INTEGRITY_COUNTER_KEYS + KERNEL_COUNTER_KEYS
                        + MOE_COUNTER_KEYS):
                if key in state:
                    state[key] = jnp.zeros_like(state[key])
        out[kind] = state
    return out


@dataclasses.dataclass(frozen=True, eq=False)
class _Site:
    """One sparsification site: a selector + latency tables for every matrix
    sharing this input (e.g. q/k/v)."""

    n: int
    selector: ChunkSelector
    tables: Tuple[LatencyTable, ...]  # one per sharing matrix
    sparsity: float
    dense_latency: float

    def budget(self) -> jnp.ndarray:
        return jnp.int32(round((1.0 - self.sparsity) * self.n))


def _site(n_rows: int, out_cols: Tuple[int, ...], device, sparsity: float,
          wbits: int = 16) -> _Site:
    primary_rb = row_stream_bytes(out_cols[0], wbits, KERNEL_BLOCK_ROWS)
    cfg = ChunkConfig.for_shape(n_rows, out_cols[0],
                                device if isinstance(device, str) else device.name)
    selector = ChunkSelector.build(n_rows, primary_rb, device=device, cfg=cfg)
    tables = tuple(
        profile_table(device, row_stream_bytes(c, wbits, KERNEL_BLOCK_ROWS),
                      max_rows=selector.max_size)
        for c in out_cols
    )
    dense = float(
        sum(
            get_profile(device if isinstance(device, str) else device.name)
            .latency_bytes(n_rows * row_stream_bytes(c, wbits, KERNEL_BLOCK_ROWS))
            for c in out_cols
        )
    )
    return _Site(n=n_rows, selector=selector, tables=tables, sparsity=sparsity,
                 dense_latency=dense)


class SparseExecution:
    """sparse_ctx implementation passed into model block functions."""

    def __init__(
        self,
        cfg: ModelConfig,
        device: str | DeviceProfile = "nano",
        sparsity: float | Dict[str, float] = 0.4,
        method: str = "chunk",
        reorderings: Optional[Dict[str, Reordering]] = None,
        cached: Optional[Dict[str, "jnp.ndarray"]] = None,
        cache_mb: float = 0.0,
        backend: str | ExecutionBackend = "reference",
        kernel_prefetch_depth: int = 1,
        kernel_interpret: Optional[bool] = None,
        wbits: int = 16,
        mesh: Optional[ServeMesh] = None,
        degradable: bool = False,
        corruption_profile: Optional[str | CorruptionProfile] = None,
        corruption_seed: int = 0,
        max_reread: int = 2,
        corruption_recover: bool = True,
    ):
        """``backend``: the decode EXECUTION backend for the planned decode
        path (kernels/backend.py) — ``"reference"`` computes the masked
        projections as the kernels' pure-jnp schedule twin, ``"kernel"``
        dispatches the PR-4 DMA gather kernels off the plan's chunk tables
        (``chunk_gather_mlp_dma`` for the SwiGLU MLP,
        ``chunk_gather_matmul_dma`` for single-site projections). The two
        are bitwise identical; an ``ExecutionBackend`` instance may be
        passed directly. ``kernel_prefetch_depth`` is the DMA kernels' VMEM
        slot count − 1 (numerics are depth-invariant);
        ``kernel_interpret=None`` compiles the kernels on TPU and interprets
        them on the CPU test platform (``resolve_interpret``).

        ``cache_mb``: DRAM byte budget of the dynamic chunk residency
        cache (paper §5 "Leveraging Additional Memory Budget"). When > 0,
        the decode plan carries a per-(layer, site) residency score vector;
        selection becomes marginal-cost aware (resident rows are free),
        refresh steps insert the selected chunks and evict by decayed
        importance rank when over budget, and the I/O estimate charges only
        cache-miss rows. Capacity is resolved per layer in ``init_plan``.

        ``cached``: per-site bool masks of neurons whose weights are
        memory-resident (the static §5 experiment). With ``cache_mb == 0``
        this is the legacy static path: they get ZERO importance for
        selection (never loaded from flash) but always participate in
        compute. With ``cache_mb > 0`` the masks are re-expressed as
        residency state that is pre-warmed and pinned (PIN_SCORE — never
        evicted, clipped to the byte budget).

        ``wbits``: offloaded chunk storage width — 16 (fp16 payload) or 8
        (int8 payload + per-block f32 scales, kernels/quantize.py). At 8
        every byte figure in the system (selector utilities, latency
        tables, residency budget, ``IOEvent.nbytes``) prices the quantized
        row, so the same I/O budget admits ~2x the rows.

        ``degradable``: adaptive-degradation support (serving/degrade.py).
        When True, ``init_plan`` adds a per-layer "bscale" leaf to every
        site entry — a traced multiplier on the selection budgets that the
        engine's ``DegradationController`` tightens while the storage
        device is degraded (fewer selected rows ⇒ fewer streamed bytes,
        leaning on residency-cache hits) and relaxes on recovery. At the
        default scale 1.0 the effective budgets are bit-exact the static
        ones, and with ``degradable=False`` (default) the plan pytree
        structure is exactly the pre-degradation one.

        ``corruption_profile`` / ``corruption_seed`` / ``max_reread`` /
        ``corruption_recover``: data-plane corruption injection
        (core/faults.py CorruptionModel). When the named profile actually
        corrupts (``p_block > 0``), every plan refresh draws per-matrix
        corrupt blocks among the rows FETCHED from flash, verifies them
        against the pack-time checksum lane (``block_checksums``), and —
        with recovery on — walks the detection/recovery ladder: bounded
        re-reads (seconds surfaced through the plan's ``crr_s`` lane),
        then the resident DRAM copy from the previous refresh epoch, then
        next-best-chunk substitution with a budget rebate, then drop.
        With recovery OFF the drawn corruption pattern is carried in the
        plan ("cblk") and applied to the weight payload at the gather
        boundary by ``apply_corruption`` — tokens CAN change, identically
        on both backends. Requires a selecting method, no reorderings and
        the unsharded mesh. ``None``/"none" ⇒ bit-identical behavior to a
        build without the integrity subsystem.

        ``mesh``: the serve-stack (data, model) mesh context
        (sharding/serve.py). Selection stays REPLICATED — importance
        vectors are constrained to full replication before any cross-batch
        reduction so every shard selects identical chunks — while the
        row-sharded sites' I/O splits by each model shard's contiguous row
        slice (``miss_shard``/``hit_shard`` plan lanes; byte totals sum to
        the unsharded figures). Defaults to the unsharded 1×1 mesh."""
        validate_method(method)
        if cache_mb < 0:
            raise ValueError(f"cache_mb must be >= 0, got {cache_mb}")
        if wbits not in WBITS_CHOICES:
            raise ValueError(
                f"wbits must be one of {WBITS_CHOICES}, got {wbits!r}"
            )
        self.wbits = int(wbits)
        self.cfg = cfg
        self.method = method
        self.mesh = mesh if mesh is not None else ServeMesh.single()
        if self.mesh.is_sharded and reorderings:
            raise ValueError(
                "sharded serving does not support reorderings: per-shard "
                "block tables and byte counters assume selection row order "
                "equals storage row order (pre-reorder the stored weights "
                "offline, or serve on the 1x1 mesh)"
            )
        self.reorderings = reorderings or {}
        self.cached = cached or {}
        self.degradable = bool(degradable)
        # data-plane corruption injection (PR 9): profile "none" (or None)
        # resolves to NO model at all, so the integrity-off refresh path is
        # bit-identical to a build without the subsystem
        self.corruption: Optional[CorruptionModel] = None
        if corruption_profile is not None:
            cm = CorruptionModel(
                corruption_profile, seed=corruption_seed,
                max_reread=max_reread, recover=corruption_recover,
            )
            if cm.enabled:
                if cfg.is_latent:
                    raise ValueError(
                        "corruption injection does not cover the latent-"
                        "attention family: its sites stream other matrices")
                if method not in ("chunk", "topk"):
                    raise ValueError(
                        "corruption injection needs a selecting method "
                        "('chunk' | 'topk') whose recovery ladder can edit "
                        f"the chunk plan, got {method!r}"
                    )
                if self.mesh.is_sharded:
                    raise ValueError(
                        "corruption injection does not support sharded "
                        "serving: the recovery ladder edits per-shard block "
                        "tables it cannot see (serve on the 1x1 mesh)"
                    )
                if reorderings:
                    raise ValueError(
                        "corruption injection does not support reorderings: "
                        "the rung-1 resident-copy check assumes selection "
                        "row order equals storage row order"
                    )
                self.corruption = cm
        self.cache_mb = float(cache_mb)
        self.cache_caps: Optional[Dict[str, int]] = None  # set by init_plan
        sp = normalize_site_sparsity(sparsity)
        # site geometry (which matrices share which mask) comes from the
        # shared table in core.offload so the overlap pipeline's compute
        # lane (ComputeModel.decode_layer_seconds) can never drift from it
        self.sites: Dict[str, _Site] = {
            kind: _site(n, cols, device, sp[kind], self.wbits)
            for kind, n, cols in decode_site_shapes(cfg)
        }
        if self.corruption is not None:
            # the checksum lane is one u32 per KERNEL_BLOCK_ROWS rows — the
            # integrity draw/verify needs whole blocks on EVERY backend
            # (the kernel backend validates this anyway; reference doesn't)
            for kind, site in self.sites.items():
                if site.n % KERNEL_BLOCK_ROWS:
                    raise ValueError(
                        f"corruption injection needs site {kind!r} input "
                        f"dim {site.n} divisible by "
                        f"block_rows={KERNEL_BLOCK_ROWS}"
                    )
        # per-shard I/O geometry: the sites whose STREAMED row dim shards
        # over the model axis ('attn_out' streams wo rows, 'ffn' streams
        # w_down/w_proj rows) get data-dependent per-shard miss counters —
        # shard s owns contiguous rows [s*n/S, (s+1)*n/S). The col-sharded
        # sites' rows replicate, so their bytes split evenly instead.
        self.n_shards = self.mesh.model if self.mesh.is_sharded else 1
        self.row_shards: Dict[str, int] = {
            kind: (self.mesh.row_shard_count(site.n)
                   if kind in ("attn_out", "ffn") else 1)
            for kind, site in self.sites.items()
        }
        # static `cached` masks re-expressed in SELECTION (reordered) row
        # order: the pre-warmed, pinned portion of the dynamic residency tier
        self.pinned_sel: Dict[str, jnp.ndarray] = {}
        for kind, cm in self.cached.items():
            if kind not in self.sites:
                continue
            cv = cm.astype(jnp.float32)
            if kind in self.reorderings:
                cv = self.reorderings[kind].apply_to_acts(cv)
            self.pinned_sel[kind] = cv > 0.0
        # the planned decode path batches all sites of a layer into one
        # selection dispatch (one vmapped greedy instead of one per site)
        self.site_order: Tuple[str, ...] = tuple(self.sites)
        self.batched = BatchedChunkSelector.build(
            [self.sites[k].selector for k in self.site_order]
        )
        self._budgets = jnp.asarray(
            [int(self.sites[k].budget()) for k in self.site_order], jnp.int32
        )
        # the layers each site spans, and one batched selector per set of
        # sites a layer refreshes together (one set, all sites, except in
        # the latent family: dense layers and expert layers differ, and the
        # expert site selects inside its step)
        self.site_span = {kind: site_layers(cfg, kind) for kind in self.sites}
        self._groups = {self.site_order: (self.batched, self._budgets)}
        for layer in range(cfg.n_layers):
            order = tuple(k for k in self.site_order
                          if layer in self.site_span[k] and k not in IN_STEP_SITES)
            if order not in self._groups:
                self._groups[order] = (
                    BatchedChunkSelector.build([self.sites[k].selector for k in order]),
                    jnp.asarray([int(self.sites[k].budget()) for k in order], jnp.int32))
        # padded kernel chunk-table length: worst case every block its own
        # chunk (masks_to_block_tables pads every site's table to this)
        self.kernel_k = -(-self.batched.n_max // KERNEL_BLOCK_ROWS)
        # the decode execution backend (reference schedule twin vs DMA
        # kernels) — the planned decode path computes through it
        if isinstance(backend, ExecutionBackend):
            if self.mesh.is_sharded and backend.mesh is None:
                # the backend's operand all-gather is what keeps sharded
                # decode bitwise — never let a pre-built backend skip it
                backend = dataclasses.replace(backend, mesh=self.mesh.mesh)
            self.backend = backend
        else:
            self.backend = ExecutionBackend.create(
                backend,
                prefetch_depth=kernel_prefetch_depth,
                interpret=kernel_interpret,
                block_rows=KERNEL_BLOCK_ROWS,
                mesh=self.mesh.mesh,
            )
        if self.backend.is_kernel:
            self._validate_kernel_backend(cfg)

    def _validate_kernel_backend(self, cfg: ModelConfig) -> None:
        """The DMA gather kernels' static preconditions, checked up front so
        a misconfigured engine fails at construction, not mid-scan."""
        if self.reorderings:
            raise ValueError(
                "backend='kernel' does not support reorderings: the kernels "
                "gather weight rows by storage offset, so reordered "
                "selection-order chunk tables would index the wrong rows of "
                "the original-order weights (pre-reorder the stored weights "
                "offline, or use backend='reference')"
            )
        # every decode site dispatches through the kernels now: hidden_attn's
        # q/k/v and attn_out's wo via chunk_gather_matmul_dma, the MLP
        # matrices via the fused chunk_gather_mlp_dma (or matmul_dma for the
        # non-gated gelu family) — so all site geometries are constrained.
        kernel_sites = ("hidden_attn", "attn_out", "hidden_mlp", "ffn", "expert_ffn")
        for kind, n, cols in decode_site_shapes(cfg):
            if kind not in kernel_sites:
                continue
            if n % KERNEL_BLOCK_ROWS:
                raise ValueError(
                    f"backend='kernel' needs site {kind!r} input dim {n} "
                    f"divisible by block_rows={KERNEL_BLOCK_ROWS}"
                )
            for c in cols:
                pick_tile(c)  # raises if no power-of-two tile >= 8 divides

    # -- chunk integrity (PR 9) ------------------------------------------------
    @property
    def integrity_enabled(self) -> bool:
        """True when data-plane corruption injection is active: plan
        refreshes draw/verify corrupt blocks and carry integrity lanes."""
        return self.corruption is not None

    @property
    def integrity_corrupting(self) -> bool:
        """True in the recovery-OFF mode: the drawn corruption pattern is
        carried in the plan ("cblk") and must be applied to the weight
        payloads at the gather boundary (``apply_corruption``)."""
        return self.corruption is not None and not self.corruption.recover

    def site_matrix_count(self, kind: str) -> int:
        """How many stored matrices actually stream through a site — the
        width of the integrity lanes. The non-gated gelu family's
        hidden_mlp site streams ONE matrix (w_fc) even though the latency
        geometry prices two lanes (decode_site_shapes)."""
        if kind == "hidden_attn":
            return 3  # wq, wk, wv
        if kind == "hidden_mlp" and self.cfg.mlp == "gelu":
            return 1  # w_fc
        if kind == "hidden_mlp":
            return 2  # w_gate, w_up
        return 1  # attn_out: wo; ffn: w_down / w_proj

    def apply_corruption(self, plan, kind: str, matrix_idx: int, w):
        """Recovery-OFF data plane: damage one (N, D) weight payload with
        the corruption pattern the last refresh drew for it (the plan's
        "cblk" lane) — re-deriving the exact bit/element draws from the
        same (seed, layer, epoch, site, matrix) key. Both execution
        backends consume the identical damaged operand, so even corrupted
        tokens stay byte-identical across backends. No-op (returns ``w``)
        unless ``integrity_corrupting``."""
        if not self.integrity_corrupting or kind not in plan:
            return w
        cm = self.corruption
        entry = plan[kind]
        key = corruption_key(
            cm.base_key(), entry["lid"], entry["epoch"],
            self.site_order.index(kind), matrix_idx,
        )
        return cm.corrupt_payload(
            w, entry["cblk"][matrix_idx], key, KERNEL_BLOCK_ROWS
        )

    def mask(self, kind: str, acts: jnp.ndarray):
        """acts (..., N) → (mask (N,) float or None, est latency seconds)."""
        site = self.sites.get(kind)
        if site is None:
            return None, jnp.float32(0.0)
        if self.method == "dense":
            return None, jnp.float32(site.dense_latency)
        return self._compute_mask(kind, site, acts)

    @jax.named_scope("decode.importance")
    def record_importance(self, kind: str, acts: jnp.ndarray, plan):
        """Stash this site's current-step importance (selection row order)
        into the plan carry as the ``pending`` vector the NEXT refresh
        step's batched selection will consume. Runs every planned decode
        step (cheap — one |·| reduction + optional gather; no selection)."""
        if kind not in plan:
            return plan
        from ..core.importance import importance

        # replicate BEFORE the cross-batch reduction inside importance():
        # on a data-sharded batch an unconstrained mean would let GSPMD
        # psum partial sums per shard — a different f32 summation order
        # than the 1x1 mesh, breaking bitwise token identity. With the
        # explicit constraint every shard reduces the full batch in the
        # single-device order. No-op on the unsharded path.
        acts = self.mesh.replicate(acts)
        v = importance(acts)
        if kind in self.reorderings:
            v = self.reorderings[kind].apply_to_acts(v)
        entry = dict(plan[kind])
        entry["pending"] = v
        new_plan = dict(plan)
        new_plan[kind] = entry
        return new_plan

    @jax.named_scope("decode.select")
    def refresh_layer(self, plan, refresh: jnp.ndarray, weights=None):
        """One batched refresh for ALL of a layer's sites — the planned
        decode path's replacement for per-site selection calls.

        ``plan`` is one layer's slice of the decode-plan carry
        ({site: {mask, pending, hit, miss, bytes[, score]}}, see
        ``init_plan``). When ``refresh`` is true, the sites' ``pending``
        importance vectors (recorded on the previous step) are padded into
        one (n_sites, N_max) problem and solved by a single vmapped greedy
        (``BatchedChunkSelector.select``; vmapped ``topk_mask`` for the
        topk baseline) — with the residency tier enabled the selection is
        marginal-cost aware and only cache-miss rows are charged. The new
        masks (original row order) land in the plan together with the
        updated residency scores and hit/miss/bytes counters. On reuse
        steps ``lax.cond`` skips everything and the cached masks cost ZERO
        I/O — their chunks are still resident from the refresh that
        selected them.

        ``weights`` (integrity mode only): {site: ((payload, checksums),
        ...)} — each site's stored payload matrices with their pack-time
        checksum lanes, in site matrix order. Each refresh draws corrupt
        blocks among the FETCHED rows, re-verifies the damaged payload
        against the checksums (identical jnp verdict computation for both
        execution backends), and with recovery on walks the ladder:
        re-read (charged through the "crr_s" plan lane, never the returned
        estimate — plan-vs-reality separation, exactly like FaultModel) →
        rung-1 resident DRAM copy (every fetched row of the block was in
        the previous epoch's mask, so the working copy still holds it) →
        rung-2 substitution of the next-best non-selected rows by pending
        importance (the budget rebate: row count never grows) → rung-3
        drop. Substituted rows are charged as fresh fetches; removed rows'
        wasted reads stay charged (they really streamed).

        Returns (new_plan, est_io_latency_seconds for this layer).
        """
        if not plan:
            return plan, jnp.float32(0.0)
        # lanes of the batched problem are indexed by position in the site
        # set — a partial plan would silently misalign budgets/schedules/
        # tables, so require exactly one of the sets a layer refreshes
        order = tuple(k for k in self.site_order
                      if k in plan and k not in IN_STEP_SITES)
        if set(plan) - set(IN_STEP_SITES) != set(order) or order not in self._groups:
            raise ValueError(
                f"refresh_layer needs a plan entry per site of one of "
                f"{tuple(self._groups)}, got {tuple(plan)}"
            )
        batched, group_budgets = self._groups[order]
        cache = self.cache_enabled
        integ = self.integrity_enabled
        if integ:
            if weights is None:
                raise ValueError(
                    "corruption injection is on but refresh_layer got no "
                    "weights — the planned decode path must pass each "
                    "site's (payload, checksums) matrices"
                )
            for kind in order:
                want = self.site_matrix_count(kind)
                got = len(weights.get(kind, ()))
                if got != want:
                    raise ValueError(
                        f"site {kind!r} streams {want} matrices, integrity "
                        f"weights carry {got}"
                    )

        def _refresh(_):
            vs = jnp.zeros((batched.n_sites, batched.n_max), jnp.float32)
            residents = []
            for i, kind in enumerate(order):
                site = self.sites[kind]
                v = plan[kind]["pending"]
                pinned = self.pinned_sel.get(kind)
                if pinned is not None and not cache:
                    # legacy static §5 path (cache_mb == 0): memory-resident
                    # neurons get ZERO importance — never streamed — and are
                    # OR'd into the compute mask below, exactly like the
                    # unplanned _compute_mask path
                    v = jnp.where(pinned, 0.0, v)
                vs = vs.at[i, : site.n].set(v)
                if cache:
                    residents.append(
                        residency_from_score(plan[kind]["score"], self._cap(kind))
                    )
            if cache:
                res_pad = jnp.zeros((batched.n_sites, batched.n_max), bool)
                for i, kind in enumerate(order):
                    res_pad = res_pad.at[i, : self.sites[kind].n].set(residents[i])
            else:
                res_pad = None
            # degradable plans carry a traced per-layer budget multiplier
            # ("bscale"): the DegradationController's lever on the selected
            # row count. floor(b × 1.0) == b exactly (site sizes ≪ 2^24 are
            # f32-exact), so scale 1.0 is bit-identical to the static
            # budgets; the clip keeps at least one row selected per site.
            bscale = plan[order[0]].get("bscale")
            if bscale is None:
                budgets = group_budgets
            else:
                budgets = jnp.clip(
                    jnp.floor(
                        group_budgets.astype(jnp.float32) * bscale
                    ).astype(jnp.int32),
                    jnp.minimum(group_budgets, 1),
                    group_budgets,
                )
            if self.method == "topk":
                # LLM-in-a-flash-style baseline: selection ignores residency
                # (pure importance rank); only the I/O charge sees the cache.
                masks = jax.vmap(topk_mask)(vs, budgets)
                masks = masks & batched.row_valid
            else:
                masks, _ = batched.select(vs, budgets, res_pad)

            # -- chunk integrity (PR 9): draw → verify → recovery ladder ----
            # Runs between selection and the chunk-table build so rung 2/3
            # edits land in the tables both backends consume. Everything is
            # shared jnp — the verdicts are bitwise identical across
            # backends by construction.
            icnt: Dict[str, Dict[str, jnp.ndarray]] = {}
            fetch_masks = {}
            if integ:
                cm = self.corruption
                base = cm.base_key()
                lid = plan[order[0]]["lid"]
                epoch_new = plan[order[0]]["epoch"] + jnp.int32(1)
                for i, kind in enumerate(order):
                    site = self.sites[kind]
                    n = site.n
                    nb = n // KERNEL_BLOCK_ROWS
                    m = masks[i, :n]
                    res = (residents[i] if cache
                           else jnp.zeros((n,), bool))
                    # only rows that actually touch the storage data plane
                    # this epoch can arrive corrupted
                    fetched = m & ~res
                    fetched_blk = jnp.any(
                        fetched.reshape(nb, KERNEL_BLOCK_ROWS), axis=1
                    )
                    # rung-1 eligibility: every fetched row of the block was
                    # in the previous epoch's mask, so its clean bytes are
                    # still in the DRAM working copy (weights are static)
                    prev = plan[kind]["mask"] > 0.0
                    prev_cover = jnp.all(
                        (~fetched | prev).reshape(nb, KERNEL_BLOCK_ROWS),
                        axis=1,
                    )
                    cdet = jnp.float32(0.0)
                    crec = jnp.float32(0.0)
                    crr = jnp.float32(0.0)
                    crr_s = jnp.float32(0.0)
                    unrec_bad = jnp.zeros((nb,), bool)
                    cblks = []
                    for mi, (w_m, ck_m) in enumerate(weights[kind]):
                        key = corruption_key(base, lid, epoch_new, i, mi)
                        corrupt = cm.draw_blocks(key, fetched_blk)
                        damaged = cm.corrupt_payload(
                            w_m, corrupt, key, KERNEL_BLOCK_ROWS
                        )
                        # the honest verify: checksum the bytes the fetch
                        # delivered against the pack-time lane (a zeroed
                        # all-zero block is undetectable AND harmless)
                        det = corrupt & (
                            block_checksums(damaged, KERNEL_BLOCK_ROWS)
                            != ck_m
                        )
                        cdet += jnp.sum(det).astype(jnp.float32)
                        if cm.recover:
                            rr, rec = cm.draw_rereads(key, det)
                            tbl = site.tables[
                                min(mi, len(site.tables) - 1)
                            ]
                            crr += jnp.sum(rr).astype(jnp.float32)
                            crr_s += (
                                jnp.sum(rr).astype(jnp.float32)
                                * tbl.lookup(KERNEL_BLOCK_ROWS).astype(
                                    jnp.float32
                                )
                                + jnp.sum(cm.backoff_seconds(rr))
                            )
                            crec += jnp.sum(rec).astype(jnp.float32)
                            unrec = det & ~rec
                            # rung 1: serve the resident DRAM copy
                            crec += jnp.sum(
                                unrec & prev_cover
                            ).astype(jnp.float32)
                            unrec_bad = unrec_bad | (unrec & ~prev_cover)
                        else:
                            # recovery off: the damage flows to compute —
                            # carry the drawn pattern for apply_corruption
                            cblks.append(corrupt)
                    csub = jnp.float32(0.0)
                    cdrop = jnp.float32(0.0)
                    m_fetch = m
                    if cm.recover:
                        # rungs 2/3: a block unreadable in ANY matrix takes
                        # the whole site's rows with it (matrices share the
                        # mask); substitute the next-best non-selected rows
                        # by pending importance — candidates exclude the
                        # unreadable blocks themselves — and drop whatever
                        # the candidate pool cannot cover
                        removed = fetched & jnp.repeat(
                            unrec_bad, KERNEL_BLOCK_ROWS
                        )
                        k = jnp.sum(removed).astype(jnp.int32)
                        cand = ~m & ~jnp.repeat(
                            unrec_bad, KERNEL_BLOCK_ROWS
                        )
                        rank = (
                            jnp.zeros((n,), jnp.int32)
                            .at[jnp.argsort(jnp.where(
                                cand, -plan[kind]["pending"], jnp.inf
                            ))]
                            .set(jnp.arange(n, dtype=jnp.int32))
                        )
                        sub = cand & (rank < k)
                        csub = jnp.sum(sub).astype(jnp.float32)
                        cdrop = k.astype(jnp.float32) - csub
                        # the budget rebate: |final| = |m| - dropped ≤ |m|
                        masks = masks.at[i, :n].set((m & ~removed) | sub)
                        # substitutes are fresh fetches; the removed rows'
                        # wasted reads really streamed, so both stay charged
                        m_fetch = m | sub
                    fetch_masks[kind] = m_fetch
                    entry = {"epoch": epoch_new, "cdet": cdet,
                             "crec": crec, "csub": csub, "cdrop": cdrop,
                             "crr": crr, "crr_s": crr_s}
                    if not cm.recover:
                        entry["cblk"] = jnp.stack(cblks)
                    icnt[kind] = entry

            # the kernel gather plan: every site's COMPUTE mask (selection /
            # storage row order; legacy static-resident rows participate in
            # compute, so they join the gather) → block-aligned chunk tables
            # in ONE vmapped dispatch — no per-site host re-splitting
            tbl_masks = masks
            for i, kind in enumerate(order):
                pinned = self.pinned_sel.get(kind)
                if pinned is not None and not cache:
                    site_n = self.sites[kind].n
                    tbl_masks = tbl_masks.at[i, :site_n].set(
                        tbl_masks[i, :site_n] | pinned
                    )
            if batched.n_max < self.batched.n_max:
                # every site's table has the plan's one length
                tbl_masks = jnp.pad(
                    tbl_masks, ((0, 0), (0, self.batched.n_max - batched.n_max)))
            kstarts, ksizes = masks_to_block_tables(
                tbl_masks, KERNEL_BLOCK_ROWS, KERNEL_MAX_CHUNK_ROWS
            )

            lat = jnp.float32(0.0)
            outs = {}
            for i, kind in enumerate(order):
                site = self.sites[kind]
                m = masks[i, : site.n]
                res = residents[i] if cache else jnp.zeros((site.n,), bool)
                # integrity mode: I/O is charged for the rows that actually
                # streamed (original selection + rung-2 substitutes; the
                # dropped rows' wasted reads included) while ``m`` is the
                # post-ladder COMPUTE mask; identical to ``m`` otherwise
                mf = fetch_masks[kind] if integ else m
                for t in site.tables:
                    # one coalesced request per selected run, charged for
                    # miss rows only (resident rows never fragment it)
                    lat += t.mask_latency_miss(mf, res) if cache else t.mask_latency(mf)
                hit = jnp.sum(m & res).astype(jnp.float32)
                miss = jnp.sum(mf & ~res).astype(jnp.float32)
                nbytes = miss * jnp.float32(self.site_row_bytes(kind))
                ns = self.row_shards[kind]
                if ns > 1:
                    # which model shard each miss row streams FROM: shard s
                    # owns contiguous rows [s*n/S, (s+1)*n/S) — counted here
                    # in selection (== storage) row order, the order the
                    # sharded path guarantees (reorderings are rejected)
                    seg = site.n // ns
                    hit_shard = jnp.sum(
                        (m & res).reshape(ns, seg), axis=1
                    ).astype(jnp.float32)
                    miss_shard = jnp.sum(
                        (m & ~res).reshape(ns, seg), axis=1
                    ).astype(jnp.float32)
                if cache:
                    # recency/score eviction state: decay all, reinforce selected
                    score = RESIDENCY_DECAY * plan[kind]["score"] + jnp.where(
                        m, plan[kind]["pending"], 0.0
                    )
                    pinned = self.pinned_sel.get(kind)
                    if pinned is not None:
                        score = jnp.where(pinned, PIN_SCORE, score)
                else:
                    score = None
                if kind in self.reorderings:
                    inv = jnp.asarray(self.reorderings[kind].inverse)
                    m = jnp.take(m, inv, axis=0)
                cached_orig = self.cached.get(kind)
                if cached_orig is not None and not cache:
                    m = m | cached_orig  # cached neurons always compute, free
                entry = {"mask": m.astype(jnp.float32), "hit": hit,
                         "miss": miss, "bytes": nbytes,
                         "kstarts": kstarts[i], "ksizes": ksizes[i]}
                if ns > 1:
                    entry["hit_shard"] = hit_shard
                    entry["miss_shard"] = miss_shard
                if cache:
                    entry["score"] = score
                if integ:
                    entry.update(icnt[kind])
                outs[kind] = entry
            return outs, lat

        def _reuse(_):
            zero = jnp.float32(0.0)
            outs = {}
            for kind in order:
                entry = {"mask": plan[kind]["mask"], "hit": zero,
                         "miss": zero, "bytes": zero,
                         "kstarts": plan[kind]["kstarts"],
                         "ksizes": plan[kind]["ksizes"]}
                ns = self.row_shards[kind]
                if ns > 1:
                    entry["hit_shard"] = jnp.zeros((ns,), jnp.float32)
                    entry["miss_shard"] = jnp.zeros((ns,), jnp.float32)
                if cache:
                    entry["score"] = plan[kind]["score"]
                if integ:
                    # no fetch ⇒ no new corruption: the epoch (and, with
                    # recovery off, the damaged DRAM copy's "cblk" pattern)
                    # carries over unchanged until the next refresh
                    entry["epoch"] = plan[kind]["epoch"]
                    for key in INTEGRITY_COUNTER_KEYS:
                        entry[key] = zero
                    if "cblk" in plan[kind]:
                        entry["cblk"] = plan[kind]["cblk"]
                outs[kind] = entry
            return outs, jnp.float32(0.0)

        results, lat = jax.lax.cond(refresh, _refresh, _reuse, None)
        new_plan = dict(plan)
        for kind in order:
            entry = dict(plan[kind])
            entry["mask"] = results[kind]["mask"]
            entry["hit"] = plan[kind]["hit"] + results[kind]["hit"]
            entry["miss"] = plan[kind]["miss"] + results[kind]["miss"]
            entry["bytes"] = plan[kind]["bytes"] + results[kind]["bytes"]
            if "hit_shard" in results[kind]:
                entry["hit_shard"] = (
                    plan[kind]["hit_shard"] + results[kind]["hit_shard"]
                )
                entry["miss_shard"] = (
                    plan[kind]["miss_shard"] + results[kind]["miss_shard"]
                )
            entry["kstarts"] = results[kind]["kstarts"]
            entry["ksizes"] = results[kind]["ksizes"]
            if KERNEL_COUNTER_KEYS[0] in entry:
                # the kernels walk the tables every step, refreshed or reused
                counts = superblock_counts(
                    entry["kstarts"], entry["ksizes"], self.sites[kind].n,
                    KERNEL_BLOCK_ROWS).astype(jnp.float32)
                for j, key in enumerate(KERNEL_COUNTER_KEYS):
                    entry[key] = plan[kind][key] + counts[j]
            if cache:
                entry["score"] = results[kind]["score"]
            if integ:
                entry["epoch"] = results[kind]["epoch"]
                for key in INTEGRITY_COUNTER_KEYS:
                    entry[key] = plan[kind][key] + results[kind][key]
                if "cblk" in results[kind]:
                    entry["cblk"] = results[kind]["cblk"]
            new_plan[kind] = entry
        return new_plan, lat

    def _compute_mask(self, kind: str, site: _Site, acts: jnp.ndarray):
        from ..core.importance import importance

        # same replication-before-reduction contract as record_importance
        # (this is the unplanned mask() path used by frame append)
        acts = self.mesh.replicate(acts)
        v = importance(acts)
        if kind in self.reorderings:
            v = self.reorderings[kind].apply_to_acts(v)
        cached = self.cached.get(kind)
        if cached is not None:
            cv = cached
            if kind in self.reorderings:
                cv = self.reorderings[kind].apply_to_acts(
                    cv.astype(jnp.float32)
                ).astype(bool)
            v = jnp.where(cv, 0.0, v)  # resident weights cost no I/O

        with jax.named_scope("decode.select"):
            if self.method == "topk":
                m = topk_mask(v, site.budget())
            else:
                m, _, _ = site.selector.select(v, site.budget())
        lat = jnp.float32(0.0)
        for t in site.tables:
            lat += t.mask_latency(m)
        if kind in self.reorderings:
            # map mask back to original row order for application to acts
            inv = jnp.asarray(self.reorderings[kind].inverse)
            m = jnp.take(m, inv, axis=0)
        if cached is not None:
            m = m | cached  # cached neurons always compute, at zero I/O
        return m.astype(jnp.float32), lat

    # -- kernel chunk-table plumbing -----------------------------------------
    def kernel_tables(self, plan, kind: str):
        """One site's kernel chunk tables from a decode-plan pytree:
        (starts, sizes), each (n_layers, kernel_k) — or (kernel_k,) for a
        single layer's slice — in selection (storage) row order, block
        aligned, directly consumable by the DMA gather kernels."""
        if kind not in plan:
            raise KeyError(f"no plan entry for site {kind!r}")
        return plan[kind]["kstarts"], plan[kind]["ksizes"]

    def mlp_kernel_plan(self, plan, layer: Optional[int] = None):
        """The fused multi-site MLP kernel's (2, kernel_k) plan lanes —
        lane 0 = hidden_mlp (gate/up), lane 1 = ffn (down) — stacked
        straight from the batched refresh's tables, no re-splitting.
        ``layer`` selects one layer of an (L, K) plan; None expects a
        single-layer slice."""
        for kind in ("hidden_mlp", "ffn"):
            if kind not in plan:
                raise KeyError(
                    f"plan has no {kind!r} site: this layer has no dense MLP "
                    "(routed experts run through run_experts)"
                )
        hs, hz = self.kernel_tables(plan, "hidden_mlp")
        fs, fz = self.kernel_tables(plan, "ffn")
        if layer is not None:
            hs, hz, fs, fz = hs[layer], hz[layer], fs[layer], fz[layer]
        return jnp.stack([hs, fs]), jnp.stack([hz, fz])

    # -- routed experts: selected at two levels inside the step ---------------
    def run_experts(self, plan, h, gates, stacks, layer):
        """One expert layer of the planned decode: the held experts some
        token of the step was routed to run, the others cost nothing; the
        importance of their rows then keeps the top (1 − sparsity) share of
        them for the down projection.

        ``h`` (B, d) the layer's expert input, ``gates`` (B, E) each token's
        weight on each held expert (0 if not chosen), ``stacks`` the whole
        stacked expert weights (``we_gate``/``we_up`` (L, E, d, F),
        ``we_down`` (L, E, F, d)), ``layer`` the index into them. The up
        phase reads each routed expert's gate and up whole: a row's
        importance, mean over the batch of |silu(gate)·up·weight|, needs
        them; a row of an unrouted expert has importance exactly 0. The
        budget keeps ``budget · routed // E`` rows among the routed experts'
        (top-k, ties to the lower row), and the down phase gathers those.
        The entry's mask also marks every row of an unrouted expert: none
        is read (its weight is 0), and none is dropped.

        Returns (y (B, d) f32, the plan with this layer's expert entry:
        the mask, the tables of the rows gathered, and the counters)."""
        from ..core.importance import importance

        kind = "expert_ffn"
        site = self.sites[kind]
        b = h.shape[0]
        n_held = gates.shape[1]
        f = site.n // n_held
        routed = jnp.any(gates > 0.0, axis=0)
        n_routed = jnp.sum(routed, dtype=jnp.int32)
        ids = jnp.argsort(~routed, stable=True).astype(jnp.int32)
        act = self.backend.expert_up(stacks["we_gate"], stacks["we_up"], layer,
                                     ids, n_routed, h)
        mid = (act.reshape(b, n_held, f) * gates[:, :, None]).reshape(b, site.n)
        v = importance(self.mesh.replicate(mid))
        rows = jnp.repeat(routed, f)
        k = site.budget() * n_routed // n_held
        if self.method == "topk":
            m = topk_mask(jnp.where(rows, v, -1.0), k)
        else:
            m, _, _ = site.selector.select(v, k)
            m = m & rows
        starts, sizes = masks_to_block_tables(
            m[None], KERNEL_BLOCK_ROWS, KERNEL_MAX_CHUNK_ROWS)
        starts, sizes = starts[0], sizes[0]
        y = self.backend.expert_down(stacks["we_down"], layer, mid, m, starts, sizes)
        # the site's mask marks an unrouted expert's rows kept: their input
        # is 0 here, so nothing is read or computed for them, and a replay
        # under this mask whose router chooses the expert computes it whole
        # instead of dropping it
        keep = m | ~rows
        n_sel = jnp.sum(m).astype(jnp.float32)
        gate_up, down = site.tables[0], site.tables[2]
        lat = 2.0 * gate_up.mask_latency(rows) + down.mask_latency(m)
        entry = dict(plan[kind])
        entry.update(
            mask=keep.astype(jnp.float32), kstarts=starts, ksizes=sizes,
            miss=entry["miss"] + n_sel,
            bytes=entry["bytes"] + (n_sel + 2.0 * f * n_routed) * jnp.float32(
                gate_up.row_bytes),
            experts=entry["experts"] + n_routed.astype(jnp.float32),
            pairs=entry["pairs"] + jnp.sum(gates > 0.0).astype(jnp.float32),
            steps=entry["steps"] + 1.0)
        if KERNEL_COUNTER_KEYS[0] in entry:
            counts = superblock_counts(starts, sizes, site.n,
                                       KERNEL_BLOCK_ROWS).astype(jnp.float32)
            for j, key in enumerate(KERNEL_COUNTER_KEYS):
                entry[key] = entry[key] + counts[j]
        return y, {**plan, kind: entry}, lat

    # -- residency-tier capacity ---------------------------------------------
    @property
    def cache_enabled(self) -> bool:
        """The dynamic residency tier applies to the selecting methods only:
        dense streams every matrix every step regardless of budget."""
        return self.cache_mb > 0 and self.method in ("chunk", "topk")

    def site_row_bytes(self, kind: str) -> float:
        """Total streamed bytes of one row across every matrix sharing the
        site — fractional at wbits=8 (int8 payload + the per-block scale
        overhead amortized over KERNEL_BLOCK_ROWS rows)."""
        return float(sum(t.row_bytes for t in self.sites[kind].tables))

    def _depth(self, kind: str, n_layers: int) -> int:
        """How many of ``n_layers`` layers a site spans: all of them, except
        in the latent family (``site_span``)."""
        return len(self.site_span[kind]) if self.cfg.is_latent else n_layers

    def sparsifiable_bytes(self, n_layers: int) -> float:
        """Total offloaded-weight footprint governed by sparsification."""
        if self.cfg.is_latent:
            return sum(
                self._depth(kind, n_layers) * site.n * self.site_row_bytes(kind)
                for kind, site in self.sites.items()
            )
        return n_layers * sum(
            site.n * self.site_row_bytes(kind) for kind, site in self.sites.items()
        )

    def _resolve_cache(self, n_layers: int) -> Dict[str, int]:
        """Split the byte budget into per-(layer, site) row caps: the same
        fraction of every matrix is cacheable, so cap_rows = frac * N."""
        total = self.sparsifiable_bytes(n_layers)
        frac = min(1.0, self.cache_mb * 1024.0 * 1024.0 / max(total, 1))
        self.cache_caps = {
            kind: int(frac * site.n) for kind, site in self.sites.items()
        }
        return self.cache_caps

    def _cap(self, kind: str) -> int:
        if self.cache_caps is None:
            raise RuntimeError(
                "residency capacity unresolved — call init_plan(n_layers) "
                "before refresh_layer with the residency cache enabled"
            )
        return self.cache_caps[kind]

    def init_plan(self, n_layers: int) -> Dict[str, Any]:
        """Per-layer decode-plan state for the planned decode loops. Empty
        for dense — there is no selection to cache.

        Per site: {"mask": (L, N) float32 [original row order, applied to
        acts], "pending": (L, N) float32 [selection row order — the
        importance recorded last step that the next refresh's batched
        selection consumes; initialized to ones so the first refresh
        bootstraps from uniform importance], "hit"/"miss"/"bytes": (L,)
        float32 counters accumulated across the refresh steps of one engine
        decode call (zeroed per call by ``reset_plan_counters``;
        ``ServeEngine.io_summary`` reads hit/miss back as the residency
        tier's hit rate and ``bytes`` feeds ``IOEvent.nbytes``)}.

        With the residency cache enabled (``cache_mb > 0``) a "score"
        (L, N) eviction state rides along (decayed importance; the resident
        set is its top cap_rows); pre-warmed ``cached`` rows start at
        PIN_SCORE.

        With the kernel backend every site also carries the three
        ``KERNEL_COUNTER_KEYS`` (L,) accumulators of what the gather
        kernels' DMA plans move (zeroed per call like hit/miss).

        With corruption injection on, every site also carries the
        integrity lanes: "lid" (L,) layer ids + "epoch" (L,) refresh
        counters (the corruption key schedule's traced inputs), the six
        ``INTEGRITY_COUNTER_KEYS`` (L,) accumulators, and — recovery OFF
        only — the drawn corrupt-block pattern "cblk"
        (L, n_matrices, n_blocks) that ``apply_corruption`` replays at the
        gather boundary.
        """
        if self.method == "dense":
            return {}
        if self.cache_enabled:
            self._resolve_cache(n_layers)
        plan: Dict[str, Any] = {}
        full = n_layers
        for kind, site in self.sites.items():
            n_layers = self._depth(kind, full)
            if kind in IN_STEP_SITES:
                entry = {
                    "mask": jnp.zeros((n_layers, site.n), jnp.float32),
                    "hit": jnp.zeros((n_layers,), jnp.float32),
                    "miss": jnp.zeros((n_layers,), jnp.float32),
                    "bytes": jnp.zeros((n_layers,), jnp.float32),
                    "kstarts": jnp.zeros((n_layers, self.kernel_k), jnp.int32),
                    "ksizes": jnp.zeros((n_layers, self.kernel_k), jnp.int32),
                }
                for key in MOE_COUNTER_KEYS + (
                        KERNEL_COUNTER_KEYS if self.backend.is_kernel else ()):
                    entry[key] = jnp.zeros((n_layers,), jnp.float32)
                plan[kind] = entry
                continue
            entry = {
                "mask": jnp.zeros((n_layers, site.n), jnp.float32),
                "pending": jnp.ones((n_layers, site.n), jnp.float32),
                "hit": jnp.zeros((n_layers,), jnp.float32),
                "miss": jnp.zeros((n_layers,), jnp.float32),
                "bytes": jnp.zeros((n_layers,), jnp.float32),
                # block-aligned kernel chunk tables (selection row order),
                # refreshed alongside the masks — the DMA gather kernels'
                # direct input (all-zero until the first refresh = no chunks)
                "kstarts": jnp.zeros((n_layers, self.kernel_k), jnp.int32),
                "ksizes": jnp.zeros((n_layers, self.kernel_k), jnp.int32),
            }
            if self.row_shards[kind] > 1:
                # per-model-shard hit/miss row counters (sharded serving):
                # which shard's flash tier each streamed row comes from —
                # summed over shards these equal the scalar hit/miss lanes
                ns = self.row_shards[kind]
                entry["hit_shard"] = jnp.zeros((n_layers, ns), jnp.float32)
                entry["miss_shard"] = jnp.zeros((n_layers, ns), jnp.float32)
            if self.cache_enabled:
                score0 = jnp.zeros((n_layers, site.n), jnp.float32)
                pinned = self.pinned_sel.get(kind)
                if pinned is not None:
                    score0 = jnp.where(pinned[None, :], PIN_SCORE, score0)
                entry["score"] = score0
            if self.backend.is_kernel:
                for key in KERNEL_COUNTER_KEYS:
                    entry[key] = jnp.zeros((n_layers,), jnp.float32)
            if self.degradable:
                # the DegradationController's traced budget multiplier —
                # rewritten between decode calls by set_plan_budget_scale,
                # consumed inside the jitted refresh (1.0 = full budgets)
                entry["bscale"] = jnp.ones((n_layers,), jnp.float32)
            if self.integrity_enabled:
                entry["lid"] = jnp.arange(n_layers, dtype=jnp.int32)
                entry["epoch"] = jnp.zeros((n_layers,), jnp.int32)
                for key in INTEGRITY_COUNTER_KEYS:
                    entry[key] = jnp.zeros((n_layers,), jnp.float32)
                if self.integrity_corrupting:
                    entry["cblk"] = jnp.zeros(
                        (n_layers, self.site_matrix_count(kind),
                         site.n // KERNEL_BLOCK_ROWS),
                        bool,
                    )
            plan[kind] = entry
        return plan

    def plan_shard_bytes(self, plan) -> jnp.ndarray:
        """Per-model-shard flash→DRAM transfer bytes accumulated in a decode
        plan pytree, shape (n_shards,). Row-sharded sites contribute their
        data-dependent ``miss_shard`` counts × per-site row bytes; the
        col-sharded / replicated sites split their byte totals evenly (each
        shard streams 1/n_shards of every replicated row's columns).
        Sums exactly to ``plan_transfer_bytes`` up to f32 round-off —
        the ISSUE's shard-accounting invariant. jit-safe."""
        out = jnp.zeros((self.n_shards,), jnp.float32)
        if not plan:
            return out
        if self.n_shards == 1:
            return out + plan_transfer_bytes(plan)
        for kind in plan:
            state = plan[kind]
            if not isinstance(state, dict) or "bytes" not in state:
                continue
            if "miss_shard" in state:
                rb = jnp.float32(self.site_row_bytes(kind))
                out = out + jnp.sum(
                    state["miss_shard"].reshape(-1, self.n_shards), axis=0
                ) * rb
            else:
                out = out + jnp.sum(state["bytes"]) / self.n_shards
        return out

    def dense_total_latency(self) -> float:
        """Full-load I/O latency per layer (all sites dense)."""
        return float(sum(s.dense_latency for s in self.sites.values()))
