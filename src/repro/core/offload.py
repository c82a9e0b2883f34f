"""Flash-offload I/O simulator.

This container has no NVMe flash (and the TPU target has none either), so the
storage tier is simulated: the simulator "executes" an access pattern against
a DeviceProfile and returns a latency sample that reproduces the behaviour the
paper measures:

  * per-chunk two-regime cost (IOPS-bound → bandwidth-bound), Fig. 3/4a;
  * the near-linear proportional lift between the additive chunk model's
    estimate and real interleaved-pattern latency, Fig. 5
    (``interleave_lift`` + lognormal noise, stronger on low-end devices);
  * the sparsity–latency inversion for scattered access, Fig. 4b.

The simulator is the measurement apparatus for every latency number in
EXPERIMENTS.md that refers to Jetson hardware, and is labeled as such.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .contiguity import Chunk, mask_to_chunks_np
from .faults import FaultModel
from .latency_model import DeviceProfile, get_profile
from .pipeline import PipelineModel


@dataclasses.dataclass
class IOEvent:
    """One simulated weight-matrix load.

    ``nbytes`` is the estimated flash→DRAM transfer volume of the event —
    for the estimate-driven decode paths it is the step's cache-miss rows ×
    per-site row bytes, threaded from the decode-plan counters (it used to
    be logged as 0 there, making ``total_bytes()`` meaningless for the scan
    path). Float because the per-row cost is fractional at wbits=8: int8
    payload plus the per-block quantization scale overhead amortized over
    the rows of a block (latency_model.row_stream_bytes). ``hit_rate`` is
    the DRAM residency-cache hit fraction of the rows the step *selected*
    (hit rows transfer nothing — the event's latency charges only the
    cache-miss bytes). 0.0 when the residency tier is disabled.

    ``shard_bytes`` (sharded serving, sharding/serve.py): the event's
    transfer volume split by the model shard whose flash tier each byte
    streams from — sums to ``nbytes`` up to f32 round-off. None on the
    unsharded path, so single-device event logs are unchanged.

    ``retries`` / ``fault_s`` (fault injection, core/faults.py): transient
    read failures retried on this event, and the extra seconds the fault
    model charged on top of the clean simulated latency (throttle + spikes
    + retries + backoff). Both stay at their defaults with faults disabled,
    so fault-off event logs compare equal to pre-fault builds.

    ``integrity_s`` (chunk integrity, PR 9): the checksum-verified re-read
    seconds the integrity subsystem charged on this event — detected
    payload corruptions re-pay their 8-row-block reads plus exponential
    backoff (serving/sparse_exec.py). 0.0 with corruption injection off,
    so integrity-off event logs compare equal to pre-integrity builds.
    """

    name: str
    nbytes: float
    n_chunks: int
    latency_s: float
    hit_rate: float = 0.0
    shard_bytes: Optional[Tuple[float, ...]] = None
    retries: int = 0
    fault_s: float = 0.0
    integrity_s: float = 0.0


class FlashOffloadSimulator:
    """Simulated flash device with paper-calibrated latency behaviour.

    ``measure(mask, row_bytes)`` returns a latency sample including the
    pattern-dependent effects the additive model deliberately ignores;
    ``estimate`` returns the pure additive-model value. The ratio between the
    two reproduces Fig. 5's proportional bias.
    """

    def __init__(
        self,
        device: str | DeviceProfile,
        seed: int = 0,
        noise: float = 0.04,
        pipeline: Optional[PipelineModel] = None,
        faults: Optional[FaultModel] = None,
    ):
        self.profile = device if isinstance(device, DeviceProfile) else get_profile(device)
        self.rng = np.random.default_rng(seed)
        self.noise = noise
        self.log: List[IOEvent] = []
        # the I/O–compute overlap timeline model the serve engine runs its
        # per-layer simulated latencies through (core/pipeline.py)
        self.pipeline = pipeline or PipelineModel()
        # storage turbulence (core/faults.py), applied at the measurement
        # boundary only — estimates keep planning against the clean table.
        # The model draws from its OWN seeded RNG, so attaching it never
        # shifts this simulator's lift/jitter stream.
        self.faults = faults
        # cumulative charged I/O seconds — the thermal trajectory's clock
        self.device_time_s = 0.0

    def _charge(self, latency_s: float) -> Tuple[float, int, float]:
        """Run one clean measured latency through the fault model (if any)
        and advance the device-busy clock. Returns (charged latency,
        retries, extra fault seconds) for the event log."""
        if self.faults is None or not self.faults.enabled or latency_s <= 0.0:
            self.device_time_s += latency_s
            return latency_s, 0, 0.0
        out = self.faults.perturb(latency_s, self.device_time_s)
        self.device_time_s += out.charged_s
        return out.charged_s, out.retries, out.extra_s

    # -- pure additive model (what the runtime uses) -------------------------
    def estimate_chunks(self, chunks: Sequence[Chunk], row_bytes: int) -> float:
        return float(
            sum(self.profile.latency_bytes(c.size * row_bytes) for c in chunks)
        )

    def estimate(self, mask: np.ndarray, row_bytes: int) -> float:
        return self.estimate_chunks(mask_to_chunks_np(mask), row_bytes)

    # -- simulated "measurement" ---------------------------------------------
    def measure_chunks(
        self, chunks: Sequence[Chunk], row_bytes: int, name: str = ""
    ) -> float:
        base = self.estimate_chunks(chunks, row_bytes)
        n = max(len(chunks), 1)
        # Pattern-dependent controller/queue effects: proportional lift with
        # lognormal jitter; tail effects grow with chunk-count diversity.
        sizes = np.array([c.size for c in chunks]) if chunks else np.array([1])
        diversity = float(np.unique(sizes).size) / n
        lift = self.profile.interleave_lift * (1.0 + 0.1 * diversity)
        jitter = self.rng.lognormal(mean=0.0, sigma=self.noise)
        latency, retries, fault_s = self._charge(base * lift * jitter)
        self.log.append(
            IOEvent(
                name=name,
                nbytes=float(sizes.sum()) * row_bytes,
                n_chunks=len(chunks),
                latency_s=latency,
                retries=retries,
                fault_s=fault_s,
            )
        )
        return latency

    def measure(self, mask: np.ndarray, row_bytes: int, name: str = "") -> float:
        return self.measure_chunks(mask_to_chunks_np(mask), row_bytes, name=name)

    def measure_from_estimate(
        self,
        est_s: float,
        n_chunks: int = 32,
        diversity: float = 0.5,
        name: str = "",
        hit_rate: float = 0.0,
        nbytes: float = 0.0,
        shard_bytes: Optional[Sequence[float]] = None,
        integrity_s: float = 0.0,
    ) -> float:
        """Turn an additive-model estimate (computed inside jit by the
        runtime) into a simulated measurement — same lift + jitter model as
        ``measure_chunks`` without re-deriving the pattern. The estimate
        already charges only cache-miss bytes when the residency tier is
        active; ``hit_rate`` records the tier's hit fraction on the event and
        ``nbytes`` the step's estimated transfer volume (miss rows × row
        bytes, from the decode-plan counters) so ``total_bytes()`` stays
        meaningful on the estimate-driven paths.

        ``integrity_s``: checksum-verified re-read seconds from the chunk
        integrity subsystem, added verbatim on top of the fault-perturbed
        latency (re-reads are deterministic per (profile, seed), so they
        must not consume this simulator's jitter stream). 0.0 leaves the
        charged time — and the RNG stream — bit-identical to pre-integrity
        behaviour; ``io_est_s`` stays the clean planning estimate either
        way."""
        if est_s <= 0.0 and integrity_s <= 0.0:
            return 0.0
        lift = self.profile.interleave_lift * (1.0 + 0.1 * diversity)
        if est_s > 0.0:
            jitter = self.rng.lognormal(mean=0.0, sigma=self.noise)
            latency, retries, fault_s = self._charge(est_s * lift * jitter)
        else:
            latency, retries, fault_s = 0.0, 0, 0.0
        if integrity_s > 0.0:
            latency += float(integrity_s)
            self.device_time_s += float(integrity_s)
        self.log.append(
            IOEvent(name=name, nbytes=float(nbytes), n_chunks=n_chunks,
                    latency_s=latency, hit_rate=float(hit_rate),
                    shard_bytes=(tuple(float(b) for b in shard_bytes)
                                 if shard_bytes is not None else None),
                    retries=retries, fault_s=fault_s,
                    integrity_s=float(integrity_s))
        )
        return latency

    def measure_from_estimate_batch(
        self,
        est_s: np.ndarray,
        n_chunks: int = 32,
        diversity: float = 0.5,
        name: str = "",
        hit_rates: Optional[np.ndarray] = None,
        nbytes: Optional[np.ndarray] = None,
        shard_bytes: Optional[np.ndarray] = None,
        integrity_s: Optional[np.ndarray] = None,
    ) -> np.ndarray:
        """Vectorized ``measure_from_estimate`` for the scan-fused decode
        path: one call consumes the whole (n_steps,) on-device estimate
        array in a single host round-trip. Zero estimates (plan-reuse steps,
        dense_free) stay exactly zero and draw no jitter. Appends one IOEvent
        per step, matching the per-token path's log granularity.

        ``hit_rates`` (optional, (n_steps,)): per-step residency-cache hit
        fraction to record on each logged IOEvent — the estimates themselves
        already charge only cache-miss bytes. ``nbytes`` (optional,
        (n_steps,)): per-step estimated transfer volume from the decode-plan
        counters, recorded on the events for ``total_bytes()``.
        ``shard_bytes`` (optional, (n_steps, n_shards)): each step's volume
        split by source model shard (sharded serving), recorded on the
        events for ``total_bytes_by_shard()``.
        ``integrity_s`` (optional, (n_steps,)): per-step checksum-verified
        re-read seconds from the chunk integrity subsystem, added verbatim
        AFTER the fault perturbation (re-reads are deterministic per
        (profile, seed) and must not consume the jitter or fault RNG
        streams). None keeps pre-integrity behaviour bit-identical."""
        est = np.asarray(est_s, dtype=np.float64).reshape(-1)
        extra = (np.zeros_like(est) if integrity_s is None
                 else np.asarray(integrity_s, dtype=np.float64).reshape(-1))
        lift = self.profile.interleave_lift * (1.0 + 0.1 * diversity)
        # consume the RNG stream and the event log exactly as the scalar
        # path would: one draw + one IOEvent per POSITIVE estimate, in order
        pos = est > 0.0
        jitter = np.ones_like(est)
        jitter[pos] = self.rng.lognormal(
            mean=0.0, sigma=self.noise, size=int(pos.sum())
        )
        latency = np.where(pos, est * lift * jitter, 0.0)
        # faults perturb each positive event sequentially, in log order —
        # the thermal clock advances event by event, as the scalar path does
        for i, lat in enumerate(latency):
            if pos[i] or extra[i] > 0.0:
                if pos[i]:
                    charged, retries, fault_s = self._charge(float(lat))
                else:
                    charged, retries, fault_s = 0.0, 0, 0.0
                if extra[i] > 0.0:
                    charged += float(extra[i])
                    self.device_time_s += float(extra[i])
                latency[i] = charged
                self.log.append(
                    IOEvent(
                        name=f"{name}[{i}]" if name else name,
                        nbytes=float(nbytes[i]) if nbytes is not None else 0.0,
                        n_chunks=n_chunks,
                        latency_s=charged,
                        hit_rate=float(hit_rates[i]) if hit_rates is not None else 0.0,
                        shard_bytes=(tuple(float(b) for b in shard_bytes[i])
                                     if shard_bytes is not None else None),
                        retries=retries,
                        fault_s=fault_s,
                        integrity_s=float(extra[i]),
                    )
                )
        return latency

    def measure_full_load(self, n_rows: int, row_bytes: int, name: str = "") -> float:
        """Dense (no sparsification) load: one saturating sequential read."""
        return self.measure_chunks([Chunk(0, n_rows)], row_bytes, name=name)

    # -- bookkeeping ----------------------------------------------------------
    def total_io_seconds(self) -> float:
        return float(sum(e.latency_s for e in self.log))

    def total_bytes(self) -> float:
        return float(sum(e.nbytes for e in self.log))

    def total_bytes_by_shard(self, n_shards: int) -> Tuple[float, ...]:
        """Lifetime transfer volume split by source model shard. Events
        logged with ``shard_bytes`` contribute their recorded split; events
        without shard info (unsharded paths, legacy callers) split evenly —
        so the tuple always sums to ``total_bytes()`` and degrades to
        ``(total_bytes(),)`` at n_shards=1."""
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        out = np.zeros(n_shards, np.float64)
        for e in self.log:
            if e.shard_bytes is not None:
                if len(e.shard_bytes) != n_shards:
                    raise ValueError(
                        f"event {e.name!r} recorded {len(e.shard_bytes)} "
                        f"shard lanes, asked for {n_shards}"
                    )
                out += np.asarray(e.shard_bytes, np.float64)
            else:
                out += e.nbytes / n_shards
        return tuple(float(b) for b in out)

    def reset(self) -> None:
        self.log.clear()


def pack_checksums(layers, names, block_rows: int = 8):
    """Pack-time integrity lane for fp (unquantized, wbits=16) offloaded
    storage: one ``block_checksums`` uint32 per ``block_rows`` row block of
    each named stacked (L, N, D) fp weight leaf, returned as new
    ``<name>_ck`` leaves (leading L dim preserved so they ride the decode
    ``lax.scan``). The wbits=8 twin is ``quantize_params(checksums=True)``,
    which checksums the int8 payload instead — each width checksums exactly
    the bytes its DMA lane streams. Missing names are skipped."""
    import jax

    from ..kernels.quantize import QUANT_SUFFIX_CHECKSUM, block_checksums

    ck = jax.vmap(lambda w: block_checksums(w, block_rows))
    out = {}
    for name in names:
        if name not in layers:
            continue
        out[name + QUANT_SUFFIX_CHECKSUM] = ck(layers[name])
    return out


SITE_KINDS = ("hidden_attn", "hidden_mlp", "ffn", "attn_out", "expert_ffn")


def normalize_site_sparsity(sparsity) -> dict:
    """A scalar sparsity → the per-site dict form ({kind: fraction} over
    SITE_KINDS); dicts pass through. Shared by SparseExecution and
    ``ComputeModel.decode_layer_seconds`` so the two can't drift."""
    if isinstance(sparsity, dict):
        return sparsity
    return {k: float(sparsity) for k in SITE_KINDS}


def decode_site_shapes(cfg):
    """[(site kind, input rows, output cols per sharing matrix)] for every
    sparsification site of one decoder layer (paper App. A: q/k/v share the
    hidden mask, gate/up share theirs; the capacity-dispatched MoE FFNs
    have no planned site, the latent family's held experts one,
    ``expert_ffn``; ``site_layers`` says which layers each site spans).
    The single source of truth for the site geometry, shared by
    SparseExecution (selection sites + latency tables) and
    ``ComputeModel.decode_layer_seconds`` (the overlap pipeline's compute
    lane) — the two must never drift apart."""
    d = cfg.d_model
    if cfg.is_latent:
        # latent attention: q and the latent share the hidden mask; the
        # leading dense layers' MLP; each held routed expert's F rows of
        # gate, up and down as one row space
        sites = [
            ("hidden_attn", d, (cfg.n_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim),
                                cfg.kv_lora_rank + cfg.qk_rope_head_dim)),
            ("attn_out", cfg.n_heads * cfg.v_head_dim, (d,)),
        ]
        if cfg.first_k_dense:
            sites.append(("hidden_mlp", d, (cfg.dense_d_ff, cfg.dense_d_ff)))
            sites.append(("ffn", cfg.dense_d_ff, (d,)))
        if cfg.n_layers > cfg.first_k_dense:
            sites.append(("expert_ffn", cfg.n_experts * cfg.d_ff, (d, d, d)))
        return sites
    hd_all = cfg.n_heads * cfg.resolved_head_dim
    kv_all = cfg.n_kv_heads * cfg.resolved_head_dim
    sites = [
        ("hidden_attn", d, (hd_all, kv_all, kv_all)),
        ("attn_out", hd_all, (d,)),
    ]
    if cfg.d_ff and not cfg.has_moe:
        sites.append(("hidden_mlp", d, (cfg.d_ff, cfg.d_ff)))
        sites.append(("ffn", cfg.d_ff, (d,)))
    return sites


def site_layers(cfg, kind: str) -> range:
    """The layers a site spans: every layer, except in the latent family,
    whose dense MLP sites span the leading dense layers and whose expert
    site spans the rest."""
    if cfg.is_latent and kind in ("hidden_mlp", "ffn"):
        return range(cfg.first_k_dense)
    if cfg.is_latent and kind == "expert_ffn":
        return range(cfg.first_k_dense, cfg.n_layers)
    return range(cfg.n_layers)


@dataclasses.dataclass
class ComputeModel:
    """First-order compute-time model for the latency breakdown (Fig. 8).

    Edge GPU sustained GEMV throughput; default ≈ Jetson Orin Nano class
    (1.2 TFLOP/s effective fp16 for memory-resident GEMV is optimistic; the
    breakdown only needs relative magnitudes)."""

    flops_per_s: float = 1.2e12

    def matmul_seconds(self, rows_loaded: int, cols: int, tokens: int = 1) -> float:
        return 2.0 * rows_loaded * cols * tokens / self.flops_per_s

    def decode_layer_seconds(
        self, cfg, sparsity=0.0, tokens: int = 1, layer_scale=None
    ) -> np.ndarray:
        """Per-layer decode-step compute seconds, (n_layers,), for the
        active model config — the compute lane of the overlapped I/O–compute
        pipeline (core/pipeline.py).

        Uses the serve stack's sparsification-site geometry
        (``decode_site_shapes`` — the same table SparseExecution builds its
        sites from): each site's GEMV runs over its kept rows
        ``(1 - sparsity) * N``. ``sparsity`` is a float or the same
        per-site dict SparseExecution takes; pass 0.0 for the dense /
        dense_free policies. First-order GEMV-only (like ``matmul_seconds``
        — attention-score FLOPs are negligible at decode batch sizes).

        ``layer_scale`` (optional, (n_layers,)): per-layer calibration
        multipliers — real stacks are NOT uniform (first/last layers carry
        embedding/head spill, attention cost grows with cache length, MoE
        layers alternate), and the prefetch timeline's hidden-I/O accounting
        is only as good as its compute lane. Pass measured per-layer
        multipliers (e.g. ``calibrate_layer_scale`` over profiled walls) to
        make the model's notion of "hidden" match the kernel's; None keeps
        the uniform first-order vector."""
        sp = normalize_site_sparsity(sparsity)
        out = np.zeros((cfg.n_layers,), np.float64)
        for kind, n, cols in decode_site_shapes(cfg):
            sec = self.matmul_seconds((1.0 - sp.get(kind, 0.0)) * n, sum(cols), tokens)
            out[list(site_layers(cfg, kind))] += sec
        if layer_scale is not None:
            scale = np.asarray(layer_scale, np.float64).reshape(-1)
            if scale.shape != (cfg.n_layers,):
                raise ValueError(
                    f"layer_scale must have shape ({cfg.n_layers},), "
                    f"got {scale.shape}"
                )
            if np.any(scale < 0):
                raise ValueError("layer_scale must be non-negative")
            out = out * scale
        return out

    @staticmethod
    def calibrate_layer_scale(layer_walls_s) -> np.ndarray:
        """Measured per-layer decode walls → mean-1 calibration multipliers
        for ``decode_layer_seconds(layer_scale=...)``: the profile keeps the
        model's per-step compute total while redistributing it across layers
        the way the hardware actually spends it."""
        walls = np.asarray(layer_walls_s, np.float64).reshape(-1)
        if walls.size == 0 or np.any(walls < 0):
            raise ValueError("layer walls must be a non-empty, non-negative vector")
        mean = walls.mean()
        if mean <= 0.0:
            return np.ones_like(walls)
        return walls / mean
