"""Serving launcher: streaming-VLM (or plain LLM) inference with the
neuron-chunking policy and flash-offload simulation.

Single-stream mode (prefill → frames → fused decode):

  PYTHONPATH=src python -m repro.launch.serve --arch internvl2-76b --reduced \
      --method chunk --sparsity 0.4 --frames 4 --decode-tokens 16

Continuous-batching mode (Poisson arrivals over request slots):

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --reduced \
      --streams 8 --arrival-rate 100 --method chunk
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..compile_cache import enable_compile_cache
from ..configs import ARCH_IDS, get_config
from ..configs.base import InputShape
from ..core.faults import CORRUPTION_PROFILES, FAULT_PROFILES
from ..kernels.backend import BACKENDS
from ..models import build_model
from ..models.inputs import make_dummy_batch
from ..serving import (
    SERVE_METHODS,
    PoissonArrivalDriver,
    Request,
    Scheduler,
    ServeEngine,
)
from ..sharding.serve import ServeMesh, validate_serve_mesh


def _nonneg_float(name: str):
    """argparse ``type=`` for flags that must be >= 0 — a bad value fails
    at parse time with an actionable message (the --mesh treatment),
    instead of erroring deep inside engine construction."""
    def parse(text: str) -> float:
        try:
            v = float(text)
        except ValueError:
            raise argparse.ArgumentTypeError(
                f"{name} must be a number, got {text!r}"
            ) from None
        if not (v >= 0.0):  # also rejects NaN
            raise argparse.ArgumentTypeError(
                f"{name} must be >= 0, got {text!r}"
            )
        return v
    return parse


def _nonneg_int(name: str):
    def parse(text: str) -> int:
        try:
            v = int(text)
        except ValueError:
            raise argparse.ArgumentTypeError(
                f"{name} must be an integer, got {text!r}"
            ) from None
        if v < 0:
            raise argparse.ArgumentTypeError(
                f"{name} must be >= 0, got {text!r}"
            )
        return v
    return parse


def _positive_int(name: str):
    def parse(text: str) -> int:
        try:
            v = int(text)
        except ValueError:
            raise argparse.ArgumentTypeError(
                f"{name} must be an integer, got {text!r}"
            ) from None
        if v < 1:
            raise argparse.ArgumentTypeError(
                f"{name} must be >= 1, got {text!r}"
            )
        return v
    return parse


def _cache_mb(text: str) -> float:
    # --cache-mb keeps None as "use the profile default", so the >= 0
    # check wraps the plain float parse
    return _nonneg_float("--cache-mb")(text)


def build_parser() -> argparse.ArgumentParser:
    """The serve CLI's argparse parser — exposed (rather than built inline
    in ``main``) so tests/test_docs.py can check every ``--flag`` the docs
    mention against the real option table."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="internvl2-76b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--method", choices=SERVE_METHODS, default="chunk")
    ap.add_argument("--backend", choices=BACKENDS, default="reference",
                    help="decode execution backend: 'reference' computes "
                         "the planned sparse projections as the DMA "
                         "kernels' pure-jnp schedule twin; 'kernel' "
                         "dispatches the Pallas chunk-gather kernels off "
                         "the decode plan's chunk tables (interpret mode "
                         "off-TPU, compiled on TPU). Tokens are "
                         "byte-identical across backends.")
    ap.add_argument("--wbits", type=int, choices=(16, 8), default=16,
                    help="offloaded chunk storage width: 16 = fp16 payload, "
                         "8 = int8 payload + one f32 scale per 8-row block, "
                         "dequantized inside the gather kernels (and "
                         "identically by the reference twin — tokens stay "
                         "byte-identical across backends at fixed wbits). "
                         "At 8 every byte/latency figure prices the "
                         "quantized rows, so the same I/O budget admits "
                         "about twice the neurons.")
    ap.add_argument("--sparsity", type=float, default=0.4)
    ap.add_argument("--device", choices=("nano", "agx"), default="nano")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--frames", type=int, default=2)
    ap.add_argument("--decode-tokens", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=512)
    ap.add_argument("--plan-refresh-interval", type=int, default=1,
                    help="recompute chunk selection every k decode steps; "
                         "reuse the resident plan in between")
    ap.add_argument("--cache-mb", type=_cache_mb, default=None,
                    help="DRAM budget (MB) of the dynamic chunk residency "
                         "cache (paper §5); resident rows cost no flash I/O. "
                         "Default: the device profile's dram_cache_mb (0 = off)")
    ap.add_argument("--kv-page-tokens", type=_positive_int("--kv-page-tokens"),
                    default=None,
                    help="paged KV cache: fixed page size in tokens (must "
                         "divide --max-seq). The KV cache becomes a "
                         "free-list page pool with per-slot page tables and "
                         "copy-on-write prefix sharing; its capacity is "
                         "carved out of the unified --cache-mb budget "
                         "(io_summary reports the kv/weights split). "
                         "Requires --streams (slot mode); greedy tokens are "
                         "byte-identical to the dense KV cache. Default: "
                         "dense per-slot KV")
    ap.add_argument("--per-token", action="store_true",
                    help="use the legacy one-jit-per-token decode loop "
                         "instead of the fused lax.scan loop")
    ap.add_argument("--overlap", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="charge decode steps through the overlapped "
                         "I/O–compute prefetch pipeline (layer l+1's chunks "
                         "stream while layer l computes); --no-overlap "
                         "retains the serial Σio+Σcompute baseline charge. "
                         "Tokens are identical either way.")
    ap.add_argument("--prefetch-depth", type=_nonneg_int("--prefetch-depth"),
                    default=1,
                    help="how many layers the prefetch pipeline's fetch "
                         "engine may run ahead of compute (the DMA kernels' "
                         "slot count - 1): 1 = double buffering, 0 = serial "
                         "schedule, >1 = deeper pipeline. Tokens are "
                         "byte-identical at every depth.")
    ap.add_argument("--mesh", type=str, default="1,1", metavar="DATA,MODEL",
                    help="serve-mesh shape 'data,model' (default 1,1 = "
                         "unsharded): serve slots partition over the data "
                         "axis (--batch and --streams must divide it), the "
                         "offloaded decode weights / chunk payloads / block "
                         "tables partition over the model axis (ffn rows "
                         "must divide model x 8). Greedy tokens are "
                         "byte-identical to the 1,1 mesh at both --wbits. "
                         "Simulate devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--streams", type=int, default=0,
                    help=">0: continuous-batching mode — serve this many "
                         "Poisson-arriving requests through --batch slots")
    ap.add_argument("--arrival-rate", type=float, default=50.0,
                    help="request arrival rate (requests/sec, sim clock)")
    ap.add_argument("--round-tokens", type=_positive_int("--round-tokens"),
                    default=4,
                    help="fused-scan decode round granularity of the "
                         "continuous-batching scheduler (tokens per jit "
                         "call per slot); must be >= 1")
    ap.add_argument("--fault-profile", choices=tuple(FAULT_PROFILES),
                    default="none",
                    help="storage-turbulence profile injected at the "
                         "simulator's measurement boundary (core/faults.py): "
                         "tail-latency spikes, transient read failures with "
                         "retry + exponential backoff, thermal-throttle "
                         "trajectories. Selection keeps planning against "
                         "the clean latency table; faults only perturb "
                         "charged time, never tokens. 'none' (default) is "
                         "bit-identical to a fault-free engine.")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the fault model's own RNG stream — a "
                         "given (--fault-profile, --fault-seed) replays "
                         "bit-identically and never shifts the simulator's "
                         "main jitter stream")
    ap.add_argument("--corruption-profile", choices=tuple(CORRUPTION_PROFILES),
                    default="none",
                    help="data-plane corruption profile injected into "
                         "fetched chunk blocks (core/faults.py): 'bit_rot' "
                         "flips one stored bit per corrupted 8-row block, "
                         "'torn_read' zeroes blocks, 'degraded_nand' "
                         "combines a high corruption rate with mostly-stuck "
                         "re-reads. Unlike --fault-profile this damages the "
                         "DATA — with --no-recover tokens can change. Every "
                         "fetched block is checksum-verified at the gather "
                         "boundary; detections climb the recovery ladder "
                         "(re-read → resident DRAM copy → substitute → "
                         "drop), counted in io_summary(). 'none' (default) "
                         "is bit-identical to a corruption-free engine.")
    ap.add_argument("--corruption-seed", type=int, default=0,
                    help="seed of the corruption model's own RNG stream — a "
                         "given (--corruption-profile, --corruption-seed) "
                         "draws the same corrupt blocks every replay; "
                         "requires a corruption profile other than 'none'")
    ap.add_argument("--max-reread", type=_nonneg_int("--max-reread"),
                    default=2,
                    help="recovery ladder rung 0: how many times a "
                         "checksum-mismatched block may be re-read (each "
                         "charged the block's latency + exponential "
                         "backoff) before escalating to the resident-copy / "
                         "substitute / drop rungs; 0 skips straight to "
                         "escalation")
    ap.add_argument("--recover", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="run the corruption recovery ladder (default). "
                         "--no-recover detects and counts corruption but "
                         "lets the damaged payloads flow into compute — the "
                         "measurable-corruption baseline (tokens CAN "
                         "change, deterministically per seed)")
    ap.add_argument("--degrade", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="enable the adaptive degradation controller: "
                         "watches the EWMA of measured-vs-estimated step "
                         "latency at decode-call boundaries and tightens "
                         "the selector's chunk I/O budget while the device "
                         "is degraded (leaning on residency-cache hits), "
                         "recovering when it stabilizes")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request SLO deadline (seconds from arrival, "
                         "sim clock) for --streams mode: admission becomes "
                         "earliest-deadline-first and deadline-blown "
                         "running requests may be preempted "
                         "(evict-and-requeue); stats gain p99 + SLO "
                         "attainment. Default: best-effort (no deadlines)")
    return ap


def validate_seed_flags(ap: argparse.ArgumentParser, args) -> None:
    """Reject seed flags whose matching profile is off, at argparse time.

    ``--fault-seed 7`` with ``--fault-profile none`` (and likewise
    ``--corruption-seed`` with ``--corruption-profile none``) used to parse
    fine and silently run a fault-free engine — the seed did nothing. That
    is always a typo (the user expected perturbation); fail with the
    standard argparse usage error instead of quietly measuring the wrong
    thing. Seed 0 is each stream's default and stays valid either way."""
    if args.fault_seed != 0 and args.fault_profile == "none":
        ap.error(
            f"--fault-seed {args.fault_seed} has no effect with "
            "--fault-profile none; pick a profile "
            f"({', '.join(p for p in FAULT_PROFILES if p != 'none')}) "
            "or drop the seed"
        )
    if args.corruption_seed != 0 and args.corruption_profile == "none":
        ap.error(
            f"--corruption-seed {args.corruption_seed} has no effect with "
            "--corruption-profile none; pick a profile "
            f"({', '.join(p for p in CORRUPTION_PROFILES if p != 'none')}) "
            "or drop the seed"
        )


def resolve_mesh(spec: str, cfg, batch: int, streams: int) -> ServeMesh:
    """Parse + validate ``--mesh`` against ``--batch``/``--streams``/the
    arch config BEFORE any model is built, so a bad mesh fails in
    milliseconds with an actionable message instead of mid-prefill
    (tests/test_sharded_serving.py pins the error cases)."""
    parts = spec.split(",")
    if len(parts) != 2:
        raise ValueError(
            f"--mesh must be 'data,model' (e.g. 2,2), got {spec!r}"
        )
    try:
        data, model = (int(p) for p in parts)
    except ValueError:
        raise ValueError(
            f"--mesh axes must be integers, got {spec!r}"
        ) from None
    validate_serve_mesh(
        data, model, batch=batch, streams=streams,
        d_ff=(cfg.d_ff if (model > 1 and cfg.d_ff and not cfg.has_moe) else 0),
        n_devices=len(jax.devices()),
    )
    return ServeMesh.create(data, model)


def build_engine(args, cfg, mesh: ServeMesh, params=None) -> ServeEngine:
    """The serve CLI's engine, built from its parsed ``args`` for ``cfg``.
    ``params`` defaults to the seed-0 random init; pass them to build
    several engines over one set of weights."""
    model = build_model(cfg)
    if params is None:
        params = model.init(jax.random.key(0))
    return ServeEngine(model, params, max_seq=args.max_seq, batch_size=args.batch,
                       device=args.device, sparsity=args.sparsity,
                       method=args.method,
                       plan_refresh_interval=args.plan_refresh_interval,
                       cache_mb=args.cache_mb, overlap=args.overlap,
                       prefetch_depth=args.prefetch_depth,
                       backend=args.backend, wbits=args.wbits, mesh=mesh,
                       fault_profile=args.fault_profile,
                       fault_seed=args.fault_seed, degrade=args.degrade,
                       corruption_profile=args.corruption_profile,
                       corruption_seed=args.corruption_seed,
                       max_reread=args.max_reread, recover=args.recover,
                       kv_page_tokens=args.kv_page_tokens)


@dataclasses.dataclass
class SingleStreamRun:
    """What one single-stream run produced: the prompt batch, the prefill's
    last-position logits (b, vocab) and the decoded tokens
    (b, decode_tokens + 1), first token included."""

    batch: dict
    prefill_logits: jnp.ndarray
    tokens: jnp.ndarray


def main():
    enable_compile_cache()
    ap = build_parser()
    args = ap.parse_args()
    validate_seed_flags(ap, args)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.kv_page_tokens is not None and args.streams <= 0:
        ap.error("--kv-page-tokens requires --streams (paged KV is slot-mode "
                 "only: requests are admitted through the page allocator)")
    mesh = resolve_mesh(args.mesh, cfg, args.batch, args.streams)
    eng = build_engine(args, cfg, mesh)
    if args.streams > 0:
        run_streams(args, cfg, eng)
    else:
        run_single_stream(args, cfg, eng)


def run_single_stream(args, cfg, eng: ServeEngine) -> SingleStreamRun:
    """Single-stream mode: prefill → ``--frames`` frame appends (VLMs) →
    ``--decode-tokens`` greedy decode, printing one line per stage."""
    shape = InputShape("cli", args.prompt_len, args.batch, "train")
    batch = make_dummy_batch(cfg, shape)
    last = eng.prefill(batch)
    print(f"[prefill] {args.prompt_len} tokens")
    rng = np.random.default_rng(0)
    if cfg.d_frontend and not cfg.is_encdec:
        n_tok = max(cfg.frontend_tokens // 4, 4)
        for i in range(args.frames):
            frame = jnp.asarray(
                rng.normal(0, 1, (args.batch, n_tok, cfg.d_frontend)), jnp.bfloat16
            )
            eng.append_frame(frame)
            st = eng.stats[-1]
            print(f"[frame {i}] {n_tok} tokens  io_est {st.io_est_s*1e3:.2f} ms  "
                  f"io_sim {st.io_sim_s*1e3:.2f} ms")
    tok0 = jnp.argmax(last, -1)[:, None].astype(jnp.int32)
    decode = eng.decode_per_token if args.per_token else eng.decode
    tokens = decode(tok0, args.decode_tokens)
    dsteps = [s for s in eng.stats if s.kind == "decode"]
    mode = "per-token" if args.per_token else "fused-scan"
    print(f"[decode:{mode}] {args.decode_tokens} tokens  "
          f"mean io_sim {np.mean([s.io_sim_s for s in dsteps])*1e3:.2f} ms/token  "
          f"wall {sum(s.wall_s for s in dsteps)*1e3:.1f} ms")
    s = eng.io_summary()
    charged = "overlap" if args.overlap else "serial"
    print(f"[pipeline] charged={charged} depth={args.prefetch_depth}  "
          f"serial {s['decode_serial_s']*1e3:.2f} ms  "
          f"overlapped {s['decode_overlap_s']*1e3:.2f} ms  "
          f"stall {s['decode_stall_s']*1e3:.2f} ms  "
          f"overlap_efficiency {s['overlap_efficiency']:.3f}")
    if eng.mesh.is_sharded:
        ss = eng.shard_summary()
        per = ", ".join(f"{b/1e6:.1f}" for b in ss["io_bytes_per_shard"])
        print(f"[mesh] data={eng.mesh.data} model={eng.mesh.model}  "
              f"slots/data_shard={ss['slots_per_data_shard']}  "
              f"cache_mb/shard={ss['cache_mb_per_shard']:g}  "
              f"io_bytes/shard MB=[{per}]")
    print(f"[total] method={args.method} backend={args.backend} "
          f"wbits={args.wbits} sparsity={args.sparsity} "
          f"refresh_interval={args.plan_refresh_interval} "
          f"cache_mb={eng.cache_mb:g} "
          f"io_est {s['io_est_s']*1e3:.1f} ms  io_sim {s['io_sim_s']*1e3:.1f} ms  "
          f"io_bytes {s['io_bytes']/1e6:.1f} MB  "
          f"cache_hit_rate {s['cache_hit_rate']:.3f}")
    fs = eng.fault_summary()
    if fs["fault_enabled"] or fs["degrade_enabled"]:
        print(f"[faults] profile={fs['fault_profile']} seed={fs['fault_seed']}  "
              f"events {fs['fault_events']}  spikes {fs['fault_spikes']}  "
              f"retries {fs['fault_retries']}  "
              f"extra {fs['fault_extra_s']*1e3:.2f} ms  "
              f"min_throttle {fs['min_throttle_scale']:.2f}  "
              f"degrade_scale {fs['degrade_scale']:.2f}")
    _print_integrity(args, s)
    return SingleStreamRun(batch=batch, prefill_logits=last, tokens=tokens)


def _print_integrity(args, s) -> None:
    """The [integrity] rollup line (corruption injection runs only)."""
    if args.corruption_profile == "none":
        return
    print(f"[integrity] profile={args.corruption_profile} "
          f"seed={args.corruption_seed} recover={args.recover} "
          f"max_reread={args.max_reread}  "
          f"detected {s['corruptions_detected']:.0f}  "
          f"recovered {s['corruptions_recovered']:.0f}  "
          f"substituted {s['corruptions_substituted']:.0f}  "
          f"dropped {s['corruptions_dropped']:.0f}  "
          f"reread {s['integrity_reread_s']*1e3:.2f} ms")


def run_streams(args, cfg, eng: ServeEngine) -> Scheduler:
    """Continuous-batching mode: ``--streams`` Poisson-arriving requests
    served to completion through ``--batch`` slots. Returns the drained
    scheduler (finished requests and their tokens)."""
    rng = np.random.default_rng(0)

    def make_request(rid: int) -> Request:
        batch = make_dummy_batch(cfg, InputShape("req", args.prompt_len, 1, "train"))
        # vary prompts so streams are not identical
        toks = jnp.asarray(
            rng.integers(0, cfg.vocab_size, batch["tokens"].shape), jnp.int32
        )
        prompt = dict(batch)
        prompt["tokens"] = toks
        return Request(rid=rid, prompt=prompt, max_new_tokens=args.decode_tokens,
                       deadline_s=args.deadline_s)

    driver = PoissonArrivalDriver(args.arrival_rate, make_request, seed=1)
    sched = Scheduler(eng, round_tokens=args.round_tokens)
    sched.submit(driver.generate(args.streams))
    stats = sched.run()
    print(f"[serve] method={args.method} slots={args.batch} "
          f"rate={args.arrival_rate}/s refresh={args.plan_refresh_interval} "
          f"cache_mb={eng.cache_mb:g}")
    print(f"[serve] {stats.row()}")
    s = eng.io_summary()
    print(f"[serve] ttft p50 {stats.ttft_p50_s*1e3:.2f} ms  "
          f"sim time {stats.sim_time_s*1e3:.1f} ms  "
          f"overlap_efficiency {s['overlap_efficiency']:.3f}  "
          f"cache_hit_rate {s['cache_hit_rate']:.3f}")
    print(f"[serve] admitted_during_stall {s['admitted_during_stall']}  "
          f"stall_hidden {s['stall_hidden_s']*1e3:.2f} ms  "
          f"bubble_utilization {s['bubble_utilization']:.3f}")
    if eng.kv_pool is not None:
        ps = eng.kv_pool.summary()
        print(f"[paged-kv] page_tokens {eng.kv_page_tokens}  "
              f"pages {eng.kv_pages} (kv {s['kv_cache_mb']:.2f} MB / "
              f"weights {s['weight_cache_mb']:.2f} MB)  "
              f"shared_hits {ps['shared_hits']}  cow {ps['cow_copies']}  "
              f"evictions {ps['evictions']}")
    if args.deadline_s is not None:
        print(f"[slo] deadline {args.deadline_s*1e3:.1f} ms  "
              f"attainment {stats.slo_attainment:.3f} "
              f"({stats.deadlines_met}/{stats.deadlines})  "
              f"p99 {stats.latency_p99_s*1e3:.2f} ms  "
              f"preempted {stats.preempted}")
    fs = eng.fault_summary()
    if fs["fault_enabled"] or fs["degrade_enabled"]:
        print(f"[faults] profile={fs['fault_profile']} "
              f"seed={fs['fault_seed']}  events {fs['fault_events']}  "
              f"spikes {fs['fault_spikes']}  retries {fs['fault_retries']}  "
              f"extra {fs['fault_extra_s']*1e3:.2f} ms  "
              f"min_throttle {fs['min_throttle_scale']:.2f}")
        print(f"[degrade] on={fs['degrade_enabled']}  "
              f"scale {fs['degrade_scale']:.2f}  "
              f"ewma {fs['degrade_ewma_ratio']:.2f}  "
              f"tighten {fs['degrade_tighten_steps']}  "
              f"relax {fs['degrade_relax_steps']}")
    _print_integrity(args, s)
    return sched


if __name__ == "__main__":
    main()
