"""Smoke test of the serve path on a TPU, through the serve CLI's own entry
points (``repro.launch.serve``: ``build_engine``, ``run_single_stream``,
``run_streams``), with the Pallas DMA gather kernels compiled.

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --mesh 2,2   # four chips: phase A's model, 2x2 vs 1x1

Phase A — the paper's VLM: internvl2-76b at its published widths (d_model
8192, 64/8 heads, d_ff 28672, vocab 128256, d_frontend 3200), depth cut to
2 of 80 layers so the weights fit one 16 GB chip. Prefill (256 visual +
256 text tokens) → 2 frame appends → fused-scan decode of 8 tokens with
``--method chunk``, on the kernel backend at wbits 16 and 8 and on the
reference backend at wbits 16. Checks: greedy tokens agree across backends,
the prefill logits agree with the float32 reference forward
(``repro.models.reference``), and the kernel decode program holds Mosaic
kernels (``tpu_custom_call``).

Phase B — the whole TinyLlama-1.1B (22 layers): 4 Poisson-arriving requests
served to completion through the ``Scheduler`` over 2 slots with
``--method topk``, on both backends; every request must finish with its
tokens agreeing across them.

Any failed check exits non-zero. The last line of standard output is the
JSON result ``{"ok": true, "device": {...}}``. Without a TPU, or outside a
checkout of the repository, the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

VLM_ARGS = ["--arch", "internvl2-76b", "--method", "chunk", "--batch", "2",
            "--prompt-len", "512", "--frames", "2", "--decode-tokens", "8",
            "--max-seq", "1024"]
VLM_LAYERS = 2
# top-k selection: on a v5e one chunk-selection refresh costs ~2 s per
# layer, and over 22 layers it put the smoke
# past its 1,200 s; the decode still runs through the gather kernels
LLM_ARGS = ["--arch", "tinyllama-1.1b", "--method", "topk", "--batch", "2",
            "--streams", "4", "--prompt-len", "32", "--decode-tokens", "8",
            "--max-seq", "128"]


class SmokeFailure(Exception):
    """A check of the smoke failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Sums the backend-compile seconds JAX reports while it is installed."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def lap(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


def tree_bytes(tree) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))


def peak_bytes() -> int:
    import jax

    return int(jax.devices()[0].memory_stats().get("peak_bytes_in_use", -1))


def decode_kernel_count(eng, n_tokens: int) -> int:
    """``tpu_custom_call`` ops in the engine's compiled fused-decode program
    (JAX's in-process compile cache returns the executable the decode call
    just built)."""
    import jax.numpy as jnp

    tok = jnp.zeros((eng.batch_size, 1), jnp.int32)
    compiled = eng._decode_scan.lower(
        eng.params, eng.mesh.put_batch(tok), eng.cache, n_tokens, eng._plan
    ).compile()
    return compiled.as_text().count("tpu_custom_call")


def vlm_run(cfg, params, backend: str, wbits: int, clock,
            mesh_spec: str = "1,1", time_decode: bool = True):
    """One phase-A run through the CLI path. Returns (tokens (b, n+1) as
    numpy, the run's ``SingleStreamRun``, a report line). ``time_decode``
    adds a second, warm decode of the same shape to time the compiled
    program."""
    import jax
    import numpy as np

    from repro.launch import serve

    args = serve.build_parser().parse_args(
        VLM_ARGS + ["--backend", backend, "--wbits", str(wbits),
                    "--mesh", mesh_spec])
    mesh = serve.resolve_mesh(args.mesh, cfg, args.batch, args.streams)
    eng = serve.build_engine(args, cfg, mesh, params)
    be = eng.sparse_ctx.backend
    interpret = be.interpret if be.is_kernel else None
    check(interpret in (None, False),
          f"{backend} backend resolved interpret={interpret} on the chip")
    run = serve.run_single_stream(args, cfg, eng)
    compile_s = clock.lap()
    tokens = np.asarray(jax.device_get(run.tokens))
    check(tokens.shape == (args.batch, args.decode_tokens + 1), "decode output shape")
    warm = "not_measured"
    if time_decode:
        t0 = time.perf_counter()
        more = jax.block_until_ready(eng.decode(run.tokens[:, -1:], args.decode_tokens))
        warm = f"{time.perf_counter() - t0:.3f}"
        check(more.shape == tokens.shape, "warm decode output shape")
    kernels = decode_kernel_count(eng, args.decode_tokens)
    if be.is_kernel:
        check(kernels > 0, "kernel-backend decode program holds no tpu_custom_call")
    line = (f"backend={backend} wbits={wbits} mesh={mesh_spec} "
            f"interpret={interpret} tpu_custom_call={kernels} "
            f"compile_s={compile_s:.1f} warm_decode_8tok_s={warm} "
            f"engine_param_bytes={tree_bytes(eng.params)} "
            f"peak_bytes_in_use={peak_bytes()} tokens={tokens.tolist()}")
    return tokens, run, line


def phase_vlm(clock, cfg, mesh_spec=None) -> None:
    import jax
    import numpy as np

    from repro.models import build_model
    from repro.models.reference import (
        LOGIT_REL_TOL,
        logit_rel_error,
        reference_last_logits,
    )

    params = build_model(cfg).init(jax.random.key(0))
    print(f"[A] {cfg.name} d_model={cfg.d_model} heads={cfg.n_heads}/"
          f"{cfg.n_kv_heads} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"d_frontend={cfg.d_frontend} layers={cfg.n_layers} "
          f"param_bytes={tree_bytes(params)} init_compile_s={clock.lap():.1f}",
          flush=True)
    if mesh_spec is not None:
        toks = {}
        for spec in ("1,1", mesh_spec):
            toks[spec], _, line = vlm_run(cfg, params, "kernel", 16, clock, spec)
            print(f"[A] {line}", flush=True)
        agree = bool(np.array_equal(toks["1,1"], toks[mesh_spec]))
        print(f"[A] tokens {mesh_spec} vs 1,1 agree={agree}", flush=True)
        check(agree, f"--mesh {mesh_spec} tokens differ from the 1,1 mesh")
        return
    toks = {}
    for backend, wbits in (("kernel", 16), ("reference", 16), ("kernel", 8)):
        first = (backend, wbits) == ("kernel", 16)
        toks[backend, wbits], run, line = vlm_run(cfg, params, backend, wbits,
                                                  clock, time_decode=first)
        print(f"[A] {line}", flush=True)
        if (backend, wbits) == ("kernel", 16):
            ref = reference_last_logits(cfg, params, run.batch)
            err = logit_rel_error(run.prefill_logits, ref)
            print(f"[A] prefill logits vs float32 reference: rel_l2={err:.3e} "
                  f"tol={LOGIT_REL_TOL} ref_compile_s={clock.lap():.1f}",
                  flush=True)
            check(err < LOGIT_REL_TOL, f"prefill logits rel error {err:.3e}")
        del run
    agree = bool(np.array_equal(toks["kernel", 16], toks["reference", 16]))
    print(f"[A] tokens kernel vs reference (wbits 16) agree={agree}", flush=True)
    check(agree, "kernel and reference backends decoded different tokens")


def phase_llm(clock, cfg) -> None:
    from repro.launch import serve

    outs = {}
    for backend in ("kernel", "reference"):
        args = serve.build_parser().parse_args(LLM_ARGS + ["--backend", backend])
        mesh = serve.resolve_mesh(args.mesh, cfg, args.batch, args.streams)
        eng = serve.build_engine(args, cfg, mesh)
        t0 = time.perf_counter()
        sched = serve.run_streams(args, cfg, eng)
        wall = time.perf_counter() - t0
        done = {r.rid: list(r.tokens_out[: r.max_new_tokens]) for r in sched.finished}
        check(len(done) == args.streams,
              f"{backend}: {len(done)} of {args.streams} requests finished")
        check(all(len(t) == args.decode_tokens for t in done.values()),
              f"{backend}: a request finished short of its tokens")
        print(f"[B] {cfg.name} layers={cfg.n_layers} backend={backend} "
              f"requests={len(done)} serve_wall_s={wall:.1f} "
              f"compile_s={clock.lap():.1f} peak_bytes_in_use={peak_bytes()} "
              f"tokens={[done[k] for k in sorted(done)]}", flush=True)
        outs[backend] = done
        del eng, sched
    agree = outs["kernel"] == outs["reference"]
    print(f"[B] tokens kernel vs reference agree={agree}", flush=True)
    check(agree, "phase B: kernel and reference backends answered differently")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="run only phase A's model on this serve mesh and on "
                         "1,1 in one process, and compare their tokens")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke.py: {SRC / 'repro'} not found — run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: no TPU — JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache_dir}", flush=True)
    from repro.configs import get_config

    clock = CompileClock()
    vlm = dataclasses.replace(get_config("internvl2-76b"), n_layers=VLM_LAYERS)
    try:
        phase_vlm(clock, vlm, args.mesh)
        if args.mesh is None:
            phase_llm(clock, get_config("tinyllama-1.1b"))
    except SmokeFailure as e:
        print(f"chip_smoke.py: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
