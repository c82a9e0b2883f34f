"""One run of one cell: set-up, a measured window on the wall clock, the
drain, the readings, and the correctness check. ``bench/run.py`` is the
command; everything a cell is made of is found by name (``bench/spec.py``).

Order of a run: weights from the seed on the device (one call), the
program's engine and scheduler, one warm request per slot over every
prompt shape the mix sends (so every program is compiled, or read from the
compile cache, before the window); then the window, in which requests are
handed over as they fall due; then the requests due in the window are
drained; the device's peak memory is read; the program is freed; and the
float32 reference checks a sample of what was served.

With ``--trace 1`` the window (at most ``TRACE_WINDOW_S``) runs under the
profiler and the per-layer metrics are read from it and from the run.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import check, roofline, spec, trace_reduce
from bench.serve import SELECTING, Program, Served, Step, clock, host_masks
from bench.traffic.common import Item, seed_words

TRACE_WINDOW_S = 10.0  # a traced window is this long at most
DRAIN_LIMIT_S = 120.0  # a request due in the window and not done by then failed


class CompileCounter:
    """Counts backend compiles (persistent-cache hits are not compiles)."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


_COUNTER: List[CompileCounter] = []


def compile_counter() -> CompileCounter:
    if not _COUNTER:  # a listener cannot be removed: register one per process
        _COUNTER.append(CompileCounter())
    return _COUNTER[0]


@dataclasses.dataclass
class Window:
    start: float
    close: float
    end: float
    steps: List[Step]
    requests: List[Served]
    late_s: List[float]
    compiles: int
    stats: range  # indices of the engine's StepStats logged in the window
    decode_calls: range  # indices of Program.decode_masks made in the window

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def tokens(self) -> int:
        return sum(s.tokens for s in self.steps)


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""

    cell: spec.Cell
    ref: object  # the cell's reference module: its sites, kernel calls, FLOPs
    dims: object
    peaks: Dict[str, float]
    window: Window
    decode_wall_s: List[float]
    trace: Optional[trace_reduce.TraceSummary]
    tokens_per_s: float
    selected_rows: Optional[Dict[str, float]]
    wbits: int


def warm(prog: Program, source, slots: int) -> None:
    """Every slot serves one short request; the prompts cycle through the
    mix's shapes, so each prefill shape, the decode round and the eviction
    path have run (and compiled) before the window."""
    shapes = source.warmup()
    for i in range(slots):
        it = shapes[i % len(shapes)]
        prog.submit(Item(-1 - i, it.tokens, it.out_len), clock())
    for _ in range(100_000):
        if prog.idle:
            break
        prog.step()
    else:
        raise RuntimeError("warm-up requests did not finish")
    prog.done.clear()
    prog.cache_snaps.clear()


def run_window(prog: Program, source, seconds: float,
               trace_dir: Optional[str]) -> Window:
    import jax

    counter = compile_counter()
    compiles0, stat0, call0 = counter.count, len(prog.engine.stats), len(prog.decode_masks)
    steps: List[Step] = []
    requests: List[Served] = []
    late: List[float] = []
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    span = jax.profiler.TraceAnnotation("bench.window")
    span.__enter__()
    t_start = clock()
    t_close = t_start + seconds

    def hand_over(t_rel):
        for it in source.poll(t_rel):
            due = t_start + it.due_rel
            sv = prog.submit(it, due)
            requests.append(sv)
            late.append(sv.submitted - due)

    while True:
        now = clock()
        if now >= t_close:
            break
        hand_over(now - t_start)
        if prog.idle:
            nxt = source.next_due()
            wake = t_close if nxt is None else min(t_start + nxt, t_close)
            time.sleep(max(0.0, wake - clock()))
            continue
        st, finished = prog.step()
        steps.append(st)
        for sv in finished:
            source.finished(sv.item, sv.finished - t_start)
    hand_over(seconds)  # due by the close: due in the window, served late
    source.close()
    t_end = max(t_close, steps[-1].end if steps else t_close)
    span.__exit__(None, None, None)
    if trace_dir:
        jax.profiler.stop_trace()
    return Window(t_start, t_close, t_end, steps, requests, late,
                  counter.count - compiles0, range(stat0, len(prog.engine.stats)),
                  range(call0, len(prog.decode_masks)))


def drain(prog: Program, limit_s: float) -> None:
    t_limit = clock() + limit_s
    while not prog.idle and clock() < t_limit:
        prog.step()


def percentile(values: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def latency(win: Window, t_final: float) -> Dict[str, float]:
    """TTFT and TPOT tails over every request due in the window; one that
    never finished counts with the time it had waited (a miss)."""
    ttft, tpot = [], []
    for sv in win.requests:
        if sv.finished is None:
            ttft.append(t_final - sv.due)
            tpot.append(t_final - sv.due)
            continue
        ttft.append(sv.token_times[0] - sv.due)
        n = len(sv.token_times)
        if n > 1:
            tpot.append((sv.token_times[-1] - sv.token_times[0]) / (n - 1))
    return {"ttft_p95_ms": percentile(ttft, 95) * 1e3,
            "tpot_p95_ms": percentile(tpot, 95) * 1e3}


def mean_selected_rows(masks: Dict[int, Dict[str, np.ndarray]]) -> Optional[Dict[str, float]]:
    if not masks:
        return None
    sites = next(iter(masks.values())).keys()
    return {s: float(np.mean([m[s].sum(axis=1).mean() for m in masks.values()]))
            for s in sites}


CONTROLS = ("program", "fp8")


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, t0: float,
             control: Optional[str] = None,
             hooks: Optional[Callable[[Program], None]] = None) -> dict:
    """One run; returns the result object (see ``emit``). ``control`` puts
    a lower precision in the program's place (see ``bench/check.py``):
    ``"program"``, its own int8 weight path; ``"fp8"``, the reference with
    float8 weights. ``hooks(program)`` may alter the program after it is
    built (tests plant faults with it)."""
    import jax
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compile_counter()
    e = cell.engine
    selecting = e["method"] in SELECTING
    if control not in (None,) + CONTROLS or (control == "program" and not selecting):
        raise ValueError(f"no control {control!r} for method {e['method']!r}")
    prog = Program(cell, seed, wbits=8 if control == "program" else None)
    if hooks:
        hooks(prog)
    source = spec.traffic_source(cell, seed, prog.dims.vocab, seconds)
    warm(prog, source, int(e["slots"]))
    window_s = min(seconds, TRACE_WINDOW_S) if trace else seconds
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        setup_s = clock() - t0
        win = run_window(prog, source, window_s, trace_dir)
        drain(prog, DRAIN_LIMIT_S)
        t_final = clock()
        drain_s = t_final - win.end
        dev = jax.local_devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()), "memory_peak_bytes": prog.memory_peak_bytes()}
        summary = trace_reduce.load(trace_dir) if trace else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    done = [sv for sv in win.requests if sv.finished is not None]
    failed = len(win.requests) - len(done)
    rng = np.random.default_rng(seed_words(seed) + [0xC0])
    cohort, cache_rows, masks, kept = None, None, {}, None
    if selecting:
        full = check.cohorts(done, int(e["slots"]))
        snaps = [sn for sn in prog.cache_snaps if sn[0] in full]
        if not snaps:
            raise RuntimeError("no full cohort of the window left its KV cache to check")
        calls_key, cache = snaps[int(rng.integers(len(snaps)))]
        cohort = full[calls_key]
        rows = np.asarray([sv.slot for sv in cohort])
        cache_rows = {name: np.asarray(a[:, rows]) for name, a in cache.items()}
        masks = host_masks(prog.decode_masks, list(calls_key) + list(win.decode_calls))
    else:
        snaps = [sn for sn in prog.cache_snaps if any(sn[0] is d for d in done)]
        if snaps:
            sv, cache = snaps[int(rng.integers(len(snaps)))]
            kept = (sv, {name: np.asarray(a) for name, a in cache.items()})
    tokens_per_s = win.tokens / win.seconds
    ctx = Context(cell, prog.ref, prog.dims, None, win,
                  [prog.engine.stats[i].wall_s for i in win.stats
                   if prog.engine.stats[i].kind == "decode"],
                  summary, tokens_per_s,
                  mean_selected_rows({c: masks[c] for c in win.decode_calls}),
                  prog.wbits)
    ref, dims = prog.ref, prog.dims
    del prog, source
    gc.collect()

    t_check = clock()
    if not done:
        raise RuntimeError("no request due in the window finished")
    if selecting:
        readings = check.check_selecting(ref, dims, seed, cohort, masks,
                                         float(e["sparsity"]), cache_rows,
                                         control_fp8=control == "fp8")
    else:
        out = cell.traffic["output_tokens"]
        n_pos = (int(out["max"]) if isinstance(out, dict) else int(out)) + 1
        if kept is None:
            raise RuntimeError("no request of the window left its KV cache to check")
        readings = check.check_dense(ref, dims, seed, done, int(e["max_seq"]), n_pos,
                                     kept, control_fp8=control == "fp8")
    checks = check.compare(readings, cell.limits)
    check_s = clock() - t_check
    correct = failed == 0 and all(c["value"] <= c["limit"] for c in checks.values())

    if trace:
        ctx.peaks = roofline.peaks_for(device["kind"]) if device["platform"] == "tpu" \
            else None
        metrics = {}
        readers = spec.metric_readers(cell)
        for m in cell.per_layer:
            value = readers[m["name"]].read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device["busy_s"] = summary.busy_s()
        device["window_s"] = summary.window_s
        breakdown = {"device_ops": summary.top_ops(10), "idle_gaps": summary.idle_gaps(10)}
    else:
        wanted = {m["name"]: m["unit"] for m in cell.end_to_end}
        values = {"setup_s": setup_s, "tokens_per_s": tokens_per_s}
        values.update(latency(win, t_final))
        metrics = {k: {"value": values[k], "unit": u} for k, u in wanted.items()}
        breakdown = None
    result = {"correct": bool(correct), "attempted": len(win.requests), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    ends = [win.start] + [st.end for st in win.steps]
    result["window"] = {"seconds": win.seconds, "steps": len(win.steps),
                        "tokens": win.tokens, "compiles": win.compiles,
                        "longest_step_s": max((st.end - st.begin for st in win.steps),
                                              default=0.0),
                        "longest_between_steps_s": max(
                            (st.begin - t for st, t in zip(win.steps, ends)), default=0.0),
                        "drain_s": drain_s, "check_s": check_s,
                        "generator_late_ms_mean": 1e3 * float(np.mean(win.late_s))
                        if win.late_s else 0.0, "control": control}
    result["checks"] = checks
    return result


def emit(result: dict) -> None:
    """Each number compared beside its limit, as the last lines on standard
    error; then the result as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or not (args.seconds > 0 and math.isfinite(args.seconds)):
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def require_chips(n: int) -> Optional[str]:
    """Why this machine cannot run a cell on ``n`` chips, or None."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        return f"JAX found no accelerator: {e}"
    if devs[0].platform != "tpu":
        return f"JAX found no TPU (platform {devs[0].platform!r})"
    if len(devs) < n:
        return f"the cell needs {n} chips, JAX found {len(devs)}"
    return None


def main(argv, t0: float) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload)
    why = require_chips(cell.chips)
    if why:
        print(f"bench: {why}; nothing measured", file=sys.stderr)
        return 3
    emit(run_cell(cell, args.seed, args.seconds, bool(args.trace), t0))
    return 0
