"""Nightly seeded fault sweep: every injection profile × seeds × backends.

Two planes, one artifact (``BENCH_faults.json``, uploaded by the nightly
CI job):

  * time plane (``FaultModel``, core/faults.py) — each fault profile runs
    the deadline-scheduled serving scenario with the adaptive
    ``DegradationController`` on, emitting SLO attainment, p99 latency and
    tokens/s per (profile, seed). Tokens never change on this plane, so
    every run also re-asserts byte-identity against the fault-off baseline.
  * data plane (``CorruptionModel``, PR 9) — each corruption profile runs
    the checksum-verified decode path per (seed, backend, wbits), emitting
    the detection/recovery/substitution/drop counters and the recovery
    rate. bit_rot (transient flips) must recover at exactly 1.0 with
    byte-identical tokens; the sticky profiles exercise the full ladder.

Everything is seeded and simulator noise is zeroed: the artifact's numbers
replay exactly, so a nightly diff is a real behavior change, never jitter.

Standalone:  PYTHONPATH=src python -m benchmarks.fault_sweep
CI artifact: PYTHONPATH=src python -m benchmarks.fault_sweep \
                 --out BENCH_faults.json
(--smoke shrinks the matrix to one seed and the two canonical
backend/wbits combos for a quick local pass.)
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.configs.base import InputShape
from repro.core.faults import CORRUPTION_PROFILES, FAULT_PROFILES
from repro.models import build_model
from repro.models.inputs import make_dummy_batch
from repro.serving import Request, Scheduler, ServeEngine

from .common import Rows

ARCH = "internvl2-76b"
BATCH = 2
PROMPT_LEN = 32
MAX_SEQ = 128
DECODE_TOKENS = 6
DEADLINE_S = 0.03
ARRIVAL_GAP_S = 0.002
N_REQUESTS = 8


def _setup():
    cfg = get_config(ARCH).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _requests(cfg, rng):
    out = []
    for rid in range(N_REQUESTS):
        p = dict(make_dummy_batch(cfg, InputShape("req", PROMPT_LEN, 1,
                                                  "train")))
        p["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, p["tokens"].shape), jnp.int32
        )
        out.append(Request(rid=rid, prompt=p, max_new_tokens=DECODE_TOKENS,
                           arrival_s=ARRIVAL_GAP_S * rid,
                           deadline_s=DEADLINE_S))
    return out


def sweep_time_plane(rows: Rows, cfg, model, params, seeds) -> None:
    """FaultModel profiles under the deadline scheduler, controller on."""
    tok0 = jnp.ones((BATCH, 1), jnp.int32)
    base = ServeEngine(model, params, max_seq=MAX_SEQ, batch_size=BATCH,
                       device="nano", sparsity=0.4, method="chunk", seed=5)
    t_base = np.asarray(base.decode(tok0, DECODE_TOKENS))
    for profile in sorted(set(FAULT_PROFILES) - {"none"}):
        for seed in seeds:
            eng = ServeEngine(model, params, max_seq=MAX_SEQ,
                              batch_size=BATCH, device="nano", sparsity=0.4,
                              method="chunk", seed=5,
                              fault_profile=profile, fault_seed=seed,
                              degrade=True)
            eng.simulator.noise = 0.0
            t = np.asarray(eng.decode(tok0, DECODE_TOKENS))
            assert np.array_equal(t_base, t), (
                f"{profile}/seed={seed}: time-plane faults moved tokens"
            )
            sched = Scheduler(eng, round_tokens=2)
            sched.submit(_requests(cfg, np.random.default_rng(17)))
            st = sched.run()
            fs = eng.fault_summary()
            rows.add(
                f"faults/{profile}/seed{seed}",
                st.latency_p99_s * 1e6,
                f"slo_attainment={st.slo_attainment:.3f} "
                f"tokens_per_s={st.tokens_per_s:.1f} "
                f"p99_ms={st.latency_p99_s * 1e3:.2f} "
                f"events={fs['fault_events']} retries={fs['fault_retries']} "
                f"degrade_scale={fs['degrade_scale']:.2f}",
            )


def sweep_data_plane(rows: Rows, cfg, model, params, seeds, combos) -> None:
    """CorruptionModel profiles through the checksum-verified decode path."""
    tok0 = jnp.ones((BATCH, 1), jnp.int32)
    bases = {}
    for backend, wbits in combos:
        b = ServeEngine(model, params, max_seq=MAX_SEQ, batch_size=BATCH,
                        device="nano", sparsity=0.4, method="chunk", seed=5,
                        backend=backend, wbits=wbits)
        bases[(backend, wbits)] = np.asarray(b.decode(tok0, DECODE_TOKENS))
    for profile in sorted(set(CORRUPTION_PROFILES) - {"none"}):
        for seed in seeds:
            for backend, wbits in combos:
                eng = ServeEngine(model, params, max_seq=MAX_SEQ,
                                  batch_size=BATCH, device="nano",
                                  sparsity=0.4, method="chunk", seed=5,
                                  backend=backend, wbits=wbits,
                                  corruption_profile=profile,
                                  corruption_seed=seed)
                t = np.asarray(eng.decode(tok0, DECODE_TOKENS))
                s = eng.io_summary()
                det = s["corruptions_detected"]
                rec = s["corruptions_recovered"]
                identical = bool(np.array_equal(
                    bases[(backend, wbits)], t))
                if profile == "bit_rot" and det > 0:
                    # transient flips: the recovery floor CI gates on
                    assert det == rec and identical, (
                        f"bit_rot/seed={seed}/{backend}/w{wbits}: recovery "
                        f"rate {rec}/{det}, identical={identical}"
                    )
                rows.add(
                    f"corruption/{profile}/seed{seed}/{backend}_w{wbits}",
                    s["integrity_reread_s"] * 1e6,
                    f"detected={det:.0f} recovered={rec:.0f} "
                    f"substituted={s['corruptions_substituted']:.0f} "
                    f"dropped={s['corruptions_dropped']:.0f} "
                    f"recovery_rate={rec / det if det else 1.0:.3f} "
                    f"tokens_identical={identical}",
                )


def run(rows: Rows, smoke: bool = False) -> None:
    cfg, model, params = _setup()
    seeds = (0,) if smoke else (0, 1, 2)
    combos = ((("reference", 16), ("kernel", 8)) if smoke else
              (("reference", 16), ("kernel", 16),
               ("reference", 8), ("kernel", 8)))
    sweep_time_plane(rows, cfg, model, params, seeds)
    sweep_data_plane(rows, cfg, model, params, seeds, combos)


def _emit_json(rows: Rows, path: str, smoke: bool) -> None:
    payload = {
        "bench": "fault_sweep",
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
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="one seed and two backend/wbits combos only")
    ap.add_argument("--out", default=None,
                    help="also write the rows as JSON (the nightly CI "
                         "artifact, e.g. BENCH_faults.json)")
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
