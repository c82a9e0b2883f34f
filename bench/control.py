"""Read the numbers the correctness check compares, over many seeds in one
process: the sound program's (the lower readings) or a control's (the
upper ones). The benchmark's own runs never run a control.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10
    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10 --control fp8

``--control program`` switches on the program's own int8 weight path;
``--control fp8`` puts the reference with float8 weights in its place;
``--fault <name>`` plants one of ``bench/faults.py``'s faults instead.
Prints one JSON line per seed. Needs a TPU, as a run does.
"""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import contextlib  # noqa: E402

from bench import faults, harness, spec  # noqa: E402


def main(argv) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", choices=harness.CONTROLS, default=None)
    ap.add_argument("--fault", choices=faults.FAULTS, default=None)
    args = ap.parse_args(argv)
    if args.control and args.fault:
        ap.error("a control or a fault, not both")
    cell = spec.load_cell(args.workload)
    ref = spec.reference_module(cell)
    why = harness.require_chips(cell.chips)
    if why:
        print(f"control: {why}", file=sys.stderr)
        return 3
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        hooks, ctx = None, contextlib.nullcontext()
        if args.fault == "stale_state":
            hooks = faults.stale_state()
        elif args.fault == "altered_token":
            hooks = faults.altered_token(ref.dims(cell.cfg).vocab)
        elif args.fault == "half_batch":
            ctx = faults.half_batch()
        try:
            with ctx:
                r = harness.run_cell(cell, seed, args.seconds, False, t0,
                                     control=args.control, hooks=hooks)
        except Exception as e:  # a control that crashes has failed: say so, go on
            print(json.dumps({"workload": cell.name, "seed": seed, "control": args.control,
                              "fault": args.fault,
                              "error": f"{type(e).__name__}: {e}"[:500]}), flush=True)
            continue
        print(json.dumps({"workload": cell.name, "seed": seed, "control": args.control,
                          "fault": args.fault, "correct": r["correct"], "failed": r["failed"],
                          "readings": {k: c["value"] for k, c in r["checks"].items()},
                          "memory_peak_bytes": r["device"]["memory_peak_bytes"],
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
