"""Readings for a cell's correctness limit: the mean logit gap of the
program's served tokens over many seeds (the lower reading) and of the
float8 control on the same samples (the upper reading), with the
widest gaps beside them, all in one process so that each seed pays a
short set-up and everything compiles once.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 101,102,... --control 3 --seconds <s>

Each seed runs the cell as the benchmark does, at its own load, for
``--seconds``; the first ``--control`` seeds also read the control.  One
JSON line per seed, then a summary line.  The limit is then set by hand
in ``limits/<cell>.json`` between the two readings, with room on both
sides, and the readings are recorded beside it.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--control", type=int, default=3,
                    help="how many of the seeds also read the control")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    from benchmarks.chip import harness
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        r = harness.run(args.workload, seed, args.seconds, False, t0=t,
                        control=i < args.control)
        row = {"seed": seed,
               "program_mean": r["checks"]["mean_gap"]["value"],
               "program_widest": r["widest_gap"],
               "tokens": r["checks"]["tokens_checked"]["value"],
               "correct": r["correct"],
               "attempted": r["attempted"], "failed": r["failed"],
               "seconds": time.perf_counter() - t}
        if "control" in r:
            c = r["control"]
            row.update(control_mean=c["checks"]["mean_gap"]["value"],
                       control_widest=c["widest_gap"],
                       control_correct=c["correct"])
        rows.append(row)
        print(json.dumps(row), flush=True)
        gc.collect()
    summary = {"workload": args.workload, "seeds": len(seeds),
               "lower": max(r["program_mean"] for r in rows),
               "program_correct": sum(r["correct"] for r in rows)}
    controls = [r for r in rows if "control_mean" in r]
    if controls:
        summary["upper"] = min(r["control_mean"] for r in controls)
        summary["control_correct"] = sum(r["control_correct"]
                                         for r in controls)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
