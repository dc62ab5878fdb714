"""Find an open-loop cell's knee: the highest Poisson rate whose queue
does not grow over the window.  All rates run in one process.

    python3 benchmarks/chip/sweep.py --workload <cell> \\
        --rates 0.5,1,1.5,2 --seconds <s> --seed <n>

For each rate it prints the requests sent, those admitted and finished
by the window's close, the queue (sent and not yet admitted) at the
middle and at the close of the window, and the time to first token.
A rate holds when the queue at the close is no longer than at the
middle and every request due before the middle has been admitted by
the close.  The cell then runs at four fifths of the highest rate that
holds, written by hand into its traffic mix.
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


def queue_at(recs: list, t: float) -> int:
    return sum(r.due <= t and (r.admitted is None or r.admitted > t)
               for r in recs)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated, /s")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from benchmarks.chip import harness, stats
    for rate in [float(r) for r in args.rates.split(",")]:
        data, _, _ = harness.measure(args.workload, args.seed, args.seconds,
                                     False, t0=time.perf_counter(),
                                     rate=rate, drain_s=0.0)
        gc.collect()
        w = data.window
        mid = w.open + args.seconds / 2
        early = [r for r in w.recs if r.due <= mid]
        q_mid, q_close = queue_at(w.recs, mid), queue_at(w.recs, w.close)
        holds = q_close <= max(q_mid, 1) and all(
            r.admitted is not None and r.admitted <= w.close for r in early)
        ttft = stats.ttft_ms(data)
        print(json.dumps({
            "rate_per_s": rate, "sent": len(w.recs),
            "admitted_by_close": sum(r.admitted is not None
                                     and r.admitted <= w.close
                                     for r in w.recs),
            "finished_by_close": sum(r.done is not None
                                     and r.done <= w.close
                                     for r in w.recs),
            "queue_mid": q_mid, "queue_close": q_close, "holds": holds,
            "ttft_p50_ms": stats.percentile(ttft, 50),
            "ttft_p90_ms": stats.percentile(ttft, 90),
            "tokens_per_s": w.tokens_in_window / args.seconds,
            "occupancy": data.occupancy}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
