"""Benchmark harness — one module per paper table/figure + system benches.

  table1    — paper Table 1 (clock-exact reproduction)
  fig456    — paper Figs 4/5/6 (speedup, S/k, α_eff vs vector length)
  roofline  — §Roofline terms per (arch × shape) from the dry-run artifact
  kernels   — per-kernel timing + arithmetic intensity vs the v5e ridge
  e2e       — tiny end-to-end train throughput + slot-pool serving
  serve     — device-resident continuous batching; writes BENCH_serve.json

Prints ``name,...`` CSV.  ``python -m benchmarks.run [section ...]`` or
``python -m benchmarks.run --suite serve``.
"""
import argparse
import traceback


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (e2e_bench, fig456, kernels_bench, roofline,
                            serve_bench, table1)
    sections = {
        "table1": table1.run,
        "fig456": fig456.run,
        "roofline": roofline.run,
        "kernels": kernels_bench.run,
        "e2e": e2e_bench.run,
        "serve": serve_bench.run,
    }
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("sections", nargs="*", choices=[[]] + list(sections),
                    help="sections to run (default: all)")
    ap.add_argument("--suite", action="append", choices=list(sections),
                    help="section to run (repeatable; alias for positional)")
    args = ap.parse_args()
    want = list(args.sections) + list(args.suite or [])
    want = want or list(sections)
    failures = 0
    for name in want:
        try:
            for line in sections[name]():
                print(line)
        except Exception:
            failures += 1
            print(f"{name},ERROR")
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
