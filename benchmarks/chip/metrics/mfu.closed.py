"""Model step: required FLOPs of the work finished in the window, over
the window's seconds times the chips' bf16 peak, %."""
from benchmarks.chip import stats


def read(run):
    peak = run.peak["bf16_flops_per_s"] * run.cell.chips
    return 100.0 * stats.window_flops(run) / (run.seconds * peak)
