"""Device time per run of the decode-chunk program, traced window."""
from benchmarks.chip import stats


def read(run):
    return stats.module_ms(run, "decode")
