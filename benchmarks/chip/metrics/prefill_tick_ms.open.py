"""Device time per run of the mixed and solo prefill tick programs,
traced window."""
from benchmarks.chip import stats


def read(run):
    return stats.module_ms(run, "prefill")
