"""Device: share of the traced window with no operation running, %."""
from benchmarks.chip import stats


def read(run):
    return stats.idle_share(run)
