"""Engine: due time to first seen holding a slot, 90th percentile."""
from benchmarks.chip import stats


def read(run):
    return stats.percentile(stats.queue_wait_ms(run), 90)
