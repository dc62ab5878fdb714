"""Due time to first token seen, 90th percentile over every request
sent: the highest percentile with ten requests beyond it at about a
hundred requests a window."""
from benchmarks.chip import stats


def read(run):
    return stats.percentile(stats.ttft_ms(run), 90)
