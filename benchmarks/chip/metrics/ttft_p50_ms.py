"""Due time to first token seen, median over every request sent."""
from benchmarks.chip import stats


def read(run):
    return stats.percentile(stats.ttft_ms(run), 50)
