"""Per request (last - first token seen) / (tokens - 1), 90th
percentile.  Per request and not per gap: the engine delivers up to 8
decode steps per host sync, so the gaps are a statistic of syncs."""
from benchmarks.chip import stats


def read(run):
    return stats.percentile(stats.tpot_ms(run), 90)
