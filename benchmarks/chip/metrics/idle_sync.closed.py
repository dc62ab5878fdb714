"""Device: share of the traced window with no operation running while
the engine's innermost open span is serve.sync (the host waits on the
tick's results), %."""
from benchmarks.chip import engine_spans


def read(run):
    split = engine_spans.idle_split(run)
    return None if split is None else split[0]
