"""Engine: share of the traced window with no operation running on the
device while the engine's innermost open span is any serve.* span but
serve.sync (the engine's host work holds the chip back), %."""
from benchmarks.chip import engine_spans


def read(run):
    split = engine_spans.idle_split(run)
    return None if split is None else split[1]
