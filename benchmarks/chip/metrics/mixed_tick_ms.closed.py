"""Device time per run of the tick program a serve.dispatch of family
mixed launched, traced window, ms."""
from benchmarks.chip import engine_spans


def read(run):
    return engine_spans.family_ms(run, "mixed")
