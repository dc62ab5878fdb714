"""Arithmetic the metric readers share: per-request latencies on the
benchmark's clock, percentiles, and the device time of the compiled
tick programs.

Every latency is taken over all the requests sent in the window.  A
request that never showed its first token before the drain stopped
counts with the time it had waited by then, a lower bound of its
time to first token, so that a request failing slows the tail and never
drops out of it.
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip import work, xplane

# compiled tick programs by the jitted function's name
# (repro.runtime.serve.build_decode_chunk, build_mixed_tick and
# build_solo_prefill_tick)
TICK_MODULES = {"decode": ("chunk_fn_paged",), "prefill": ("tick_paged",)}


def counts(window) -> tuple:
    """``(attempted, failed)``: requests sent in the window, and those
    of them that had not finished when the drain stopped."""
    return (len(window.recs),
            sum(r.done is None for r in window.recs))


def percentile(values, q: float):
    """The ``q``-th percentile (linear between order statistics), or
    ``None`` for no values."""
    return float(np.percentile(values, q)) if len(values) else None


def ttft_ms(run) -> list:
    """Due time to first token seen, every request sent in the window."""
    w = run.window
    return [((r.first if r.first is not None else w.end) - r.due) * 1e3
            for r in w.recs]


def tpot_ms(run) -> list:
    """(last - first token seen) / (tokens - 1) of every request that
    showed two tokens or more."""
    return [(r.last - r.first) / (r.n_seen - 1) * 1e3
            for r in run.window.recs if r.n_seen >= 2]


def queue_wait_ms(run) -> list:
    """Due time to first seen holding a slot; the end of the drain for a
    request never admitted."""
    w = run.window
    return [((r.admitted if r.admitted is not None else w.end) - r.due)
            * 1e3 for r in w.recs]


def module_ms(run, family: str):
    """Mean device time per run of a tick family's compiled program in
    the traced window, or ``None`` where none ran there."""
    if run.trace is None:
        return None
    calls = [m for base in TICK_MODULES[family]
             for m in xplane.module_calls(run.trace, base)]
    if not calls:
        return None
    return sum(m.end - m.start for m in calls) / len(calls) * 1e-6


def idle_share(run):
    """Share of the traced window in which the device ran nothing, %."""
    if run.trace is None:
        return None
    return 100.0 * (1.0 - xplane.busy_s(run.trace) / run.trace.window_s)


def window_flops(run) -> float:
    """Required FLOPs of the work finished in the window: every token
    generated in it, and the whole prompt of every request whose first
    token came in it.  Generated token j >= 1 of a request with a
    P-token prompt was computed from position P + j - 1 over P + j
    positions; token 0 is the prompt's."""
    arch, m = run.cell.arch, run.m
    n_mm = 2.0 * arch.matmul_params(m)
    total = 0.0
    for r in run.window.recs:
        n, p = r.n_in_window, r.prompt_len
        if n == 0:
            continue
        total += work.prompt_flops(arch, m, p)
        gen = n - 1                     # tokens 1 .. n-1
        total += n_mm * gen + work.attention_sum(arch, m, p + 1, gen)
    return total
