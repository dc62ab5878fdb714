"""The device's idle time and the mixed tick's device time, read by the
engine's own host spans (``serve.*``, recorded by
``repro.runtime.serve.ServingEngine.step``).

Each step is a ``serve.tick`` span holding ``serve.admit``,
``serve.schedule``, ``serve.dispatch`` (the uploads and the jitted tick
call, with the tick's ``family``), ``serve.sync`` (the host waiting on
the tick's results), ``serve.emit``, ``serve.preempt`` and
``serve.epilogue``.  The device's idle gaps in the traced window are
split by the innermost program span open on the host, with the
benchmark's own ``bench.*`` spans left out, so that a ``bench.tick.*``
span the benchmark opens inside ``serve.dispatch`` takes none of it.

The spans are read from ``Trace.program_spans``; where a trace
reduction keeps none, every reader here reads nothing.
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Optional

from benchmarks.chip import stats, xplane

PREFIX = "serve."
SYNC = "serve.sync"
DISPATCH = "serve.dispatch"


def program_spans(trace) -> list:
    """The trace's ``serve.*`` host spans, or ``[]``."""
    return getattr(trace, "program_spans", None) or []


def idle_split(run) -> Optional[tuple]:
    """``(sync, engine host)``: the shares of the traced window, %, in
    which the device ran nothing while the innermost program span open
    on the host was ``serve.sync``, or any other ``serve.*`` span;
    ``None`` where the trace holds no program spans."""
    trace = run.trace
    spans = program_spans(trace)
    if not spans:
        return None
    # idle_by_host splits by whatever spans the trace holds: give it
    # the program's alone
    only = dataclasses.replace(trace, spans=spans)
    idle = dict(xplane.idle_by_host(only, n=len(spans) + 1))
    sync = idle.get(SYNC, 0.0)
    engine = sum(v for k, v in idle.items() if k.startswith(PREFIX)) - sync
    return 100.0 * sync / trace.window_s, 100.0 * engine / trace.window_s


def family_ms(run, family: str) -> Optional[float]:
    """Mean device time per run of the ``tick_paged`` program (the
    mixed, solo-prefill and speculative ticks all compile to it) that a
    ``serve.dispatch`` of ``family`` launched, ms; ``None`` where none
    ran in the traced window.

    A run belongs to the latest dispatch that began before the run
    ended.  Its start would do as well but for the clocks: the profiler
    aligns the device's to the host's to about a millisecond, so a run
    may be seen to start just before the span that launched it; it ends
    tens of milliseconds later, and the next dispatch waits for its
    sync."""
    trace = run.trace
    dispatches = sorted((s for s in program_spans(trace)
                         if s.name == DISPATCH), key=lambda s: s.start)
    if not dispatches:
        return None
    starts = [s.start for s in dispatches]
    runs = [m.end - m.start for base in stats.TICK_MODULES["prefill"]
            for m in xplane.module_calls(trace, base)
            if (i := bisect.bisect_left(starts, m.end) - 1) >= 0
            and dispatches[i].stats.get("family") == family]
    return sum(runs) / len(runs) * 1e-6 if runs else None
