"""The readers of the engine's own spans (``engine_spans.py`` and the
metrics ``idle_sync.closed``, ``idle_engine_host.closed`` and
``mixed_tick_ms.closed``): known answers on a hand-made trace, and
nothing read from ``fixtures/tiny.xplane.pb``, recorded before the
engine had spans."""
import dataclasses
import types
from pathlib import Path

import pytest

from benchmarks.chip import cells, engine_spans, xplane
from benchmarks.chip.xplane import Event, Trace

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "tiny.xplane.pb"
READERS = ("idle_sync.closed", "idle_engine_host.closed",
           "mixed_tick_ms.closed")


def tick(t0, t1, *phases):
    """A ``serve.tick`` span and the ``(name, start, end[, stats])``
    spans inside it."""
    return [Event("serve.tick", t0, t1)] + [Event(*p) for p in phases]


def hand_trace():
    # window 0..200; the device runs a mixed tick 20-60, a solo-prefill
    # tick 100-130 and a decode chunk 150-180
    dev = "/device:TPU:0"
    ops = {dev: [Event("fusion.1", 20, 60), Event("fusion.2", 100, 130),
                 Event("paged_attention", 150, 180)]}
    modules = {dev: [Event("jit_tick_paged(4)", 20, 60),
                     Event("jit_tick_paged(4)", 100, 130),
                     Event("jit_chunk_fn_paged(2)", 150, 180)]}
    spans = [Event(xplane.WINDOW_SPAN, 0, 200),
             Event("bench.step", 4, 94, {"i": 0}),
             Event("bench.tick.mixed", 13, 17),
             Event("bench.step", 95, 144, {"i": 1}),
             Event("bench.tick.solo_prefill", 97, 99),
             Event("bench.step", 145, 194, {"i": 2}),
             Event("bench.tick.decode", 147, 148)]
    program = (
        tick(5, 95, ("serve.admit", 5, 10, {"queued": 1}),
             ("serve.schedule", 10, 12),
             ("serve.dispatch", 12, 18, {"family": "mixed"}),
             ("serve.sync", 18, 65, {"compiles": 0}),
             ("serve.emit", 65, 80), ("serve.epilogue", 80, 90))
        + tick(95, 145, ("serve.dispatch", 96, 99,
                         {"family": "solo_prefill"}),
               ("serve.sync", 99, 135), ("serve.emit", 135, 140))
        + tick(145, 195, ("serve.dispatch", 146, 149,
                          {"family": "decode"}),
               ("serve.sync", 149, 185), ("serve.emit", 185, 190)))
    tr = Trace(window=(0, 200), ops=ops, modules=modules, spans=spans)
    tr.program_spans = program
    return tr


def read(metric, trace):
    run = types.SimpleNamespace(trace=trace)
    return cells.load_reader(metric)(run)


def test_idle_split_by_the_innermost_program_span():
    tr = hand_trace()
    # idle 0-20, 60-100, 130-150, 180-200 (100 of 200).  Under sync:
    # 18-20, 60-65, 99-100, 130-135, 149-150, 180-185 (19).  Under no
    # program span: 0-5 and 195-200 (10).  The rest (71) lies under the
    # engine's other spans, among them the whole solo-prefill dispatch
    # 96-99, though the benchmark's span 97-99 is nested in it.
    assert read("idle_sync.closed", tr) == pytest.approx(100 * 19 / 200)
    assert read("idle_engine_host.closed", tr) == pytest.approx(
        100 * 71 / 200)
    # the benchmark's own breakdown still reads its own spans
    assert dict(xplane.idle_by_host(tr))["bench.tick.solo_prefill"] \
        == pytest.approx(2e-9)


def test_mixed_tick_time_counts_mixed_dispatches_only():
    tr = hand_trace()
    # the solo-prefill tick also runs tick_paged, 30 long: not counted
    assert read("mixed_tick_ms.closed", tr) == pytest.approx(40e-6)
    # a program the device clock shows starting before its dispatch
    # span still belongs to it
    early = dataclasses.replace(tr, modules={d: [
        dataclasses.replace(m, start=m.start - 9) for m in evs]
        for d, evs in tr.modules.items()})
    early.program_spans = tr.program_spans
    assert read("mixed_tick_ms.closed", early) == pytest.approx(49e-6)
    # no mixed tick in the window: nothing to read
    tr.program_spans = [s for s in tr.program_spans
                        if s.stats.get("family") != "mixed"]
    assert read("mixed_tick_ms.closed", tr) is None


@pytest.mark.parametrize("metric", READERS)
def test_readers_read_nothing_without_program_spans(metric):
    tr = hand_trace()
    tr.program_spans = []
    assert read(metric, tr) is None
    assert read(metric, None) is None


@pytest.mark.parametrize("metric", READERS)
def test_recorded_trace_has_no_program_spans(metric):
    recorded = xplane.read(str(FIXTURE))
    assert engine_spans.program_spans(recorded) == []
    assert [s.name for s in recorded.spans
            if not s.name.startswith("bench.")] == []
    assert read(metric, recorded) is None
