"""The trace reduction: on hand-made traces whose answers are known, and
on ``fixtures/tiny.xplane.pb``, a small trace recorded on a TPU v5e by
``make_trace_fixture.py`` (three decode-chunk steps and one prefill
step, each followed by 20 ms of idle host)."""
import dataclasses
import types
from pathlib import Path

import pytest

from benchmarks.chip import stats, xplane
from benchmarks.chip.xplane import Event, Trace

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "tiny.xplane.pb"


def hand_trace():
    # window 0..100; device busy 10-30 and 25-40 (one interval 10-40)
    # and 60-70; host: a step 5-45 holding a tick 8-12, idle 45-100
    ops = {"/device:TPU:0": [Event("fusion.1", 10, 30),
                             Event("paged_attention", 25, 40),
                             Event("fusion.1", 60, 70)]}
    modules = {"/device:TPU:0": [Event("jit_chunk_fn_paged(3)", 10, 40),
                                 Event("jit_tick_paged(9)", 60, 70)]}
    spans = [Event(xplane.WINDOW_SPAN, 0, 100),
             Event("bench.step", 5, 45, {"i": 0}),
             Event("bench.tick.decode", 8, 12),
             Event("bench.idle", 45, 100)]
    return Trace(window=(0, 100), ops=ops, modules=modules, spans=spans)


def test_union_merges_and_clips():
    assert xplane.union([(10, 30), (25, 40), (60, 70), (90, 120)],
                        0, 100) == [[10, 40], [60, 70], [90, 100]]


def test_busy_idle_and_breakdown():
    tr = hand_trace()
    assert xplane.busy_s(tr) == pytest.approx(40e-9)
    run = types.SimpleNamespace(trace=tr)
    assert stats.idle_share(run) == pytest.approx(60.0)
    assert xplane.top_ops(tr) == [
        ["chunk_fn_paged/fusion.1", pytest.approx(20e-9)],
        ["chunk_fn_paged/paged_attention", pytest.approx(15e-9)],
        ["tick_paged/fusion.1", pytest.approx(10e-9)]]
    # idle 0-10: 0-5 under no span, 5-8 the step, 8-10 its tick;
    # 40-60: the step to 45, then idle; 70-100: idle
    idle = dict(xplane.idle_by_host(tr))
    assert idle == {"host:other": pytest.approx(5e-9),
                    "bench.step": pytest.approx(8e-9),
                    "bench.tick.decode": pytest.approx(2e-9),
                    "bench.idle": pytest.approx(45e-9)}
    assert sum(idle.values()) == pytest.approx(tr.window_s
                                               - xplane.busy_s(tr))


def test_self_time_of_nested_ops():
    # a loop 0-100 holding two body ops; a later op on its own
    evs = [Event("while.1", 0, 100), Event("fusion.2", 10, 40),
           Event("paged_attention.3", 50, 90), Event("copy.4", 120, 130)]
    own = {e.name: t for e, t in xplane.self_times(evs)}
    assert own == {"while.1": 30, "fusion.2": 30, "paged_attention.3": 40,
                   "copy.4": 10}


def test_module_time_by_family():
    run = types.SimpleNamespace(trace=hand_trace())
    assert stats.module_ms(run, "decode") == pytest.approx(30e-6)
    assert stats.module_ms(run, "prefill") == pytest.approx(10e-6)
    assert stats.module_ms(types.SimpleNamespace(trace=None),
                           "decode") is None
    assert xplane.module_base("jit_chunk_fn_paged(3)") == "chunk_fn_paged"


def test_kernel_names():
    assert xplane.is_kernel("paged_attention.3", "paged_attention")
    assert xplane.is_kernel("paged_attention", "paged_attention")
    assert not xplane.is_kernel("paged_attention_wide", "paged_attention")


@pytest.fixture(scope="module")
def recorded():
    return xplane.read(str(FIXTURE))


def test_recorded_trace_planes_and_window(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    steps = [s for s in recorded.spans if s.name == "bench.step"]
    assert sorted(s.stats["i"] for s in steps) == [0, 1, 2, 3]
    assert 0.06 < recorded.window_s < 1.0
    busy = xplane.busy_s(recorded)
    assert 0 < busy < recorded.window_s


def test_recorded_trace_programs_and_kernel(recorded):
    # the device's clock agrees with the host's to about a millisecond:
    # the first program is seen to start 0.56 ms before the window span
    # that the host opened before launching it, so the window holds two
    # of the three decode-chunk runs
    everything = dataclasses.replace(recorded, window=(0, 2**62))
    decode = xplane.module_calls(everything, "chunk_fn_paged")
    prefill = xplane.module_calls(everything, "tick_paged")
    assert (len(decode), len(prefill)) == (3, 1)
    assert len(xplane.module_calls(recorded, "chunk_fn_paged")) == 2
    kernels = [e for e in recorded.ops["/device:TPU:0"]
               if xplane.is_kernel(e.name, "paged_attention")]
    assert len(kernels) == 3
    # each kernel call runs inside a decode-chunk program
    assert all(any(m.start <= k.start and k.end <= m.end for m in decode)
               for k in kernels)


def test_recorded_trace_idle_lies_under_bench_idle(recorded):
    idle = dict(xplane.idle_by_host(recorded))
    assert max(idle, key=idle.get) == "bench.idle"
    assert idle["bench.idle"] >= 4 * 0.02 * 0.9
    total = sum(idle.values())
    assert total == pytest.approx(recorded.window_s
                                  - xplane.busy_s(recorded), rel=1e-6)
