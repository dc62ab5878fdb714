"""The window's arithmetic, on a fake engine with a fake clock: every
percentile is over all requests sent, a rate is over the whole window,
TPOT is per request, latencies run from the due time, and a request not
finished by the end of the drain counts as failed and as slow."""
import gc
import types

import numpy as np
import pytest

from benchmarks.chip import cells, driver, stats, traffic


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class FakeEngine:
    """``slots`` requests at a time, FIFO; every step takes ``dt`` and
    gives each admitted request one token (the first in the step that
    admits it)."""

    def __init__(self, clock, slots=2, dt=0.1):
        self.clock, self.slots, self.dt = clock, slots, dt
        self.queue, self.active, self.done = [], [], []

    def submit(self, req):
        self.queue.append(req)

    def step(self):
        while self.queue and len(self.active) < self.slots:
            req = self.queue.pop(0)
            req.slot = len(self.active)
            self.active.append(req)
        self.clock.t += self.dt
        for req in list(self.active):
            req.out.append(7)
            if len(req.out) == req.max_new:
                self.active.remove(req)
                self.done.append(req)

    def poll(self):
        out, self.done = self.done, []
        return out

    @property
    def has_work(self):
        return bool(self.queue or self.active or self.done)


def spec(rid, due, max_new, client=0):
    return traffic.Spec(rid=rid, prompt=np.arange(2, 12, dtype=np.int32),
                        max_new=max_new, due_s=due, client=client)


def request(s):
    return types.SimpleNamespace(rid=s.rid, prompt=s.prompt,
                                 max_new=s.max_new, out=[], slot=None)


def run(specs, mix, seconds, drain_s=100.0, **engine_kw):
    clock = Clock()
    eng = FakeEngine(clock, **engine_kw)
    w = driver.run_window(eng, specs, mix, seconds=seconds, drain_s=drain_s,
                          make_request=request, log=driver.TickLog(),
                          clock=clock, sleep=clock.sleep)
    return types.SimpleNamespace(window=w, seconds=seconds)


OPEN = {"loop": "open"}


def test_open_loop_latencies_from_due_time():
    # two slots, 0.1 s steps; three requests due together at t=0.05:
    # the third waits for a slot, and all are timed from 0.05
    r = run([spec(0, 0.05, 3), spec(1, 0.05, 3), spec(2, 0.05, 2)],
            OPEN, seconds=1.0)
    ttft = stats.ttft_ms(r)
    assert ttft == pytest.approx([100.0, 100.0, 400.0])
    wait = stats.queue_wait_ms(r)
    assert wait == pytest.approx([100.0, 100.0, 400.0])
    # per request: (last - first) / (tokens - 1) = 0.1 s
    assert stats.tpot_ms(r) == pytest.approx([100.0] * 3)
    assert stats.counts(r.window) == (3, 0)
    assert cells.load_reader("ttft_p50_ms")(r) == pytest.approx(100.0)
    assert cells.load_reader("ttft_p90_ms")(r) == pytest.approx(
        np.percentile([100, 100, 400], 90))


def test_rate_counts_tokens_seen_inside_the_window_only():
    # one slot, 8 tokens at 0.1 s each from t=0: 0.1 .. 0.8; a 0.45 s
    # window sees 4 of them, and the rate is over the whole window
    r = run([spec(0, 0.0, 8)], {"loop": "closed"}, seconds=0.45,
            slots=1)
    assert r.window.tokens_in_window == 4
    assert r.window.recs[0].n_in_window == 4
    assert r.window.recs[0].n_seen == 8          # drained after close
    assert cells.load_reader("output_tok_s")(r) == pytest.approx(4 / 0.45)


def test_drained_request_counts_failed_and_slow():
    # the second request cannot finish within a 0.25 s drain
    r = run([spec(0, 0.0, 2), spec(1, 0.1, 50)], OPEN, seconds=0.2,
            drain_s=0.25, slots=1)
    w = r.window
    assert stats.counts(w) == (2, 1)
    assert w.end - w.close == pytest.approx(0.3)   # stops at the step
    rec = w.recs[1]
    assert rec.done is None and rec.n_seen == 3
    # a request that never started counts with its wait so far
    r = run([spec(0, 0.0, 30), spec(1, 0.0, 2)], OPEN, seconds=0.1,
            drain_s=0.5, slots=1)
    assert r.window.recs[1].first is None
    assert stats.ttft_ms(r)[1] == pytest.approx(
        (r.window.end - r.window.recs[1].due) * 1e3)


def test_generator_lag_is_submit_minus_due():
    # a request falls due during a 0.5 s step: submitted at its end
    r = run([spec(0, 0.0, 2), spec(1, 0.05, 1)], OPEN, seconds=2.0,
            slots=1, dt=0.5)
    assert r.window.recs[1].submitted - r.window.recs[1].due == \
        pytest.approx(0.45)
    assert cells.load_reader("gen_lag_max_ms")(r) == pytest.approx(450.0)


def test_closed_loop_client_sends_next_on_completion():
    specs = [spec(i, 0.0, 2, client=i % 2) for i in range(6)]
    r = run(specs, {"loop": "closed"}, seconds=0.55, slots=2)
    recs = sorted(r.window.recs, key=lambda x: x.req.rid)
    # each client: one request per 0.2 s, sent when the last was seen
    assert [x.req.rid for x in recs] == [0, 1, 2, 3, 4, 5]
    assert recs[2].due == pytest.approx(recs[0].done)
    assert recs[4].due == pytest.approx(recs[2].done)


def test_percentile_of_nothing_is_none():
    assert stats.percentile([], 90) is None


def test_host_step_times_and_collections_inside_the_window():
    # one slot, 0.1 s steps returning at 0.1 .. 0.8: a 0.45 s window
    # times the four that return inside it; a collection run by a step
    # inside the window is counted, one after its close is not
    from benchmarks.chip.harness import host_steps

    class Collecting(FakeEngine):
        def step(self):
            super().step()
            gc.collect()

    clock = Clock()
    eng = Collecting(clock, slots=1)
    w = driver.run_window(eng, [spec(0, 0.0, 8)], {"loop": "closed"},
                          seconds=0.45, drain_s=100.0, make_request=request,
                          log=driver.TickLog(), clock=clock,
                          sleep=clock.sleep)
    assert w.step_s == pytest.approx([0.1] * 4)
    assert len(w.gc_s) == 4 and all(s >= 0 for s in w.gc_s)
    line = host_steps(w)
    assert "none 4 in 0.400 s, median 100.0 ms, slowest 100.0" in line
    assert "in 4 passes" in line
    assert on_gc_left() == 0


def on_gc_left() -> int:
    """Callbacks a window left registered with the collector."""
    return sum(getattr(cb, "__name__", "") == "on_gc"
               for cb in gc.callbacks)
