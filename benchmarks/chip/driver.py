"""The measured window: offers the traffic to the system under test and
keeps the benchmark's own clock of every request.

One thread drives everything.  Each pass of the loop submits what is
due, calls ``step()`` once, and then looks at every request in flight:
the host time at which ``step()`` returned is when its new tokens, its
slot and its completion were seen.  An open-loop request is timed from
its due time, so a late submission counts against the system; a closed
loop client sends its next request as soon as it has seen the last one
complete.

The benchmark records its spans around the calls into the engine
(``bench.submit``, ``bench.step``, ``bench.poll``, ``bench.idle``) and
around each tick-family call the engine makes (``bench.tick.<family>``,
by wrapping the engine's jitted ticks), so that a profile can attribute
the device's time and its idle gaps to what the host was doing.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Callable, Optional

from jax.profiler import TraceAnnotation

# the engine's jitted tick families, by attribute
TICK_ATTRS = {"_chunk_fn": "decode", "_mixed_fn": "mixed",
              "_solo_fn": "solo_prefill"}


@dataclasses.dataclass
class Rec:
    """What the benchmark saw of one request (host clock, seconds)."""

    req: object                     # repro.runtime.serve.Request
    due: float
    submitted: Optional[float] = None
    admitted: Optional[float] = None   # first seen holding a slot
    first: Optional[float] = None      # first token seen
    last: Optional[float] = None       # latest token seen
    done: Optional[float] = None       # completion seen
    n_seen: int = 0
    n_in_window: int = 0            # tokens seen by the window's close
    client: int = 0

    @property
    def prompt_len(self) -> int:
        return len(self.req.prompt)


@dataclasses.dataclass
class TickCall:
    """One call of a jitted tick, and the decode work it did: ``(prompt
    length, tokens out before the tick, tokens the tick emitted)`` of
    each slot decoding when it was called (the request itself until the
    step that made the call has returned)."""

    step: int
    family: str
    decoding: list


@dataclasses.dataclass
class Window:
    open: float
    close: float
    end: float                      # when the drain stopped
    recs: list                      # every Rec sent in the window
    ticks: list                     # TickCall of every step
    tokens_in_window: int
    steps: int
    # host seconds of each step() that returned inside the window
    step_s: list = dataclasses.field(default_factory=list)
    # seconds of each garbage collection pass inside the window
    gc_s: list = dataclasses.field(default_factory=list)


class TickLog:
    """Wraps an engine's jitted ticks: each call is logged with the
    decode work it carries and runs inside a ``bench.tick.<family>``
    span.  The engine calls the wrapper exactly as it calls the tick."""

    def __init__(self):
        self.calls: list[TickCall] = []
        self.step = 0

    def instrument(self, engine) -> None:
        for attr, family in TICK_ATTRS.items():
            fn = getattr(engine, attr, None)
            if fn is not None:
                setattr(engine, attr, self._wrap(engine, family, fn))

    def settle(self) -> None:
        """After a step: what each of its tick calls emitted."""
        for call in reversed(self.calls):
            if call.step != self.step:
                break
            call.decoding = [(p, k, len(r.out) - k)
                             for p, k, r in call.decoding]
        self.step += 1

    def _wrap(self, engine, family: str, fn: Callable) -> Callable:
        def tick(*args):
            jobs = engine._jobs
            decoding = [(len(r.prompt), len(r.out), r)
                        for s, r in engine.active.items() if s not in jobs]
            self.calls.append(TickCall(self.step, family, decoding))
            with TraceAnnotation(f"bench.tick.{family}"):
                return fn(*args)
        return tick


class Frontier:
    """``submit / step / poll / has_work`` over one ServingEngine, whose
    own asynchronous frontier does the admission."""

    def __init__(self, engine):
        self.engine = engine

    def submit(self, req) -> None:
        self.engine.submit(req)

    def step(self) -> None:
        self.engine.step()

    def poll(self) -> list:
        return self.engine.poll()

    @property
    def has_work(self) -> bool:
        return self.engine.has_work


class FleetFrontier:
    """The same four calls over a FleetSupervisor: arrivals queue here
    and are routed by ``admit_many`` before every fleet step."""

    def __init__(self, fleet):
        self.fleet = fleet
        self.pending: list = []
        self.done: list = []

    def submit(self, req) -> None:
        self.pending.append(req)

    def step(self) -> None:
        n = self.fleet.admit_many(self.pending)
        del self.pending[:n]
        self.done += self.fleet.step()

    def poll(self) -> list:
        out, self.done = self.done, []
        return out

    @property
    def has_work(self) -> bool:
        return bool(self.pending or self.done
                    or any(e.has_work for e in self.fleet.engines))


def run_window(frontier, specs: list, mix: dict, *, seconds: float,
               drain_s: float, make_request: Callable, log: TickLog,
               at_open: Callable = lambda: None,
               hooks: tuple = (), clock: Callable = time.perf_counter,
               sleep: Callable = time.sleep) -> Window:
    """Offer ``specs`` for ``seconds``, then drain what was sent for at
    most ``drain_s``.  ``at_open`` runs just before the window opens
    (the stats reset); ``hooks`` are ``(seconds after open, fn)``
    called once each from the loop (starting and stopping a trace)."""
    closed_loop = mix["loop"] == "closed"
    if closed_loop:
        queues: dict = {}
        for spec in specs:
            queues.setdefault(spec.client, []).append(spec)
        for q in queues.values():
            q.reverse()                 # pop() takes each client's next
    else:
        queues = {None: sorted(specs, key=lambda s: (s.due_s, s.rid),
                               reverse=True)}
    hooks = sorted(hooks, key=lambda h: h[0], reverse=True)
    at_open()
    t_open = clock()
    t_close = t_open + seconds
    recs: dict = {}
    inflight: list = []
    tokens_in_window = 0
    step_s: list = []
    gc_s: list = []
    gc_start: list = []

    def on_gc(phase: str, info: dict) -> None:
        if phase == "start":
            gc_start[:] = [time.perf_counter()]
        elif gc_start and clock() < t_close:
            gc_s.append(time.perf_counter() - gc_start[0])

    gc.callbacks.append(on_gc)

    def send(spec, due: float, now: float) -> None:
        req = make_request(spec)
        rec = Rec(req=req, due=due, submitted=now, client=spec.client)
        recs[spec.rid] = rec
        inflight.append(rec)
        frontier.submit(req)

    if closed_loop:
        with TraceAnnotation("bench.submit"):
            for q in queues.values():
                send(q.pop(), t_open, t_open)
    while True:
        now = clock()
        while hooks and now >= t_open + hooks[-1][0]:
            hooks.pop()[1]()
            now = clock()
        if not closed_loop and now < t_close:
            due = queues[None]
            if due and t_open + due[-1].due_s <= now:
                with TraceAnnotation("bench.submit"):
                    while due and t_open + due[-1].due_s <= now:
                        spec = due.pop()
                        send(spec, t_open + spec.due_s, clock())
        if frontier.has_work:
            before = clock()
            with TraceAnnotation("bench.step", i=log.step):
                frontier.step()
            seen = clock()
            if seen <= t_close:
                step_s.append(seen - before)
            log.settle()
            with TraceAnnotation("bench.poll"):
                done = {id(r) for r in frontier.poll()}
                still = []
                for rec in inflight:
                    n = len(rec.req.out)
                    if n > rec.n_seen:
                        if rec.first is None:
                            rec.first = seen
                        rec.last = seen
                        if seen <= t_close:
                            tokens_in_window += n - rec.n_seen
                            rec.n_in_window = n
                        rec.n_seen = n
                    if rec.admitted is None and rec.req.slot is not None:
                        rec.admitted = seen
                    if id(rec.req) in done:
                        rec.done = seen
                        if closed_loop and seen < t_close:
                            q = queues[rec.client]
                            if q:
                                send(q.pop(), seen, seen)
                    else:
                        still.append(rec)
                inflight[:] = still
        now = clock()
        if now >= t_close and (not inflight or now >= t_close + drain_s):
            break
        if not frontier.has_work:
            wake = t_close
            if not closed_loop and queues[None]:
                wake = min(wake, t_open + queues[None][-1].due_s)
            if hooks:
                wake = min(wake, t_open + hooks[-1][0])
            with TraceAnnotation("bench.idle"):
                sleep(max(0.0, wake - clock()))
    gc.callbacks.remove(on_gc)
    for _, fn in reversed(hooks):       # a hook past the end still runs
        fn()
    return Window(open=t_open, close=t_close, end=clock(),
                  recs=list(recs.values()), ticks=log.calls,
                  tokens_in_window=tokens_in_window, steps=log.step,
                  step_s=step_s, gc_s=gc_s)
