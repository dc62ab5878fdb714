"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the device's operations and compiled programs, the
benchmark's own host spans, and the traced window.

The trace is read with ``jax.profiler.ProfileData``, nothing else.  A
device plane is one named ``/device:TPU:<n>``; its ``XLA Ops`` line
holds one event per operation executed and its ``XLA Modules`` line one
per compiled program run, named after the jitted function
(``jit_<name>(<id>)``).  Host spans are the benchmark's own
``TraceAnnotation`` events, whose names start with ``bench.``; the
window is the ``bench.trace_window`` span.  All times are nanoseconds
on the trace's one clock, to which the profiler aligns the device's to
about a millisecond (a program is seen to start up to ~1 ms before the
host span that launched it); the tick programs measured here run for
tens to hundreds of milliseconds.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.trace_window"


@dataclasses.dataclass
class Event:
    name: str
    start: int
    end: int
    stats: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Trace:
    window: tuple                 # (start, end) of bench.trace_window
    ops: dict                     # device plane -> [Event] (XLA Ops)
    modules: dict                 # device plane -> [Event] (XLA Modules)
    spans: list                   # [Event] host spans named bench.*

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find(directory: str) -> str:
    """The one ``.xplane.pb`` a trace wrote under ``directory``."""
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} .xplane.pb files under "
                                f"{directory}, expected one")
    return paths[0]


def op_name(name: str) -> str:
    """An ``XLA Ops`` event is named by its HLO instruction,
    ``%paged_attention.1 = bf16[...] custom-call(...)``: its name is
    ``paged_attention.1``."""
    return name.split(" ", 1)[0].lstrip("%")


def _events(line, name=lambda n: n) -> list:
    return [Event(name(e.name), int(e.start_ns),
                  int(e.start_ns + e.duration_ns)) for e in line.events]


def read(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, modules, spans = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = _events(line, op_name)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    spans.append(Event(e.name, int(e.start_ns),
                                       int(e.start_ns + e.duration_ns),
                                       dict(e.stats)))
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW_SPAN} spans in {path}")
    if not ops:
        raise ValueError(f"no {DEVICE_PREFIX}* plane with {OPS_LINE!r} "
                         f"events in {path}")
    return Trace(window=(windows[0].start, windows[0].end), ops=ops,
                 modules=modules, spans=spans)


def union(intervals, lo: int, hi: int) -> list:
    """Merged ``(start, end)`` pairs of ``intervals`` clipped to
    ``[lo, hi)``."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_s(trace: Trace) -> float:
    """Seconds of the window in which some operation ran on the device,
    averaged over the device planes."""
    lo, hi = trace.window
    per = [sum(e - s for s, e in union(((x.start, x.end) for x in evs),
                                        lo, hi))
           for evs in trace.ops.values()]
    return sum(per) / len(per) * 1e-9


def module_base(name: str) -> str:
    """``jit_chunk_fn_paged(3)`` -> ``chunk_fn_paged``."""
    name = name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def module_calls(trace: Trace, base: str) -> list:
    """Every run of the compiled program ``base`` inside the window."""
    lo, hi = trace.window
    return [m for evs in trace.modules.values() for m in evs
            if module_base(m.name) == base and m.start >= lo and m.end <= hi]


def is_kernel(name: str, kernel: str) -> bool:
    """An op event of the Pallas kernel ``kernel`` (``paged_attention``,
    ``paged_attention.3``)."""
    return name == kernel or name.startswith(kernel + ".")


def self_times(evs: list) -> list:
    """``(event, self ns)``: an operation that encloses others on the
    same line (a ``while`` around its body) keeps only the time no child
    covers."""
    out, stack = [], []
    for e in sorted(evs, key=lambda x: (x.start, -x.end)):
        while stack and stack[-1][0].end <= e.start:
            stack.pop()
        own = [e, e.end - e.start]
        if stack and e.end <= stack[-1][0].end:
            stack[-1][1] -= e.end - e.start
        stack.append(own)
        out.append(own)
    return out


def top_ops(trace: Trace, n: int = 10) -> list:
    """``[program/operation, seconds]`` of the ``n`` operations with the
    most device self time in the window, summed over the devices; each
    is named with the compiled program it ran in (``chunk_fn_paged/
    paged_attention.1``), since programs reuse operation names."""
    lo, hi = trace.window
    total: dict = defaultdict(int)
    for plane, evs in trace.ops.items():
        mods = sorted(trace.modules.get(plane, []), key=lambda m: m.start)
        starts = [m.start for m in mods]
        inside = [e for e in evs if e.start >= lo and e.end <= hi]
        for e, own in self_times(inside):
            i = bisect.bisect_right(starts, e.start) - 1
            where = module_base(mods[i].name) \
                if i >= 0 and e.start < mods[i].end else "?"
            total[f"{where}/{e.name}"] += own
    best = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def idle_by_host(trace: Trace, n: int = 10) -> list:
    """``[host span, seconds]``: the device's idle time in the window
    (averaged over devices), split by the innermost benchmark span open
    on the host at each moment; time under no span is ``host:other``."""
    lo, hi = trace.window
    segs = _host_segments([s for s in trace.spans if s.name != WINDOW_SPAN],
                          lo, hi)
    total: dict = defaultdict(int)
    for evs in trace.ops.values():
        busy = union(((x.start, x.end) for x in evs), lo, hi)
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        j = 0
        for g0, g1 in gaps:             # both lists sorted and disjoint
            while j < len(segs) and segs[j][1] <= g0:
                j += 1
            k = j
            while k < len(segs) and segs[k][0] < g1:
                a, b, name = segs[k]
                total[name] += min(b, g1) - max(a, g0)
                k += 1
    best = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9 / len(trace.ops)] for k, v in best]


def _host_segments(spans: list, lo: int, hi: int) -> list:
    """Disjoint ``(start, end, name)`` covering ``[lo, hi)``: at each
    moment the innermost open span (the one that started last), or
    ``host:other`` where none is open."""
    points = sorted([(s.start, 1, i) for i, s in enumerate(spans)]
                    + [(s.end, 0, i) for i, s in enumerate(spans)])
    segs, active, t = [], [], lo
    for when, starts, i in points + [(hi, 0, None)]:
        a, b = max(t, lo), min(when, hi)
        if b > a:
            segs.append((a, b, spans[active[-1]].name if active
                         else "host:other"))
        t = max(t, when)
        if i is None:
            break
        if starts:
            active.append(i)
        else:
            active.remove(i)
    return segs
