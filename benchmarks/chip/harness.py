"""One run of one cell: set-up, the measured window, the metrics, and
the correctness check.

Set-up checks the configuration's model against ``ArchConfig`` and its
deployment against the engine's options, makes the weights of its
architecture module (``archs/<arch>.py``) on the device from the seed,
builds the engine (or the fleet) as the deployment says, and runs
a few short requests through it so that every tick program the traffic
uses is compiled (from JAX's persistent cache after a cell's first run)
before the window opens.  The window is ``driver.run_window``.  With
``trace`` a profile is taken over the middle ``TRACE_S`` seconds of the
window and the per-layer metrics are read; without, the end-to-end
ones.  Then the peak device memory is read, the program's state freed,
and the sample of served requests compared with the module's float32
reference.
"""
from __future__ import annotations

import dataclasses
import gc
import inspect
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

import jax
import numpy as np

from benchmarks.chip import (cells, correct, driver, stats, traffic,
                             weights, xplane)

TRACE_S = 10.0           # profiled part of a --trace 1 window, at most
DRAIN_S = 90.0           # how long requests sent in the window may finish
WARMUP = ((40, 24), (40, 4), (40, 12))  # (prompt, output) of warm-up requests
# the traffic fixes every output length: no EOS stops one
FIXED = {"eos_id": -1}
# deployment keys the harness reads itself, not engine options
PLACEMENT = ("chips", "replicas")
# arguments the harness fills in from the cell, or that a JSON file
# cannot state (a mesh, sharding rules, a decode function)
FILLED = ("n_replicas", "model", "devices", "mesh", "rules", "decode_fn")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class RunData:
    """What the metric readers read (``metrics/<name>.py``)."""

    cell: cells.Cell
    m: dict                         # the configuration's model entry
    seconds: float
    setup_s: float
    window: driver.Window
    peak: dict                      # peaks.json entry of the chip
    occupancy: Optional[float]      # slot occupancy over the window
    routed: Optional[list]          # fleet: requests per replica
    trace: Optional[xplane.Trace]


def devices_for(chips: int, require_tpu: bool) -> list:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, and JAX found platform "
                     f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs[:chips]


def arch_config(m: dict):
    """The ``ArchConfig`` of a configuration's ``model`` entry: every key
    passed through; one that is not a field raises ``ValueError``."""
    from repro.configs.base import ArchConfig
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    unknown = sorted(set(m) - fields)
    if unknown:
        raise ValueError(f"model keys {unknown} are not ArchConfig fields")
    return ArchConfig(**m)


def _options(fn) -> set:
    return {p.name for p in inspect.signature(fn).parameters.values()
            if p.kind in (p.POSITIONAL_OR_KEYWORD, p.KEYWORD_ONLY)} \
        - {"self", "params", "cfg"}


def engine_options(dep: dict) -> dict:
    """A configuration's ``deployment`` as keyword options: every key but
    ``PLACEMENT`` goes to ``ServingEngine`` (with ``replicas`` over 1,
    also a ``FleetSupervisor`` option), with ``FIXED`` added.  A key that
    is neither, or that is ``FIXED`` or ``FILLED``, raises
    ``ValueError``."""
    from repro.runtime.serve import ServingEngine
    allowed = _options(ServingEngine.__init__)
    if dep["replicas"] > 1:
        from repro.runtime.supervisor import FleetSupervisor
        allowed |= _options(FleetSupervisor.__init__)
    allowed -= {*FIXED, *FILLED}
    kw = {k: v for k, v in dep.items() if k not in PLACEMENT}
    unknown = sorted(set(kw) - allowed)
    if unknown:
        raise ValueError(f"deployment keys {unknown} are not options of "
                         f"the engine (or, with replicas, the fleet) that "
                         f"the harness leaves open")
    return {**kw, **FIXED}


def build(params, cfg, options: dict, replicas: int, devs: list):
    """The system under test as the deployment says: a ServingEngine,
    or a FleetSupervisor of ``replicas`` one-chip replicas, given
    ``options`` (``engine_options``).  Returns (frontier, engines)."""
    from repro.runtime.serve import ServingEngine
    if replicas == 1:
        engine = ServingEngine(params, cfg, **options)
        return driver.Frontier(engine), [engine]
    from repro.runtime.supervisor import FleetSupervisor
    fleet = FleetSupervisor(params, cfg, n_replicas=replicas, model=1,
                            devices=devs, **options)
    return driver.FleetFrontier(fleet), list(fleet.engines)


def warm_up(engines: list, vocab: int) -> None:
    """Compile every program the traffic uses on every engine, in the
    state the window will call it in: a request alone (solo prefill,
    then decode chunks), a second one admitted while the first decodes
    (mixed ticks), a third alone once both are done (the solo tick
    again, now on state a tick returned), and the stats reset."""
    from repro.runtime.serve import Request
    rng = np.random.default_rng(0)
    for engine in engines:
        f = driver.Frontier(engine)
        reqs = [Request(rid=-1 - i, prompt=rng.integers(
                    traffic.FIRST_TOKEN_ID, vocab, p).astype(np.int32),
                        max_new=n) for i, (p, n) in enumerate(WARMUP)]
        f.submit(reqs[0])
        f.step()
        f.step()
        f.submit(reqs[1])
        while f.has_work:
            f.step()
            f.poll()
        f.submit(reqs[2])
        while f.has_work:
            f.step()
            f.poll()
        engine.reset_stats()


def memory_peak_bytes(devs: list) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def measure(name: str, seed: int, seconds: float, trace: bool, *,
            t0: float, require_tpu: bool = True,
            rate: Optional[float] = None, drain_s: float = DRAIN_S,
            root: Path = cells.ROOT, here: Path = cells.HERE) -> tuple:
    """Set-up and the measured window of cell ``name``, at the mix's
    rate, or at ``rate`` with a drain of ``drain_s`` (``sweep.py``).
    Returns ``(RunData, devices, peak device memory)``; the program's
    state is left to be freed."""
    from repro.compile_cache import enable_compile_cache
    from repro.runtime.serve import Request
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = cells.find_cell(name, root=root, here=here)
    if rate is not None:
        cell.mix["rate_per_s"] = rate
    m, dep = cell.config["model"], cell.config["deployment"]
    cfg, options = arch_config(m), engine_options(dep)
    devs = devices_for(cell.chips, require_tpu)
    peak = cells.peaks(devs[0].device_kind) if require_tpu else None
    params = weights.make(cell.arch, m, seed, devs[0])
    frontier, engines = build(params, cfg, options, dep["replicas"], devs)
    warm_up(engines, m["vocab"])
    log = driver.TickLog()
    for e in engines:
        log.instrument(e)
    specs = traffic.make_requests(cell.mix, seed=seed, seconds=seconds,
                                  vocab=m["vocab"])
    fleet = getattr(frontier, "fleet", None)
    seen: dict = {}
    routed0 = list(fleet.routed) if fleet else None

    def at_open():
        for e in engines:
            e.reset_stats()

    def at_close():
        occ = [e.occupancy_stats() for e in engines]
        ticks = sum(o["ticks"] * o["n_slots"] for o in occ)
        seen["occupancy"] = (sum(o["slot_ticks"] for o in occ) / ticks
                             if ticks else None)
        if fleet:
            seen["routed"] = [b - a for a, b in zip(routed0, fleet.routed)]

    hooks = [(seconds, at_close)]
    tmp = tempfile.TemporaryDirectory() if trace else None
    if trace:
        t_trace = min(TRACE_S, seconds)
        # the Python tracer would add an event per Python call to the
        # host the benchmark runs on; the benchmark's own spans suffice
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        span = []

        def start():
            jax.profiler.start_trace(tmp.name, profiler_options=options)
            # a span begun before the trace started would not be recorded
            span.append(jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN))
            span[0].__enter__()

        def stop():
            span[0].__exit__(None, None, None)
            jax.profiler.stop_trace()

        hooks += [((seconds - t_trace) / 2, start),
                  ((seconds + t_trace) / 2, stop)]
    setup_s = time.perf_counter() - t0
    window = driver.run_window(
        frontier, specs, cell.mix, seconds=seconds, drain_s=drain_s,
        make_request=lambda s: Request(rid=s.rid, prompt=s.prompt,
                                       max_new=s.max_new),
        log=log, at_open=at_open, hooks=hooks)
    mem = memory_peak_bytes(devs)
    say(f"set-up {setup_s:.3f} s; window {window.close - window.open:.3f} "
        f"s, drained in {window.end - window.close:.3f} s; "
        f"{len(window.recs)} requests sent, {window.steps} steps")
    say(host_steps(window) + "; compiles after the window opened: "
        + ", ".join(f"{e.compiles} in {e.compile_s:.3f} s" for e in engines))
    tr = None
    if trace:
        t = time.perf_counter()
        tr = xplane.read(xplane.find(tmp.name))
        tmp.cleanup()
        say(f"trace read in {time.perf_counter() - t:.3f} s")
    data = RunData(cell=cell, m=m, seconds=seconds, setup_s=setup_s,
                   window=window, peak=peak,
                   occupancy=seen.get("occupancy"),
                   routed=seen.get("routed"), trace=tr)
    return data, devs, mem


def run(name: str, seed: int, seconds: float, trace: bool, *, t0: float,
        require_tpu: bool = True, control: bool = False,
        root: Path = cells.ROOT, here: Path = cells.HERE) -> dict:
    """One run of cell ``name``; returns the result line's object.
    With ``control`` the float8 control is put in the program's place on
    the same sample and judged by the same rule: ``result["control"]``
    holds its ``correct``, its ``checks`` and its widest gap
    (``calibrate.py``; the benchmark's own runs never read it)."""
    data, devs, mem = measure(name, seed, seconds, trace, t0=t0,
                              require_tpu=require_tpu, root=root, here=here)
    # what measure() built is unreachable now: the engine's ticks and
    # the benchmark's wrappers around them form a cycle that this frees,
    # so the program's state is gone before the reference runs
    gc.collect()
    cell, window, tr = data.cell, data.window, data.trace
    metrics = {}
    for metric in (cell.per_layer if trace else cell.end_to_end):
        value = cells.load_reader(metric["name"])(data)
        if value is not None:
            metrics[metric["name"]] = {"value": value,
                                       "unit": metric["unit"]}
    attempted, failed = stats.counts(window)
    finished = [r.req for r in window.recs if r.done is not None]
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=xplane.busy_s(tr), window_s=tr.window_s)
        result["breakdown"] = {"device_ops": xplane.top_ops(tr),
                               "idle_gaps": xplane.idle_by_host(tr)}
    t = time.perf_counter()
    checked = correct.sample(finished, seed)
    ref_params = weights.make(cell.arch, data.m, seed, devs[0])
    readings = correct.gaps(cell.arch, data.m, ref_params, checked,
                            control=control)
    ok, checks = correct.decide(readings["program"], cell.limits)
    if control:
        c_ok, c_checks = correct.decide(readings["control"], cell.limits)
        result["control"] = {"correct": bool(c_ok), "checks": c_checks,
                             "widest_gap": readings["control"]["widest"]}
    result["widest_gap"] = readings["program"]["widest"]   # information
    result["correct"] = bool(ok)
    result["checks"] = checks
    say(f"reference over {len(checked)} of {len(finished)} finished "
        f"requests in {time.perf_counter() - t:.3f} s; widest gap "
        f"{readings['program']['widest']}")
    return result


def host_steps(window: driver.Window) -> str:
    """One line on how long the window's steps took on the host, by the
    tick family they called, and on garbage collection: a run that reads
    slower than its seed did shows here whether every step was slower or
    a few stood still."""
    family = {c.step: c.family for c in window.ticks}
    by: dict = {}
    for i, s in enumerate(window.step_s):
        by.setdefault(family.get(i, "none"), []).append(s)
    parts = [f"{f} {len(v)} in {sum(v):.3f} s, median "
             f"{1e3 * float(np.median(v)):.1f} ms, slowest {1e3 * max(v):.1f}"
             for f, v in sorted(by.items())]
    return (f"steps in the window: {'; '.join(parts) or 'none'}; garbage "
            f"collection {sum(window.gc_s):.3f} s in {len(window.gc_s)} "
            f"passes (longest {1e3 * max(window.gc_s, default=0):.1f} ms)")


def say(line: str) -> None:
    print(line, file=sys.stderr, flush=True)
