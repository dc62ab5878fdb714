"""Model step (recurrent state): the least time the traced decode-chunk
ticks need at the chip's HBM bandwidth, over the decode-chunk program's
device time in them, %.

Each model step of a decode chunk reads the weights once, reads and
writes every slot's recurrent state once (``state_bytes_per_slot`` of
the cell's architecture module, times the deployment's ``n_slots``), and
reads each decoding row's live keys and values (``work.py``).  Only
ticks that are decode-chunk calls by the benchmark's own span around
their step(), and whose step shows the decode-chunk program on the
device, count; a block without recurrent state reads nothing.
"""
import math

import jax.numpy as jnp

from benchmarks.chip import stats, work, xplane


def weight_bytes(arch, m: dict) -> int:
    size = jnp.dtype(m["dtype"]).itemsize
    return sum(math.prod(shape) * size
               for shape, _ in arch.shapes(m).values())


def read(run):
    trace, arch = run.trace, run.cell.arch
    if trace is None or not hasattr(arch, "state_bytes_per_slot"):
        return None
    m = run.m
    step_bytes = (weight_bytes(arch, m) + 2 * arch.state_bytes_per_slot(m)
                  * run.cell.config["deployment"]["n_slots"])
    lo, hi = trace.window
    steps = {s.stats.get("i"): s for s in trace.spans
             if s.name == "bench.step" and lo <= s.start and s.end <= hi}
    programs = [p for base in stats.TICK_MODULES["decode"]
                for p in xplane.module_calls(trace, base)]
    need_s = device_s = 0.0
    for call in run.window.ticks:
        span = steps.get(call.step)
        if call.family != "decode" or span is None:
            continue
        runs = [p for p in programs
                if p.start < span.end and p.end > span.start]
        if not runs:
            continue
        model_steps = max((n for _, _, n in call.decoding), default=0)
        kv = sum(work.decode_attention_work(arch, m, *d)[1]
                 for d in call.decoding)
        need_s += (model_steps * step_bytes + kv) \
            / run.peak["hbm_bytes_per_s"]
        device_s += sum(p.end - p.start for p in runs) * 1e-9
    return 100.0 * need_s / device_s if device_s > 0 else None
