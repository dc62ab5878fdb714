"""Kernel paged_attention: the least time the decode attention of the
traced decode-chunk ticks needs (each active slot's cached keys and
values read once per step, at the chip's HBM bandwidth; its FLOPs at the
bf16 peak where that is longer), over the summed time of the kernel's
device events in them, %.

Only ticks that are decode-chunk calls by the benchmark's own span
around their step(), and whose step shows the decode-chunk program on
the device, count: mixed ticks decode through the wide prefill kernel.
"""
from benchmarks.chip import stats, work, xplane

KERNEL = "paged_attention"


def read(run):
    trace = run.trace
    if trace is None:
        return None
    lo, hi = trace.window
    steps = {s.stats.get("i"): s for s in trace.spans
             if s.name == "bench.step" and lo <= s.start and s.end <= hi}
    programs = [m for base in stats.TICK_MODULES["decode"]
                for m in xplane.module_calls(trace, base)]
    kernels = [e for evs in trace.ops.values() for e in evs
               if xplane.is_kernel(e.name, KERNEL)]
    need_s = kernel_s = 0.0
    for call in run.window.ticks:
        span = steps.get(call.step)
        if call.family != "decode" or span is None:
            continue
        runs = [m for m in programs
                if m.start < span.end and m.end > span.start]
        if not runs:
            continue
        work_done = [work.decode_attention_work(run.cell.arch, run.m, *d)
                     for d in call.decoding]
        flops = sum(f for f, _ in work_done)
        nbytes = sum(b for _, b in work_done)
        need_s += work.roofline_s(flops, nbytes, run.peak)
        kernel_s += sum(e.end - e.start for e in kernels
                        if any(m.start <= e.start and e.end <= m.end
                               for m in runs)) * 1e-9
    return 100.0 * need_s / kernel_s if kernel_s > 0 else None
