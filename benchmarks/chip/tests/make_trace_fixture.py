"""Record ``fixtures/tiny.xplane.pb``, the small TPU trace the trace
reduction's tests read.  Run on a TPU from the checkout root:

    python3 benchmarks/chip/tests/make_trace_fixture.py [OUT]

Inside one ``bench.trace_window`` span it runs three ``bench.step``
spans, each calling a jitted ``chunk_fn_paged`` (a matrix product and
the ``paged_attention`` kernel at a small shape), then one step calling
a jitted ``tick_paged``, and sleeps 20 ms between steps so that the
device is idle under ``bench.idle``.  It prints what the reduction reads
from the recording, which the tests then expect.
"""
import glob
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

OUT = Path(__file__).resolve().parent / "fixtures" / "tiny.xplane.pb"


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    from benchmarks.chip import xplane
    from repro.kernels.paged_attention import paged_attention

    out = Path(sys.argv[1]) if len(sys.argv) > 1 else OUT
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (8, 8, 128), jnp.bfloat16)
    kp = jax.random.normal(key, (64, 16, 2, 128), jnp.bfloat16)
    tables = jnp.arange(64, dtype=jnp.int32).reshape(8, 8)
    lengths = jnp.full((8,), 100, jnp.int32)
    w = jax.random.normal(key, (1024, 1024), jnp.bfloat16)

    def chunk_fn_paged(q, kp, tables, lengths, w):
        o = paged_attention(q, kp, kp, tables, lengths)
        return o, w @ w

    def tick_paged(w):
        return (w @ w) @ w

    decode, prefill = jax.jit(chunk_fn_paged), jax.jit(tick_paged)
    jax.block_until_ready(decode(q, kp, tables, lengths, w))
    jax.block_until_ready(prefill(w))
    tmp = tempfile.mkdtemp()
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=options)
        with TraceAnnotation(xplane.WINDOW_SPAN):
            for i in range(4):
                with TraceAnnotation("bench.step", i=i):
                    if i < 3:
                        with TraceAnnotation("bench.tick.decode"):
                            res = decode(q, kp, tables, lengths, w)
                    else:
                        with TraceAnnotation("bench.tick.mixed"):
                            res = prefill(w)
                    jax.block_until_ready(res)
                with TraceAnnotation("bench.idle"):
                    time.sleep(0.02)
        jax.profiler.stop_trace()
        out.parent.mkdir(exist_ok=True)
        shutil.copy(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                              recursive=True)[0], out)
    finally:
        shutil.rmtree(tmp)
    tr = xplane.read(str(out))
    print(f"{out}: {out.stat().st_size} bytes; window {tr.window_s} s, "
          f"busy {xplane.busy_s(tr)} s; planes {sorted(tr.ops)}; "
          f"modules {sorted({m.name for v in tr.modules.values() for m in v})}")
    print("top ops", xplane.top_ops(tr))
    print("idle by host", xplane.idle_by_host(tr))
    return 0


if __name__ == "__main__":
    sys.exit(main())
