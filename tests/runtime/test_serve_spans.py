"""The engine's host spans, read back from a profiler trace.

``ServingEngine.step()`` records its phases as ``TraceAnnotation`` spans
(``serve.*``) on the profiler's host plane.  A tiny paged, chunked-
prefill engine is driven under ``jax.profiler`` on the CPU and the
spans are read with ``jax.profiler.ProfileData``: one ``serve.tick`` per
step, dispatch -> sync -> emit nested in order inside it, the dispatched
family, fragment tokens and decode KV pages as the engine took them, and
compiles counted on a fresh engine's first tick and on no repeat of the
same shapes.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_arch, reduced
from repro.models import model
from repro.runtime.serve import Request, ServingEngine

# the jitted tick of each family, by engine attribute
FAMILY_ATTRS = {"_chunk_fn": "decode", "_mixed_fn": "mixed",
                "_solo_fn": "solo_prefill", "_spec_fn": "spec",
                "_spec_chunk_fn": "spec_chunk"}
PROMPTS = (20, 13)          # the first prefills alone, the second mixed
BLOCK = 8


def record_families(eng) -> list:
    """Wraps the engine's jitted ticks: the family of each call, in
    order."""
    calls = []
    for attr, family in FAMILY_ATTRS.items():
        fn = getattr(eng, attr, None)
        if fn is not None:
            def tick(*args, fn=fn, family=family):
                calls.append(family)
                return fn(*args)
            setattr(eng, attr, tick)
    return calls


def record_live_pages(eng) -> list:
    """Wraps the decode chunk: for each call, the KV pages the paged
    decode kernel walks at the first step, ``ceil((pos + 1) / block)``
    over every row, active or not, clipped to the table's width, read
    from the device state it is given."""
    pages = []
    fn = eng._chunk_fn

    def tick(params, state, cache, *rest):
        pos = np.asarray(cache["pos"])
        nb = cache["block_tables"].shape[1]
        pages.append(int(np.sum(np.minimum(-(-(pos + 1) // BLOCK), nb))))
        return fn(params, state, cache, *rest)
    eng._chunk_fn = tick
    return pages


def serve_two(eng, rng) -> tuple:
    """Submits a request and steps until its prompt is in, then submits
    a second while the first decodes, and steps until both are done.
    Returns (steps, the two requests, steps taken before the second was
    submitted)."""
    a, b = (Request(rid, rng.integers(2, 100, n).astype(np.int32),
                    max_new=6) for rid, n in enumerate(PROMPTS))
    eng.submit(a)
    steps = 0
    while steps == 0 or eng._jobs:
        eng.step()
        steps += 1
    first = steps
    eng.submit(b)
    while eng.has_work:
        eng.step()
        eng.poll()
        steps += 1
    return steps, (a, b), first


def host_spans(directory: str) -> list:
    """Every ``serve.*`` event of the trace as (name, start, end,
    stats), in start order."""
    (path,) = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                        recursive=True)
    out = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
           for plane in ProfileData.from_file(path).planes
           for line in plane.lines for e in line.events
           if e.name.startswith("serve.")]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def inside(spans: list, outer) -> list:
    return [s for s in spans
            if s is not outer and outer[1] <= s[1] and s[2] <= outer[2]]


@pytest.fixture(scope="module", params=[False, True], ids=["plain", "spec"])
def traced(request, serve_setup, tmp_path_factory):
    """Two rounds of the same two requests through one fresh engine,
    traced; the stats are reset between the rounds."""
    cfg, params = serve_setup
    eng = ServingEngine(params, cfg, n_slots=2, max_seq=64, chunk=4,
                        paged=True, block_size=BLOCK, n_blocks=24,
                        prefix_sharing=False, chunked_prefill=True,
                        prefill_chunk_tokens=8, speculative=request.param)
    return dict(trace_two_rounds(eng, tmp_path_factory),
                spec=request.param)


HYBRID = reduced(get_arch("granite-4.0-h-micro"), d_model=64, vocab=128,
                 d_ff=128, head_dim=16, ssm_state=8, ssm_headdim=16,
                 ssm_chunk=8)


@pytest.fixture(scope="module")
def traced_hybrid(tmp_path_factory):
    """The same two rounds through a tiny interleaved engine: two Mamba
    layers, whose recurrent state the ticks carry, and one attention
    layer."""
    params = model.init(jax.random.PRNGKey(0), HYBRID, jnp.float32)
    eng = ServingEngine(params, HYBRID, n_slots=2, max_seq=64, chunk=4,
                        paged=True, block_size=BLOCK, n_blocks=24,
                        prefix_sharing=False, chunked_prefill=True,
                        prefill_chunk_tokens=8)
    return trace_two_rounds(eng, tmp_path_factory)


def trace_two_rounds(eng, tmp_path_factory) -> dict:
    calls = record_families(eng)
    pages = record_live_pages(eng)
    rng = np.random.default_rng(11)
    directory = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(directory, profiler_options=options)
    try:
        rounds = []
        for _ in range(2):
            steps, reqs, first = serve_two(eng, rng)
            rounds.append(dict(steps=steps, reqs=reqs, first=first,
                               stats=eng.sync_stats(),
                               n_calls=len(calls), pages=list(pages)))
            pages.clear()
            eng.reset_stats()
    finally:
        jax.profiler.stop_trace()
    return dict(rounds=rounds, calls=calls, spans=host_spans(directory),
                stats=eng.sync_stats(), n_slots=eng.pool.n)


def ticks_of(traced) -> list:
    ticks = [s for s in traced["spans"] if s[0] == "serve.tick"]
    n = [r["steps"] for r in traced["rounds"]]
    assert len(ticks) == sum(n)          # one serve.tick per step()
    return [ticks[:n[0]], ticks[n[0]:]]


def test_each_step_nests_dispatch_sync_emit_in_order(traced):
    for ticks in ticks_of(traced):
        for tick in ticks:
            within = inside(traced["spans"], tick)
            phase = {name: [s for s in within if s[0] == name]
                     for name in ("serve.dispatch", "serve.sync",
                                  "serve.emit")}
            assert all(len(v) == 1 for v in phase.values()), within
            (d,), (s,), (e,) = phase.values()
            assert d[2] <= s[1] and s[2] <= e[1]
            for other in within:
                if other[0] in ("serve.admit", "serve.schedule"):
                    assert other[2] <= d[1]
                elif other[0] == "serve.epilogue":
                    assert e[2] <= other[1]


def test_family_and_frag_tokens_match_the_path_taken(traced):
    dispatches = [s for s in traced["spans"] if s[0] == "serve.dispatch"]
    assert [s[3]["family"] for s in dispatches] == traced["calls"]
    prefill = "solo_prefill"
    mixed = "spec" if traced["spec"] else "mixed"
    assert {prefill, mixed} <= set(traced["calls"])
    at = 0
    for r in traced["rounds"]:
        mine = dispatches[at:at + r["steps"]]
        at += r["steps"]
        a, b = r["reqs"]
        # the first request prefills alone, before the second arrives
        first = mine[:r["first"]]
        assert {s[3]["family"] for s in first} == {prefill}
        assert all(s[3]["decode_rows"] == 0 for s in first)
        assert sum(s[3]["frag_tokens"] for s in first) == len(a.prompt)
        rest = mine[r["first"]:]
        assert sum(s[3]["frag_tokens"] for s in rest) == len(b.prompt)
        assert any(s[3]["family"] == mixed and s[3]["decode_rows"] == 1
                   and s[3]["frag_tokens"] > 0 for s in rest)
        assert r["stats"]["frag_tokens"] == len(a.prompt) + len(b.prompt)


def test_kv_pages_are_the_live_pages_of_the_decode_rows(traced):
    """``kv_pages`` on each decode dispatch, and ``decode_kv_pages`` in
    the stats, sum the pages the paged decode kernel walks at the
    chunk's first step: every row's, a retired row's at its frozen
    length; no other family walks the paged decode kernel."""
    dispatches = [s for s in traced["spans"] if s[0] == "serve.dispatch"]
    decode = [s[3]["kv_pages"] for s in dispatches
              if s[3]["family"] == "decode"]
    assert all(s[3]["kv_pages"] == 0 for s in dispatches
               if s[3]["family"] != "decode")
    pages = [p for r in traced["rounds"] for p in r["pages"]]
    assert decode == pages
    if not traced["spec"]:
        assert pages and all(p > 0 for p in pages)
    for r in traced["rounds"]:
        assert r["stats"]["decode_kv_pages"] == sum(r["pages"])


def test_compiles_on_a_fresh_engine_and_not_on_a_repeat(traced):
    syncs = [s for s in traced["spans"] if s[0] == "serve.sync"]
    n_first = traced["rounds"][0]["steps"]
    assert syncs[0][3]["compiles"] > 0
    assert all(s[3]["compiles"] == 0 for s in syncs[n_first:])
    first, repeat = (r["stats"] for r in traced["rounds"])
    assert first["compiles"] > 0 and first["compile_s"] > 0
    assert first["compiles"] >= sum(s[3]["compiles"]
                                    for s in syncs[:n_first])
    assert repeat["compiles"] == 0


def test_reset_stats_zeroes_the_new_counters(traced):
    stats = traced["stats"]
    assert (stats["frag_tokens"], stats["decode_kv_pages"],
            stats["compiles"], stats["compile_s"]) == (0, 0, 0, 0.0)
    assert (stats["state_bytes"], stats["state_resets"]) == (0, 0)


def test_state_rows_on_dispatch(traced, traced_hybrid):
    """``state_rows``: every slot's recurrent state in a batched tick,
    the one slot's in the solo prefill tick; none without recurrent
    layers."""
    assert all(s[3]["state_rows"] == 0 for s in traced["spans"]
               if s[0] == "serve.dispatch")
    dispatches = [s[3] for s in traced_hybrid["spans"]
                  if s[0] == "serve.dispatch"]
    assert {d["family"] for d in dispatches} == {"solo_prefill", "mixed",
                                                 "decode"}
    n = traced_hybrid["n_slots"]
    for d in dispatches:
        assert d["state_rows"] == (1 if d["family"] == "solo_prefill"
                                   else n)


def test_state_bytes_and_resets_follow_rows_and_admissions(traced_hybrid):
    """``state_bytes``: each row a tick carries, read and written once a
    model step (a decode chunk's steps are the device ticks it ran);
    ``state_resets``: one zeroed slot per admission."""
    cfg = HYBRID
    row = (cfg.n_layers - cfg.n_attn_layers) * 4 * (
        cfg.ssm_nheads * cfg.ssm_headdim * cfg.ssm_state
        + (cfg.ssm_conv - 1) * (cfg.d_inner + 2 * cfg.ssm_state))
    n = traced_hybrid["n_slots"]
    dispatches = [s[3] for s in traced_hybrid["spans"]
                  if s[0] == "serve.dispatch"]
    at = 0
    for r in traced_hybrid["rounds"]:
        mine = dispatches[at:at + r["steps"]]
        at += r["steps"]
        solo = sum(d["family"] == "solo_prefill" for d in mine)
        steps = r["stats"]["device_ticks"] - solo
        assert r["stats"]["state_bytes"] == 2 * row * (n * steps + solo)
        assert r["stats"]["state_resets"] == len(r["reqs"]) == 2
    assert (traced_hybrid["stats"]["state_bytes"],
            traced_hybrid["stats"]["state_resets"]) == (0, 0)
