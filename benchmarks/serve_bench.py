"""Serving benchmark: device-resident continuous batching economics.

Measures the refactored engine on CPU-sized configs and writes
``BENCH_serve.json`` so the perf trajectory keeps recording:

* ``tokens_per_s`` — end-to-end greedy decode throughput,
* ``device_ticks`` — decode iterations executed on device,
* ``host_syncs_per_100_tokens`` — actual blocking host round-trips,
* ``baseline_syncs_per_100_tokens`` — what the pre-refactor engine paid
  (one ``int(jnp.argmax(...))`` per slot per tick + one per admission),
  measured in the *same run* from the same token stream,
* ``sync_reduction_x`` — the ratio (acceptance floor: ≥ 5×),
* ``kv`` — paged-vs-contiguous KV economics from the same request
  stream: allocated KV bytes per admitted token under each layout and
  the reduction ratio (acceptance floor: paged strictly smaller), plus
  shared-prefix block hits and peak block usage,
* ``ttft`` / ``inter_token_p50`` / ``inter_token_p99`` — head-of-line
  latency: a long prompt is admitted *mid-decode* and the active slots'
  token arrival gaps are measured under monolithic admission (the whole
  prompt prefills in one call, stalling every decoder) vs chunked
  prefill (one fragment per mixed tick).  Floors: chunked output is
  token-exact vs monolithic, and chunked p99 inter-token latency is no
  worse than a decode-only run's by more than one fragment tick's cost.
  ``ttft.long_chunked_idle_s`` is the cold-start case: with no decoder
  to protect, the solo tick packs fragments up to the per-tick budget
  through a single-row forward instead of paying the n_slots-row
  fragment tax — it must land within 2x of the monolithic prefill,
* ``spec`` — speculative decoding on a repetitive-suffix workload:
  ``tokens_per_forward`` (decode tokens per decoding slot per verify
  forward; the non-speculative engine is exactly 1.0),
  ``acceptance_rate``, ``spec_decode_tokens_per_s`` vs
  ``baseline_decode_tokens_per_s`` — decode tokens per second of
  serving-tick wall time (the engine's ``step()`` calls) on the same
  stream — plus the per-phase breakdown ``verify_forward_s`` /
  ``draft_s`` and ``spec_token_exact`` (greedy argmax verification is
  bit-exact — asserted on BOTH cache layouts).  Floors:
  ``tokens_per_forward > 1.3`` and, since the span-clamped
  chunk-attention kernels, ``spec_decode_tokens_per_s >=
  baseline_decode_tokens_per_s``,
* ``overcommit`` — preemptive over-commit on a deliberately undersized
  block pool: mean ``occupancy`` (running slots per tick) vs the
  reserved-admission engine on the same stream, ``preemptions`` /
  ``resumes`` / ``preempted_tokens_recomputed``, throughput vs
  reserved, and ``preempt_token_exact`` (eviction + recompute-based
  resume changes no token).  Floors: >= 1 preemption actually fired,
  token-exact, and occupancy strictly above the reserved baseline,
* ``scaling`` / ``sharded_token_exact`` — the mesh curve: a
  FleetSupervisor of one replica per device at 1/2/4/8 forced host
  devices (each device count in a subprocess — XLA reads the flag at
  import), tok/s + host syncs + routing balance per point, and the
  tensor-parallel (model=2) engine's byte-exactness vs the
  single-device oracle.  Floors: every point token-exact and every
  replica routed to; ``sharded_token_exact`` true.  Also appends the
  single-device baseline to ``benchmarks/artifacts/
  serve_trajectory.jsonl`` (the perf-trajectory anchor),
* ``fault_recovery`` — chaos: a seeded FaultPlan kills replica 0 of a
  2-replica fleet mid-run; the fleet quarantines it and migrates its
  in-flight requests to the survivor via token-exact replay.  Records
  ``requests_migrated`` / ``migrated_token_exact`` / ``dead_letter`` /
  ``recovery_overhead_x`` (fault-free tok/s over faulted tok/s).
  Floors: >= 1 migration, bit-exact vs the unfaulted single-engine
  oracle, zero dead letters,
* ``sla`` — priority tiers under a bursty open-loop trace: throughput
  requests arrive in bursts that saturate the slots, latency-tier
  requests arrive mid-run and displace throughput victims through the
  admission controller.  Per tier and per layout: ``ttft_p99`` /
  ``inter_token_p99`` (``TierAccounting``), ``displacements``, and
  ``tier_token_exact`` (the tiered run's outputs vs the same engine's
  untiered closed-loop oracle).  Floors: >= 1 displacement fired,
  token-exact on both layouts, and latency-tier p99 TTFT < 0.5x the
  throughput tier's.
"""
import json
import os
import sys
import time


def _phase_time(fn, *args, reps: int = 20) -> float:
    """Steady-state seconds per call of a jitted fn (compile excluded)."""
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def _requests(cfg, np, Request, n=16):
    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab, size=16,
                          dtype=np.int64).astype(np.int32)
    reqs = []
    for i in range(n):
        if i % 2 == 0:   # half the stream shares a 16-token (1-block) prefix
            tail = rng.integers(1, cfg.vocab, size=int(rng.integers(2, 8)),
                                dtype=np.int64).astype(np.int32)
            prompt = np.concatenate([shared, tail])
        else:
            prompt = rng.integers(1, cfg.vocab,
                                  size=int(rng.integers(4, 16)),
                                  dtype=np.int64).astype(np.int32)
        reqs.append(Request(i, prompt, max_new=int(rng.integers(6, 20))))
    return reqs


def run_serve(out_path: str = None) -> list[str]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_arch, reduced
    from repro.models import model as model_lib
    from repro.runtime.serve import Request, ServingEngine

    out_path = out_path or os.path.join(os.getcwd(), "BENCH_serve.json")
    cfg = reduced(get_arch("granite-3-2b"), n_layers=2, d_model=128,
                  vocab=512)
    params = model_lib.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    chunk = 8

    def engine(paged: bool) -> ServingEngine:
        kw = dict(paged=True, block_size=16, n_blocks=20) if paged else {}
        return ServingEngine(params, cfg, n_slots=4, max_seq=96,
                             chunk=chunk, **kw)

    results = {}
    for paged in (False, True):
        eng = engine(paged)
        # warmup on the SAME engine (each engine owns its jitted
        # closures), then reset the counters for a clean measurement
        eng.run_to_completion([Request(99, np.arange(1, 9, dtype=np.int32),
                                       max_new=4)])
        eng.reset_stats()
        reqs = _requests(cfg, np, Request)
        t0 = time.perf_counter()
        done, ticks = eng.run_to_completion(reqs)
        dt = time.perf_counter() - t0
        assert len(done) == len(reqs)
        results[eng.kv_stats()["layout"]] = dict(
            engine=eng, done=done, ticks=ticks, dt=dt,
            outputs={r.rid: list(r.out) for r in done})
    # paged decode is token-exact vs the contiguous cache (same stream)
    token_exact = results["paged"]["outputs"] == results["contiguous"]["outputs"]
    assert token_exact, "paged decode diverged from the contiguous cache"

    eng = results["contiguous"]["engine"]
    dt, ticks = results["contiguous"]["dt"], results["contiguous"]["ticks"]
    total_tokens = sum(len(r.out) for r in results["contiguous"]["done"])
    stats = eng.sync_stats()
    kv_c = eng.kv_stats()
    kv_p = results["paged"]["engine"].kv_stats()
    kv_reduction = kv_c["kv_bytes_per_token"] / kv_p["kv_bytes_per_token"]
    record = {
        "suite": "serve",
        "config": {"arch": cfg.name, "n_slots": 4, "chunk": chunk,
                   "n_requests": len(results["contiguous"]["done"]),
                   "max_seq": 96, "block_size": 16, "n_blocks": 20},
        "tokens_per_s": total_tokens / dt,
        "total_tokens": total_tokens,
        "device_ticks": ticks,
        "wall_s": dt,
        **stats,
        "kv": {
            "contiguous_bytes_per_token": kv_c["kv_bytes_per_token"],
            "paged_bytes_per_token": kv_p["kv_bytes_per_token"],
            "kv_bytes_reduction_x": kv_reduction,
            "paged_token_exact": token_exact,
            "shared_block_hits": kv_p["shared_block_hits"],
            "peak_blocks": kv_p["peak_blocks"],
            "stalls": kv_p["stalls"],
        },
    }
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)

    rows = ["serve.header,name,metric,value,derived"]
    rows.append(f"serve,continuous_batching,tokens_per_s,"
                f"{record['tokens_per_s']:.0f},ticks={ticks}")
    rows.append(f"serve,host_sync_economy,syncs_per_100_tokens,"
                f"{stats['host_syncs_per_100_tokens']:.2f},"
                f"baseline={stats['baseline_syncs_per_100_tokens']:.2f};"
                f"reduction={stats['sync_reduction_x']:.1f}x")
    rows.append(f"serve,paged_kv_economy,kv_bytes_per_token,"
                f"{kv_p['kv_bytes_per_token']:.0f},"
                f"contiguous={kv_c['kv_bytes_per_token']:.0f};"
                f"reduction={kv_reduction:.2f}x;"
                f"shared_hits={kv_p['shared_block_hits']}")
    rows.append(f"serve,artifact,path,{out_path},")
    # acceptance floors: ≥ 5× fewer host syncs than per-slot-per-tick;
    # paged KV bytes per token strictly below contiguous, with no stalls
    assert stats["sync_reduction_x"] >= 5.0, stats
    assert kv_reduction > 1.0, record["kv"]
    assert kv_p["stalls"] == 0, record["kv"]
    return rows


# ---------------------------------------------------------------------------
# Head-of-line latency: monolithic admission vs chunked prefill
# ---------------------------------------------------------------------------

N_DECODERS = 3
LONG_LEN = 960          # long enough that a monolithic prefill (~0.3 s at
LATENCY_MAX_SEQ = 1024  # this size) dwarfs ambient scheduler noise
INJECT_AT = 2           # steps of pure decode before the long prompt lands
PREFILL_CHUNK = 32


def _latency_requests(np, Request):
    rng = np.random.default_rng(11)
    decoders = [Request(i, rng.integers(1, 500, size=8,
                                        dtype=np.int64).astype(np.int32),
                        max_new=60) for i in range(N_DECODERS)]
    long_req = Request(99, rng.integers(1, 500, size=LONG_LEN,
                                        dtype=np.int64).astype(np.int32),
                      max_new=4)
    return decoders, long_req


def _timed_run(eng, np, Request, inject_long: bool):
    """Drive the engine step by step, recording token-arrival times.

    Returns (outputs, arrivals {rid: [(t, n_new), ...]}, ttft_long,
    tick_times)."""
    decoders, long_req = _latency_requests(np, Request)
    reqs = decoders + ([long_req] if inject_long else [])
    assert eng.admit_many(decoders) == len(decoders)
    arrivals = {r.rid: [] for r in reqs}
    t_admit_long, pending_long = None, inject_long
    tick_times, steps = [], 0
    while eng.active or pending_long or eng._finished_instant:
        if pending_long and steps >= INJECT_AT:
            t_admit_long = time.perf_counter()
            assert eng.admit(long_req)
            pending_long = False
        before = {r.rid: len(r.out) for r in reqs}
        t0 = time.perf_counter()
        eng.step()
        t1 = time.perf_counter()
        tick_times.append(t1 - t0)
        for r in reqs:
            d = len(r.out) - before[r.rid]
            if d:
                arrivals[r.rid].append((t1, d))
        steps += 1
    ttft_long = arrivals[long_req.rid][0][0] - t_admit_long \
        if inject_long else None
    return {r.rid: list(r.out) for r in reqs}, arrivals, ttft_long, \
        tick_times


def _per_token_latencies(arrivals, rids):
    """Gap between consecutive deliveries, amortized over the tokens the
    later delivery carried (a `chunk`-token decode delivery is `chunk`
    tokens per sync, not one slow token)."""
    lats = []
    for rid in rids:
        ds = arrivals[rid]
        for (prev_t, _), (t, n) in zip(ds, ds[1:]):
            lats.extend([(t - prev_t) / n] * n)
    return lats


def run_latency(out_path: str = None) -> list[str]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_arch, reduced
    from repro.models import model as model_lib
    from repro.runtime.serve import Request, ServingEngine

    out_path = out_path or os.path.join(os.getcwd(), "BENCH_serve.json")
    cfg = reduced(get_arch("granite-3-2b"), n_layers=4, d_model=256,
                  vocab=512)
    params = model_lib.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    chunk = 4

    def engine(chunked: bool) -> ServingEngine:
        kw = dict(chunked_prefill=True,
                  prefill_chunk_tokens=PREFILL_CHUNK) if chunked else {}
        return ServingEngine(params, cfg, n_slots=4, max_seq=LATENCY_MAX_SEQ,
                             chunk=chunk, **kw)

    dec_rids = list(range(N_DECODERS))
    reps = 3              # best-of-N: a shared box injects ~30ms
    #                       scheduler hiccups at random ticks; the min-p99
    #                       pass is the engine's behavior, not the OS's
    runs, p = {}, {}
    for name, chunked, inject in (("decode_only", True, False),
                                  ("monolithic", False, True),
                                  ("chunked", True, True)):
        eng = engine(chunked)
        # warm every compile this workload touches on the SAME engine
        # (each engine owns its jitted closures), then measure
        _timed_run(eng, np, Request, inject_long=inject)
        best = None
        for _ in range(reps):
            eng.reset_stats()
            outputs, arrivals, ttft_long, ticks = _timed_run(
                eng, np, Request, inject_long=inject)
            lats = _per_token_latencies(arrivals, dec_rids)
            gaps = [t - pt for rid in dec_rids
                    for (pt, _), (t, _) in zip(arrivals[rid],
                                               arrivals[rid][1:])]
            stats = {"p50": float(np.percentile(lats, 50)),
                     "p99": float(np.percentile(lats, 99)),
                     "stall_max": float(max(gaps))}
            if best is None:
                best = (stats, dict(outputs=outputs, ttft_long=ttft_long,
                                    ticks=ticks))
            else:
                # min per metric across passes: a genuine engine stall
                # (the monolithic prefill) survives the min, a random
                # scheduler hiccup does not
                best[0].update({k: min(best[0][k], stats[k])
                                for k in stats})
                if ttft_long is not None:
                    best[1]["ttft_long"] = min(best[1]["ttft_long"],
                                               ttft_long)
        p[name], runs[name] = best

    # chunked prefill must not change a single token vs monolithic
    token_exact = runs["chunked"]["outputs"] == runs["monolithic"]["outputs"]
    assert token_exact, "chunked prefill diverged from monolithic admission"

    # one fragment tick's cost: the mixed ticks right after injection
    # (mean = typical; max = worst observed, which is the honest slack
    # for a p99 bound on a shared box)
    mixed = runs["chunked"]["ticks"][INJECT_AT:
                                     INJECT_AT + LONG_LEN // PREFILL_CHUNK]
    chunk_cost = float(np.mean(mixed))
    chunk_cost_max = float(np.max(mixed))

    record = json.load(open(out_path))
    record["latency_config"] = {
        "n_decoders": N_DECODERS, "long_len": LONG_LEN,
        "prefill_chunk_tokens": PREFILL_CHUNK, "decode_chunk": chunk,
        "inject_at_step": INJECT_AT, "max_seq": LATENCY_MAX_SEQ,
    }
    record["ttft"] = {
        "long_monolithic_s": runs["monolithic"]["ttft_long"],
        "long_chunked_s": runs["chunked"]["ttft_long"],
    }
    record["inter_token_p50"] = {k: v["p50"] for k, v in p.items()}
    record["inter_token_p99"] = {k: v["p99"] for k, v in p.items()}
    record["decode_stall_max_s"] = {k: v["stall_max"] for k, v in p.items()}
    record["fragment_tick_cost_s"] = chunk_cost
    record["fragment_tick_cost_max_s"] = chunk_cost_max
    record["chunked_token_exact"] = token_exact
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)

    # cold-start TTFT: the long prompt admitted on an idle engine — no
    # decoder to protect, so the solo tick packs fragments up to the
    # per-tick budget through a single-row forward (the fix for the
    # fragment-per-tick TTFT regression; ~n_slots x less compute than
    # fragment ticks and a fraction of the host round-trips)
    eng = engine(True)
    rng_idle = np.random.default_rng(11)

    def run_idle():
        req = Request(199, rng_idle.integers(
            1, 500, size=LONG_LEN, dtype=np.int64).astype(np.int32),
            max_new=4)
        t0 = time.perf_counter()
        assert eng.admit(req)
        while not req.out:
            eng.step()
        ttft = time.perf_counter() - t0
        while eng.active:
            eng.step()
        return ttft

    run_idle()                      # warm the solo-tick compile
    ttft_idle = min(run_idle() for _ in range(reps))
    record["ttft"]["long_chunked_idle_s"] = ttft_idle
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)

    rows = [
        f"serve,chunked_prefill,ttft_long_s,"
        f"{record['ttft']['long_chunked_s']:.4f},"
        f"monolithic={record['ttft']['long_monolithic_s']:.4f};"
        f"idle={ttft_idle:.4f}",
        f"serve,chunked_prefill,inter_token_p99_s,"
        f"{p['chunked']['p99']:.5f},"
        f"decode_only={p['decode_only']['p99']:.5f};"
        f"monolithic={p['monolithic']['p99']:.5f}",
        f"serve,chunked_prefill,decode_stall_max_s,"
        f"{p['chunked']['stall_max']:.5f},"
        f"monolithic={p['monolithic']['stall_max']:.5f};"
        f"fragment_tick={chunk_cost:.5f}",
    ]
    # acceptance floors: admitting a long prompt mid-decode may cost the
    # active decoders at most one fragment tick over a decode-only run.
    # The p99 bound uses the worst *observed* fragment tick (+20% timer
    # margin): on a shared box a single ~30ms scheduler hiccup is the
    # top percentile of a ~140-sample distribution, and that same hiccup
    # is part of "one chunk's cost" when it lands in a fragment tick.
    # The p50 bound is the noise-immune version of the same claim.
    slack = 1.2 * chunk_cost_max
    assert p["chunked"]["p99"] <= p["decode_only"]["p99"] + slack, \
        (p, chunk_cost_max)
    assert p["chunked"]["p50"] <= p["decode_only"]["p50"] + 1.2 * chunk_cost, \
        (p, chunk_cost)
    # cold-start floor: with nobody decoding, packed solo prefill must
    # land within 2x of one monolithic prefill (same compute, a few more
    # host round-trips) — the pre-fix fragment-per-tick path paid the
    # full n_slots-row tax and ~3x the monolithic latency
    assert ttft_idle <= 2.0 * record["ttft"]["long_monolithic_s"], record["ttft"]
    return rows


# ---------------------------------------------------------------------------
# Speculative decoding: drafter cores ahead, k tokens per verify forward
# ---------------------------------------------------------------------------

SPEC_K = 4
SPEC_MAX_SEQ = 128


def _spec_params(cfg):
    """Copy-model: every block's residual contribution is zeroed and the
    unembedding tied, so the forward copies its input token.  Greedy
    decode becomes perfectly repetitive — the regime repetitive/
    code-like serving traffic lives in, which the tiny *random* seed
    model cannot produce — while the verify pass stays a real
    transformer forward over real caches."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as model_lib

    params = model_lib.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    p = dict(params)
    p["layers"] = dict(p["layers"],
                       wo=jnp.zeros_like(p["layers"]["wo"]),
                       w_down=jnp.zeros_like(p["layers"]["w_down"]))
    if not cfg.tie_embeddings:
        p["unembed"] = p["embed"]["tok"]
    return p


def _spec_requests(np, Request, n=8):
    """Repetitive-suffix prompts: a random head, then a constant run the
    copy-model continues — prompt-lookup's home turf."""
    rng = np.random.default_rng(7)
    reqs = []
    for i in range(n):
        head = rng.integers(2, 500,
                            size=int(rng.integers(4, 10))).astype(np.int32)
        tail = np.full(int(rng.integers(6, 12)),
                       int(rng.integers(2, 500)), np.int32)
        reqs.append(Request(i, np.concatenate([head, tail]),
                            max_new=int(rng.integers(24, 48))))
    return reqs


def _time_steps(eng) -> list:
    """Times every ``eng.step()`` call from now on (the serving ticks:
    ``run_to_completion`` admits outside them); returns the one-element
    list the seconds accumulate in."""
    total = [0.0]
    step = eng.step

    def timed():
        t = time.perf_counter()
        try:
            return step()
        finally:
            total[0] += time.perf_counter() - t

    eng.step = timed
    return total


def run_spec(out_path: str = None) -> list[str]:
    import numpy as np

    from repro.configs import get_arch, reduced
    from repro.runtime.serve import Request, ServingEngine

    out_path = out_path or os.path.join(os.getcwd(), "BENCH_serve.json")
    cfg = reduced(get_arch("granite-3-2b"), n_layers=2, d_model=128,
                  vocab=512)
    params = _spec_params(cfg)

    def engine(spec: bool, paged: bool) -> ServingEngine:
        kw = dict(paged=True, block_size=16, n_blocks=40) if paged else {}
        if spec:
            kw.update(speculative=True, spec_k=SPEC_K)
        return ServingEngine(params, cfg, n_slots=4, max_seq=SPEC_MAX_SEQ,
                             chunk=8, **kw)

    results = {}
    for spec in (False, True):
        for paged in (False, True):
            eng = engine(spec, paged)
            eng.run_to_completion([Request(99, np.arange(1, 9,
                                                         dtype=np.int32),
                                           max_new=6)])       # warm
            eng.reset_stats()
            reqs = _spec_requests(np, Request)
            tick_s = _time_steps(eng)
            t0 = time.perf_counter()
            done, _ = eng.run_to_completion(reqs)
            dt = time.perf_counter() - t0
            assert len(done) == len(reqs)
            results[(spec, paged)] = dict(
                engine=eng, dt=dt, tick_s=tick_s[0],
                outputs={r.rid: list(r.out) for r in done})

    # bit-exactness: speculative == non-speculative, on BOTH layouts
    token_exact = all(
        results[(True, paged)]["outputs"] == results[(False, paged)]["outputs"]
        for paged in (False, True))
    assert token_exact, "speculative decode diverged from greedy decode"

    st = results[(True, False)]["engine"].spec_stats()
    st_paged = results[(True, True)]["engine"].spec_stats()
    base_eng = results[(False, False)]["engine"]
    spec_eng = results[(True, False)]["engine"]
    # decode wall-clock: tokens per second of *serving-tick* time (the
    # engine's step() calls — admission prefill excluded: identical
    # work in both configs and, on CPU, dominated by per-prompt-bucket
    # XLA compiles that drown the decode signal; the whole-run number
    # stays in the record as run_tokens_per_s).  With the span-clamped
    # verify forward (kernels/chunk_attention and the jnp ladder) a
    # verify tick emits ~k+1 tokens for well under (k+1)x a decode
    # step, so speculation now wins wall-clock, not just forward count.
    spec_tps = spec_eng.decode_tokens \
        / max(results[(True, False)]["tick_s"], 1e-9)
    base_tps = base_eng.decode_tokens \
        / max(results[(False, False)]["tick_s"], 1e-9)

    # per-phase timing: one jitted verify forward (width k+1) and one
    # drafter proposal on the bench config — where a spec tick's time
    # actually goes
    import jax
    import jax.numpy as jnp

    from repro.models import model as model_lib
    from repro.runtime import draft as draft_lib
    cache = model_lib.init_cache(cfg, 4, SPEC_MAX_SEQ, dtype=jnp.float32)
    cache = dict(cache, pos=jnp.full((4,), 40, jnp.int32))
    w = SPEC_K + 1
    toks = jnp.full((4, w), 7, jnp.int32)
    lens = jnp.full((4,), w, jnp.int32)
    fwd_fn = jax.jit(lambda p, t, l, c: model_lib.prefill_chunk(
        p, t, l, c, cfg, all_logits=True)[0])
    verify_forward_s = _phase_time(fwd_fn, params, toks, lens, cache)
    dstate = draft_lib.DraftState(
        hist=jnp.full((4, 64), 7, jnp.int32),
        count=jnp.full((4,), 64, jnp.int32))
    draft_fn = jax.jit(lambda d, t: draft_lib.propose(d, t, SPEC_K))
    draft_s = _phase_time(draft_fn, dstate, jnp.full((4,), 7, jnp.int32))

    spec_record = {
        "spec_k": SPEC_K,
        "acceptance_rate": st["acceptance_rate"],
        "tokens_per_forward": st["tokens_per_forward"],
        "tokens_per_forward_paged": st_paged["tokens_per_forward"],
        "spec_decode_tokens_per_s": spec_tps,
        "baseline_decode_tokens_per_s": base_tps,
        "spec_run_tokens_per_s":
            spec_eng.decode_tokens / results[(True, False)]["dt"],
        "baseline_run_tokens_per_s":
            base_eng.decode_tokens / results[(False, False)]["dt"],
        "verify_forward_s": verify_forward_s,
        "draft_s": draft_s,
        "decode_forwards": int(spec_eng.device_ticks),
        "baseline_decode_forwards": int(base_eng.device_ticks),
        "forwards_reduction_x":
            base_eng.device_ticks / max(1, spec_eng.device_ticks),
        "host_sync_reduction_x":
            base_eng.host_syncs / max(1, spec_eng.host_syncs),
        "spec_token_exact": token_exact,
    }
    record = json.load(open(out_path))
    record["spec"] = spec_record
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)

    rows = [
        f"serve,spec_decode,tokens_per_forward,"
        f"{st['tokens_per_forward']:.2f},"
        f"acceptance={st['acceptance_rate']:.2f};"
        f"paged={st_paged['tokens_per_forward']:.2f}",
        f"serve,spec_decode,forwards_reduction,"
        f"{spec_record['forwards_reduction_x']:.2f}x,"
        f"spec={spec_record['decode_forwards']};"
        f"baseline={spec_record['baseline_decode_forwards']}",
        f"serve,spec_decode,decode_tokens_per_s,{spec_tps:.0f},"
        f"baseline={base_tps:.0f};"
        f"verify_forward_ms={verify_forward_s * 1e3:.2f};"
        f"draft_ms={draft_s * 1e3:.3f}",
    ]
    # acceptance floors: the drafter must actually multiply the decode
    # (> 1.3 tokens per slot-forward on this workload, both layouts,
    # proportionally fewer memory-bound decode forwards) and the
    # outputs must be bit-exact (asserted above).  Since PR 6 the
    # speculative path must also pay for itself in decode wall-clock —
    # the span-clamped verify forward makes a verify tick cheaper than
    # the k+1 decode steps it replaces.
    assert st["tokens_per_forward"] > 1.3, spec_record
    assert st_paged["tokens_per_forward"] > 1.3, spec_record
    assert spec_record["forwards_reduction_x"] > 1.3, spec_record
    assert spec_tps >= base_tps, spec_record
    return rows


# ---------------------------------------------------------------------------
# Preemptive over-commit: occupancy under KV pressure vs reserved admission
# ---------------------------------------------------------------------------

OC_N_SLOTS = 6
OC_BLOCKS = 14          # deliberately too small for every worst case:
#                         6 slots x up to 6 worst-case blocks >> 14
OC_BLOCK_SIZE = 8
OC_MAX_SEQ = 96


def _overcommit_requests(np, Request, n=16):
    """Medium prompts with real decode budgets: reserved admission can
    seat only a couple of worst cases at once, over-commit seats what
    the pool physically holds and claws back under pressure."""
    rng = np.random.default_rng(13)
    return [Request(i, rng.integers(1, 500, size=int(rng.integers(8, 20)),
                                    dtype=np.int64).astype(np.int32),
                    max_new=int(rng.integers(12, 24))) for i in range(n)]


def run_overcommit(out_path: str = None) -> list[str]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_arch, reduced
    from repro.models import model as model_lib
    from repro.runtime.serve import Request, ServingEngine

    out_path = out_path or os.path.join(os.getcwd(), "BENCH_serve.json")
    cfg = reduced(get_arch("granite-3-2b"), n_layers=2, d_model=128,
                  vocab=512)
    params = model_lib.init(jax.random.PRNGKey(0), cfg, jnp.float32)

    def engine(overcommit: bool) -> ServingEngine:
        return ServingEngine(params, cfg, n_slots=OC_N_SLOTS,
                             max_seq=OC_MAX_SEQ, chunk=4, paged=True,
                             block_size=OC_BLOCK_SIZE, n_blocks=OC_BLOCKS,
                             chunked_prefill=True, prefill_chunk_tokens=8,
                             overcommit=overcommit)

    results = {}
    for overcommit in (False, True):
        eng = engine(overcommit)
        eng.run_to_completion([Request(99, np.arange(1, 9, dtype=np.int32),
                                       max_new=4)])            # warm
        eng.reset_stats()
        reqs = _overcommit_requests(np, Request)
        t0 = time.perf_counter()
        done, _ = eng.run_to_completion(reqs, max_ticks=50_000)
        dt = time.perf_counter() - t0
        assert len(done) == len(reqs)
        results[overcommit] = dict(
            engine=eng, dt=dt,
            tokens=sum(len(r.out) for r in done),
            outputs={r.rid: list(r.out) for r in done})

    eng_o = results[True]["engine"]
    eng_r = results[False]["engine"]
    occ = eng_o.occupancy_stats()
    occ_r = eng_r.occupancy_stats()
    # the exactness guarantee: eviction + recompute-based resume changed
    # no token vs the reserved (never-preempting) engine, and every
    # resume's replayed pending token matched what was delivered
    token_exact = results[True]["outputs"] == results[False]["outputs"] \
        and occ["preempt_replay_mismatches"] == 0
    assert token_exact, "preempted/resumed requests diverged"
    tps_o = results[True]["tokens"] / results[True]["dt"]
    tps_r = results[False]["tokens"] / results[False]["dt"]
    record = json.load(open(out_path))
    record["overcommit"] = {
        "n_slots": OC_N_SLOTS, "n_blocks": OC_BLOCKS,
        "block_size": OC_BLOCK_SIZE,
        "n_requests": len(results[True]["outputs"]),
        "occupancy": occ["occupancy"],
        "occupancy_reserved": occ_r["occupancy"],
        "preemptions": occ["preemptions"],
        "resumes": occ["resumes"],
        "preempted_tokens_recomputed": occ["preempted_tokens_recomputed"],
        "preempt_token_exact": token_exact,
        "tokens_per_s": tps_o,
        "reserved_tokens_per_s": tps_r,
        "throughput_vs_reserved_x": tps_o / tps_r,
        "stalls": int(eng_o.stalls),
    }
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)

    rows = [
        f"serve,overcommit,occupancy,{occ['occupancy']:.2f},"
        f"reserved={occ_r['occupancy']:.2f};"
        f"preemptions={occ['preemptions']};resumes={occ['resumes']}",
        f"serve,overcommit,tokens_per_s,{tps_o:.0f},"
        f"reserved={tps_r:.0f};"
        f"ratio={tps_o / tps_r:.2f}x;"
        f"recomputed={occ['preempted_tokens_recomputed']}",
    ]
    # acceptance floors: the pool really contended (>= 1 eviction), the
    # recompute replayed token-exactly, and over-commit admission ran
    # strictly more of the fleet than the worst-case reservation allowed
    assert occ["preemptions"] >= 1, record["overcommit"]
    assert occ["occupancy"] > occ_r["occupancy"], record["overcommit"]
    return rows


# ---------------------------------------------------------------------------
# Mesh scaling: fleet throughput vs device count + sharded token exactness
# ---------------------------------------------------------------------------
#
# Each device count runs in a SUBPROCESS: XLA reads
# ``--xla_force_host_platform_device_count`` once at import, so a fresh
# interpreter is the only way to vary it.  The child builds a
# FleetSupervisor of one replica per device, serves the same stream the
# single-engine oracle serves, and reports throughput + host syncs +
# token exactness; the 2-device child additionally runs a
# tensor-parallel (model=2) engine for the ``sharded_token_exact``
# acceptance bit.  Forced host devices share one physical CPU — the
# curve records the router's scaling behavior (per-replica jit caches,
# routing overhead, sync totals), not hardware speedup; on real
# accelerators the same code path is the one that scales.

SCALING_DEVICE_COUNTS = (1, 2, 4, 8)
SCALING_N_REQUESTS = 16


def _scaling_requests(np, Request, cfg, n=SCALING_N_REQUESTS):
    rng = np.random.default_rng(17)
    return [Request(i, rng.integers(1, cfg.vocab,
                                    size=int(rng.integers(6, 16)),
                                    dtype=np.int64).astype(np.int32),
                    max_new=int(rng.integers(8, 16))) for i in range(n)]


def _scaling_worker(n_devices: int) -> dict:
    """Child-process body (device count already forced via XLA_FLAGS)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_arch, reduced
    from repro.models import model as model_lib
    from repro.runtime.serve import Request, ServingEngine
    from repro.runtime.sharding import serve_mesh
    from repro.runtime.supervisor import FleetSupervisor

    assert jax.device_count() >= n_devices, (jax.device_count(), n_devices)
    cfg = reduced(get_arch("granite-3-2b"), n_layers=2, d_model=128,
                  vocab=512)
    params = model_lib.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    kw = dict(n_slots=4, max_seq=96, chunk=8, paged=True, block_size=16,
              n_blocks=24)

    oracle = ServingEngine(params, cfg, **kw)
    done, _ = oracle.run_to_completion(_scaling_requests(np, Request, cfg))
    want = {r.rid: list(r.out) for r in done}

    fleet = FleetSupervisor(params, cfg, n_replicas=n_devices, model=1,
                            devices=jax.devices()[:n_devices], **kw)
    for eng in fleet.engines:       # warm each replica's jitted closures
        eng.run_to_completion([Request(99, np.arange(1, 9, dtype=np.int32),
                                       max_new=4)])
    fleet.reset_stats()
    reqs = _scaling_requests(np, Request, cfg)
    t0 = time.perf_counter()
    done, _ = fleet.run_to_completion(reqs)
    dt = time.perf_counter() - t0
    got = {r.rid: list(r.out) for r in done}
    sync = fleet.sync_stats()["fleet"]
    out = {
        "devices": n_devices,
        "tokens_per_s": sum(len(t) for t in got.values()) / dt,
        "wall_s": dt,
        "host_syncs": sync["host_syncs"],
        "device_ticks": sync["device_ticks"],
        "requests_per_replica": list(fleet.routed),
        "fleet_token_exact": got == want,
    }
    if n_devices == 2:
        # tensor-parallel exactness: heads + KV sharded over model=2,
        # same stream, must be bit-identical to the single-device oracle
        eng = ServingEngine(params, cfg, mesh=serve_mesh(2), **kw)
        done, _ = eng.run_to_completion(
            _scaling_requests(np, Request, cfg))
        ks = eng.kv_stats()
        out["sharded_token_exact"] = \
            {r.rid: list(r.out) for r in done} == want \
            and ks["model_shards"] == 2 and ks["kv_shard_fraction"] == 0.5
    return out


def run_scaling(out_path: str = None) -> list[str]:
    import subprocess
    import sys

    import jax
    backend = jax.default_backend()
    if backend != "cpu":
        # a device belongs to one process: this one already holds it,
        # so a child interpreter would fail or hang reaching it
        raise RuntimeError(
            f"run_scaling starts one child interpreter per device count, "
            f"each with forced host devices; this process holds the "
            f"{backend} devices, which no child could use.  Run it on "
            f"the CPU backend (JAX_PLATFORMS=cpu).")
    out_path = out_path or os.path.join(os.getcwd(), "BENCH_serve.json")
    points = []
    for d in SCALING_DEVICE_COUNTS:
        env = dict(
            os.environ,
            XLA_FLAGS=f"--xla_force_host_platform_device_count={d}")
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--scaling-worker", str(d)],
            env=env, capture_output=True, text=True, timeout=1200)
        if proc.returncode != 0:
            raise RuntimeError(
                f"scaling worker (devices={d}) failed:\n"
                f"{proc.stderr[-4000:]}")
        points.append(json.loads(proc.stdout.splitlines()[-1]))

    sharded_exact = next(p["sharded_token_exact"] for p in points
                         if "sharded_token_exact" in p)
    scaling = {
        "device_counts": [p["devices"] for p in points],
        "tokens_per_s": [p["tokens_per_s"] for p in points],
        "host_syncs": [p["host_syncs"] for p in points],
        "device_ticks": [p["device_ticks"] for p in points],
        "requests_per_replica": [p["requests_per_replica"] for p in points],
        "fleet_token_exact": all(p["fleet_token_exact"] for p in points),
        "note": "forced host devices share one physical CPU: the curve "
                "records the fleet router's behavior (balance, syncs, "
                "exactness), not hardware speedup",
    }
    record = json.load(open(out_path))
    record["scaling"] = scaling
    record["sharded_token_exact"] = sharded_exact
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)

    # the perf-trajectory file: one JSONL line per bench run, seeded with
    # the single-device baseline so device-count regressions have an
    # anchor to diff against
    traj_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "artifacts")
    os.makedirs(traj_dir, exist_ok=True)
    with open(os.path.join(traj_dir, "serve_trajectory.jsonl"), "a") as f:
        f.write(json.dumps({
            "ts": time.time(),
            "suite": "serve_scaling",
            "single_device_tokens_per_s": points[0]["tokens_per_s"],
            "scaling": {k: scaling[k] for k in
                        ("device_counts", "tokens_per_s", "host_syncs")},
            "sharded_token_exact": sharded_exact,
        }) + "\n")

    rows = []
    for p in points:
        rows.append(f"serve,scaling,tokens_per_s@{p['devices']}dev,"
                    f"{p['tokens_per_s']:.0f},"
                    f"host_syncs={p['host_syncs']};"
                    f"routed={p['requests_per_replica']}")
    rows.append(f"serve,scaling,sharded_token_exact,{sharded_exact},"
                f"model_shards=2")
    # acceptance floors: every device count served the stream
    # byte-identically (fleet AND tensor-parallel), and the router used
    # every replica at each point
    assert scaling["fleet_token_exact"] is True, scaling
    assert sharded_exact is True, scaling
    for p in points:
        assert all(n > 0 for n in p["requests_per_replica"]), p
    return rows


# ---------------------------------------------------------------------------
# Chaos: fault injection, quarantine, and in-flight request migration
# ---------------------------------------------------------------------------
#
# A 2-replica fleet serves the same stream twice: once fault-free (the
# throughput baseline) and once with a seeded FaultPlan killing replica
# 0's tick mid-run.  The fleet quarantines the replica and migrates its
# in-flight requests to the survivor by replaying prompt +
# generated-so-far through chunked prefill — greedy determinism makes
# the replay token-exact, asserted against the unfaulted single-engine
# oracle.  ``fault_recovery`` records the cost of surviving: migrated
# request count, exactness, dead letters (must be zero — the fleet sheds
# throughput, never correctness), and throughput vs the fault-free run.

CHAOS_FAULT_KIND = "tick_exception"
CHAOS_FAULT_TICK = 4


def run_chaos(out_path: str = None) -> list[str]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_arch, reduced
    from repro.models import model as model_lib
    from repro.runtime import faults
    from repro.runtime.serve import Request, ServingEngine
    from repro.runtime.supervisor import FleetSupervisor

    out_path = out_path or os.path.join(os.getcwd(), "BENCH_serve.json")
    cfg = reduced(get_arch("granite-3-2b"), n_layers=2, d_model=128,
                  vocab=512)
    params = model_lib.init(jax.random.PRNGKey(0), cfg, jnp.float32)
    kw = dict(n_slots=4, max_seq=96, chunk=8, paged=True, block_size=16,
              n_blocks=24, chunked_prefill=True, prefill_chunk_tokens=8)

    # the unfaulted single-replica oracle every survivor is held to
    oracle = ServingEngine(params, cfg, **kw)
    done, _ = oracle.run_to_completion(_scaling_requests(np, Request, cfg))
    want = {r.rid: list(r.out) for r in done}

    def fleet_run(plan):
        fleet = FleetSupervisor(params, cfg, n_replicas=2, model=1,
                                devices=jax.devices()[:1],
                                validate_outputs=True, **kw)
        for eng in fleet.engines:   # warm each replica's jitted closures
            eng.run_to_completion([Request(99,
                                           np.arange(1, 9, dtype=np.int32),
                                           max_new=4)])
            eng.reset_stats()
        if plan is not None:
            fleet.arm_faults(plan)
        reqs = _scaling_requests(np, Request, cfg)
        t0 = time.perf_counter()
        done, _ = fleet.run_to_completion(reqs, max_wall_s=600)
        dt = time.perf_counter() - t0
        got = {r.rid: list(r.out) for r in done}
        return got, sum(len(t) for t in got.values()) / dt, fleet

    got0, tps0, _ = fleet_run(None)
    assert got0 == want, "fault-free fleet diverged from the oracle"

    plan = faults.FaultPlan([faults.FaultEvent(
        kind=CHAOS_FAULT_KIND, tick=CHAOS_FAULT_TICK, replica=0)])
    got_f, tps_f, fleet = fleet_run(plan)
    fh = fleet.fleet_health()

    fault_recovery = {
        "fault_kind": CHAOS_FAULT_KIND,
        "fault_tick": CHAOS_FAULT_TICK,
        "requests_migrated": fh["migrations"],
        "migrated_token_exact": got_f == want,
        "migrate_replay_mismatches": fh["migrate_replay_mismatches"],
        "dead_letter": len(fh["dead_letters"]),
        "replicas_quarantined": len(fleet.engines) - fh["healthy"],
        "tokens_per_s": tps_f,
        "fault_free_tokens_per_s": tps0,
        "recovery_overhead_x": tps0 / tps_f,
    }
    record = json.load(open(out_path))
    record["fault_recovery"] = fault_recovery
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)

    rows = [
        f"serve,fault_recovery,requests_migrated,"
        f"{fault_recovery['requests_migrated']},"
        f"token_exact={fault_recovery['migrated_token_exact']};"
        f"dead_letter={fault_recovery['dead_letter']}",
        f"serve,fault_recovery,tokens_per_s,{tps_f:.0f},"
        f"fault_free={tps0:.0f};"
        f"overhead={fault_recovery['recovery_overhead_x']:.2f}x;"
        f"quarantined={fault_recovery['replicas_quarantined']}",
    ]
    # acceptance floors: work really migrated, every survivor bit-exact
    # vs the unfaulted oracle, and nothing was dead-lettered — losing a
    # replica mid-run costs throughput, never tokens
    assert fault_recovery["requests_migrated"] >= 1, fault_recovery
    assert fault_recovery["migrated_token_exact"] is True, fault_recovery
    assert fault_recovery["migrate_replay_mismatches"] == 0, fault_recovery
    assert fault_recovery["dead_letter"] == 0, fault_recovery
    return rows


# ---------------------------------------------------------------------------
# Priority/SLA tiers: per-tier p99 TTFT under a bursty open-loop trace
# ---------------------------------------------------------------------------

SLA_N_SLOTS = 4
SLA_BURSTS = (0, 2, 4, 6)        # step indices of the throughput bursts
SLA_BURST_SIZE = 8
SLA_LATENCY_ARRIVALS = (3, 7, 11, 15, 19, 23)


def _sla_trace(np, Request):
    """The bursty open-loop arrival trace: (step, request) pairs.
    Throughput bursts land early and saturate the slots; latency-tier
    requests arrive mid-run, one at a time, and must displace."""
    rng = np.random.default_rng(23)

    def prompt():
        return rng.integers(1, 500, size=int(rng.integers(8, 16)),
                            dtype=np.int64).astype(np.int32)

    arrivals, rid = [], 0
    for step in SLA_BURSTS:
        for _ in range(SLA_BURST_SIZE):
            # batch-class requests carry real decode budgets: the queue
            # the latency tier gets to jump is what the bench measures
            arrivals.append((step, Request(
                rid, prompt(), max_new=int(rng.integers(16, 28)),
                tier="throughput")))
            rid += 1
    for step in SLA_LATENCY_ARRIVALS:
        arrivals.append((step, Request(
            rid, prompt(), max_new=int(rng.integers(8, 16)),
            tier="latency")))
        rid += 1
    return arrivals


def _drive_sla_trace(eng, arrivals, max_steps=50_000):
    """Open-loop drive: submit at step indices, poll completions."""
    out, steps = {}, 0
    pending = sorted(arrivals, key=lambda kv: (kv[0], kv[1].rid))
    while pending or eng.has_work:
        while pending and pending[0][0] <= steps:
            eng.submit(pending.pop(0)[1])
        eng.step()
        for req in eng.poll():
            assert req.rid not in out, f"rid {req.rid} delivered twice"
            out[req.rid] = list(req.out)
        steps += 1
        assert steps < max_steps, "SLA trace did not converge"
    return out


def run_sla(out_path: str = None) -> list[str]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_arch, reduced
    from repro.models import model as model_lib
    from repro.runtime.accounting import TierAccounting
    from repro.runtime.serve import Request, ServingEngine

    out_path = out_path or os.path.join(os.getcwd(), "BENCH_serve.json")
    cfg = reduced(get_arch("granite-3-2b"), n_layers=2, d_model=128,
                  vocab=512)
    params = model_lib.init(jax.random.PRNGKey(0), cfg, jnp.float32)

    layouts = {
        "contiguous": dict(),
        "paged": dict(paged=True, block_size=16, n_blocks=24,
                      overcommit=True),
    }
    sla: dict = {
        "trace": {
            "n_throughput": len(SLA_BURSTS) * SLA_BURST_SIZE,
            "n_latency": len(SLA_LATENCY_ARRIVALS),
            "burst_steps": list(SLA_BURSTS),
            "latency_arrival_steps": list(SLA_LATENCY_ARRIVALS),
        },
    }
    rows: list[str] = []
    for layout, extra in layouts.items():
        eng = ServingEngine(params, cfg, n_slots=SLA_N_SLOTS, max_seq=96,
                            chunk=4, chunked_prefill=True,
                            prefill_chunk_tokens=8, **extra)
        # warmup in two passes so TTFT measures scheduling, not XLA:
        # the untiered closed-loop run is the exactness oracle, and one
        # throwaway tiered pass compiles the displacement-path tick
        # shapes the oracle never reaches
        oracle_reqs = [Request(r.rid, r.prompt, max_new=r.max_new)
                       for _, r in _sla_trace(np, Request)]
        done, _ = eng.run_to_completion(oracle_reqs, max_ticks=50_000)
        want = {r.rid: list(r.out) for r in done}
        warm = _drive_sla_trace(eng, _sla_trace(np, Request))
        assert warm == want, f"{layout}: tiered warmup diverged"
        eng.reset_stats()
        eng.sla = TierAccounting()

        got = _drive_sla_trace(eng, _sla_trace(np, Request))
        token_exact = got == want
        assert token_exact, f"{layout}: tiered run diverged from oracle"
        rep = eng.sla.report()
        lat, thr = rep["latency"], rep["throughput"]
        assert eng.displacements >= 1, (layout, eng.displacements)
        assert lat["finished"] == len(SLA_LATENCY_ARRIVALS)
        # the point of the tier: arrivals that displace instead of
        # queueing see a fraction of the backlogged tier's p99 TTFT
        assert lat["ttft_p99"] < 0.5 * thr["ttft_p99"], (layout, rep)
        sla[layout] = {
            "latency": lat,
            "throughput": thr,
            "tier_token_exact": token_exact,
            "displacements": int(eng.displacements),
            "preempt_replay_mismatches":
                int(eng.preempt_replay_mismatches),
            "ttft_p99_vs_throughput_x": lat["ttft_p99"] / thr["ttft_p99"],
        }
        rows.append(
            f"serve,sla,{layout}_ttft_p99_s,{lat['ttft_p99']:.3f},"
            f"throughput_tier={thr['ttft_p99']:.3f};"
            f"ratio={lat['ttft_p99'] / thr['ttft_p99']:.2f}x;"
            f"displacements={eng.displacements};"
            f"token_exact={token_exact}")

    record = json.load(open(out_path))
    record["sla"] = sla
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    return rows


def run() -> list[str]:
    return run_serve() + run_latency() + run_spec() + run_overcommit() \
        + run_scaling() + run_chaos() + run_sla()


if __name__ == "__main__":
    if "--scaling-worker" in sys.argv:
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "..", "src"))
        d = int(sys.argv[sys.argv.index("--scaling-worker") + 1])
        print(json.dumps(_scaling_worker(d)))
    else:
        print("\n".join(run()))
