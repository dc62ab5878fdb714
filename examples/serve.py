"""Serving example: device-resident continuous batching over the EMPA pool.

Requests are QTs, KV-cache slots are cores: rented on admission, returned
at EOS; more requests than slots exercises queueing (pool exhaustion =
"SV out of cores", §3.3).  The slot supervisor — active mask, greedy
argmax, EOS/budget retirement — runs inside one jitted decode chunk, so
the host syncs once per `chunk` generated tokens instead of once per slot
per tick.

The same run then repeats with ``paged=True``: the rented resource drops
from a whole `max_seq` slot to a fixed-size KV *block* (runtime/paging),
identical prompt prefixes share blocks, and the outputs stay token-exact
while the allocated KV bytes per token shrink.  The final section turns
on ``overcommit=True`` against a pool too small for every worst case:
the supervisor evicts and resumes requests under KV pressure and the
streams still match the reserved run token for token.

With ``--devices N`` the run finishes one level up the hierarchy: a
``FleetSupervisor`` owns N serving replicas (one per device; replicas
share devices when the host has fewer) and routes the same stream
least-loaded-by-blocks across them — engines are cores to the fleet
exactly as slots are cores to an engine, and the tokens still match.

    PYTHONPATH=src python examples/serve.py
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        PYTHONPATH=src python examples/serve.py --devices 4
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_arch, reduced
from repro.models import model
from repro.runtime.serve import Request, ServingEngine
from repro.runtime.supervisor import FleetSupervisor


def make_requests(cfg, n=10):
    rng = np.random.default_rng(0)
    shared_prefix = rng.integers(1, cfg.vocab, size=16,
                                 dtype=np.int64).astype(np.int32)
    reqs = []
    for i in range(n):
        tail = rng.integers(1, cfg.vocab, size=rng.integers(2, 8),
                            dtype=np.int64).astype(np.int32)
        # half the stream shares a 16-token prefix (one full block)
        prompt = np.concatenate([shared_prefix, tail]) if i % 2 == 0 \
            else rng.integers(1, cfg.vocab, size=rng.integers(4, 12),
                              dtype=np.int64).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt,
                            max_new=int(rng.integers(4, 10))))
    return reqs


def run(engine, requests, label):
    print(f"-- {label}: serving {len(requests)} requests over "
          f"{engine.pool.n} slots")
    t0 = time.perf_counter()
    done, ticks = engine.run_to_completion(requests)
    dt = time.perf_counter() - t0
    total = sum(len(r.out) for r in done)
    stats = engine.sync_stats()
    kv = engine.kv_stats()
    print(f"   done in {ticks} on-device decode ticks; slots rented "
          f"{engine.pool.created_total} times; pool back to "
          f"{engine.pool.available}/{engine.pool.n} free")
    print(f"   {total} tokens in {dt:.2f}s = {total / dt:.0f} tok/s; "
          f"{stats['host_syncs']} host syncs "
          f"({stats['host_syncs_per_100_tokens']:.1f}/100tok, baseline "
          f"{stats['baseline_syncs_per_100_tokens']:.1f}/100tok -> "
          f"{stats['sync_reduction_x']:.1f}x fewer)")
    print(f"   KV allocated: {kv['kv_bytes_allocated']} B over "
          f"{kv['tokens_finished']} tokens = "
          f"{kv['kv_bytes_per_token']:.0f} B/token"
          + (f"; {kv['shared_block_hits']} shared-block hits, peak "
             f"{kv['peak_blocks']}/{kv['n_blocks']} blocks"
             if engine.layout else ""))
    assert len(done) == len(requests)
    assert engine.pool.used == 0
    return {r.rid: r.out for r in done}, kv


def run_fleet(params, cfg, requests, want, n_replicas):
    print(f"-- fleet: {n_replicas} serving replicas over "
          f"{jax.device_count()} devices")
    fleet = FleetSupervisor(params, cfg, n_replicas=n_replicas, model=1,
                            n_slots=4, max_seq=96, chunk=8,
                            paged=True, block_size=16, n_blocks=16)
    t0 = time.perf_counter()
    done, ticks = fleet.run_to_completion(requests)
    dt = time.perf_counter() - t0
    got = {r.rid: r.out for r in done}
    assert got == want, "fleet routing must not change a token"
    total = sum(len(t) for t in got.values())
    ks = fleet.kv_stats()["fleet"]
    sync = fleet.sync_stats()["fleet"]
    print(f"   {total} tokens in {dt:.2f}s = {total / dt:.0f} tok/s over "
          f"{ticks} summed ticks; requests per replica {fleet.routed}")
    print(f"   fleet pool: {ks['slot_pool']['n_units']} slots / "
          f"{ks['n_blocks']} blocks across {ks['n_replicas']} replicas; "
          f"{sync['host_syncs']} host syncs fleet-wide")
    print("token-exact across the fleet: which replica serves a request "
          "cannot matter")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, default=1,
                    help="fleet replicas (one per device; replicas share "
                         "devices when the host has fewer — set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count"
                         "=N for a real N-device CPU mesh)")
    args = ap.parse_args()
    enable_compile_cache()
    cfg = reduced(get_arch("granite-3-2b"), n_layers=2, d_model=128,
                  vocab=512)
    params = model.init(jax.random.PRNGKey(0), cfg, jnp.float32)

    out_c, kv_c = run(
        ServingEngine(params, cfg, n_slots=4, max_seq=96, chunk=8),
        make_requests(cfg), "contiguous slots")
    out_p, kv_p = run(
        ServingEngine(params, cfg, n_slots=4, max_seq=96, chunk=8,
                      paged=True, block_size=16, n_blocks=16),
        make_requests(cfg), "paged blocks")
    assert out_c == out_p, "paged decode must be token-exact"
    print(f"token-exact across layouts; paged KV bytes/token "
          f"{kv_p['kv_bytes_per_token']:.0f} vs contiguous "
          f"{kv_c['kv_bytes_per_token']:.0f} "
          f"({kv_c['kv_bytes_per_token'] / kv_p['kv_bytes_per_token']:.1f}x"
          f" smaller)")

    # chunked prefill: prompts are outsourced fragment by fragment (the
    # paper's cores never hand over a whole job), so a long prompt can't
    # head-of-line-block the decoders — and tokens stay exact
    out_f, _ = run(
        ServingEngine(params, cfg, n_slots=4, max_seq=96, chunk=8,
                      paged=True, block_size=16, n_blocks=16,
                      chunked_prefill=True, prefill_chunk_tokens=16),
        make_requests(cfg), "paged blocks + chunked prefill")
    assert out_f == out_c, "chunked prefill must be token-exact"
    print("token-exact with chunked prefill (fragments of 16)")

    # speculative decoding: a drafter core (n-gram prompt lookup) runs
    # ahead, one verify forward accepts up to spec_k+1 tokens per slot —
    # greedy argmax verification keeps the output bit-exact, so the only
    # possible outcome is fewer memory-bound decode forwards
    spec_eng = ServingEngine(params, cfg, n_slots=4, max_seq=96, chunk=8,
                             paged=True, block_size=16, n_blocks=16,
                             speculative=True, spec_k=4)
    out_s, _ = run(spec_eng, make_requests(cfg),
                   "paged blocks + speculative decode")
    assert out_s == out_c, "speculative decode must be token-exact"
    st = spec_eng.spec_stats()
    print(f"token-exact with speculative decode (spec_k=4): "
          f"{st['tokens_per_forward']:.2f} tokens/forward at "
          f"{st['acceptance_rate']:.2f} draft acceptance")

    # preemptive over-commit: admission takes only what a request needs
    # *now* (no §5.1 worst-case reservation), and when decode growth
    # runs the deliberately undersized pool dry the supervisor evicts a
    # victim — its chain is clawed back, its request parks with its
    # token history and resumes later by replaying that history through
    # chunked prefill.  Greedy determinism keeps the stream token-exact.
    reqs = make_requests(cfg, n=12)
    for r in reqs:
        r.max_new = max(r.max_new, 28)        # real decode budgets:
        #                                       worst case ~3 blocks each
    base = ServingEngine(params, cfg, n_slots=4, max_seq=96, chunk=4,
                         paged=True, block_size=16, n_blocks=9,
                         chunked_prefill=True, prefill_chunk_tokens=16)
    out_r, _ = run(base, [Request(r.rid, r.prompt, max_new=r.max_new)
                          for r in reqs], "small pool, reserved admission")
    oc_eng = ServingEngine(params, cfg, n_slots=4, max_seq=96, chunk=4,
                           paged=True, block_size=16, n_blocks=9,
                           chunked_prefill=True, prefill_chunk_tokens=16,
                           overcommit=True)
    out_o, _ = run(oc_eng, [Request(r.rid, r.prompt, max_new=r.max_new)
                            for r in reqs], "small pool, over-commit")
    assert out_o == out_r, "preempted/resumed requests must be token-exact"
    occ = oc_eng.occupancy_stats()
    occ_r = base.occupancy_stats()
    print(f"token-exact under over-commit: occupancy "
          f"{occ['occupancy']:.2f} vs {occ_r['occupancy']:.2f} reserved, "
          f"{occ['preemptions']} preemptions / {occ['resumes']} resumes, "
          f"{occ['preempted_tokens_recomputed']} tokens recomputed")

    # the fleet: one supervisor up — N engines as the rented cores,
    # requests routed least-loaded-by-blocks, preemption-aware
    if args.devices > 1:
        run_fleet(params, cfg, make_requests(cfg), out_c, args.devices)


if __name__ == "__main__":
    main()
