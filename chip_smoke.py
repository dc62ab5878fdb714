"""Serve granite-3-2b at its published widths, in bf16, on TPU, through
the engine's own entry points, and check what comes out.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the multi-chip paths, and only them

One chip:

1. kernels: each served attention kernel runs on the chip at the served
   shapes and must match its ``ref.py`` oracle, and must compile to a
   Mosaic ``tpu_custom_call``;
2. engine A, ``ServingEngine(paged=True, chunked_prefill=True)``: paged
   decode, wide prefill fragments and the solo prefill tick;
3. engine B, engine A with ``speculative=True``: the narrow verify kernel;
4. engine C, contiguous, whose attention is plain jnp: its tokens are
   compared with engine A's for information only.

Every request must complete with in-vocabulary tokens, and the compiled
ticks must call the paged-decode, wide and narrow kernels.

Four chips (``--chips 4``): a one-chip engine on ``jax.devices()[0]``, a
tensor-parallel engine over ``model=4`` (prefill logits within a bf16
tolerance of the one-chip forward; parameters and KV cache on all four
chips), and a fleet of four one-chip replicas (tokens equal to the
one-chip engine's on each replica's requests).

The weights are random, made from ``--seed``: no checkpoint is in the
repository.  The script runs in one process and starts none.  It exits
non-zero, and prints no result line, when JAX finds no TPU or when any
check fails.  Its last line of output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

# ---- serving shape -------------------------------------------------------
# 8 slots x 2048 positions in blocks of 16.  The pool holds 640 blocks,
# not the capacity-equivalent 8 x 128: the 8 requests below need at most
# 8 x 65 = 520, and the decode tick's temporaries hold about six copies
# of the pool (compile rehearsal for v5e), which at 1024 blocks would
# put the tick at 14.7 GB of a 16 GB chip.
N_SLOTS, MAX_SEQ, BLOCK, N_BLOCKS = 8, 2048, 16, 640
PREFILL_CHUNK = 16        # > 8 (NARROW_MAX_WIDTH): fragments take the wide kernel
SPEC_K = 4                # verify width 5: the narrow kernel
DECODE_CHUNK = 8
N_REQUESTS, PROMPT_LEN, MAX_NEW = 8, (100, 1000), 32
CONTIGUOUS_MAX_SEQ = 1088  # longest prompt + MAX_NEW, in whole blocks

ENGINE_KW = dict(n_slots=N_SLOTS, max_seq=MAX_SEQ, chunk=DECODE_CHUNK,
                 paged=True, block_size=BLOCK, n_blocks=N_BLOCKS,
                 chunked_prefill=True, prefill_chunk_tokens=PREFILL_CHUNK,
                 # random weights, no tokenizer: no EOS, so every request
                 # runs its whole budget and the run's work is fixed
                 eos_id=-1, validate_outputs=True)

# ---- tolerances ----------------------------------------------------------
# kernel vs ref.py, as max |kernel - ref| / max |ref| over the output.
# Both round the output to bf16 (2^-9 relative each), and the kernel may
# feed its f32 probabilities to the MXU as bf16 (another 2^-9).  A wrong
# block, head or mask gives an error of the order of the output itself.
KERNEL_TOL = 1e-2
# tensor-parallel vs one-chip prefill logits through the first layer,
# same measure.  The sharded contractions round their bf16 partial sums
# before the all-reduce: a change of ~2^-9 of a branch's output in a few
# places, which the unembedding of this random model amplifies about
# tenfold (a 4e-3 relative change of the embeddings moves one layer's
# logits by 4e-2, CPU f32 at these widths).  A mis-sharded head or
# weight gives an error of order 1.
TP_LOGIT_TOL = 1e-1

# the engine's jitted tick families, by attribute
TICKS = {"_chunk_fn": "decode", "_mixed_fn": "mixed",
         "_solo_fn": "solo_prefill", "_spec_fn": "spec",
         "_spec_chunk_fn": "spec_chunk", "_admit_fn": "admit"}


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


class CompiledTick:
    """Stands in for one of the engine's jitted ticks: compiles it ahead
    of time on the first call with each argument signature (timing the
    compile and keeping the compiled program), then runs that program.
    The engine calls it exactly as it calls the jitted tick."""

    def __init__(self, fn):
        self.fn, self.compiled, self.compile_s = fn, {}, 0.0

    def __call__(self, *args):
        import jax
        leaves, tree = jax.tree_util.tree_flatten(args)
        key = (tree, tuple((getattr(x, "shape", ()),
                            str(getattr(x, "dtype", type(x))))
                           for x in leaves))
        if key not in self.compiled:
            t0 = time.perf_counter()
            self.compiled[key] = self.fn.lower(*args).compile()
            self.compile_s += time.perf_counter() - t0
        return self.compiled[key](*args)

    def kernels(self) -> set:
        from repro.kernels import tpu_kernel_names
        names = set()
        for c in self.compiled.values():
            names |= tpu_kernel_names(c.as_text())
        return names


def instrument(engine) -> dict:
    ticks = {}
    for attr, family in TICKS.items():
        fn = getattr(engine, attr, None)
        if fn is not None:
            ticks[family] = CompiledTick(fn)
            setattr(engine, attr, ticks[family])
    return ticks


def make_requests(vocab: int, seed: int, n: int = N_REQUESTS):
    import numpy as np
    from repro.runtime.serve import Request
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1))
        prompt = rng.integers(2, vocab, size=plen).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt, max_new=MAX_NEW))
    return reqs


def peak_gb() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.3f} GB"


def check_done(done, requests, label: str, vocab: int) -> None:
    check(sorted(r.rid for r in done) == sorted(r.rid for r in requests),
          f"{label}: {len(done)} of {len(requests)} requests completed")
    for r in done:
        check(len(r.out) == r.max_new,
              f"{label}: rid {r.rid} emitted {len(r.out)} of {r.max_new}")
        check(all(0 <= t < vocab for t in r.out),
              f"{label}: rid {r.rid} emitted a token outside [0, {vocab})")


def serve(engine, requests, label: str, vocab: int) -> dict:
    """Run `requests` to completion; every one must finish its whole
    budget with tokens in [0, vocab).  Returns rid -> tokens."""
    t0 = time.perf_counter()
    done, ticks = engine.run_to_completion(requests)
    dt = time.perf_counter() - t0
    check_done(done, requests, label, vocab)
    n_tok = sum(len(r.out) for r in done)
    n_prompt = sum(len(r.prompt) for r in done)
    print(f"{label}: {len(done)} requests ({n_prompt} prompt tokens) -> "
          f"{n_tok} tokens in {dt:.6f} s over {ticks} device ticks = "
          f"{n_tok / dt:.3f} generated tok/s (host clock, prefill "
          f"included); device peak {peak_gb()}")
    return {r.rid: list(r.out) for r in done}


def agreement(a: dict, b: dict) -> str:
    same = sum(a[k] == b[k] for k in a)
    prefix = []
    for k in a:
        n = 0
        while n < len(a[k]) and a[k][n] == b[k][n]:
            n += 1
        prefix.append(n)
    return (f"{same}/{len(a)} requests token-identical, common prefix "
            f"{sum(prefix) / len(prefix):.2f} of {MAX_NEW} tokens on average")


def check_ticks(label: str, ticks: dict, want: dict) -> None:
    for family, t in ticks.items():
        if t.compiled:
            print(f"{label}: tick {family}: compiled {len(t.compiled)} "
                  f"program(s) in {t.compile_s:.3f} s, kernels "
                  f"{sorted(t.kernels()) or 'none'}")
    for family, kernel in want.items():
        check(family in ticks and ticks[family].compiled,
              f"{label}: tick {family} never ran")
        check(kernel in ticks[family].kernels(),
              f"{label}: compiled tick {family} does not call {kernel} "
              f"as a tpu_custom_call")


# ---- phases: one chip ----------------------------------------------------

def check_kernels(cfg, seed: int) -> None:
    """Each served kernel on the chip at the served shapes vs ref.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import tpu_kernel_names
    from repro.kernels.chunk_attention import (
        NARROW_MAX_WIDTH, chunk_attention_kernel, chunk_attention_ref,
        paged_chunk_attention_kernel, paged_chunk_attention_ref)
    from repro.kernels.paged_attention import (paged_attention,
                                               paged_attention_ref)
    h, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rng = np.random.default_rng(seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def normal(shape):
        return jax.random.normal(next(keys), shape, jnp.bfloat16)

    # disjoint chains of N_BLOCKS // N_SLOTS blocks per slot
    per_slot = N_BLOCKS // N_SLOTS
    nb = MAX_SEQ // BLOCK
    tables = np.full((N_SLOTS, nb), -1, np.int32)
    tables[:, :per_slot] = rng.permutation(N_BLOCKS).reshape(
        N_SLOTS, per_slot)
    tables = jnp.asarray(tables)
    span = per_slot * BLOCK
    kp, vp = normal((N_BLOCKS, BLOCK, hkv, d)), normal((N_BLOCKS, BLOCK,
                                                        hkv, d))
    kc, vc = normal((N_SLOTS, MAX_SEQ, hkv, d)), normal((N_SLOTS, MAX_SEQ,
                                                         hkv, d))
    lengths = jnp.asarray(rng.integers(1, span + 1, N_SLOTS), jnp.int32)

    def frag(b, c):
        q = normal((b, c, h, d))
        pos0 = rng.integers(0, span - c + 1, b)
        return q, jnp.asarray(pos0[:, None] + np.arange(c), jnp.int32)

    wide, narrow = PREFILL_CHUNK, SPEC_K + 1
    solo = max(PREFILL_CHUNK, min(PREFILL_CHUNK * N_SLOTS, MAX_SEQ))
    assert narrow <= NARROW_MAX_WIDTH < wide
    cases = [("paged_attention", paged_attention, paged_attention_ref,
              (normal((N_SLOTS, h, d)), kp, vp, tables, lengths))]
    for name, c, b in (("paged_chunk_attention_wide", wide, N_SLOTS),
                       ("paged_chunk_attention_wide", solo, 1),
                       ("paged_chunk_attention_narrow", narrow, N_SLOTS)):
        q, qp = frag(b, c)
        cases.append((name, paged_chunk_attention_kernel,
                      paged_chunk_attention_ref,
                      (q, kp, vp, tables[:b], qp)))
    for name, c in (("chunk_attention_wide", wide),
                    ("chunk_attention_narrow", narrow)):
        q, qp = frag(N_SLOTS, c)
        cases.append((name, chunk_attention_kernel, chunk_attention_ref,
                      (q, kc, vc, qp)))
    for name, fn, ref, args in cases:
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        check(tpu_kernel_names(compiled.as_text()) == {name},
              f"{name}: the compiled call is not one Mosaic kernel "
              f"{name!r}")
        got = np.asarray(compiled(*args), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(ref)(*args), np.float32)
        err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        print(f"kernel {name} q{tuple(args[0].shape)}: compiled in "
              f"{compile_s:.3f} s, max|kernel-ref|/max|ref| = {err:.3e} "
              f"(tolerance {KERNEL_TOL})")
        check(np.all(np.isfinite(got)), f"{name}: non-finite output")
        check(err <= KERNEL_TOL, f"{name}: {err:.3e} > {KERNEL_TOL}")


def one_chip(cfg, params, seed: int) -> None:
    from repro.runtime.serve import ServingEngine

    check_kernels(cfg, seed)

    # engine A: paged decode, wide fragments, solo prefill
    eng = ServingEngine(params, cfg, **ENGINE_KW)
    ticks = instrument(eng)
    out_a = serve(eng, make_requests(cfg.vocab, seed), "engine A cold",
                  cfg.vocab)
    warm = serve(eng, make_requests(cfg.vocab, seed), "engine A warm",
                 cfg.vocab)
    print(f"engine A: warm run vs cold run: {agreement(out_a, warm)}")
    check_ticks("engine A", ticks,
                {"decode": "paged_attention",
                 "mixed": "paged_chunk_attention_wide",
                 "solo_prefill": "paged_chunk_attention_wide"})
    del eng, ticks
    gc.collect()

    # engine B: speculative decode, narrow verify
    eng = ServingEngine(params, cfg, speculative=True, spec_k=SPEC_K,
                        **ENGINE_KW)
    ticks = instrument(eng)
    out_b = serve(eng, make_requests(cfg.vocab, seed), "engine B", cfg.vocab)
    st = eng.spec_stats()
    print(f"engine B: {st['tokens_per_forward']:.3f} tokens per slot "
          f"forward, draft acceptance {st['acceptance_rate']:.3f}; vs "
          f"engine A: {agreement(out_a, out_b)}")
    check_ticks("engine B", ticks,
                {"spec_chunk": "paged_chunk_attention_narrow"})
    del eng, ticks
    gc.collect()

    # engine C: contiguous slots, jnp attention throughout
    eng = ServingEngine(params, cfg, n_slots=N_SLOTS,
                        max_seq=CONTIGUOUS_MAX_SEQ, chunk=DECODE_CHUNK,
                        eos_id=-1, validate_outputs=True)
    ticks = instrument(eng)
    out_c = serve(eng, make_requests(cfg.vocab, seed), "engine C (jnp)",
                  cfg.vocab)
    check_ticks("engine C", ticks, {})
    print(f"engine A (kernels) vs engine C (jnp): "
          f"{agreement(out_a, out_c)} [information only]")


# ---- phases: four chips --------------------------------------------------

def four_chips(cfg, params, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.runtime.serve import ServingEngine, build_prefill_step
    from repro.runtime.sharding import serve_mesh
    from repro.runtime.supervisor import FleetSupervisor

    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, have "
                             f"{len(devices)}")
    devices = devices[:4]

    # what both are compared with: one chip, jax.devices()[0], built as
    # a fleet replica is (a one-device mesh), so that it runs the same
    # program as each replica does
    one = ServingEngine(params, cfg, mesh=serve_mesh(1, devices=devices[:1]),
                        **ENGINE_KW)
    out_one = serve(one, make_requests(cfg.vocab, seed), "one-chip engine",
                    cfg.vocab)
    # prefill logits are compared on the first layer of the same weights:
    # the random 40-layer model is chaotic (a 1e-6 relative change of its
    # embeddings moves its logits by ~100%), so two forwards that round
    # in different places agree only at shallow depth.  Every layer's
    # weights follow one sharding rule, so one layer checks them all.
    cut = dataclasses.replace(cfg, n_layers=1)
    prefill_cut = build_prefill_step(cut, 256)

    def first_layer(p):
        return dict(p, layers=jax.tree_util.tree_map(lambda x: x[:1],
                                                     p["layers"]))
    rng = np.random.default_rng(seed + 1)
    toks = jnp.asarray(rng.integers(2, cfg.vocab, (2, 256)), jnp.int32)
    logits_one, _ = jax.jit(prefill_cut)(first_layer(params),
                                         {"tokens": toks})

    # tensor-parallel engine: heads and KV over model=4
    tp = ServingEngine(params, cfg, mesh=serve_mesh(4, devices=devices),
                       **ENGINE_KW)
    want = set(devices)
    for path, leaf in jax.tree_util.tree_leaves_with_path(tp.params):
        check(leaf.sharding.device_set == want,
              f"TP param {jax.tree_util.keystr(path)} on "
              f"{len(leaf.sharding.device_set)} devices")
    for name in ("k", "v"):
        leaf = tp.cache[name]
        shard = leaf.sharding.shard_shape(leaf.shape)
        check(leaf.sharding.device_set == want and shard != leaf.shape,
              f"TP cache {name}: {leaf.sharding} is not split over 4 chips")
    print(f"TP engine: params and KV cache on {len(want)} devices; KV "
          f"shard {tp.cache['k'].sharding.shard_shape(tp.cache['k'].shape)}"
          f" of {tp.cache['k'].shape}; model shards {tp.model_shards}")
    logits_tp, _ = jax.jit(build_prefill_step(cut, 256, rules=tp.rules))(
        first_layer(tp.params), {"tokens": toks})
    # the padded vocabulary columns hold -1e30 in both: leave them out
    a = np.asarray(logits_one, np.float32)[:, :cfg.vocab]
    b = np.asarray(logits_tp, np.float32)[:, :cfg.vocab]
    err = float(np.max(np.abs(a - b)) / np.max(np.abs(a)))
    same_top = int(np.sum(a.argmax(-1) == b.argmax(-1)))
    print(f"TP vs one chip, layer-1 prefill logits: max|tp-one|/max|one| = "
          f"{err:.3e} (tolerance {TP_LOGIT_TOL}), argmax equal on "
          f"{same_top}/{a.shape[0]} rows")
    check(err <= TP_LOGIT_TOL, f"TP logits: {err:.3e} > {TP_LOGIT_TOL}")
    out_tp = serve(tp, make_requests(cfg.vocab, seed), "TP engine",
                   cfg.vocab)
    print(f"TP engine vs one chip: {agreement(out_one, out_tp)} "
          f"[information only]")
    del tp
    gc.collect()

    # data-parallel fleet: one one-chip replica per device
    fleet = FleetSupervisor(params, cfg, n_replicas=4, model=1,
                            devices=devices, **ENGINE_KW)
    routed = [[] for _ in fleet.engines]
    for i, e in enumerate(fleet.engines):
        placed = {d for leaf in jax.tree_util.tree_leaves(e.params)
                  for d in leaf.sharding.device_set}
        placed |= e.cache["k"].sharding.device_set
        check(placed == {devices[i]},
              f"fleet replica {i} state on {placed}, not {devices[i]}")

        def admit(req, _i=i, _admit=e.admit):
            ok = _admit(req)
            if ok:
                routed[_i].append(req.rid)
            return ok
        e.admit = admit
    requests = make_requests(cfg.vocab, seed)
    t0 = time.perf_counter()
    done, ticks = fleet.run_to_completion(requests)
    dt = time.perf_counter() - t0
    check_done(done, requests, "fleet", cfg.vocab)
    out_fleet = {r.rid: list(r.out) for r in done}
    n_tok = sum(len(t) for t in out_fleet.values())
    print(f"fleet: 4 replicas on {[str(d) for d in devices]}, requests "
          f"per replica {[len(r) for r in routed]}, {n_tok} tokens in "
          f"{dt:.6f} s over {ticks} summed device ticks")
    # each replica's requests, replayed in its admission order on the
    # one-chip engine: the same program on the same inputs
    reqs = {r.rid: r for r in make_requests(cfg.vocab, seed)}
    for i, rids in enumerate(routed):
        if rids:
            ref = serve(one, [reqs[k] for k in rids],
                        f"one-chip engine, replica {i}'s requests",
                        cfg.vocab)
            for k in rids:
                check(out_fleet[k] == ref[k],
                      f"fleet replica {i}, rid {k}: tokens differ from "
                      f"the one-chip engine")
    print(f"fleet vs one chip: all {N_REQUESTS} requests token-identical "
          f"on each replica's schedule; vs the one-chip run of all "
          f"{N_REQUESTS} at once: {agreement(out_one, out_fleet)}")


def main() -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernels and engines A/B/C on one chip; 4: "
                         "the tensor-parallel engine and the fleet")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, and JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}); nothing was run",
              file=sys.stderr)
        return 2

    from repro.configs import get_arch
    from repro.models import model
    cfg = get_arch("granite-3-2b")
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        model.init(jax.random.PRNGKey(args.seed), cfg, jnp.bfloat16))
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads} x {cfg.head_dim}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab}; {n} bf16 parameters, random "
          f"(seed {args.seed}), made in {time.perf_counter() - t0:.3f} s on "
          f"{dev.device_kind}")
    if args.chips == 4:
        four_chips(cfg, params, args.seed)
    else:
        one_chip(cfg, params, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
