"""The granite-4.0-h block (``archs/granite_hybrid.py``) on the CPU at a
tiny size, 2 Mamba-2 layers and 1 attention layer, with seeded random
weights: the program's chunked prefill and decode through a paged cache
against the float32 reference's full forward pass, compared by logits;
the engine's mixed ticks, preemption and refusals; a tiny hybrid cell
through the harness; and the decode-state roofline reader.

Tolerance.  The program runs in float32 here, so it differs from the
float32 reference only in the order of summation (the scan in chunks of
another length, attention over a paged cache): the logits, of standard
deviation about 1.7, agree within 3e-6 when measured.  ``TOL`` = 1e-4
leaves thirty times that; a state carried wrongly across one fragment
boundary moves them by order 0.1 to 1 (``test_a_lost_carry_is_caught``).
"""
import json
import math
import time
import types
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import cells, correct, driver, harness, weights, \
    work, xplane
from repro.models import model
from repro.runtime.serve import Request, ServingEngine

TINY = Path(__file__).resolve().parent / "tiny_hybrid"
ARCH = cells.load_arch("granite_hybrid")
TOL = 1e-4
SEED = 2**31 + 17


@pytest.fixture(scope="module")
def tiny():
    m = dict(cells.load_json(TINY / "configs" / "tiny-hybrid.json")["model"],
             dtype="float32")
    params = weights.make(ARCH, m, SEED)
    return m, harness.arch_config(m), params


def reference(m, params, tokens):
    return np.asarray(ARCH.logits(m, params, tokens))[:len(tokens)]


def paged_cache(cfg, batch=1, max_seq=64, block=8):
    """A paged cache whose rows own scattered blocks."""
    nb = max_seq // block
    cache = model.init_cache(cfg, batch, max_seq, dtype=jnp.float32,
                             layout=model.PagedLayout(block, batch * nb))
    order = np.random.default_rng(0).permutation(batch * nb)
    cache["block_tables"] = jnp.asarray(order.reshape(batch, nb), jnp.int32)
    return cache


def prefill_in_fragments(params, cfg, cache, tokens, width):
    """All-position logits of ``tokens`` fed ``width`` at a time."""
    out = []
    for i in range(0, len(tokens), width):
        frag = tokens[i:i + width]
        row = np.zeros((1, width), np.int32)
        row[0, :len(frag)] = frag
        logits, cache = model.prefill_chunk(
            params, jnp.asarray(row), jnp.asarray([len(frag)]), cache, cfg,
            all_logits=True)
        out.append(np.asarray(logits)[0, :len(frag), :cfg.vocab])
    return np.concatenate(out), cache


def prompt(n, seed=3):
    return np.random.default_rng(seed).integers(2, 256, n).astype(np.int32)


@pytest.mark.parametrize("width", [4, 16])
def test_chunked_prefill_then_decode_matches_reference(tiny, width):
    """Prefill in fragments through a paged cache, then decode through
    it: each position's logits are the reference's full forward pass
    over the same tokens."""
    m, cfg, params = tiny
    tokens = prompt(30)
    got, cache = prefill_in_fragments(params, cfg, paged_cache(cfg),
                                      tokens, width)
    seq, rows = list(tokens), [got]
    for _ in range(6):
        seq.append(int(np.argmax(rows[-1][-1])))
        logits, cache = model.decode_step(params, jnp.asarray(seq[-1:]),
                                          cache, cfg)
        rows.append(np.asarray(logits)[:, :cfg.vocab])
    ref = reference(m, params, np.asarray(seq, np.int32))
    np.testing.assert_allclose(np.concatenate(rows), ref, rtol=0, atol=TOL)


@pytest.mark.parametrize("width", [1, 2, 3])
def test_fragment_boundary_inside_the_conv_window(tiny, width):
    """Fragments no wider than the conv window's history (3 inputs): the
    window a fragment starts from spans earlier fragments."""
    m, cfg, params = tiny
    tokens = prompt(17, seed=width)
    got, _ = prefill_in_fragments(params, cfg, paged_cache(cfg), tokens,
                                  width)
    np.testing.assert_allclose(got, reference(m, params, tokens), rtol=0,
                               atol=TOL)


@pytest.mark.parametrize("width", [5, 13])
def test_fragment_length_not_dividing_the_ssd_chunk(tiny, width):
    """The SSD chunk is 8 positions: a fragment of 5 is one short chunk,
    one of 13 a chunk and a padded part."""
    m, cfg, params = tiny
    assert cfg.ssm_chunk == 8
    tokens = prompt(29, seed=width)
    got, _ = prefill_in_fragments(params, cfg, paged_cache(cfg), tokens,
                                  width)
    np.testing.assert_allclose(got, reference(m, params, tokens), rtol=0,
                               atol=TOL)


def test_a_lost_carry_is_caught(tiny):
    """The tolerance tells a right carry from a lost one: zeroing the
    recurrent state between two fragments moves the second's logits by
    far more than ``TOL``."""
    m, cfg, params = tiny
    tokens = prompt(16)
    cache = paged_cache(cfg)
    _, cache = prefill_in_fragments(params, cfg, cache, tokens[:8], 8)
    cache = dict(cache, state=jnp.zeros_like(cache["state"]),
                 conv=jnp.zeros_like(cache["conv"]))
    got, _ = prefill_in_fragments(params, cfg, cache, tokens[8:], 8)
    err = np.abs(got - reference(m, params, tokens)[8:]).max()
    assert err > 100 * TOL


def test_decode_rows_beside_a_fragment_in_one_mixed_tick(tiny):
    """One call: row 0 consumes a 7-token fragment, rows 1 and 2 decode
    one token each, row 3 has nothing.  Each live row's logits are the
    reference's at its position; row 3's state and position are left
    exactly as they were."""
    m, cfg, params = tiny
    width = 8
    prompts = [prompt(n, seed=10 + i) for i, n in enumerate((9, 12, 5, 6))]
    cache = paged_cache(cfg, batch=4)
    first = np.zeros((4, 16), np.int32)
    for i, p in enumerate(prompts):
        first[i, :len(p)] = p
    logits, cache = model.prefill_chunk(
        params, jnp.asarray(first), jnp.asarray([len(p) for p in prompts]),
        cache, cfg)
    nxt = np.argmax(np.asarray(logits)[:, :cfg.vocab], axis=-1)
    before = {k: np.asarray(cache[k])[:, 3] for k in ("conv", "state")}
    tick = np.zeros((4, width), np.int32)
    frag = prompt(7, seed=20)
    tick[0, :7] = frag
    tick[1, 0], tick[2, 0] = nxt[1], nxt[2]
    logits, cache = model.prefill_chunk(
        params, jnp.asarray(tick), jnp.asarray([7, 1, 1, 0]), cache, cfg)
    logits = np.asarray(logits)[:, :cfg.vocab]
    seqs = [np.concatenate([prompts[0], frag]),
            np.append(prompts[1], nxt[1]), np.append(prompts[2], nxt[2])]
    for row, seq in enumerate(seqs):
        np.testing.assert_allclose(logits[row],
                                   reference(m, params, seq)[-1], rtol=0,
                                   atol=TOL)
    for k, v in before.items():
        np.testing.assert_array_equal(np.asarray(cache[k])[:, 3], v)
    assert np.asarray(cache["pos"]).tolist() == [16, 13, 6, 6]


def test_decode_step_holds_the_state_of_rows_it_does_not_advance(tiny):
    """A decode step told to advance row 0 only: row 0's logits are the
    reference's, row 1's recurrent state is left exactly as it was (a
    row stalled by the block pool retries the same token)."""
    m, cfg, params = tiny
    prompts = [prompt(9, seed=30), prompt(6, seed=31)]
    cache = paged_cache(cfg, batch=2)
    first = np.zeros((2, 16), np.int32)
    for i, p in enumerate(prompts):
        first[i, :len(p)] = p
    logits, cache = model.prefill_chunk(
        params, jnp.asarray(first), jnp.asarray([9, 6]), cache, cfg)
    nxt = np.argmax(np.asarray(logits)[:, :cfg.vocab], axis=-1)
    before = {k: np.asarray(cache[k])[:, 1] for k in ("conv", "state")}
    logits, cache = model.decode_step(params, jnp.asarray(nxt, jnp.int32),
                                      cache, cfg,
                                      advance=jnp.asarray([True, False]))
    np.testing.assert_allclose(
        np.asarray(logits)[0, :cfg.vocab],
        reference(m, params, np.append(prompts[0], nxt[0]))[-1], rtol=0,
        atol=TOL)
    for k, v in before.items():
        np.testing.assert_array_equal(np.asarray(cache[k])[:, 1], v)


def serve(params, cfg, requests, preempt_at=(), **kw):
    eng = ServingEngine(params, cfg, **{
        **dict(n_slots=3, max_seq=64, chunk=4, eos_id=-1, paged=True,
               block_size=8, n_blocks=24, prefix_sharing=False,
               chunked_prefill=True, prefill_chunk_tokens=5), **kw})
    pending, done, steps = list(requests), [], 0
    while pending or eng.active or eng._parked:
        del pending[:eng.admit_many(pending)]
        done += eng.step()
        steps += 1
        if steps in preempt_at:
            assert eng.preempt() is not None
        assert steps < 500
    return eng, sorted(done, key=lambda r: r.rid)


def requests():
    rng = np.random.default_rng(4)
    return [Request(i, rng.integers(2, 256, int(rng.integers(8, 20)))
                    .astype(np.int32), max_new=int(rng.integers(6, 12)))
            for i in range(5)]


def test_preempt_and_resume_matches_an_uninterrupted_run(tiny):
    """Slots preempted mid-decode resume by replaying their history
    through chunked prefill from zeroed state: every served token is
    the reference's choice (gap within ``TOL``), as in a run left
    alone, and the replays reproduce the tokens already delivered."""
    m, cfg, params = tiny
    plain, alone = serve(params, cfg, requests())
    eng, done = serve(params, cfg, requests(), preempt_at=(4, 9, 14))
    assert eng.resumes >= 3 and eng.preempt_replay_mismatches == 0
    assert [len(r.out) for r in done] == [len(r.out) for r in alone]
    for served in (alone, done):
        reading = correct.gaps(ARCH, m, params, served)["program"]
        assert reading["widest"] <= TOL
    assert eng.state_resets == len(done) + eng.resumes
    assert eng.pool.used == 0 and plain.pool.used == 0


def test_overcommit_stalls_and_evictions_keep_the_reference(tiny):
    """Over-commit on a pool too small for every chain to grow: decode
    rows stall and slots are evicted and replayed, and every served
    token is still the reference's choice."""
    m, cfg, params = tiny
    eng, done = serve(params, cfg, requests(), overcommit=True, n_blocks=7)
    assert eng.stalls > 0 and eng.preemptions > 0
    assert eng.preempt_replay_mismatches == 0 and len(done) == 5
    assert correct.gaps(ARCH, m, params, done)["program"]["widest"] <= TOL


@pytest.mark.parametrize("option,why", [
    ({"prefix_sharing": True}, "state snapshots"),
    ({"speculative": True}, "rewind"),
    ({"chunked_prefill": False}, "chunked_prefill=True"),
])
def test_prefix_sharing_speculation_and_static_prefill_raise(tiny, option,
                                                              why):
    _, cfg, params = tiny
    kw = dict(n_slots=2, max_seq=64, paged=True, block_size=8, n_blocks=16,
              prefix_sharing=False, chunked_prefill=True)
    with pytest.raises(ValueError, match=why):
        ServingEngine(params, cfg, **{**kw, **option})


@pytest.fixture
def no_compile_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_tiny_hybrid_cell_is_correct_and_the_control_is_not(
        no_compile_cache):
    """``tiny-hybrid.closed`` served through ``harness.run`` on the CPU:
    the bfloat16 program's mean gap is under the limit, the float8
    control's over it."""
    r = harness.run("tiny-hybrid.closed", 2**31 + 5, 2.0, False,
                    t0=time.perf_counter(), require_tpu=False,
                    control=True, root=TINY, here=TINY)
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["checks"]["tokens_checked"]["value"] >= 256
    c = r["control"]
    assert c["correct"] is False
    assert c["checks"]["mean_gap"]["value"] > c["checks"]["mean_gap"]["limit"]


def fake_run(arch, m, decoding, device_ns):
    ev = xplane.Event
    trace = xplane.Trace(
        window=(0, 10**10), ops={},
        modules={"TPU:0": [ev("jit_chunk_fn_paged(3)", 1000,
                              1000 + device_ns)]},
        spans=[ev("bench.step", 500, 10**9, {"i": 0})])
    return types.SimpleNamespace(
        trace=trace, m=m, peak={"hbm_bytes_per_s": 819e9},
        cell=types.SimpleNamespace(arch=arch,
                                   config={"deployment": {"n_slots": 16}}),
        window=types.SimpleNamespace(
            ticks=[driver.TickCall(0, "decode", decoding),
                   driver.TickCall(0, "mixed", decoding)]))


def test_state_decode_roofline_reads_the_decode_ticks():
    """The floor of a decode chunk of 8 steps: the weights once a step,
    every slot's state read and written once a step, each decoding
    row's live keys and values; over the decode program's device time.
    A block with no recurrent state reads nothing."""
    read = cells.load_reader("state_decode_roofline")
    m = cells.load_json(cells.ROOT / "benchmarks/chip/configs/"
                        "granite-4.0-h-micro.json")["model"]
    decoding = [(1500, 3, 8)] * 10
    weights_b = 2 * sum(math.prod(s) for s, _ in ARCH.shapes(m).values())
    step_b = weights_b + 2 * 16 * ARCH.state_bytes_per_slot(m)
    kv = 10 * ARCH.kv_bytes_per_position(m) * work.decode_contexts(1500, 3,
                                                                   8)
    need = (8 * step_b + kv) / 819e9
    got = read(fake_run(ARCH, m, decoding, 200_000_000))
    assert got == pytest.approx(100 * need / 0.2, rel=1e-9)
    assert ARCH.state_bytes_per_slot(m) == 36 * (64 * 64 * 128 * 4
                                                 + 3 * 4352 * 2)
    dense = cells.load_arch("dense")
    dm = json.loads((cells.HERE / "configs" / "granite-3-2b.json")
                    .read_text())["model"]
    assert read(fake_run(dense, dm, decoding, 200_000_000)) is None
