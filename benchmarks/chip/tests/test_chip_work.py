"""The FLOP and byte counters count the work the traffic requires, not
the work a kernel or a padding does: the same requests served with a
block table of 8 or of 16 blocks per slot count the same bytes."""
import types

import numpy as np
import pytest

from benchmarks.chip import cells, driver, stats, traffic, weights, work
from benchmarks.chip.harness import arch_config

DENSE = cells.load_arch("dense")

TINY = {"name": "tiny", "family": "dense", "n_layers": 2, "d_model": 64,
        "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
        "vocab": 256, "act": "silu", "rope_theta": 10000.0,
        "norm_eps": 1e-5, "dtype": "float32"}
MIX = {"loop": "closed", "clients": 3, "requests": 12, "deal": 9,
       "prompt": {"median": 20, "sigma": 0.5, "min": 8, "max": 40},
       "output": {"median": 10, "sigma": 0.5, "min": 4, "max": 24}}


class Clock:
    """Stands still while the engine works: the run's ticks then depend
    on the requests alone, not on how fast this machine is."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


def decode_work(max_seq):
    from repro.runtime.serve import Request, ServingEngine
    params = weights.make(DENSE, TINY, seed=3)
    eng = ServingEngine(params, arch_config(TINY), n_slots=3,
                        max_seq=max_seq, paged=True, block_size=16,
                        n_blocks=24, chunked_prefill=True, eos_id=-1)
    log = driver.TickLog()
    log.instrument(eng)
    specs = traffic.make_requests(MIX, seed=9, seconds=1, vocab=256)
    clock = Clock()
    w = driver.run_window(
        driver.Frontier(eng), specs, MIX, seconds=1.0, drain_s=1.0,
        make_request=lambda s: Request(rid=s.rid, prompt=s.prompt,
                                       max_new=s.max_new),
        log=log, clock=clock, sleep=clock.sleep)
    assert all(r.done is not None for r in w.recs)
    decoding = [d for call in w.ticks if call.family == "decode"
                for d in call.decoding]
    return (eng.cache["block_tables"].shape[1], decoding,
            [len(r.req.out) for r in w.recs])


def summed(m, decoding):
    """Contexts, FLOPs and bytes of the decode steps ``decoding``."""
    got = [work.decode_attention_work(DENSE, m, *d) for d in decoding]
    return (sum(work.decode_contexts(*d) for d in decoding),
            sum(f for f, _ in got), sum(b for _, b in got))


def test_same_requests_same_work_whatever_the_table_length():
    nb_a, dec_a, out_a = decode_work(max_seq=128)
    nb_b, dec_b, out_b = decode_work(max_seq=256)
    assert (nb_a, nb_b) == (8, 16)
    assert out_a == out_b
    m = types.MappingProxyType(TINY)
    ctx_a, flops_a, bytes_a = summed(m, dec_a)
    assert (ctx_a, flops_a, bytes_a) == summed(m, dec_b)
    assert ctx_a > 0
    # every cached key and value of every step read once
    assert bytes_a == DENSE.kv_bytes_per_position(m) * ctx_a
    assert flops_a == DENSE.attention_flops(m, 1) * ctx_a


def test_decode_contexts():
    # prompt 10, one token out: the step feeds it at position 10 and
    # attends over 11 positions, then 12, then 13
    assert work.decode_contexts(10, 1, 3) == 11 + 12 + 13
    assert work.decode_contexts(10, 5, 0) == 0


def test_counts_from_the_configuration():
    m = dict(TINY)
    d, f, L, v = 64, 128, 2, 256
    per_layer = 2 * d * 4 * 16 + 2 * d * 2 * 16 + 3 * d * f
    assert DENSE.matmul_params(m) == L * per_layer + d * v
    assert DENSE.kv_bytes_per_position(m) == L * 2 * 2 * 16 * 2
    # a prompt's FLOPs are the sum of its tokens' over growing contexts
    assert work.prompt_flops(DENSE, m, 7) == pytest.approx(
        sum(work.token_flops(DENSE, m, p + 1) for p in range(7)))
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert work.roofline_s(4e12, 1e9, peak) == 4.0
    assert work.roofline_s(1e12, 3e9, peak) == 3.0
    np.testing.assert_allclose(DENSE.attention_flops(m, 5),
                               4 * L * 4 * 16 * 5)


class Windowed:
    """An architecture module whose attention reads at most 4 positions."""

    @staticmethod
    def matmul_params(m):
        return 10

    @staticmethod
    def attention_flops(m, context):
        return 1.0 * min(context, 4)

    @staticmethod
    def kv_bytes_per_position(m):
        return 3


def test_sums_come_from_the_cells_module():
    # prompt of 6: 2 * 10 * 6, and contexts 1, 2, 3, 4, 4, 4
    assert work.prompt_flops(Windowed, {}, 6) == 120 + 18
    # a request with 3 tokens in the window: its prompt, then tokens 1-2
    # over contexts 7 and 8; a request with none counts nothing
    recs = [types.SimpleNamespace(n_in_window=3, prompt_len=6),
            types.SimpleNamespace(n_in_window=0, prompt_len=2)]
    run = types.SimpleNamespace(
        cell=types.SimpleNamespace(arch=Windowed), m={},
        window=types.SimpleNamespace(recs=recs))
    assert stats.window_flops(run) == 138 + 2 * 10 * 2 + 4 + 4
    # decode from 7 positions, 2 steps: contexts 7 and 8 read 4 each, and
    # 7 + 8 cached positions of 3 bytes
    assert work.decode_attention_work(Windowed, {}, 6, 1, 2) == \
        (4 + 4, 3 * (7 + 8))
