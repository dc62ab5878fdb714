"""Pins what the dense block gives the benchmark: the weights, the
reference logits and their float8 control, the mean-gap reading and the
work counts.  The values were recorded before the block moved into an
architecture module of its own (``fixtures/dense_logits.npz``: the
first 45 rows of both logits); any change to them moves what the
benchmark reads.

The weights are elementwise and the counts integers: both are compared
exactly.  A matrix product's float32 sums run in an order that differs
from one CPU to another, so the logits are compared to that rounding:
the float32 logits within 1e-5 of their largest magnitude, the float8
control, where such a difference can tip a value to the next float8
step, by its mean difference (a control that did not round would read
about 1e-2 of the largest magnitude).
"""
import hashlib
from pathlib import Path

import jax
import numpy as np
import pytest

from benchmarks.chip import cells, correct, weights, work

TINY = cells.load_json(cells.HERE / "tests/tiny/configs/tiny.json")["model"]
GRANITE = cells.load_json(cells.HERE / "configs/granite-3-2b.json")["model"]
DENSE = cells.load_arch("dense")
TOKENS = (np.arange(45) * 37 + 5) % 254 + 2
RECORDED = np.load(Path(__file__).resolve().parent / "fixtures"
                   / "dense_logits.npz")


class Served:
    """A request as the engine leaves it: its prompt and served tokens."""

    prompt = TOKENS[:30].astype(np.int32)
    out = [int(t) for t in TOKENS[30:]]


def digest(arrays) -> str:
    h = hashlib.sha256()
    for key, a in arrays:
        h.update(key.encode())
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()


def test_weights_are_pinned():
    params = weights.make(DENSE, TINY, 0)
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    got = digest(sorted((jax.tree_util.keystr(p), a) for p, a in leaves))
    assert got == ("9f87204f2873d913a9ddb5835ea0b5ca"
                   "52df5b14c9cc687994e28d3034846313")


def test_reference_and_control_logits_are_pinned():
    params = weights.make(DENSE, TINY, 0)
    f32 = np.asarray(DENSE.logits(TINY, params, TOKENS))
    fp8 = np.asarray(DENSE.logits(TINY, params, TOKENS, "fp8"))
    assert f32.shape == fp8.shape == (512, 256)
    scale = np.abs(RECORDED["float32"]).max()
    np.testing.assert_allclose(f32[:45], RECORDED["float32"], rtol=0,
                               atol=1e-5 * scale)
    assert np.abs(fp8[:45] - RECORDED["fp8"]).mean() < 1e-4 * scale


def test_gap_readings_are_pinned():
    params = weights.make(DENSE, TINY, 0)
    got = correct.gaps(DENSE, TINY, params, [Served()])["program"]
    assert got == {"mean": pytest.approx(4.586214542388916, abs=1e-4),
                   "widest": pytest.approx(9.228322982788086, abs=1e-4),
                   "tokens": 15}


def test_work_counts_are_pinned():
    assert DENSE.matmul_params(GRANITE) == 2533365760
    assert DENSE.kv_bytes_per_position(GRANITE) == 81920
    assert DENSE.attention_flops(GRANITE, 1) == 327680.0
    assert work.prompt_flops(DENSE, GRANITE, 1536) == 8169298329600.0
    assert work.token_flops(DENSE, GRANITE, 1000) == 5394411520.0
    assert work.decode_attention_work(DENSE, GRANITE, 192, 5, 8) == \
        (525598720.0, 131399680)
