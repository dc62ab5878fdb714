"""The dense block's float32 reference (``archs/dense.py``) against the
program's own full-sequence forward (``repro.models.model.forward``) at
a reduced size, on the benchmark's weights: the two are written apart
and must agree to float32 rounding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import cells, weights

DENSE = cells.load_arch("dense")

SMALL = {"name": "small", "family": "dense", "n_layers": 3, "d_model": 64,
         "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 96,
         "vocab": 200, "rope_theta": 10000.0, "norm_eps": 1e-5,
         "dtype": "float32"}


def program_logits(m, params, tokens):
    from benchmarks.chip.harness import arch_config
    from repro.models import layers, model
    cfg = arch_config(m)
    h, _ = model.forward(params, {"tokens": jnp.asarray(tokens)[None]}, cfg)
    return np.asarray(layers.unembed_logits(h[0], params["unembed"]))[
        :, :m["vocab"]]


@pytest.mark.parametrize("act,theta", [("silu", 10000.0),
                                       ("gelu", 999999.0)])
def test_reference_matches_the_program_forward(act, theta):
    m = dict(SMALL, act=act, rope_theta=theta)
    params = weights.make(DENSE, m, seed=2**33 + 5)
    tokens = np.random.default_rng(0).integers(2, m["vocab"], 700)
    want = program_logits(m, params, tokens)
    got = np.asarray(DENSE.logits(m, params, tokens))
    assert got.shape == (1024, m["vocab"])      # padded to the bucket
    scale = np.abs(want).max()
    np.testing.assert_allclose(got[:700], want, atol=1e-4 * scale)


def test_reference_is_causal():
    """A later token never changes an earlier position's logits, and the
    padding after the sequence changes none."""
    m = dict(SMALL, act="silu")
    params = weights.make(DENSE, m, seed=1)
    tokens = np.random.default_rng(1).integers(2, m["vocab"], 600)
    a = np.asarray(DENSE.logits(m, params, tokens))
    b = np.asarray(DENSE.logits(m, params, tokens[:300]))
    np.testing.assert_allclose(a[:300], b[:300], rtol=1e-5, atol=1e-5)


def test_control_rounds_to_float8():
    m = dict(SMALL, act="silu")
    params = weights.make(DENSE, m, seed=1)
    tokens = np.random.default_rng(2).integers(2, m["vocab"], 100)
    f32 = np.asarray(DENSE.logits(m, params, tokens))[:100]
    fp8 = np.asarray(DENSE.logits(m, params, tokens, "fp8"))[:100]
    err = np.abs(fp8 - f32).max() / np.abs(f32).max()
    assert 1e-3 < err < 0.5
    with pytest.raises(ValueError):
        DENSE.logits(m, params, tokens, "int4")


def test_weights_follow_the_seed():
    m = dict(SMALL, act="silu", dtype="bfloat16")
    a = weights.make(DENSE, m, seed=2**40 + 1)
    b = weights.make(DENSE, m, seed=2**40 + 1)
    c = weights.make(DENSE, m, seed=1)
    leaves = jax.tree_util.tree_leaves
    assert all(x.dtype == jnp.bfloat16 for x in leaves(a))
    assert all(bool((x == y).all()) for x, y in zip(leaves(a), leaves(b)))
    assert not bool((a["layers"]["wq"] == c["layers"]["wq"]).all())
    assert a["embed"]["tok"].shape == (weights.padded_vocab(200), 64)
