"""Head-sharded kernel entries vs their unsharded twins — bit-exact.

GSPMD cannot partition a ``pallas_call``; under a head-sharded serving
mesh the kernels run per-shard on their local head slice via
``shard_map`` (kernels/*/ops.py ``*_sharded``).  Heads never mix in
attention, so each shard executes literally the same program the
unsharded kernel runs on that head slice — the outputs must match to
the bit, and the width-picks-the-schedule dispatch must be unchanged
(the fragment axis is unsharded).  The paged decode kernel contracts a
page group's rows of all the KV heads it is given at once, so its
whole-width call sums in another order than a shard's: its twin is held
to the unsharded kernel run on each shard's head slice.  Cells skip on
a single-device host; CI runs them under the forced multi-device
step.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >= 2 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count)")

B, H, HKV, D, SMAX = 3, 4, 2, 32, 64
BS = 8
NB = SMAX // BS
N_PAGES = 32


@pytest.fixture(scope="module")
def mesh():
    from repro.runtime.sharding import serve_mesh
    return serve_mesh(2)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    kc = jnp.asarray(rng.normal(size=(B, SMAX, HKV, D)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(B, SMAX, HKV, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(N_PAGES, BS, HKV, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N_PAGES, BS, HKV, D)), jnp.float32)
    bt = jnp.asarray(rng.permutation(N_PAGES)[:B * NB].reshape(B, NB),
                     jnp.int32)
    return rng, kc, vc, kp, vp, bt


def _q(rng, width):
    q = jnp.asarray(rng.normal(size=(B, width, H, D)), jnp.float32)
    q_pos = jnp.asarray(rng.integers(width, SMAX - 1, size=(B, width)),
                        jnp.int32)
    return q, q_pos


@pytest.mark.parametrize("width", [4, 16], ids=["narrow", "wide"])
def test_chunk_attention_sharded_bit_exact(mesh, data, width):
    from repro.kernels.chunk_attention import (
        chunk_attention_kernel, chunk_attention_kernel_sharded)
    rng, kc, vc, *_ = data
    q, q_pos = _q(rng, width)
    ref = chunk_attention_kernel(q, kc, vc, q_pos)
    out = chunk_attention_kernel_sharded(q, kc, vc, q_pos, mesh=mesh)
    assert jnp.array_equal(ref, out)


@pytest.mark.parametrize("width", [4, 16], ids=["narrow", "wide"])
def test_paged_chunk_attention_sharded_bit_exact(mesh, data, width):
    from repro.kernels.chunk_attention import (
        paged_chunk_attention_kernel, paged_chunk_attention_kernel_sharded)
    rng, _, _, kp, vp, bt = data
    q, q_pos = _q(rng, width)
    ref = paged_chunk_attention_kernel(q, kp, vp, bt, q_pos)
    out = paged_chunk_attention_kernel_sharded(q, kp, vp, bt, q_pos,
                                               mesh=mesh)
    assert jnp.array_equal(ref, out)


def test_paged_attention_sharded_bit_exact(mesh, data):
    from repro.kernels.paged_attention import (
        paged_attention, paged_attention_sharded)
    rng, _, _, kp, vp, bt = data
    q, _ = _q(rng, 1)
    q1 = q[:, 0]
    lengths = jnp.asarray(rng.integers(4, SMAX, size=(B,)), jnp.int32)
    n = mesh.shape["model"]
    h, hkv = H // n, HKV // n
    ref = jnp.concatenate(
        [paged_attention(q1[:, i * h:(i + 1) * h],
                         kp[:, :, i * hkv:(i + 1) * hkv],
                         vp[:, :, i * hkv:(i + 1) * hkv], bt, lengths)
         for i in range(n)], axis=1)
    out = paged_attention_sharded(q1, kp, vp, bt, lengths, mesh=mesh)
    assert jnp.array_equal(ref, out)


def test_dispatcher_routes_sharded_under_rules(mesh, data):
    """`models/attention.py` picks the sharded entry exactly when the
    active rules' model axis divides both head counts; non-divisible
    head counts fall back to the unsharded kernel (the sharding-rules
    divisibility discipline)."""
    from repro.models import attention as attn
    from repro.runtime.sharding import ShardingRules, use_rules
    rng, kc, vc, kp, vp, bt = data
    q, q_pos = _q(rng, 4)
    want = attn.chunk_attention(q, kc, vc, q_pos, use_kernel=True)
    want_p = attn.paged_chunk_attention(q, kp, vp, bt, q_pos,
                                        use_kernel=True)
    with use_rules(ShardingRules(mesh)):
        assert attn._head_shard_mesh(H, HKV) is mesh
        assert attn._head_shard_mesh(6, 3) is None      # 2 divides neither
        got = attn.chunk_attention(q, kc, vc, q_pos, use_kernel=True)
        got_p = attn.paged_chunk_attention(q, kp, vp, bt, q_pos,
                                           use_kernel=True)
    assert jnp.array_equal(want, got)
    assert jnp.array_equal(want_p, got_p)
