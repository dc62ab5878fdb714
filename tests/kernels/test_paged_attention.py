"""Paged-attention kernel vs its oracles (interpret mode).

Three-way agreement: the Pallas kernel (scalar-prefetched block tables,
online softmax) == the pure-jnp ref.py gather == the model path
(`models/attention.paged_decode_attention`, which itself must match
contiguous `decode_attention` bit-for-bit on the same chains).  The
kernel walks each row's chain in groups of pages: lengths at and around
a group's edge, across groups and at the full table are checked, and a
run under TPU interpret mode with NaN-filled memory and out-of-bounds
reads raising shows that nothing past a chain is read.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax._src.pallas.mosaic.interpret import (
    interpret_pallas_call as mosaic_interpret)
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention import paged_attention, paged_attention_ref
from repro.kernels.paged_attention.kernel import (
    GROUP_VMEM_BYTES, page_vmem_bytes, paged_attention_call, pages_per_step)


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


def _chains(rng, b, n_pages, nb, bs, lengths):
    """Random disjoint chains covering each row's length."""
    tables = np.full((b, nb), -1, np.int32)
    perm = rng.permutation(n_pages)
    i = 0
    for r in range(b):
        for j in range(-(-int(lengths[r]) // bs)):
            tables[r, j] = perm[i]
            i += 1
    return jnp.asarray(tables)


@pytest.mark.parametrize("b,h,hkv,d,n_pages,bs,nb", [
    (1, 2, 2, 32, 8, 8, 4),       # MHA
    (3, 4, 2, 32, 16, 8, 4),      # GQA 2:1
    (2, 8, 2, 64, 12, 16, 3),     # GQA 4:1
    (2, 2, 1, 64, 10, 8, 4),      # MQA
    (2, 32, 8, 64, 40, 16, 20),   # granite-3-2b widths, 8-page groups
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_kernel_vs_ref(b, h, hkv, d, n_pages, bs, nb, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(b * h + d), 3)
    q = _rand(k1, (b, h, d), dtype)
    kp = _rand(k2, (n_pages, bs, hkv, d), dtype)
    vp = _rand(k3, (n_pages, bs, hkv, d), dtype)
    rng = np.random.default_rng(b + nb)
    lengths = jnp.asarray(rng.integers(1, nb * bs + 1, size=b), jnp.int32)
    tables = _chains(rng, b, n_pages, nb, bs, lengths)
    got = paged_attention(q, kp, vp, tables, lengths)
    want = paged_attention_ref(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.array(got, np.float32),
                               np.array(want, np.float32), **_tol(dtype))


def _group_edges(h, hkv, d, bs, nb, dtype):
    """Lengths at 1, at a page group's edge and one either side, across
    several groups, and at the full table."""
    page = page_vmem_bytes(bs, h, hkv, d, jnp.dtype(dtype).itemsize)
    span = pages_per_step(bs, nb, page) * bs
    assert 2 * span < nb * bs
    return [1, span - 1, span, span + 1, 2 * span + bs // 2, nb * bs]


@pytest.mark.parametrize("h,hkv,d,bs,nb,lengths,dtype", [
    # groups of 16 pages of 8
    (4, 2, 32, 8, 40, _group_edges(4, 2, 32, 8, 40, jnp.float32),
     jnp.float32),
    # granite-3-2b widths: groups of 8 pages of 16
    (32, 8, 64, 16, 20, _group_edges(32, 8, 64, 16, 20, jnp.bfloat16),
     jnp.bfloat16),
    # a row of length 0 between rows that fill the table
    (4, 2, 32, 8, 20, [160, 0, 160], jnp.float32),
], ids=["group-edges", "group-edges-granite", "empty-beside-full"])
def test_paged_attention_lengths_vs_ref(h, hkv, d, bs, nb, lengths, dtype):
    """Each row matches the ref, and a row of length 0 reads out
    zeros."""
    b = len(lengths)
    n_pages = sum(-(-n // bs) for n in lengths)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(h + bs), 3)
    q = _rand(k1, (b, h, d), dtype)
    kp = _rand(k2, (n_pages, bs, hkv, d), dtype)
    vp = _rand(k3, (n_pages, bs, hkv, d), dtype)
    lengths = jnp.asarray(lengths, jnp.int32)
    tables = _chains(np.random.default_rng(nb), b, n_pages, nb, bs, lengths)
    got = paged_attention(q, kp, vp, tables, lengths)
    want = jnp.where(lengths[:, None, None] > 0,
                     paged_attention_ref(q, kp, vp, tables, lengths), 0)
    np.testing.assert_allclose(np.array(got, np.float32),
                               np.array(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("bs,nb,widths,want", [
    (16, 256, (32, 8, 64), 8),       # granite-3-2b: 128 positions
    (16, 256, (16, 16, 128), 8),     # moonshot-v1-16b-a3b
    (8, 256, (32, 8, 64), 16),
    (16, 4, (32, 8, 64), 4),         # the table is narrower
    (16, 256, (64, 32, 256), 2),     # the VMEM budget binds
])
def test_pages_per_step_fits_positions_table_and_vmem(bs, nb, widths, want):
    page = page_vmem_bytes(bs, *widths, 2)
    g = pages_per_step(bs, nb, page)
    assert g == want
    assert g * page <= GROUP_VMEM_BYTES or g == 1


def test_paged_attention_reads_nothing_past_the_chain():
    """Under TPU interpret mode with uninitialised memory NaN, races
    detected and out-of-bounds reads raising: every page no live table
    entry names holds NaN, and past each chain the table names a page
    beyond the pool.  A kernel that read one such entry would raise or
    carry NaN into its output; the output is finite, matches the ref on
    the same pages, and no race is reported.  The lengths end mid-group
    and mid-page, so the last group's unused page slots are exercised,
    and each last page holds NaN past the length, which ``p · v`` must
    not carry."""
    b, h, hkv, d, bs, nb = 3, 4, 2, 32, 8, 40
    lengths = np.asarray([5, 130, 203], np.int32)
    n_pages = 64
    live = [-(-int(n) // bs) for n in lengths]
    rng = np.random.default_rng(9)
    perm = rng.permutation(n_pages)
    tables = np.full((b, nb), n_pages + 100, np.int32)
    i = 0
    for r in range(b):
        tables[r, :live[r]] = perm[i:i + live[r]]
        i += live[r]
    dead = np.ones((n_pages, bs), bool)          # (page, offset) unread
    for r in range(b):
        for j in range(live[r]):
            dead[tables[r, j], :max(0, min(bs, lengths[r] - j * bs))] = False
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    q = _rand(k1, (b, h, d), jnp.float32)
    kp = _rand(k2, (n_pages, bs, hkv, d), jnp.float32)
    vp = _rand(k3, (n_pages, bs, hkv, d), jnp.float32)
    nan = jnp.asarray(dead)[:, :, None, None]
    want = paged_attention_ref(q, kp, vp, jnp.asarray(tables),
                               jnp.asarray(lengths))
    mosaic_interpret.reset_tpu_interpret_mode_state()
    got = paged_attention_call(
        q, jnp.where(nan, jnp.nan, kp), jnp.where(nan, jnp.nan, vp),
        jnp.asarray(tables), jnp.asarray(lengths),
        interpret=pltpu.InterpretParams(uninitialized_memory="nan",
                                        detect_races=True,
                                        out_of_bounds_reads="raise"))
    got = np.asarray(got)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, np.asarray(want), **_tol(jnp.float32))
    assert not mosaic_interpret.races.races_found


def test_paged_matches_contiguous_decode_attention():
    """Gathering the chain == attending the contiguous cache: the ref
    (and the kernel) must agree with `models/attention.decode_attention`
    on the same logical sequence."""
    from repro.models import attention as A
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    b, h, hkv, d, bs, nb = 3, 4, 2, 32, 8, 4
    max_seq = bs * nb
    q = jax.random.normal(k1, (b, 1, h, d), jnp.float32)
    k_cont = jax.random.normal(k2, (b, max_seq, hkv, d), jnp.float32)
    v_cont = jax.random.normal(k3, (b, max_seq, hkv, d), jnp.float32)
    lengths = jnp.asarray([5, 17, 32], jnp.int32)
    # scatter the contiguous rows into shuffled pages
    rng = np.random.default_rng(7)
    tables = _chains(rng, b, b * nb, nb, bs, [max_seq] * b)
    kp = jnp.zeros((b * nb, bs, hkv, d), jnp.float32)
    vp = jnp.zeros((b * nb, bs, hkv, d), jnp.float32)
    for r in range(b):
        for j in range(nb):
            blk = int(tables[r, j])
            kp = kp.at[blk].set(k_cont[r, j * bs:(j + 1) * bs])
            vp = vp.at[blk].set(v_cont[r, j * bs:(j + 1) * bs])
    want = A.decode_attention(q, k_cont, v_cont, lengths)
    # model path (pure jnp): bit-exact vs contiguous
    got_model = A.paged_decode_attention(q, kp, vp, tables, lengths,
                                         use_kernel=False)
    np.testing.assert_array_equal(np.asarray(got_model), np.asarray(want))
    # kernel path (interpret): allclose (own accumulation schedule)
    got_kernel = A.paged_decode_attention(q, kp, vp, tables, lengths,
                                          use_kernel=True)
    np.testing.assert_allclose(np.asarray(got_kernel), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_empty_rows_are_finite():
    """Rows with length 0 (unadmitted slots riding in the batch) must
    produce finite output, never NaN (the engine discards them)."""
    q = jnp.ones((2, 4, 32), jnp.float32)
    kp = jnp.zeros((4, 8, 2, 32), jnp.float32)
    vp = jnp.zeros((4, 8, 2, 32), jnp.float32)
    tables = jnp.full((2, 2), -1, jnp.int32)
    lengths = jnp.asarray([0, 0], jnp.int32)
    out = paged_attention(q, kp, vp, tables, lengths)
    assert bool(jnp.all(jnp.isfinite(out)))
