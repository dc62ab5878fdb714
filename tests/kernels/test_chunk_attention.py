"""Chunk-attention kernels vs their oracles (interpret mode).

Three independent sources of truth, all required to agree:

* ``ref.py`` — the naive full-cache-mask jnp schedule;
* ``models/attention.chunk_attention(use_kernel=False)`` — the
  span-clamped jnp ladder (must be BIT-exact vs the unclamped math:
  the pow2-slice append-zeros invariance every token-exactness
  guarantee in the serving tests leans on);
* ``full_attention`` over the logical prefix — an oracle that never
  saw the chunk/cache machinery at all.

Coverage per the shape-dispatch table: fragment widths {1, non-pow2,
spec k+1}, ``q_pos`` at 0 / a block boundary / ``max_seq - width``,
contiguous and paged layouts, wide and narrow kernel schedules.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.chunk_attention import (
    NARROW_MAX_WIDTH,
    chunk_attention_kernel,
    chunk_attention_ref,
    paged_chunk_attention_kernel,
    paged_chunk_attention_ref,
)
from repro.models import attention as A

TOL = dict(rtol=2e-5, atol=2e-5)


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _inputs(b, c, h, hkv, d, smax, pos0, seed=0):
    """Contiguous cache + fragment at per-row start positions pos0."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = _rand(k1, (b, c, h, d))
    kc = _rand(k2, (b, smax, hkv, d))
    vc = _rand(k3, (b, smax, hkv, d))
    q_pos = jnp.asarray(pos0, jnp.int32)[:, None] + jnp.arange(c)
    return q, kc, vc, q_pos


def _paged_from_contiguous(kc, vc, bs, seed=0):
    """Scatter each row's contiguous cache into shuffled pages."""
    b, smax, hkv, d = kc.shape
    nb = smax // bs
    rng = np.random.default_rng(seed)
    tables = np.full((b, nb), -1, np.int32)
    perm = rng.permutation(b * nb)
    kp = np.zeros((b * nb, bs, hkv, d), np.float32)
    vp = np.zeros((b * nb, bs, hkv, d), np.float32)
    i = 0
    for r in range(b):
        for j in range(nb):
            tables[r, j] = perm[i]
            kp[perm[i]] = np.asarray(kc[r, j * bs:(j + 1) * bs])
            vp[perm[i]] = np.asarray(vc[r, j * bs:(j + 1) * bs])
            i += 1
    return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables)


# -- widths {1, non-pow2, spec k+1} x q_pos {0, block boundary, smax-w} -----

WIDTHS = [1, 3, 5]          # 1 = decode-like, 3 = non-pow2, 5 = spec k+1
POS_CASES = ["zero", "block_boundary", "max"]


def _pos0(case, b, c, smax, bs=16):
    if case == "zero":
        return [0] * b
    if case == "block_boundary":
        return [bs, bs * 2, bs - 1, bs * 3][:b]
    return [smax - c] * b


@pytest.mark.parametrize("c", WIDTHS)
@pytest.mark.parametrize("case", POS_CASES)
def test_kernel_vs_ref_contiguous(c, case):
    b, h, hkv, d, smax = 4, 4, 2, 32, 64
    q, kc, vc, q_pos = _inputs(b, c, h, hkv, d, smax,
                               _pos0(case, b, c, smax), seed=c)
    got = chunk_attention_kernel(q, kc, vc, q_pos)
    want = chunk_attention_ref(q, kc, vc, q_pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("c", WIDTHS)
@pytest.mark.parametrize("case", POS_CASES)
def test_kernel_vs_ref_paged(c, case):
    b, h, hkv, d, smax, bs = 4, 4, 2, 32, 64, 16
    q, kc, vc, q_pos = _inputs(b, c, h, hkv, d, smax,
                               _pos0(case, b, c, smax, bs), seed=10 + c)
    kp, vp, tables = _paged_from_contiguous(kc, vc, bs, seed=c)
    got = paged_chunk_attention_kernel(q, kp, vp, tables, q_pos)
    want = paged_chunk_attention_ref(q, kp, vp, tables, q_pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    # gathering the chain back == the contiguous cache: one more oracle
    want_cont = chunk_attention_ref(q, kc, vc, q_pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_cont),
                               **TOL)


@pytest.mark.parametrize("c,tiles", [(NARROW_MAX_WIDTH + 8, 1), (96, 2)],
                         ids=["one_q_tile", "two_q_tiles"])
@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_wide_schedule_vs_ref(c, tiles, paged):
    """Fragments above NARROW_MAX_WIDTH dispatch to the wide kernel,
    which cuts them into query tiles of <= WIDE_TILE_ROWS rows."""
    from repro.kernels.chunk_attention.kernel import _wide_q_tile
    b, h, hkv, d, smax, bs = 2, 8, 2, 64, 128, 16
    assert c // _wide_q_tile(c, h // hkv) == tiles
    q, kc, vc, q_pos = _inputs(b, c, h, hkv, d, smax, [0, 32], seed=3)
    want = chunk_attention_ref(q, kc, vc, q_pos)
    if paged:
        kp, vp, tables = _paged_from_contiguous(kc, vc, bs, seed=3)
        got = paged_chunk_attention_kernel(q, kp, vp, tables, q_pos)
    else:
        got = chunk_attention_kernel(q, kc, vc, q_pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


# -- full_attention as the independent oracle -------------------------------

@pytest.mark.parametrize("c", WIDTHS)
def test_against_full_attention_oracle(c):
    """A fragment continuing a prefix must produce exactly what one
    monolithic causal forward over [prefix; fragment] produces at the
    fragment's positions — checked against `full_attention`, which
    never saw the cache/chunk machinery (not just the ref)."""
    b, h, hkv, d, smax = 2, 4, 2, 32, 64
    plen = 21                                        # prefix length
    key = jax.random.PRNGKey(40 + c)
    k1, k2, k3 = jax.random.split(key, 3)
    total = plen + c
    q_all = _rand(k1, (b, total, h, d))
    k_all = _rand(k2, (b, total, hkv, d))
    v_all = _rand(k3, (b, total, hkv, d))
    want = A.full_attention(q_all, k_all, v_all, causal=True)[:, plen:]
    # the same math as a cached fragment: cache rows 0..plen+c hold K/V
    kc = jnp.zeros((b, smax, hkv, d)).at[:, :total].set(k_all)
    vc = jnp.zeros((b, smax, hkv, d)).at[:, :total].set(v_all)
    q = q_all[:, plen:]
    q_pos = plen + jnp.arange(c)[None, :] + jnp.zeros((b, 1), jnp.int32)
    for fn in (chunk_attention_kernel,
               lambda *a: A.chunk_attention(*a, use_kernel=False)):
        got = fn(q, kc, vc, q_pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **TOL)


# -- the span clamp must be invisible: bit-exact vs unclamped ---------------

@pytest.mark.parametrize("c", WIDTHS)
@pytest.mark.parametrize("case", POS_CASES)
def test_clamped_jnp_bit_exact_vs_full_mask(c, case):
    """The ladder slice is the *same bits* as masking the whole cache —
    the invariance every serving token-exactness test leans on."""
    b, h, hkv, d, smax = 4, 4, 2, 32, 128
    q, kc, vc, q_pos = _inputs(b, c, h, hkv, d, smax,
                               _pos0(case, b, c, smax), seed=20 + c)
    clamped = A.chunk_attention(q, kc, vc, q_pos, use_kernel=False)
    full = A.chunk_attention(q, kc, vc, q_pos,
                             span_idx=jnp.int32(len(A.span_ladder(smax))
                                                - 1),
                             use_kernel=False)
    np.testing.assert_array_equal(np.asarray(clamped), np.asarray(full))


# -- satellite 2: short fragment over a long chain touches few blocks ------

def test_paged_clamp_touches_expected_block_count():
    b, h, hkv, d, smax, bs = 2, 4, 2, 32, 128, 16
    c = 4
    q, kc, vc, q_pos = _inputs(b, c, h, hkv, d, smax, [10, 17], seed=5)
    kp, vp, tables = _paged_from_contiguous(kc, vc, bs, seed=5)
    out, blocks = A.paged_chunk_attention(q, kp, vp, tables, q_pos,
                                          use_kernel=False,
                                          return_blocks=True)
    # limit = max(q_pos)+1 = 21 -> rung 32 -> ceil(32/16) = 2 of the
    # 8-block chain gathered
    assert int(blocks) == 2, int(blocks)
    want = chunk_attention_ref(q, kc, vc, q_pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)
    # fragment at the chain's end -> the whole chain
    q_pos_end = jnp.asarray([smax - c, smax - c], jnp.int32)[:, None] \
        + jnp.arange(c)
    _, blocks_end = A.paged_chunk_attention(q, kp, vp, tables, q_pos_end,
                                            use_kernel=False,
                                            return_blocks=True)
    assert int(blocks_end) == smax // bs, int(blocks_end)


def test_span_ladder_shapes():
    assert A.span_ladder(128) == [16, 32, 64, 128]
    assert A.span_ladder(96) == [16, 32, 64, 96]
    assert A.span_ladder(16) == [16]
    assert A.span_ladder(8) == [8]
    assert A.span_ladder(1024) == [128, 256, 512, 1024]
    # attended_span picks the smallest covering rung
    qp = jnp.asarray([[20], [5]], jnp.int32)
    assert int(A.attended_span(qp, 128)) == 1          # rung 32
    assert int(A.attended_span(jnp.zeros((2, 1), jnp.int32), 128)) == 0
    assert int(A.attended_span(jnp.full((2, 1), 127, jnp.int32),
                               128)) == 3


def test_garbage_rows_are_finite():
    """Rows whose q_pos points at an empty cache region (unadmitted
    slots riding in the batch) must stay finite — the engine discards
    their outputs but NaNs would poison donated buffers."""
    b, c, h, hkv, d, smax = 2, 5, 4, 2, 32, 64
    q = jnp.ones((b, c, h, d), jnp.float32)
    kc = jnp.zeros((b, smax, hkv, d), jnp.float32)
    vc = jnp.zeros((b, smax, hkv, d), jnp.float32)
    q_pos = jnp.zeros((b, 1), jnp.int32) + jnp.arange(c)
    out = chunk_attention_kernel(q, kc, vc, q_pos)
    assert bool(jnp.all(jnp.isfinite(out)))
    out = A.chunk_attention(q, kc, vc, q_pos, use_kernel=False)
    assert bool(jnp.all(jnp.isfinite(out)))
