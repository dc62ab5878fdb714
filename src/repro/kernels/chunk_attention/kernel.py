"""Chunk attention — write-then-attend over an existing KV cache.

Two schedules for the same math, dispatched by fragment width (the
charm_u50 ``mm_large`` / ``mm_small`` pattern — one fabric
configuration per problem shape):

* **wide** — grid ``(batch, q_tiles, kv_blocks)``: the fragment is cut
  into query tiles of at most ``WIDE_TILE_ROWS`` (position, head) rows,
  so the score panel and the accumulator stay bounded in VMEM however
  long the fragment.  Serves chunked prefill and resume replay, where
  the fragment is the scheduler chunk or the solo-prefill budget.
* **narrow** — grid ``(batch, 1, kv_blocks)``: the whole fragment is
  one tile.  Serves the speculative verify fragment ``(n_slots, k+1)``.
  Paged decode, width 1, has a schedule of its own that walks only the
  live pages (kernels/paged_attention).

Both take *all* KV heads of a KV block in one ``(1, bs, Hkv, D)`` tile
and contract it as a (Hkv, rows, bs) batched product over heads.  Mosaic
requires a block's last two dimensions to be (8, 128)-divisible or whole,
so a one-head ``(1, bs, 1, D)`` tile of the ``(…, Hkv, D)`` cache is
refused; the whole-``Hkv`` tile is legal for any head count, including
the per-shard head slice under tensor parallelism.  Queries are laid out
head-major, ``(B, Hkv, C·group, D)``, so the contraction needs no
in-tile transpose of q.

Both clamp KV work to the attended span: the per-row fragment start
rides in as a **scalar-prefetch** operand and ``@pl.when(j·bs < pos0 +
tile)`` skips every KV block past the tile's last query position — the
cache tail beyond ``pos + fragment`` is never computed on.  The paged
entries aim each KV DMA through the scalar-prefetched block table.

Fragment positions are assumed contiguous per row (``q_pos[b, c] ==
q_pos[b, 0] + c``), which is what ``prefill_chunk`` produces; the mask
is rebuilt in-register from the prefetched row start.  Online softmax
(running max / denominator / accumulator scratch in VMEM) keeps the
accumulation exact across the sequential last grid dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Upper bound on the (position, head) rows of one wide query tile: at
# Hkv 8, D 64 this keeps q, acc, running max/denominator and the score
# panel of a tile within a few MiB of VMEM.
WIDE_TILE_ROWS = 256


def _body(*refs, paged: bool, kv_block: int, q_tile: int, group: int,
          sm_scale: float):
    if paged:
        refs = refs[1:]            # the block table is used by the index map
    qpos_ref, q_ref, k_ref, v_ref, o_ref, acc, m, l = refs
    b = pl.program_id(0)
    iq = pl.program_id(1)
    j = pl.program_id(2)
    nkb = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, NEG_INF)
        l[...] = jnp.zeros_like(l)

    pos0 = qpos_ref[b, 0] + iq * q_tile      # first position of the tile

    # the KV clamp: blocks past the tile's last query position are dead
    # under the offset-causal mask — skip their compute entirely
    @pl.when(j * kv_block < pos0 + q_tile)
    def _compute():
        q = q_ref[0].astype(jnp.float32)           # (Hkv, tile·group, D)
        rows = q.shape[1]
        k = k_ref[0].astype(jnp.float32)           # (bs, Hkv, D)
        v = v_ref[0].astype(jnp.float32)
        # batch over kv heads without transposing the KV tile: contract
        # D, batch Hkv (dim 0 of q, dim 1 of k)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32) * sm_scale
        kpos = j * kv_block + jax.lax.broadcasted_iota(
            jnp.int32, (1, rows, kv_block), 2)
        qp = pos0 + jax.lax.broadcasted_iota(
            jnp.int32, (1, rows, kv_block), 1) // group
        s = jnp.where(kpos <= qp, s, NEG_INF)      # (Hkv, rows, bs)
        m_prev = m[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l[...] = l[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)
        m[...] = m_new

    @pl.when(j == nkb - 1)
    def _readout():
        out = acc[...] / jnp.maximum(l[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


# ------------------------------------------------------------- helpers

def _kv_block(smax: int, cap: int = 128) -> int:
    """Largest power of two <= cap that divides the cache length."""
    bs = 1
    while bs < cap and smax % (bs * 2) == 0:
        bs *= 2
    return bs


def _wide_q_tile(c: int, group: int) -> int:
    """Largest divisor t of the width with t·group <= WIDE_TILE_ROWS and
    t·group a multiple of 8 (Mosaic's sublane rule for a partial block);
    the whole fragment when no such divisor exists."""
    for t in range(min(c, WIDE_TILE_ROWS // group), 0, -1):
        if c % t == 0 and (t * group) % 8 == 0:
            return t
    return c


def _head_major(q, hkv: int):
    """(B, C, H, D) -> (B, Hkv, C·group, D)."""
    b, c, h, d = q.shape
    group = h // hkv
    return (q.reshape(b, c, hkv, group, d)
             .transpose(0, 2, 1, 3, 4)
             .reshape(b, hkv, c * group, d))


def _head_minor(o, c: int, group: int):
    b, hkv, cg, d = o.shape
    return (o.reshape(b, hkv, c, group, d)
             .transpose(0, 2, 1, 3, 4)
             .reshape(b, c, hkv * group, d))


def _chunk_call(q, k, v, q_pos, tables, *, kv_block: int, q_tile: int,
                name: str, interpret: bool):
    """One pallas_call for every entry below.  ``tables`` is None for a
    contiguous (B, Smax, Hkv, D) cache, else the (B, NB) block table of
    a (P, bs, Hkv, D) page pool."""
    b, c, h, d = q.shape
    hkv = k.shape[2]
    assert h % hkv == 0
    group = h // hkv
    nkb = k.shape[1] // kv_block if tables is None else tables.shape[1]
    rows = q_tile * group
    q_r = _head_major(q, hkv)

    if tables is None:
        prefetch = (q_pos.astype(jnp.int32),)

        def q_map(ib, iq, j, qpos):
            return (ib, 0, iq, 0)

        def kv_map(ib, iq, j, qpos):
            return (ib, j, 0, 0)
    else:
        prefetch = (tables.astype(jnp.int32), q_pos.astype(jnp.int32))

        def q_map(ib, iq, j, tbl, qpos):
            return (ib, 0, iq, 0)

        def kv_map(ib, iq, j, tbl, qpos):
            # address indirection: table entry -> physical block (blocks
            # past the clamp are skipped by the body, so the clamped-to-0
            # NO_BLOCK entries are never *used*, only harmlessly fetched)
            return (jnp.maximum(tbl[ib, j], 0), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, c // q_tile, nkb),
        in_specs=[
            pl.BlockSpec((1, hkv, rows, d), q_map),
            pl.BlockSpec((1, kv_block, hkv, d), kv_map),
            pl.BlockSpec((1, kv_block, hkv, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, hkv, rows, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((hkv, rows, d), jnp.float32),   # acc
            pltpu.VMEM((hkv, rows, 1), jnp.float32),   # running max
            pltpu.VMEM((hkv, rows, 1), jnp.float32),   # denominator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_body, paged=tables is not None,
                          kv_block=kv_block, q_tile=q_tile, group=group,
                          sm_scale=1.0 / (d ** 0.5)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, c * group, d), q.dtype),
        name=name,
        interpret=interpret,
    )(*prefetch, q_r, k, v)
    return _head_minor(out, c, group)


# ------------------------------------------------------ contiguous API

def chunk_attention_wide_call(q, k_cache, v_cache, q_pos, *,
                              interpret: bool = True):
    """q: (B, C, H, D) at contiguous positions q_pos (B, C);
    k/v_cache: (B, Smax, Hkv, D).  -> (B, C, H, D)."""
    c, group = q.shape[1], q.shape[2] // k_cache.shape[2]
    return _chunk_call(q, k_cache, v_cache, q_pos, None,
                       kv_block=_kv_block(k_cache.shape[1]),
                       q_tile=_wide_q_tile(c, group),
                       name="chunk_attention_wide", interpret=interpret)


def chunk_attention_narrow_call(q, k_cache, v_cache, q_pos, *,
                                interpret: bool = True):
    return _chunk_call(q, k_cache, v_cache, q_pos, None,
                       kv_block=_kv_block(k_cache.shape[1]),
                       q_tile=q.shape[1],
                       name="chunk_attention_narrow", interpret=interpret)


# ----------------------------------------------------------- paged API

def paged_chunk_attention_wide_call(q, k_pages, v_pages, block_tables,
                                    q_pos, *, interpret: bool = True):
    """q: (B, C, H, D); k/v_pages: (P, bs, Hkv, D); block_tables:
    (B, NB) int32 (-1 = end of chain).  -> (B, C, H, D)."""
    c, group = q.shape[1], q.shape[2] // k_pages.shape[2]
    return _chunk_call(q, k_pages, v_pages, q_pos, block_tables,
                       kv_block=k_pages.shape[1],
                       q_tile=_wide_q_tile(c, group),
                       name="paged_chunk_attention_wide",
                       interpret=interpret)


def paged_chunk_attention_narrow_call(q, k_pages, v_pages, block_tables,
                                      q_pos, *, interpret: bool = True):
    return _chunk_call(q, k_pages, v_pages, q_pos, block_tables,
                       kv_block=k_pages.shape[1], q_tile=q.shape[1],
                       name="paged_chunk_attention_narrow",
                       interpret=interpret)
