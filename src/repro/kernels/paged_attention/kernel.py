"""Paged decode attention — walk each slot's live pages, several a step.

The serving cache stores K/V in fixed-size pages rented from the block
pool (runtime/paging.py); a slot's sequence is a *chain* of pages named
by its block-table row.  This kernel is the SUMUP-mode schedule of
``flash_attention`` applied to that layout: the (1 × length) score row
is the §5.2 partial sum — groups of pages stream their scores into the
slot's running (max m, denominator l, accumulator acc) scratch, kept in
float32, and HBM never sees a gathered contiguous copy of the sequence.

The grid is a work list, one step per live *group* of ``pages_per_step``
pages, rows in order: a row of length n takes ``ceil(n / (bs ·
pages_per_step))`` steps, and a row of length 0 one step that computes
nothing and reads out zeros.  Its size is the sum over rows, a dynamic
grid bound, so the kernel's steps follow the live context and not the
block table's width.  Block table, lengths and each step's (row, group)
ride in as **scalar-prefetch** operands.  A step brings its group's
pages in as ``pages_per_step`` BlockSpec operands per pool, each
``(1, bs, Hkv, D)`` tile (all KV heads of one page) aimed by its index
map at ``table[row, j]``; the pipeline fetches step i+1's pages while
step i computes.  ``pages_per_step`` follows from the page shape: the
largest power of two whose pages cover at most 128 positions, fit the
table and fit an 8 MiB VMEM budget (8 pages of 16 at every paged
config's widths).

Only the chain's first ``ceil(length / bs)`` entries are ever read: a
page slot past the chain in a row's last group re-aims at the chain's
last page, and a ``-1`` inside the chain reads page 0, as in
``ref.py``.  So the table's tail (``-1``, or pages reserved past the
length) costs nothing.

A group's K and V are taken as ``(positions · Hkv, D)`` rows, the tiles'
own layout, and every query head of the row meets every row at once: the
``(H, positions · Hkv)`` scores keep only each head's own KV head and
the positions ``< length``, and V's rows past the length are zeroed, so
stale rows of the last page carry nothing into ``p · v``.

Why not one program per row with hand-issued DMAs: Mosaic refuses a
copy out of a ``(P, bs, Hkv, D)`` pool whose head dimension is narrower
than the 128-lane tile (D 64: "slice shape along dimension 3 must be
aligned to tiling"), and a lane-dense view of the pool makes XLA
relayout every layer's pool on every call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Positions one group of pages covers at most, and the VMEM its pages
# may take (half of v5e's default 16 MiB scoped limit).
GROUP_POSITIONS = 128
GROUP_VMEM_BYTES = 8 << 20


def page_vmem_bytes(bs: int, h: int, hkv: int, d: int, itemsize: int) -> int:
    """VMEM one page of a group takes: its K and V tiles double-buffered
    and their float32 copies, and its columns of the (H, positions ·
    Hkv) float32 scores, probabilities and mask."""
    return bs * hkv * (2 * (2 * itemsize + 4) * d + 3 * 4 * h)


def pages_per_step(bs: int, nb: int, page_bytes: int) -> int:
    """Largest power of two g with g·bs <= GROUP_POSITIONS, g <= nb and
    g·page_bytes <= GROUP_VMEM_BYTES (at least 1)."""
    g = 1
    while (2 * g * bs <= GROUP_POSITIONS and 2 * g <= nb
           and 2 * g * page_bytes <= GROUP_VMEM_BYTES):
        g *= 2
    return g


def _work_list(lengths, *, bs: int, nb: int, pps: int):
    """Each grid step's (row, group) and the number of steps: rows in
    order, ``max(1, ceil(length / (bs·pps)))`` steps a row."""
    b = lengths.shape[0]
    length = jnp.clip(lengths.astype(jnp.int32), 0, nb * bs)
    steps = jnp.maximum((length + bs * pps - 1) // (bs * pps), 1)
    ends = jnp.cumsum(steps)
    t = jnp.arange(b * (-(-nb // pps)), dtype=jnp.int32)[:, None]
    before = ends[None, :] <= t                  # rows wholly before step t
    row = jnp.minimum(jnp.sum(before, axis=1), b - 1)
    group = t[:, 0] - jnp.sum(jnp.where(before, steps[None, :], 0), axis=1)
    return row.astype(jnp.int32), group.astype(jnp.int32), ends[-1]


def _body(tables_ref, lengths_ref, rows_ref, groups_ref, q_ref, *refs,
          bs: int, nb: int, pps: int, group: int, sm_scale: float):
    k_refs, v_refs = refs[:pps], refs[pps:2 * pps]
    o_ref, acc, m, l = refs[2 * pps:]
    t = pl.program_id(0)
    row, g = rows_ref[t], groups_ref[t]
    length = jnp.clip(lengths_ref[row], 0, nb * bs)
    span = pps * bs
    n_groups = jnp.maximum((length + span - 1) // span, 1)

    @pl.when(g == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m[...] = jnp.full_like(m, NEG_INF)
        l[...] = jnp.zeros_like(l)

    @pl.when(g * span < length)
    def _compute():
        hkv = k_refs[0].shape[2]
        q = q_ref[0].astype(jnp.float32)                     # (H, D)
        # row r of a group's K/V is position r // Hkv, KV head r % Hkv
        k = jnp.concatenate([r[0] for r in k_refs], axis=0) \
            .astype(jnp.float32).reshape(span * hkv, -1)
        v = jnp.concatenate([r[0] for r in v_refs], axis=0) \
            .astype(jnp.float32).reshape(span * hkv, -1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # (H, span·Hkv)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        s = jnp.where((col % hkv == head) & (g * span + col // hkv < length),
                      s, NEG_INF)
        vrow = jax.lax.broadcasted_iota(jnp.int32, (span * hkv, 1), 0)
        v = jnp.where(g * span + vrow // hkv < length, v, 0.0)
        m_prev = m[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l[...] = l[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m[...] = m_new

    @pl.when(g == n_groups - 1)
    def _readout():
        out = acc[...] / jnp.maximum(l[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


def paged_attention_call(q, k_pages, v_pages, block_tables, lengths, *,
                         interpret=True):
    """q: (B, H, D); k/v_pages: (P, bs, Hkv, D); block_tables: (B, NB)
    int32 (-1 = end of chain); lengths: (B,) valid tokens.  -> (B, H, D).
    ``interpret`` is a bool or a ``pltpu.InterpretParams``."""
    b, h, d = q.shape
    _, bs, hkv, _ = k_pages.shape
    nb = block_tables.shape[1]
    assert h % hkv == 0
    pps = pages_per_step(bs, nb, page_vmem_bytes(
        bs, h, hkv, d, k_pages.dtype.itemsize))
    rows, groups, n_steps = _work_list(lengths, bs=bs, nb=nb, pps=pps)

    def row_map(t, tbl, lens, rows, groups):
        return (rows[t], 0, 0)

    def page_map(i):
        def index(t, tbl, lens, rows, groups):
            row = rows[t]
            length = jnp.clip(lens[row], 0, nb * bs)
            live = (length + bs - 1) // bs
            # past the chain: re-aim at its last page (same block, no
            # table entry past the chain read); no chain: page 0
            j = jnp.clip(jnp.minimum(groups[t] * pps + i, live - 1),
                         0, nb - 1)
            page = jnp.where(live > 0, tbl[row, j], 0)
            return (jnp.maximum(page, 0), 0, 0, 0)
        return index

    page_spec = [pl.BlockSpec((1, bs, hkv, d), page_map(i))
                 for i in range(pps)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_steps,),
        in_specs=[pl.BlockSpec((1, h, d), row_map), *page_spec, *page_spec],
        out_specs=pl.BlockSpec((1, h, d), row_map),
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),   # acc
            pltpu.VMEM((h, 1), jnp.float32),   # running max
            pltpu.VMEM((h, 1), jnp.float32),   # denominator
        ],
    )
    return pl.pallas_call(
        functools.partial(_body, bs=bs, nb=nb, pps=pps, group=h // hkv,
                          sm_scale=1.0 / (d ** 0.5)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        name="paged_attention",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), rows,
      groups, q, *([k_pages] * pps), *([v_pages] * pps))
