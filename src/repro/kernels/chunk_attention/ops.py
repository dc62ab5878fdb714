"""Public jit'd wrappers for chunk attention — shape dispatch lives here.

The caller (``models/attention.py``) hands every fragment to one entry
point per layout; the width of the fragment picks the schedule, the
charm_u50 way (``mm_large`` / ``mm_small`` chosen by the supervisor to
match the fabric configuration to the job):

* width <= ``NARROW_MAX_WIDTH``  ->  narrow kernel (the whole fragment
  in one tile; the speculative verify fragment ``(n_slots, k+1)`` and
  other skinny resumes)
* wider fragments                ->  wide kernel (query tiles of at most
  ``WIDE_TILE_ROWS`` rows; scheduler-chunk and solo prefill)

Width is a static shape, so the dispatch is resolved at trace time —
each (width, layout) pair jits once and the tick graph contains only
the matching ``pallas_call``.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.chunk_attention.kernel import (
    chunk_attention_narrow_call,
    chunk_attention_wide_call,
    paged_chunk_attention_narrow_call,
    paged_chunk_attention_wide_call,
)

# Fragments at or below this width take the narrow (one-tile) kernel.
# The speculative verify width is k+1 (k in 2..6 across the configs
# here); the scheduler chunk is 8+.
NARROW_MAX_WIDTH = 8


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@jax.jit
def chunk_attention_kernel(q, k_cache, v_cache, q_pos):
    """Fragment attention against a contiguous cache.  q (B,C,H,D) at
    contiguous positions q_pos (B,C) vs (B,Smax,Hkv,D); KV reads are
    clamped to pos + fragment."""
    call = (chunk_attention_narrow_call
            if q.shape[1] <= NARROW_MAX_WIDTH else
            chunk_attention_wide_call)
    return call(q, k_cache, v_cache, q_pos, interpret=_interpret())


@jax.jit
def paged_chunk_attention_kernel(q, k_pages, v_pages, block_tables,
                                 q_pos):
    """Fragment attention through the block table.  q (B,C,H,D) vs
    (P,bs,Hkv,D) pages addressed by (B,NB) tables; KV blocks past
    pos + fragment are never touched."""
    call = (paged_chunk_attention_narrow_call
            if q.shape[1] <= NARROW_MAX_WIDTH else
            paged_chunk_attention_wide_call)
    return call(q, k_pages, v_pages, block_tables, q_pos,
                interpret=_interpret())


# -- head-sharded entries (tensor-parallel serving) --------------------------
#
# GSPMD cannot partition a ``pallas_call``: under a head-sharded mesh the
# jit'd wrappers above would force an all-gather of the KV cache onto
# every shard.  These entries instead run the SAME shape dispatch
# per-shard on the local head slice via ``shard_map`` — heads are
# embarrassingly parallel in attention (GQA groups never mix), so the
# width-picks-the-schedule contract is untouched: the fragment axis is
# unsharded and each shard sees the global width.  Callers guard on
# divisibility (``model`` must divide H and Hkv — the sharding-rules
# fallback) before routing here; these functions are not jit'd at this
# level because mesh/axis are part of the closure — the serving tick
# that traces them holds the jit.

def chunk_attention_kernel_sharded(q, k_cache, v_cache, q_pos, *,
                                   mesh: Mesh, axis: str = "model"):
    """:func:`chunk_attention_kernel` with q/K/V head-sharded over
    ``axis``; q_pos replicated.  Per-shard GQA ratio equals the global
    one, so narrow/wide tile shapes are valid on the slice."""
    hs = P(None, None, axis, None)
    f = jax.shard_map(chunk_attention_kernel, mesh=mesh,
                      in_specs=(hs, hs, hs, P(None, None)), out_specs=hs,
                      check_vma=False)
    return f(q, k_cache, v_cache, q_pos)


def paged_chunk_attention_kernel_sharded(q, k_pages, v_pages, block_tables,
                                         q_pos, *, mesh: Mesh,
                                         axis: str = "model"):
    """:func:`paged_chunk_attention_kernel` with pages head-sharded over
    ``axis``; block tables and positions replicated — every shard walks
    the same chain, reads its own head slice of each block."""
    f = jax.shard_map(paged_chunk_attention_kernel, mesh=mesh,
                      in_specs=(P(None, None, axis, None),
                                P(None, None, axis, None),
                                P(None, None, axis, None),
                                P(None, None), P(None, None)),
                      out_specs=P(None, None, axis, None),
                      check_vma=False)
    return f(q, k_pages, v_pages, block_tables, q_pos)
