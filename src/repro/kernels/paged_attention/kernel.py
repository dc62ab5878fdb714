"""Paged decode attention — gather K/V through the block table in VMEM.

The serving cache stores K/V in fixed-size blocks rented from the block
pool (runtime/paging.py); a slot's sequence is a *chain* of blocks named
by its block-table row.  This kernel is the SUMUP-mode schedule of
``flash_attention`` applied to that layout: the (1 × Skv) score row is
the §5.2 partial sum — children (KV blocks) stream their scores into the
parent's running (max m, denominator l, accumulator acc) scratch, and
HBM never sees a gathered contiguous copy of the sequence.

Decode is the one-token fragment of chunk attention: the query sits at
position ``length - 1``, so the offset-causal mask ``kpos <= length - 1``
is exactly the length mask.  The kernel is therefore the paged narrow
chunk-attention schedule at width 1 (kernels/chunk_attention): block
table and the per-row query position ride in as **scalar-prefetch**
operands, the BlockSpec index map reads ``tables[b, j]`` to aim each KV
DMA at the right physical block, all KV heads of a block come in one
``(1, bs, Hkv, D)`` tile, and blocks past the chain are skipped.  A row
of length 0 attends to nothing and reads out zeros.
"""
from __future__ import annotations

from repro.kernels.chunk_attention.kernel import (
    paged_chunk_attention_narrow_call,
)


def paged_attention_call(q, k_pages, v_pages, block_tables, lengths, *,
                         interpret: bool = True):
    """q: (B, H, D); k/v_pages: (P, bs, Hkv, D); block_tables: (B, NB)
    int32 (-1 = end of chain); lengths: (B,) valid tokens.  -> (B, H, D).
    """
    out = paged_chunk_attention_narrow_call(
        q[:, None], k_pages, v_pages, block_tables, (lengths - 1)[:, None],
        interpret=interpret, name="paged_attention")
    return out[:, 0]
