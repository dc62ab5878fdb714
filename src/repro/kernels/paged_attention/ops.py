"""Public jit'd wrapper for paged decode attention."""
from __future__ import annotations

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.paged_attention.kernel import paged_attention_call


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@jax.jit
def paged_attention(q, k_pages, v_pages, block_tables, lengths):
    """Block-table decode attention.  q (B,H,D) against (P,bs,Hkv,D)
    pages addressed by (B,NB) tables, masked by (B,) lengths."""
    return paged_attention_call(q, k_pages, v_pages, block_tables, lengths,
                                interpret=_interpret())


def paged_attention_sharded(q, k_pages, v_pages, block_tables, lengths, *,
                            mesh: Mesh, axis: str = "model"):
    """:func:`paged_attention` under a head-sharded mesh: GSPMD cannot
    partition a ``pallas_call``, so each ``axis`` shard runs the kernel
    on its local head slice via ``shard_map`` (heads never mix in
    attention — no collective).  Block tables and lengths are
    replicated: every shard walks the same chain, reads its own head
    slice of each block.  Callers guard divisibility (``axis`` must
    divide H and Hkv) before routing here."""
    f = jax.shard_map(paged_attention, mesh=mesh,
                      in_specs=(P(None, axis, None),
                                P(None, None, axis, None),
                                P(None, None, axis, None),
                                P(None, None), P(None)),
                      out_specs=P(None, axis, None),
                      check_vma=False)
    return f(q, k_pages, v_pages, block_tables, lengths)
