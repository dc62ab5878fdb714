"""ShapeDtypeStruct stand-ins for every model input (dry-run, no allocation).

Weak-type-correct, shardable.  [audio]/[vlm] archs get precomputed
frame/patch embeddings (the modality frontend is a stub per assignment).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import model as model_lib


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def batch_inputs(cfg: ArchConfig, shape: ShapeConfig, *, with_labels: bool,
                 dtype=jnp.bfloat16) -> dict:
    """Inputs for train (with labels) / prefill (without)."""
    b, s = shape.global_batch, shape.seq_len
    batch = {}
    s_txt = s
    if cfg.frontend == "vision":
        nv = cfg.n_frontend_tokens
        batch["vision_embeds"] = _sds((b, nv, cfg.frontend_dim), dtype)
        s_txt = s - nv
    if cfg.family == "encdec":
        batch["enc_embeds"] = _sds((b, s, cfg.frontend_dim), dtype)
    batch["tokens"] = _sds((b, s_txt), jnp.int32)
    if with_labels:
        batch["labels"] = _sds((b, s_txt), jnp.int32)
    return batch


def batch_axes(cfg: ArchConfig, shape: ShapeConfig, *, with_labels: bool) -> dict:
    ax = {}
    if cfg.frontend == "vision":
        ax["vision_embeds"] = ("batch", None, None)
    if cfg.family == "encdec":
        ax["enc_embeds"] = ("batch", None, None)
    ax["tokens"] = ("batch", None)
    if with_labels:
        ax["labels"] = ("batch", None)
    return ax


def decode_inputs(cfg: ArchConfig, shape: ShapeConfig, dtype=jnp.bfloat16):
    """(token, cache) stand-ins for a decode step with a seq_len cache."""
    b, s = shape.global_batch, shape.seq_len
    token = _sds((b,), jnp.int32)
    cache = model_lib.init_cache(cfg, b, s, dtype=dtype, abstract_only=True)
    return token, cache


def cache_axes(cfg: ArchConfig, paged: bool = False) -> dict:
    """Logical axes for each cache leaf (family-dependent).

    Paged K/V pages are `(layers, n_blocks, block_size, hkv, dh)`: any
    slot's chain may live on any block, so the block axes replicate
    across the data axis and TP stays on the head/head-dim axes; the
    per-slot block tables shard with the slot batch."""
    kv = ("layers", None, None, "cache_kv_heads", "cache_head_dim") \
        if paged else \
        ("layers", "cache_batch", None, "cache_kv_heads", "cache_head_dim")
    ax = {"pos": ("cache_batch",)}
    fam = cfg.family
    if fam in ("dense", "moe", "vlm", "encdec", "interleaved"):
        ax.update(k=kv, v=kv)
        if paged:
            ax["block_tables"] = ("cache_batch", None)
        if fam == "encdec":
            ax.update(xk=kv, xv=kv)
    if fam in ("ssm", "hybrid", "interleaved"):
        ax.update(conv=("layers", "cache_batch", None, "conv_dim"),
                  state=("layers", "cache_batch", "ssm_heads", None,
                         "ssm_state"))
    if fam == "hybrid":
        ax.update(k=kv, v=kv)
    return ax
