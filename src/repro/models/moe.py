"""Mixture-of-Experts with sort-based fixed-capacity dispatch.

EMPA mapping: routing a token to an expert is *outsourcing a QT* — the
router is compile-time parallelization metadata, the expert pool is the
core pool (experts are rented per token, capacity = pool size), and the
weighted combine is a SUMUP-mode reduction (per-token partial results
stream back and are combined without materializing the dispatch tensor).

Implementation notes (TPU-native):
* group-local dispatch — tokens are processed in groups (the leading axis,
  sharded over the data axes), so argsort/gather/scatter stay shard-local;
  the expert einsums contract against expert-sharded weights, which GSPMD
  turns into the EP all-to-all pair.
* fixed capacity ``C = ceil(T·k/E · capacity_factor)`` per group; overflow
  tokens are dropped (standard Switch/GShard semantics; the capacity
  factor is configurable per arch).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.models import layers


def capacity(tokens_per_group: int, top_k: int, n_experts: int,
             factor: float) -> int:
    c = int(math.ceil(tokens_per_group * top_k * factor / n_experts))
    c = max(c, 1)
    if c >= 8:  # MXU-friendly
        c = (c + 7) // 8 * 8
    return c


def route(x, router_w, top_k: int):
    """x: (G, T, d); router_w: (d, E) -> (gates, idx, probs)."""
    logits = jnp.einsum("gtd,de->gte", x, router_w).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)           # (G, T, k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates, idx, probs


def load_balancing_loss(probs, idx, n_experts: int):
    """Switch-style aux loss: E * Σ_e f_e · P_e."""
    g, t, k = idx.shape
    sel = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32)   # (G,T,k,E)
    f = jnp.mean(jnp.sum(sel, axis=2), axis=(0, 1))           # fraction routed
    p = jnp.mean(probs, axis=(0, 1))                          # mean router prob
    return n_experts * jnp.sum(f * p) / k


def dispatch_tables(idx, gates, n_experts: int, cap: int):
    """Sort-based dispatch: (G,T,k) assignments -> (G,E,C) token/gate tables.

    Shard-local per group: argsort + searchsorted give each assignment its
    rank within its expert; ranks >= capacity are dropped.
    Returns (buf_tok, buf_gate); buf_tok == T marks an empty slot.
    """
    g, t, k = idx.shape
    flat = idx.reshape(g, t * k)
    gflat = gates.reshape(g, t * k)
    order = jnp.argsort(flat, axis=-1, stable=True)           # (G, T*k)
    sorted_eid = jnp.take_along_axis(flat, order, axis=-1)
    sorted_gate = jnp.take_along_axis(gflat, order, axis=-1)
    # rank within expert group = position - first occurrence of the expert
    first = jax.vmap(lambda se: jnp.searchsorted(se, se, side="left"))(sorted_eid)
    rank = jnp.arange(t * k)[None, :] - first
    keep = rank < cap
    tok_of = order // k
    gi = jnp.arange(g)[:, None]
    # scatter into (G, E, C+1); dropped slots land in the trash column C
    buf_tok = jnp.full((g, n_experts, cap + 1), t, jnp.int32)
    buf_gate = jnp.zeros((g, n_experts, cap + 1), jnp.float32)
    col = jnp.where(keep, rank, cap)
    buf_tok = buf_tok.at[gi, sorted_eid, col].set(
        jnp.where(keep, tok_of, t).astype(jnp.int32))
    buf_gate = buf_gate.at[gi, sorted_eid, col].set(
        jnp.where(keep, sorted_gate, 0.0))
    return buf_tok[:, :, :cap], buf_gate[:, :, :cap]


def moe_ffn(x, p, cfg, act: str = "silu"):
    """x: (G, T, d) -> (y, aux_loss).

    p: router (d, E); w_gate/w_up (E, d, f); w_down (E, f, d);
       optional shared expert: sh_gate/sh_up (d, f·n_sh), sh_down (f·n_sh, d).
    """
    gdim, t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = capacity(t, k, e, cfg.capacity_factor)

    gates, idx, probs = route(x, p["router"], k)
    aux = load_balancing_loss(probs, idx, e)
    buf_tok, buf_gate = dispatch_tables(idx, gates, e, cap)

    # gather: (G, E, C, d); row T is a zero pad
    x_pad = jnp.concatenate([x, jnp.zeros((gdim, 1, d), x.dtype)], axis=1)
    gi = jnp.arange(gdim)[:, None, None]
    xe = x_pad[gi, buf_tok]                                    # (G, E, C, d)
    xe = _shard(xe, ("batch", "experts", None, None))

    # expert computation (E contracted against expert-sharded weights -> EP)
    a = layers.act_fn(act)
    h = a(jnp.einsum("gecd,edf->gecf", xe, p["w_gate"])) * \
        jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    ye = _shard(ye, ("batch", "experts", None, None))

    # combine: weighted scatter-add back to tokens (SUMUP-style reduce).
    # Accumulate in the activation dtype: the cross-expert-shard psum of
    # this tensor dominates the MoE's collective bytes, and bf16 halves it
    # (§Perf E1); top-k gates are normalized, so the sum has ≤ k addends.
    y = jnp.zeros((gdim, t + 1, d), x.dtype)
    y = y.at[gi, buf_tok].add((ye.astype(jnp.float32)
                               * buf_gate[..., None]).astype(x.dtype))
    y = y[:, :t]
    # name the combined output so the remat policy can SAVE it: recomputing
    # the MoE block in backward would replay its collectives (§Perf E2)
    y = checkpoint_name(y, "moe_out")

    if "sh_up" in p:  # always-on shared experts (Moonlight/DeepSeek style)
        y = y + layers.mlp(x, {"w_gate": p["sh_gate"], "w_up": p["sh_up"],
                               "w_down": p["sh_down"]}, act)
    return y, aux


def _shard(x, axes):
    from repro.runtime.sharding import shard
    return shard(x, axes)


# ---------------------------------------------------------------------------
# shard_map EP path (§Perf E2): explicit locality
# ---------------------------------------------------------------------------
# GSPMD cannot prove the dispatch gather / combine scatter are batched-local
# per data shard, so the pjit path all-gathers the full (G, T+1, d) hidden
# over the data axis per MoE layer (measured: the dominant collective term
# for both MoE archs).  The shard_map path makes the EMPA structure
# explicit: routing and dispatch are LOCAL to the data shard (a parent
# keeps its own QTs), each model shard computes its expert slice, and ONE
# psum over "model" combines the partial outputs (the latched clone-back).

def moe_ffn_sharded(x, p, cfg, act: str, mesh):
    """x: (G, T, d).  Requires G divisible by the data axes product."""
    from jax.sharding import PartitionSpec as P

    e, k = cfg.n_experts, cfg.top_k
    gdim, t, d = x.shape
    cap = capacity(t, k, e, cfg.capacity_factor)
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_model = mesh.shape["model"]
    e_loc = e // n_model

    def body(x_loc, router_w, wg, wu, wd):
        # FSDP: clone the glue on rent — gather the weight shards once
        wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, "data", axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, "data", axis=2, tiled=True)

        gates, idx, probs = route(x_loc, router_w, k)
        aux = load_balancing_loss(probs, idx, e)
        for ax in dp:
            aux = jax.lax.pmean(aux, ax)
        buf_tok, buf_gate = dispatch_tables(idx, gates, e, cap)
        # this model shard serves experts [e0, e0 + e_loc)
        e0 = jax.lax.axis_index("model") * e_loc
        tok_loc = jax.lax.dynamic_slice_in_dim(buf_tok, e0, e_loc, axis=1)
        gate_loc = jax.lax.dynamic_slice_in_dim(buf_gate, e0, e_loc, axis=1)

        g_loc = x_loc.shape[0]
        x_pad = jnp.concatenate(
            [x_loc, jnp.zeros((g_loc, 1, d), x_loc.dtype)], axis=1)
        gi = jnp.arange(g_loc)[:, None, None]
        xe = x_pad[gi, tok_loc]                       # local gather
        a = layers.act_fn(act)
        h = a(jnp.einsum("gecd,edf->gecf", xe, wg)) * \
            jnp.einsum("gecd,edf->gecf", xe, wu)
        ye = jnp.einsum("gecf,efd->gecd", h, wd)
        y = jnp.zeros((g_loc, t + 1, d), x_loc.dtype)
        y = y.at[gi, tok_loc].add(
            (ye.astype(jnp.float32) * gate_loc[..., None]).astype(x_loc.dtype))
        # the ONE combine collective: partial expert outputs -> tokens
        y = jax.lax.psum(y[:, :t], "model")
        return y, aux

    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None),
                  P("model", "data", None), P("model", "data", None),
                  P("model", None, "data")),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    y = checkpoint_name(y, "moe_out")

    if "sh_up" in p:   # always-on shared experts: plain dense MLP (GSPMD)
        y = y + layers.mlp(x, {"w_gate": p["sh_gate"], "w_up": p["sh_up"],
                               "w_down": p["sh_down"]}, act)
    return y, aux


def moe_flops(tokens: int, d: int, f: int, top_k: int, n_shared: int) -> float:
    return 2.0 * tokens * d * f * 3 * (top_k + n_shared)
