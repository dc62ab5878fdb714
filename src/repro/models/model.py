"""Unified LM covering all assigned architecture families.

One parameter-definition table (`param_defs`) drives initialization,
abstract (dry-run) parameters and partition specs.  One set of step
functions (`loss_fn`, `prefill`, `decode_step`) covers:

* dense / MoE / VLM decoder-only transformers (GQA + RoPE + SwiGLU),
* Mamba2 SSD stacks (attention-free),
* zamba2-style hybrids (SSD stack + ONE shared attention block applied
  every k layers — the shared block is a rented core: one weight set,
  many QTs),
* interleaved stacks (a Mamba2 or a GQA mixer per layer, as
  ``cfg.layer_types`` names them, each followed by an MLP; muP
  multipliers — granite-4.0-h),
* whisper-style encoder-decoder (stub audio frontend per assignment).

Layers are stacked (leading L axis) and scanned — the FOR-mode discipline:
the loop lives in one compiled `lax.scan`, layer weights are all-gathered
(FSDP) right before use, exactly EMPA's clone-the-glue-on-rent.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ArchConfig
from repro.models import attention as attn_lib
from repro.models import layers, moe, ssm
from repro.models.params import ParamDef, abstract_params, axes_tree, init_params

BLOCKWISE_THRESHOLD = 2048   # use online-softmax attention above this S
AUX_LOSS_WEIGHT = 0.01
LOSS_CHUNK = 1024            # FOR-mode chunked CE (never materialize B,S,V)


# ===========================================================================
# Parameter definitions
# ===========================================================================

def _attn_defs(prefix, cfg: ArchConfig, n_layers: Optional[int],
               cross: bool = False) -> list[ParamDef]:
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    lead = () if n_layers is None else (n_layers,)
    la = () if n_layers is None else ("layers",)
    sfx = "x" if cross else ""
    return [
        ParamDef(prefix + (f"w{sfx}q",), lead + (d, h, dh),
                 la + ("w_embed", "heads", None)),
        ParamDef(prefix + (f"w{sfx}k",), lead + (d, hkv, dh),
                 la + ("w_embed", "kv_heads", None)),
        ParamDef(prefix + (f"w{sfx}v",), lead + (d, hkv, dh),
                 la + ("w_embed", "kv_heads", None)),
        ParamDef(prefix + (f"w{sfx}o",), lead + (h, dh, d),
                 la + ("heads", None, "w_embed"),
                 scale=1.0 / (h * dh) ** 0.5),
    ]


def _mlp_defs(prefix, cfg: ArchConfig, n_layers: Optional[int]) -> list[ParamDef]:
    d, f = cfg.d_model, cfg.d_ff
    lead = () if n_layers is None else (n_layers,)
    la = () if n_layers is None else ("layers",)
    out = []
    if cfg.act == "silu":
        out.append(ParamDef(prefix + ("w_gate",), lead + (d, f),
                            la + ("w_embed", "ffn")))
    out += [
        ParamDef(prefix + ("w_up",), lead + (d, f), la + ("w_embed", "ffn")),
        ParamDef(prefix + ("w_down",), lead + (f, d), la + ("ffn", "w_embed")),
    ]
    return out


def _moe_defs(prefix, cfg: ArchConfig, n_layers: int) -> list[ParamDef]:
    d, fe, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ll, la = (n_layers,), ("layers",)
    out = [
        ParamDef(prefix + ("router",), ll + (d, e), la + (None, None)),
        ParamDef(prefix + ("w_gate",), ll + (e, d, fe),
                 la + ("experts", "w_embed", "ffn")),
        ParamDef(prefix + ("w_up",), ll + (e, d, fe),
                 la + ("experts", "w_embed", "ffn")),
        ParamDef(prefix + ("w_down",), ll + (e, fe, d),
                 la + ("experts", "ffn", "w_embed")),
    ]
    if cfg.n_shared_experts:
        fs = fe * cfg.n_shared_experts
        out += [
            ParamDef(prefix + ("sh_gate",), ll + (d, fs), la + ("w_embed", "ffn")),
            ParamDef(prefix + ("sh_up",), ll + (d, fs), la + ("w_embed", "ffn")),
            ParamDef(prefix + ("sh_down",), ll + (fs, d), la + ("ffn", "w_embed")),
        ]
    return out


def _mamba_defs(prefix, cfg: ArchConfig, n_layers: int) -> list[ParamDef]:
    d, di = cfg.d_model, cfg.d_inner
    k, cdim, h = ssm.proj_dim(cfg), ssm.conv_dim(cfg), cfg.ssm_nheads
    ll, la = (n_layers,), ("layers",)
    return [
        ParamDef(prefix + ("ln",), ll + (d,), la + (None,), init="ones"),
        ParamDef(prefix + ("w_in",), ll + (d, k), la + ("w_embed", "conv_dim")),
        ParamDef(prefix + ("conv_w",), ll + (cfg.ssm_conv, cdim),
                 la + (None, "conv_dim"), scale=0.1),
        ParamDef(prefix + ("conv_b",), ll + (cdim,), la + ("conv_dim",),
                 init="zeros"),
        ParamDef(prefix + ("a_log",), ll + (h,), la + ("ssm_heads",),
                 init="zeros"),
        ParamDef(prefix + ("d_skip",), ll + (h,), la + ("ssm_heads",),
                 init="ones"),
        ParamDef(prefix + ("dt_bias",), ll + (h,), la + ("ssm_heads",),
                 init="zeros"),
        ParamDef(prefix + ("norm_w",), ll + (di,), la + ("conv_dim",),
                 init="ones"),
        ParamDef(prefix + ("w_out",), ll + (di, d), la + ("conv_dim", "w_embed"),
                 scale=1.0 / di**0.5),
    ]


def _norm(prefix, cfg, n_layers, name) -> ParamDef:
    lead = () if n_layers is None else (n_layers,)
    la = () if n_layers is None else ("layers",)
    return ParamDef(prefix + (name,), lead + (cfg.d_model,), la + (None,),
                    init="ones")


def param_defs(cfg: ArchConfig) -> list[ParamDef]:
    # embedding tables use the TP-padded vocab; logits beyond cfg.vocab are
    # masked at the loss/decode boundary (layers.unembed_logits)
    d, v = cfg.d_model, cfg.vocab_padded
    defs: list[ParamDef] = [
        ParamDef(("embed", "tok"), (v, d), ("vocab", "w_embed"), init="embed"),
        ParamDef(("final_norm",), (d,), (None,), init="ones"),
    ]
    if not cfg.tie_embeddings:
        defs.append(ParamDef(("unembed",), (v, d), ("vocab", "w_embed"),
                             init="embed"))
    if cfg.pos_embed == "learned":
        defs.append(ParamDef(("embed", "pos"), (cfg.max_position, d),
                             (None, "w_embed"), init="embed"))
    if cfg.frontend:
        defs.append(ParamDef(("frontend", "proj"), (cfg.frontend_dim, d),
                             (None, "w_embed")))

    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        L = cfg.n_layers
        defs += [_norm(("layers",), cfg, L, "ln1"), _norm(("layers",), cfg, L, "ln2")]
        defs += _attn_defs(("layers",), cfg, L)
        if cfg.is_moe:
            defs += _moe_defs(("layers",), cfg, L)
        else:
            defs += _mlp_defs(("layers",), cfg, L)
    elif fam == "ssm":
        defs += _mamba_defs(("layers",), cfg, cfg.n_layers)
    elif fam == "interleaved":
        L, na = cfg.n_layers, cfg.n_attn_layers
        defs += [_norm(("mlp",), cfg, L, "ln")] + _mlp_defs(("mlp",), cfg, L)
        defs += [_norm(("attn",), cfg, na, "ln")]
        defs += _attn_defs(("attn",), cfg, na)
        defs += _mamba_defs(("mamba",), cfg, L - na)
    elif fam == "hybrid":
        defs += _mamba_defs(("layers",), cfg, cfg.n_layers)
        defs += [_norm(("shared",), cfg, None, "ln1"),
                 _norm(("shared",), cfg, None, "ln2")]
        defs += _attn_defs(("shared",), cfg, None)
        defs += _mlp_defs(("shared",), cfg, None)
    elif fam == "encdec":
        Le, Ld = cfg.enc_layers, cfg.dec_layers
        defs += [_norm(("encoder",), cfg, Le, "ln1"),
                 _norm(("encoder",), cfg, Le, "ln2")]
        defs += _attn_defs(("encoder",), cfg, Le)
        defs += _mlp_defs(("encoder",), cfg, Le)
        defs.append(ParamDef(("enc_norm",), (d,), (None,), init="ones"))
        defs += [_norm(("decoder",), cfg, Ld, "ln1"),
                 _norm(("decoder",), cfg, Ld, "lnx"),
                 _norm(("decoder",), cfg, Ld, "ln2")]
        defs += _attn_defs(("decoder",), cfg, Ld)
        defs += _attn_defs(("decoder",), cfg, Ld, cross=True)
        defs += _mlp_defs(("decoder",), cfg, Ld)
    else:
        raise ValueError(fam)
    return defs


def init(key, cfg: ArchConfig, dtype=jnp.bfloat16):
    return init_params(param_defs(cfg), key, dtype)


def abstract(cfg: ArchConfig, dtype=jnp.bfloat16):
    return abstract_params(param_defs(cfg), dtype)


def logical_axes(cfg: ArchConfig):
    return axes_tree(param_defs(cfg))


# ===========================================================================
# Blocks
# ===========================================================================

def _sh(x, axes):
    from repro.runtime.sharding import shard
    return shard(x, axes)


def _attention(x_q, x_kv, p, cfg: ArchConfig, q_pos, kv_pos, *, causal,
               sfx="", cache_kv=None, cache_len=None):
    """Projections + RoPE + attention.  Returns (out, (k, v))."""
    q = jnp.einsum("bsd,dhk->bshk", x_q, p[f"w{sfx}q"])
    k = jnp.einsum("bsd,dhk->bshk", x_kv, p[f"w{sfx}k"])
    v = jnp.einsum("bsd,dhk->bshk", x_kv, p[f"w{sfx}v"])
    if cfg.pos_embed == "rope":
        q = layers.apply_rope(q, q_pos, cfg.rope_theta)
        k = layers.apply_rope(k, kv_pos, cfg.rope_theta)
    q = _sh(_scale_q(q, cfg), ("batch", None, "heads_act", None))
    if cache_kv is not None:
        # decode: attend over the cache (k/v already written by caller)
        ck, cv = cache_kv
        o = attn_lib.decode_attention(q, ck, cv, cache_len)
    elif x_q.shape[1] > BLOCKWISE_THRESHOLD:
        o = attn_lib.blockwise_attention(q, k, v, causal=causal)
    else:
        o = attn_lib.full_attention(q, k, v, causal=causal)
    out = jnp.einsum("bshk,hkd->bsd", o, p[f"w{sfx}o"])
    return out, (k, v)


def _scale_q(q, cfg: ArchConfig):
    """muP attention scale: the kernels scale scores by 1/sqrt(head_dim),
    so q is pre-scaled to make the product ``attention_multiplier``."""
    if cfg.attention_multiplier is None:
        return q
    return q * (cfg.attention_multiplier * cfg.head_dim ** 0.5)


def _residual(h, cfg: ArchConfig):
    """muP residual branch scale."""
    return h if cfg.residual_multiplier == 1.0 else \
        h * cfg.residual_multiplier


def _embed_tokens(params, tokens, cfg: ArchConfig):
    x = layers.embed(params["embed"]["tok"], tokens)
    return x if cfg.embedding_multiplier == 1.0 else \
        x * cfg.embedding_multiplier


def _ffn(x, p, cfg: ArchConfig):
    """MLP or MoE.  Returns (y, aux_loss)."""
    if cfg.is_moe:
        from repro.runtime.sharding import current_rules
        rules = current_rules()
        if rules is not None and _moe_shardable(x, cfg, rules.mesh):
            return moe.moe_ffn_sharded(x, p, cfg, cfg.act, rules.mesh)
        return moe.moe_ffn(x, p, cfg, cfg.act)
    return layers.mlp(x, p, cfg.act), jnp.float32(0.0)


def _moe_shardable(x, cfg, mesh) -> bool:
    """The explicit-locality EP path needs clean divisibility (see moe.py)."""
    if "model" not in mesh.shape or "data" not in mesh.shape:
        return False
    dp = 1
    for a in ("pod", "data"):
        dp *= mesh.shape.get(a, 1)
    return (cfg.n_experts % mesh.shape["model"] == 0
            and cfg.d_model % mesh.shape["data"] == 0
            and x.shape[0] % dp == 0)


def _decoder_layer(x, lp, cfg: ArchConfig, positions):
    # NOTE (§Perf, granite-8b E3 — REFUTED): constraining the residual to
    # S-sharded-over-model here (Megatron sequence parallelism) made the
    # collective term 4× WORSE under GSPMD: the blockwise-attention KV
    # chunk path hits involuntary remat and the per-microbatch weight
    # grads get all-reduced over data.  Proper SP needs a manual
    # shard_map attention block; left as future work.
    h, _ = _attention(layers.rms_norm(x, lp["ln1"], cfg.norm_eps),
                      layers.rms_norm(x, lp["ln1"], cfg.norm_eps),
                      lp, cfg, positions, positions, causal=True)
    # named so the "block_save" remat policy can keep the TP-psum'd block
    # outputs: backward then never replays the psums (§Perf E4)
    x = x + checkpoint_name(h, "attn_out")
    y, aux = _ffn(layers.rms_norm(x, lp["ln2"], cfg.norm_eps), lp, cfg)
    return x + checkpoint_name(y, "mlp_out"), aux


def _mamba_layer(x, lp, cfg: ArchConfig):
    h, _ = ssm.mamba2_block(layers.rms_norm(x, lp["ln"], cfg.norm_eps), lp, cfg)
    return x + h


MAMBA_LEAVES, ATTN_LEAVES = ("conv", "state"), ("k", "v")


def slot_state_leaves(cfg: ArchConfig) -> tuple:
    """Cache leaves of per-slot recurrent state (batch axis 1) that a
    paged cache keeps beside its pages."""
    return MAMBA_LEAVES if cfg.family == "interleaved" else ()


def _interleaved_stack(params, x, cfg: ArchConfig, mamba_fn, attn_fn,
                       state: dict, remat=False, remat_policy=None):
    """The interleaved family's layers.  Each layer adds its mixer, of
    the kind ``cfg.layer_types`` names, over its pre-norm, then its MLP
    over its own pre-norm, both through ``_residual``.

    One ``lax.scan`` over whole periods of the pattern
    (``cfg.layer_period``) with each period's layers unrolled, so a
    layer's kind is static and each mixer reads only its own weights.
    ``state`` holds cache leaves stacked over the layers of their kind
    (``MAMBA_LEAVES`` over the Mamba layers, ``ATTN_LEAVES`` over the
    attention layers; ``{}`` without a cache).  ``mamba_fn`` and
    ``attn_fn`` take ``(normed x, layer weights, the layer's leaves)``
    and return ``(out, new leaves)``.  Returns ``(x, new state)``.

    Each layer indexes its weights and leaves out of the whole stacks,
    and writes its leaves back into the carried stacks in place: as
    scan inputs and outputs, XLA copies out each period's weights and
    builds a second stack of the leaves (at granite-4.0-h-micro's
    decode chunk, 9.3 GB of temporaries against 2.4 GB)."""
    period = cfg.layer_period
    kinds = cfg.layer_types[:period]
    count = {k: kinds.count(k) for k in ("mamba", "attention")}
    group = {"mamba": ("mamba", mamba_fn, MAMBA_LEAVES),
             "attention": ("attn", attn_fn, ATTN_LEAVES)}

    def take(tree, i):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
            tree)

    def body(carry, p):
        x, st = carry
        at = {"mamba": 0, "attention": 0}
        for j, kind in enumerate(kinds):
            name, fn, leaf_names = group[kind]
            i = p * count[kind] + at[kind]
            at[kind] += 1
            lp = take(params[name], i)
            names = [k for k in leaf_names if k in st]
            h, leaves = fn(layers.rms_norm(x, lp["ln"], cfg.norm_eps), lp,
                           tuple(take(st[k], i) for k in names))
            st = dict(st, **{
                k: jax.lax.dynamic_update_index_in_dim(st[k], leaf, i, 0)
                for k, leaf in zip(names, leaves)})
            x = x + _residual(h, cfg)
            mp = take(params["mlp"], p * period + j)
            y = layers.mlp(layers.rms_norm(x, mp["ln"], cfg.norm_eps), mp,
                           cfg.act)
            x = x + _residual(y, cfg)
        return (x, st), None

    (x, state), _ = jax.lax.scan(_maybe_remat(body, remat, remat_policy),
                                 (x, state),
                                 jnp.arange(cfg.n_layers // period))
    return x, state


def _shared_attn_block(x, sp, cfg: ArchConfig, positions):
    h, kv = _attention(layers.rms_norm(x, sp["ln1"], cfg.norm_eps),
                       layers.rms_norm(x, sp["ln1"], cfg.norm_eps),
                       sp, cfg, positions, positions, causal=True)
    x = x + h
    y = layers.mlp(layers.rms_norm(x, sp["ln2"], cfg.norm_eps), sp, cfg.act)
    return x + y, kv


# ===========================================================================
# Forward (training / full-sequence)
# ===========================================================================

def _embed_inputs(params, batch, cfg: ArchConfig):
    """Token (+frontend) embedding.  Returns (x (B,S,d), positions (S,))."""
    tok = batch["tokens"]
    x = _embed_tokens(params, tok, cfg)
    if cfg.frontend == "vision":
        vis = jnp.einsum("bnf,fd->bnd",
                         batch["vision_embeds"].astype(x.dtype),
                         params["frontend"]["proj"])
        x = jnp.concatenate([vis, x], axis=1)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    if cfg.pos_embed == "learned":
        x = x + layers.learned_pos_embed(params["embed"]["pos"], positions)
    return _sh(x, ("batch", None, None)), positions


def _maybe_remat(body, remat, policy):
    if not remat:
        return body
    return jax.checkpoint(body, policy=policy)


def _run_stack(params, x, cfg: ArchConfig, positions, *, remat,
               remat_policy=None):
    """Scan the decoder stack. Returns (x, aux_loss)."""
    fam = cfg.family

    if fam in ("dense", "moe", "vlm"):
        def body(carry, lp):
            y, aux = _decoder_layer(carry, lp, cfg, positions)
            return y, aux
        f = _maybe_remat(body, remat, remat_policy)
        x, auxs = jax.lax.scan(f, x, params["layers"])
        return x, jnp.sum(auxs)

    if fam == "ssm":
        def body(carry, lp):
            return _mamba_layer(carry, lp, cfg), jnp.float32(0.0)
        f = _maybe_remat(body, remat, remat_policy)
        x, _ = jax.lax.scan(f, x, params["layers"])
        return x, jnp.float32(0.0)

    if fam == "interleaved":
        ssd = functools.partial(ssm.ssd_chunked, chunk=cfg.ssm_chunk)

        def mamba(h, lp, _):
            return ssm.mamba2_block(h, lp, cfg, ssd_fn=ssd)[0], ()

        def attend(h, lp, _):
            return _attention(h, h, lp, cfg, positions, positions,
                              causal=True)[0], ()
        x, _ = _interleaved_stack(params, x, cfg, mamba, attend, {},
                                  remat=remat, remat_policy=remat_policy)
        return x, jnp.float32(0.0)

    if fam == "hybrid":
        every = cfg.shared_attn_every
        sp = params["shared"]

        def body(carry, inp):
            lp, idx = inp
            y = _mamba_layer(carry, lp, cfg)
            y = jax.lax.cond(
                (idx % every) == every - 1,
                lambda z: _shared_attn_block(z, sp, cfg, positions)[0],
                lambda z: z, y)
            return y, jnp.float32(0.0)
        f = _maybe_remat(body, remat, remat_policy)
        x, _ = jax.lax.scan(f, x, (params["layers"],
                                   jnp.arange(cfg.n_layers)))
        return x, jnp.float32(0.0)

    raise ValueError(fam)


def _encoder(params, batch, cfg: ArchConfig):
    """Whisper-style encoder over precomputed frame embeddings (stub)."""
    frames = batch["enc_embeds"]
    x = jnp.einsum("bsf,fd->bsd",
                   frames.astype(params["frontend"]["proj"].dtype),
                   params["frontend"]["proj"])
    pos = jnp.arange(x.shape[1], dtype=jnp.int32)
    if cfg.pos_embed == "learned":
        x = x + layers.learned_pos_embed(params["embed"]["pos"], pos)

    def body(carry, lp):
        h, _ = _attention(layers.rms_norm(carry, lp["ln1"], cfg.norm_eps),
                          layers.rms_norm(carry, lp["ln1"], cfg.norm_eps),
                          lp, cfg, pos, pos, causal=False)
        y = carry + h
        m = layers.mlp(layers.rms_norm(y, lp["ln2"], cfg.norm_eps), lp, cfg.act)
        return y + m, None
    x, _ = jax.lax.scan(body, x, params["encoder"])
    return layers.rms_norm(x, params["enc_norm"], cfg.norm_eps)


def _decoder_encdec(params, x, enc_out, cfg: ArchConfig, positions,
                    remat, remat_policy=None):
    enc_pos = jnp.arange(enc_out.shape[1], dtype=jnp.int32)

    def body(carry, lp):
        h, _ = _attention(layers.rms_norm(carry, lp["ln1"], cfg.norm_eps),
                          layers.rms_norm(carry, lp["ln1"], cfg.norm_eps),
                          lp, cfg, positions, positions, causal=True)
        y = carry + h
        hx, _ = _attention(layers.rms_norm(y, lp["lnx"], cfg.norm_eps),
                           enc_out, lp, cfg, positions, enc_pos,
                           causal=False, sfx="x")
        y = y + hx
        m = layers.mlp(layers.rms_norm(y, lp["ln2"], cfg.norm_eps), lp, cfg.act)
        return y + m, None
    f = _maybe_remat(body, remat, remat_policy)
    x, _ = jax.lax.scan(f, x, params["decoder"])
    return x


def forward(params, batch, cfg: ArchConfig, *, remat=False,
            remat_policy=None):
    """Full-sequence forward.  Returns (hidden (B,S,d), aux_loss)."""
    if cfg.family == "encdec":
        enc_out = _encoder(params, batch, cfg)
        x = layers.embed(params["embed"]["tok"], batch["tokens"])
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
        if cfg.pos_embed == "learned":
            x = x + layers.learned_pos_embed(params["embed"]["pos"], positions)
        x = _decoder_encdec(params, x, enc_out, cfg, positions, remat,
                            remat_policy)
        aux = jnp.float32(0.0)
    else:
        x, positions = _embed_inputs(params, batch, cfg)
        x, aux = _run_stack(params, x, cfg, positions, remat=remat,
                            remat_policy=remat_policy)
    return layers.rms_norm(x, params["final_norm"], cfg.norm_eps), aux


def _unembed_table(params, cfg: ArchConfig):
    return params["embed"]["tok"] if cfg.tie_embeddings else params["unembed"]


def _logits(x, params, cfg: ArchConfig):
    logits = layers.unembed_logits(x, _unembed_table(params, cfg),
                                   true_vocab=cfg.vocab)
    return logits if cfg.logits_scaling == 1.0 else \
        logits / cfg.logits_scaling


def chunked_loss(x, table, labels, chunk: int = LOSS_CHUNK,
                 true_vocab=None):
    """FOR-mode CE: scan over sequence chunks; (B,S,V) never materializes."""
    b, s, _ = x.shape
    chunk = min(chunk, s)
    if s % chunk:
        chunk = s  # odd smoke-test sizes: single chunk
    nc = s // chunk
    xc = x.reshape(b, nc, chunk, -1).swapaxes(0, 1)
    lc = labels.reshape(b, nc, chunk).swapaxes(0, 1)

    def body(carry, inp):
        xb, lb = inp
        logits = layers.unembed_logits(xb, table, true_vocab=true_vocab)
        logits = _sh(logits, ("batch", None, "vocab_act"))
        n = jnp.sum((lb >= 0).astype(jnp.float32))
        return (carry[0] + layers.cross_entropy(logits, lb) * n,
                carry[1] + n), None
    (tot, n), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)),
                               (xc, lc))
    return tot / jnp.maximum(n, 1.0)


def loss_fn(params, batch, cfg: ArchConfig, *, remat=True, remat_policy=None):
    """Mean next-token CE (+ MoE aux).  Returns (loss, metrics)."""
    x, aux = forward(params, batch, cfg, remat=remat,
                     remat_policy=remat_policy)
    labels = batch["labels"]
    if cfg.frontend == "vision":
        x = x[:, -labels.shape[1]:]   # loss over the text tail only
    if cfg.logits_scaling != 1.0:
        x = x / cfg.logits_scaling    # logits are linear in x
    ce = chunked_loss(x, _unembed_table(params, cfg), labels,
                      true_vocab=cfg.vocab)
    loss = ce + AUX_LOSS_WEIGHT * aux
    return loss, {"ce": ce, "aux": aux}


# ===========================================================================
# Serving: prefill + decode with caches
# ===========================================================================

def _kv_cache_axes():
    return ("cache_batch", None, "cache_kv_heads", None)


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Paged KV layout: the cache is a pool of `n_blocks` blocks of
    `block_size` positions, addressed per slot through a block table
    (runtime/paging.py owns the rent/release discipline over them).

    Causal attention caches page (dense/moe/vlm, and the attention
    layers of the interleaved family); recurrent state is O(1) per slot
    and has nothing to page: the interleaved family keeps it per slot
    beside the pages.
    """

    block_size: int
    n_blocks: int

    def max_blocks(self, max_seq: int) -> int:
        return -(-max_seq // self.block_size)


# families whose attention caches page and whose prompts go in as
# fragments (prefill_chunk); the interleaved family's Mamba layers carry
# their state across fragments per slot
PAGED_FAMILIES = ("dense", "moe", "vlm", "interleaved")


def init_cache(cfg: ArchConfig, batch: int, max_seq: int, dtype=jnp.bfloat16,
               abstract_only: bool = False,
               layout: Optional[PagedLayout] = None):
    """Cache pytree for `decode_step` (shapes depend on the family).

    With `layout` given, attention K/V live in `(L, n_blocks, block_size,
    hkv, dh)` pages plus a per-slot `block_tables` leaf (-1 = end of
    chain); without it, the contiguous `(L, batch, max_seq, hkv, dh)`
    allocation.  Both shapes go through the same `decode_step`.
    """
    mk = (lambda s, dt: jax.ShapeDtypeStruct(s, dt)) if abstract_only else \
         (lambda s, dt: jnp.zeros(s, dt))

    def kv(n_layers: int, *names: str) -> dict:
        hkv, dh = cfg.n_kv_heads, cfg.head_dim
        shape = (n_layers, batch, max_seq, hkv, dh) if layout is None else \
            (n_layers, layout.n_blocks, layout.block_size, hkv, dh)
        return {name: mk(shape, dtype) for name in names}

    def recurrent(n_layers: int) -> dict:
        return {
            "conv": mk((n_layers, batch, cfg.ssm_conv - 1,
                        ssm.conv_dim(cfg)), dtype),
            "state": mk((n_layers, batch, cfg.ssm_nheads,
                         cfg.ssm_headdim, cfg.ssm_state), jnp.float32),
        }

    fam = cfg.family
    if layout is not None and fam not in PAGED_FAMILIES:
        raise ValueError(
            f"paged KV cache supports {PAGED_FAMILIES}, not {fam!r}: "
            "recurrent/cross-attention state is not paged")
    cache = {"pos": mk((batch,), jnp.int32)}
    if fam in PAGED_FAMILIES:
        n_attn = cfg.n_attn_layers if fam == "interleaved" else cfg.n_layers
        cache.update(kv(n_attn, "k", "v"))
        if fam == "interleaved":
            cache.update(recurrent(cfg.n_layers - n_attn))
        if layout is not None:
            nb = layout.max_blocks(max_seq)
            cache["block_tables"] = mk((batch, nb), jnp.int32) \
                if abstract_only else jnp.full((batch, nb), -1, jnp.int32)
    elif fam == "ssm":
        cache.update(recurrent(cfg.n_layers))
    elif fam == "hybrid":
        cache.update(recurrent(cfg.n_layers))
        cache.update(kv(cfg.n_layers // cfg.shared_attn_every, "k", "v"))
    elif fam == "encdec":
        cache.update(kv(cfg.dec_layers, "k", "v", "xk", "xv"))
    return cache


def _prefill_paged(params, batch, cfg: ArchConfig, max_seq: int,
                   layout: PagedLayout, lengths=None):
    """Paged prefill: contiguous prefill over the (block-rounded) prompt
    span, then scatter the K/V blocks into pages with full identity
    chains (row i owns blocks ``i*nb_full .. (i+1)*nb_full - 1``, so
    decode up to ``max_seq`` never needs growth).  The serving engine
    instead scatters into *rented* blocks and grows chains on demand
    (runtime/paging.py); this path is the standalone cache API (plans,
    parity tests, single-shot generation)."""
    if cfg.family not in PAGED_FAMILIES or cfg.recurrent:
        # fail before the inner prefill; recurrent state goes in through
        # prefill_chunk
        raise ValueError(
            f"paged KV cache supports {PAGED_FAMILIES} without recurrent "
            f"layers in one static prefill, not {cfg.family!r}")
    bsz = batch["tokens"].shape[0]
    bs = layout.block_size
    span = batch["tokens"].shape[1]
    if cfg.frontend == "vision":
        span += cfg.n_frontend_tokens
    span_pad = -(-span // bs) * bs
    nb = span_pad // bs
    nb_full = layout.max_blocks(max_seq)
    if bsz * nb_full > layout.n_blocks:
        raise ValueError(f"static paged prefill needs {bsz * nb_full} "
                         f"blocks, pool has {layout.n_blocks}")
    logits, cc = prefill(params, batch, cfg, span_pad, lengths=lengths)
    cache = init_cache(cfg, bsz, max_seq, dtype=cc["k"].dtype, layout=layout)
    n_layers = cc["k"].shape[0]
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    chains = jnp.arange(bsz * nb_full, dtype=jnp.int32).reshape(bsz, nb_full)
    for name in ("k", "v"):
        blocks = cc[name].reshape(n_layers, bsz, nb, bs, hkv, dh)
        cache[name] = cache[name].at[:, chains[:, :nb]].set(blocks)
    cache["block_tables"] = chains
    cache["pos"] = cc["pos"]
    return logits, cache


def prefill(params, batch, cfg: ArchConfig, max_seq: int, lengths=None,
            layout: Optional[PagedLayout] = None):
    """Run the prompt; return (last-token logits (B, V), filled cache).

    With ``lengths`` (B,) given, rows are right-padded prompts: logits are
    gathered at each row's last *valid* position and ``cache["pos"]`` is
    set per row, so one batched call prefills many admitted requests at
    once (continuous-batching packed prefill).  Causal attention keeps the
    valid prefix exact under right-padding, and the pad tail of the KV
    cache is masked at decode by ``pos``.  For recurrent families
    (ssm/hybrid) the state would absorb pad tokens — callers must pass
    exact-length rows (or ``lengths=None``) there.

    With ``layout`` given the returned cache is paged (see
    :class:`PagedLayout`); ``decode_step`` accepts either.
    """
    if layout is not None:
        return _prefill_paged(params, batch, cfg, max_seq, layout,
                              lengths=lengths)
    fam = cfg.family
    bsz = batch["tokens"].shape[0]
    # cache precision follows the parameters (bf16 in production, f32 in
    # the CPU consistency tests)
    cache = init_cache(cfg, bsz, max_seq, dtype=params["embed"]["tok"].dtype)
    if fam == "interleaved":
        # one fragment per row from position 0: right-padding is exact,
        # padded columns leave every state as it was
        tokens = batch["tokens"]
        if lengths is None:
            lengths = jnp.full((bsz,), tokens.shape[1], jnp.int32)
        return prefill_chunk(params, tokens, lengths, cache, cfg)

    if fam in ("dense", "moe", "vlm"):
        x, positions = _embed_inputs(params, batch, cfg)
        s = x.shape[1]

        def body(carry, lp):
            h_in = layers.rms_norm(carry, lp["ln1"], cfg.norm_eps)
            h, (k, v) = _attention(h_in, h_in, lp, cfg, positions, positions,
                                   causal=True)
            y = carry + h
            f, _ = _ffn(layers.rms_norm(y, lp["ln2"], cfg.norm_eps), lp, cfg)
            return y + f, (k, v)
        x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
        cache["k"] = cache["k"].at[:, :, :s].set(ks.astype(cache["k"].dtype))
        cache["v"] = cache["v"].at[:, :, :s].set(vs.astype(cache["v"].dtype))
        cache["pos"] = jnp.full((bsz,), s, jnp.int32)

    elif fam == "ssm":
        x, _ = _embed_inputs(params, batch, cfg)
        s = x.shape[1]

        def body(carry, lp):
            h_in = layers.rms_norm(carry, lp["ln"], cfg.norm_eps)
            h, state = ssm.mamba2_block(h_in, lp, cfg)
            # conv tail for seamless decode continuation
            zxbcdt = jnp.einsum("bsd,dk->bsk", h_in[:, -cfg.ssm_conv + 1:],
                                lp["w_in"])
            conv_tail = zxbcdt[..., cfg.d_inner:
                               2 * cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state]
            return carry + h, (state, conv_tail)
        x, (states, tails) = jax.lax.scan(body, x, params["layers"])
        cache["state"] = states
        cache["conv"] = tails.astype(cache["conv"].dtype)
        cache["pos"] = jnp.full((bsz,), s, jnp.int32)

    elif fam == "hybrid":
        x, positions = _embed_inputs(params, batch, cfg)
        s = x.shape[1]
        every = cfg.shared_attn_every
        sp = params["shared"]
        shk, shv = cache["k"], cache["v"]

        def body(carry, inp):
            lp, idx = inp
            x_c, shk_c, shv_c = carry
            h_in = layers.rms_norm(x_c, lp["ln"], cfg.norm_eps)
            h, state = ssm.mamba2_block(h_in, lp, cfg)
            zxbcdt = jnp.einsum("bsd,dk->bsk", h_in[:, -cfg.ssm_conv + 1:],
                                lp["w_in"])
            conv_tail = zxbcdt[..., cfg.d_inner:
                               2 * cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state]
            y = x_c + h
            app = idx // every

            def apply_shared(args):
                z, shk_i, shv_i = args
                out, (k, v) = _shared_attn_block(z, sp, cfg, positions)
                shk_i = jax.lax.dynamic_update_slice(
                    shk_i, k[None, :, :, :, :].astype(shk_i.dtype),
                    (app, 0, 0, 0, 0))
                shv_i = jax.lax.dynamic_update_slice(
                    shv_i, v[None].astype(shv_i.dtype), (app, 0, 0, 0, 0))
                return out, shk_i, shv_i

            y, shk_c, shv_c = jax.lax.cond(
                (idx % every) == every - 1, apply_shared,
                lambda args: args, (y, shk_c, shv_c))
            return (y, shk_c, shv_c), (state, conv_tail)
        (x, shk, shv), (states, tails) = jax.lax.scan(
            body, (x, shk[:, :, :s], shv[:, :, :s]),
            (params["layers"], jnp.arange(cfg.n_layers)))
        cache["k"] = cache["k"].at[:, :, :s].set(shk.astype(cache["k"].dtype))
        cache["v"] = cache["v"].at[:, :, :s].set(shv.astype(cache["v"].dtype))
        cache["state"] = states
        cache["conv"] = tails.astype(cache["conv"].dtype)
        cache["pos"] = jnp.full((bsz,), s, jnp.int32)

    elif fam == "encdec":
        enc_out = _encoder(params, batch, cfg)
        se = enc_out.shape[1]
        x = layers.embed(params["embed"]["tok"], batch["tokens"])
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
        if cfg.pos_embed == "learned":
            x = x + layers.learned_pos_embed(params["embed"]["pos"], positions)
        enc_pos = jnp.arange(se, dtype=jnp.int32)
        s = x.shape[1]

        def body(carry, lp):
            h_in = layers.rms_norm(carry, lp["ln1"], cfg.norm_eps)
            h, (k, v) = _attention(h_in, h_in, lp, cfg, positions, positions,
                                   causal=True)
            y = carry + h
            hx, (xk, xv) = _attention(
                layers.rms_norm(y, lp["lnx"], cfg.norm_eps), enc_out, lp, cfg,
                positions, enc_pos, causal=False, sfx="x")
            y = y + hx
            m = layers.mlp(layers.rms_norm(y, lp["ln2"], cfg.norm_eps), lp,
                           cfg.act)
            return y + m, (k, v, xk, xv)
        x, (ks, vs, xks, xvs) = jax.lax.scan(body, x, params["decoder"])
        cache["k"] = cache["k"].at[:, :, :s].set(ks.astype(cache["k"].dtype))
        cache["v"] = cache["v"].at[:, :, :s].set(vs.astype(cache["v"].dtype))
        cache["xk"] = cache["xk"].at[:, :, :se].set(xks.astype(cache["xk"].dtype))
        cache["xv"] = cache["xv"].at[:, :, :se].set(xvs.astype(cache["xv"].dtype))
        cache["pos"] = jnp.full((bsz,), s, jnp.int32)
    else:
        raise ValueError(fam)

    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if lengths is None:
        x_last = x[:, -1]
    else:
        lengths = jnp.asarray(lengths, jnp.int32)
        # vision frontends prepend stub tokens: offset the text positions
        offset = x.shape[1] - batch["tokens"].shape[1]
        x_last = x[jnp.arange(bsz), offset + lengths - 1]
        cache["pos"] = (offset + lengths).astype(jnp.int32)
    logits = _logits(x_last, params, cfg)
    return logits, cache


def _decode_attn_layer(x1, lp, cfg, k_l, v_l, pos, sfx=""):
    """One-token attention against a cache layer; writes K/V at `pos`."""
    bsz = x1.shape[0]
    q_pos = pos[:, None] if pos.ndim == 1 else pos
    q = jnp.einsum("bsd,dhk->bshk", x1, lp[f"w{sfx}q"])
    k = jnp.einsum("bsd,dhk->bshk", x1, lp[f"w{sfx}k"])
    v = jnp.einsum("bsd,dhk->bshk", x1, lp[f"w{sfx}v"])
    if cfg.pos_embed == "rope":
        q = layers.apply_rope(q, q_pos, cfg.rope_theta)
        k = layers.apply_rope(k, q_pos, cfg.rope_theta)
    q = _scale_q(q, cfg)
    # write the new K/V at each row's position
    bidx = jnp.arange(bsz)
    k_l = k_l.at[bidx, pos].set(k[:, 0].astype(k_l.dtype))
    v_l = v_l.at[bidx, pos].set(v[:, 0].astype(v_l.dtype))
    o = attn_lib.decode_attention(q, k_l, v_l, pos + 1)
    out = jnp.einsum("bshk,hkd->bsd", o, lp[f"w{sfx}o"])
    return out, k_l, v_l


def _decode_attn_layer_paged(x1, lp, cfg, k_l, v_l, pos, blk, off, tables,
                             sfx=""):
    """One-token attention against a paged cache layer: write the new
    K/V into (block, offset) of each row's chain, then attend through
    the block table.  Rows with no valid block (retired / released
    chains, `blk` < 0) drop the write — they can never corrupt a live
    chain's pages."""
    n_pages = k_l.shape[0]
    q_pos = pos[:, None]
    q = jnp.einsum("bsd,dhk->bshk", x1, lp[f"w{sfx}q"])
    k = jnp.einsum("bsd,dhk->bshk", x1, lp[f"w{sfx}k"])
    v = jnp.einsum("bsd,dhk->bshk", x1, lp[f"w{sfx}v"])
    if cfg.pos_embed == "rope":
        q = layers.apply_rope(q, q_pos, cfg.rope_theta)
        k = layers.apply_rope(k, q_pos, cfg.rope_theta)
    q = _scale_q(q, cfg)
    wblk = jnp.where(blk >= 0, blk, n_pages)   # out of range -> dropped
    k_l = k_l.at[wblk, off].set(k[:, 0].astype(k_l.dtype), mode="drop")
    v_l = v_l.at[wblk, off].set(v[:, 0].astype(v_l.dtype), mode="drop")
    o = attn_lib.paged_decode_attention(q, k_l, v_l, tables, pos + 1)
    out = jnp.einsum("bshk,hkd->bsd", o, lp[f"w{sfx}o"])
    return out, k_l, v_l


def decode_step(params, token, cache, cfg: ArchConfig, advance=None):
    """One decode step.  token: (B,) int32.  Returns (logits (B,V), cache).

    Accepts either cache layout from :func:`init_cache`: the presence of
    ``block_tables`` selects the paged write/attend path.  ``advance``
    (B,) bool, optional (interleaved family): the rows whose recurrent
    state the step advances; the others keep theirs.
    """
    bsz = token.shape[0]
    pos = cache["pos"]
    x = _embed_tokens(params, token, cfg)[:, None]   # (B,1,d)
    if cfg.pos_embed == "learned":
        x = x + layers.learned_pos_embed(params["embed"]["pos"],
                                         pos[:, None])
    fam = cfg.family
    paged = fam in PAGED_FAMILIES and "block_tables" in cache

    if paged:
        tables = cache["block_tables"]
        blk_size = cache["k"].shape[2]
        nb = tables.shape[1]
        blk_idx = pos // blk_size
        blk = jnp.take_along_axis(
            tables, jnp.clip(blk_idx, 0, nb - 1)[:, None], axis=1)[:, 0]
        # beyond-capacity rows (frozen retired slots at pos == max_seq)
        # must not clamp into a live block
        blk = jnp.where(blk_idx < nb, blk, -1)
        off = pos % blk_size

    if fam == "interleaved":
        def mamba(h, lp, leaves):
            y, conv_l, state_l = ssm.mamba2_decode(h[:, 0], lp, cfg,
                                                   *leaves, advance)
            return y[:, None], (conv_l, state_l)

        def attend(h, lp, leaves):
            if paged:
                out, k_l, v_l = _decode_attn_layer_paged(
                    h, lp, cfg, *leaves, pos, blk, off, tables)
            else:
                out, k_l, v_l = _decode_attn_layer(h, lp, cfg, *leaves, pos)
            return out, (k_l, v_l)
        x, new = _interleaved_stack(
            params, x, cfg, mamba, attend,
            {k: cache[k] for k in MAMBA_LEAVES + ATTN_LEAVES})
        cache = dict(cache, **new)

    elif paged:
        def body(carry, inp):
            lp, k_l, v_l = inp
            h_in = layers.rms_norm(carry, lp["ln1"], cfg.norm_eps)
            h, k_l, v_l = _decode_attn_layer_paged(h_in, lp, cfg, k_l, v_l,
                                                   pos, blk, off, tables)
            y = carry + h
            f, _ = _ffn(layers.rms_norm(y, lp["ln2"], cfg.norm_eps), lp, cfg)
            return y + f, (k_l, v_l)
        x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache["k"],
                                             cache["v"]))
        cache = dict(cache, k=ks, v=vs)

    elif fam in ("dense", "moe", "vlm"):
        def body(carry, inp):
            lp, k_l, v_l = inp
            h_in = layers.rms_norm(carry, lp["ln1"], cfg.norm_eps)
            h, k_l, v_l = _decode_attn_layer(h_in, lp, cfg, k_l, v_l, pos)
            y = carry + h
            f, _ = _ffn(layers.rms_norm(y, lp["ln2"], cfg.norm_eps), lp, cfg)
            return y + f, (k_l, v_l)
        x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache["k"],
                                             cache["v"]))
        cache = dict(cache, k=ks, v=vs)

    elif fam == "ssm":
        def body(carry, inp):
            lp, conv_l, state_l = inp
            h_in = layers.rms_norm(carry[:, 0], lp["ln"], cfg.norm_eps)
            h, conv_l, state_l = ssm.mamba2_decode(h_in, lp, cfg, conv_l,
                                                   state_l)
            return carry + h[:, None], (conv_l, state_l)
        x, (convs, states) = jax.lax.scan(
            body, x, (params["layers"], cache["conv"], cache["state"]))
        cache = dict(cache, conv=convs, state=states)

    elif fam == "hybrid":
        every = cfg.shared_attn_every
        sp = params["shared"]

        def body(carry, inp):
            lp, conv_l, state_l, idx = inp
            x_c, shk, shv = carry
            h_in = layers.rms_norm(x_c[:, 0], lp["ln"], cfg.norm_eps)
            h, conv_l, state_l = ssm.mamba2_decode(h_in, lp, cfg, conv_l,
                                                   state_l)
            y = x_c + h[:, None]
            app = idx // every

            def apply_shared(args):
                z, shk_i, shv_i = args
                k_l = jax.lax.dynamic_slice_in_dim(shk_i, app, 1, 0)[0]
                v_l = jax.lax.dynamic_slice_in_dim(shv_i, app, 1, 0)[0]
                h_a, k_l, v_l = _decode_attn_layer(
                    layers.rms_norm(z, sp["ln1"], cfg.norm_eps), sp, cfg,
                    k_l, v_l, pos)
                z2 = z + h_a
                m = layers.mlp(layers.rms_norm(z2, sp["ln2"], cfg.norm_eps),
                               sp, cfg.act)
                shk_i = jax.lax.dynamic_update_slice_in_dim(
                    shk_i, k_l[None], app, 0)
                shv_i = jax.lax.dynamic_update_slice_in_dim(
                    shv_i, v_l[None], app, 0)
                return z2 + m, shk_i, shv_i

            y, shk, shv = jax.lax.cond((idx % every) == every - 1,
                                       apply_shared, lambda a: a,
                                       (y, shk, shv))
            return (y, shk, shv), (conv_l, state_l)
        (x, shk, shv), (convs, states) = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["layers"], cache["conv"], cache["state"],
             jnp.arange(cfg.n_layers)))
        cache = dict(cache, k=shk, v=shv, conv=convs, state=states)

    elif fam == "encdec":
        enc_len = cache["pos"] * 0 + cache["xk"].shape[2]  # full cross memory

        def body(carry, inp):
            lp, k_l, v_l, xk_l, xv_l = inp
            h_in = layers.rms_norm(carry, lp["ln1"], cfg.norm_eps)
            h, k_l, v_l = _decode_attn_layer(h_in, lp, cfg, k_l, v_l, pos)
            y = carry + h
            qx = jnp.einsum("bsd,dhk->bshk",
                            layers.rms_norm(y, lp["lnx"], cfg.norm_eps),
                            lp["wxq"])
            ox = attn_lib.decode_attention(qx, xk_l, xv_l, enc_len)
            y = y + jnp.einsum("bshk,hkd->bsd", ox, lp["wxo"])
            m = layers.mlp(layers.rms_norm(y, lp["ln2"], cfg.norm_eps), lp,
                           cfg.act)
            return y + m, (k_l, v_l)
        x, (ks, vs) = jax.lax.scan(
            body, x, (params["decoder"], cache["k"], cache["v"],
                      cache["xk"], cache["xv"]))
        cache = dict(cache, k=ks, v=vs)
    else:
        raise ValueError(fam)

    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(x[:, 0], params, cfg)
    cache["pos"] = pos + 1
    return logits, cache


def prefill_chunk(params, tokens, lengths, cache, cfg: ArchConfig,
                  skip_until=None, all_logits: bool = False):
    """Prefill *continuation*: consume one left-aligned prompt fragment
    per row against an existing cache, at each row's position offset.

    The paper's cores outsource fragments, not whole jobs — this is that
    discipline for prompts: instead of one monolithic prefill, the prompt
    is fed in ``(B, C)`` chunks, each writing K/V at positions
    ``cache["pos"] .. cache["pos"] + length - 1`` and attending causally
    through the position-offset mask (:func:`attention.chunk_attention`).

    * ``tokens`` (B, C) int32, ``lengths`` (B,) int32 — rows with length
      0 are untouched (no writes, ``pos`` unchanged, logits garbage);
      a length-1 row is exactly a decode step, so one call advances a
      mix of prefilling and decoding rows (the serving engine's unified
      tick).
    * ``skip_until`` (B,) int32 — optional write fence: positions below
      it are *not* stored (they live in shared prefix blocks an earlier
      chain already wrote); attention still reads them from the cache.
    * Works on both cache layouts from :func:`init_cache` (contiguous
      and paged).  ``PAGED_FAMILIES`` without a frontend only: a
      frontend's prepended embeddings are not in token space.  In the
      interleaved family each row's Mamba layers advance their conv
      window and SSM state over that row's ``length`` tokens only
      (``ssm.mamba2_chunk``), so any fragment width is exact; they
      cannot skip a prefix, so ``skip_until`` fences only the K/V
      writes of the attention layers.

    Returns ``(logits (B, V) at each row's last valid column, advanced
    cache)``.  With ``all_logits=True`` the logits are returned for
    *every* fragment column — ``(B, C, V)`` — which is what the
    speculative verify tick needs: one forward scores all k+1 candidate
    positions at once (columns past a row's length carry garbage; the
    caller masks by length).
    """
    if cfg.family not in PAGED_FAMILIES or cfg.frontend:
        raise ValueError(
            f"chunked prefill supports causal attention caches "
            f"{PAGED_FAMILIES} without a frontend, not "
            f"{cfg.family!r} (frontend={cfg.frontend!r})")
    bsz, span = tokens.shape
    lengths = jnp.asarray(lengths, jnp.int32)
    pos0 = cache["pos"]
    cols = jnp.arange(span, dtype=jnp.int32)
    q_pos = pos0[:, None] + cols[None, :]           # (B, C) absolute
    valid = cols[None, :] < lengths[:, None]
    if skip_until is not None:
        valid = valid & (q_pos >= jnp.asarray(skip_until,
                                              jnp.int32)[:, None])
    x = _embed_tokens(params, tokens, cfg)
    if cfg.pos_embed == "learned":
        x = x + layers.learned_pos_embed(params["embed"]["pos"], q_pos)

    paged = "block_tables" in cache
    if paged:
        tables = cache["block_tables"]
        n_pages, blk_size = cache["k"].shape[1], cache["k"].shape[2]
        nb = tables.shape[1]
        # attended-span rung for the whole tick: every layer clamps its
        # KV work to the same pow2 slice (hoisted out of the scan)
        span_idx = attn_lib.attended_span(q_pos, nb * blk_size)
        blk_idx = q_pos // blk_size
        blk = jnp.take_along_axis(tables, jnp.clip(blk_idx, 0, nb - 1),
                                  axis=1)
        blk = jnp.where(blk_idx < nb, blk, -1)
        # invalid columns (and chain holes) -> out of range -> dropped
        wblk = jnp.where(valid & (blk >= 0), blk, n_pages)
        off = q_pos % blk_size
    else:
        smax = cache["k"].shape[2]
        wpos = jnp.where(valid, q_pos, smax)
        bidx = jnp.arange(bsz)[:, None]
        span_idx = attn_lib.attended_span(q_pos, smax)

    def attend(h_in, lp, k_l, v_l):
        q = jnp.einsum("bsd,dhk->bshk", h_in, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h_in, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h_in, lp["wv"])
        if cfg.pos_embed == "rope":
            q = layers.apply_rope(q, q_pos, cfg.rope_theta)
            k = layers.apply_rope(k, q_pos, cfg.rope_theta)
        q = _scale_q(q, cfg)
        # write-then-attend: the fragment's own K/V are in the cache
        # before the position-offset causal mask reads them
        if paged:
            k_l = k_l.at[wblk, off].set(k.astype(k_l.dtype), mode="drop")
            v_l = v_l.at[wblk, off].set(v.astype(v_l.dtype), mode="drop")
            o = attn_lib.paged_chunk_attention(q, k_l, v_l, tables, q_pos,
                                               span_idx=span_idx)
        else:
            k_l = k_l.at[bidx, wpos].set(k.astype(k_l.dtype), mode="drop")
            v_l = v_l.at[bidx, wpos].set(v.astype(v_l.dtype), mode="drop")
            o = attn_lib.chunk_attention(q, k_l, v_l, q_pos,
                                         span_idx=span_idx)
        return jnp.einsum("bshk,hkd->bsd", o, lp["wo"]), k_l, v_l

    def body(carry, inp):
        lp, k_l, v_l = inp
        h_in = layers.rms_norm(carry, lp["ln1"], cfg.norm_eps)
        h, k_l, v_l = attend(h_in, lp, k_l, v_l)
        y = carry + h
        f, _ = _ffn(layers.rms_norm(y, lp["ln2"], cfg.norm_eps), lp, cfg)
        return y + f, (k_l, v_l)

    if cfg.family == "interleaved":
        def mamba(h, lp, leaves):
            y, conv_l, state_l = ssm.mamba2_chunk(h, lp, cfg, *leaves,
                                                  lengths)
            return y, (conv_l, state_l)

        def attend_layer(h, lp, leaves):
            out, k_l, v_l = attend(h, lp, *leaves)
            return out, (k_l, v_l)
        x, new = _interleaved_stack(
            params, x, cfg, mamba, attend_layer,
            {k: cache[k] for k in MAMBA_LEAVES + ATTN_LEAVES})
        cache = dict(cache, **new)
    else:
        x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache["k"],
                                             cache["v"]))
        cache = dict(cache, k=ks, v=vs)
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if all_logits:
        logits = _logits(x, params, cfg)                   # (B, C, V)
    else:
        x_last = x[jnp.arange(bsz), jnp.clip(lengths - 1, 0, span - 1)]
        logits = _logits(x_last, params, cfg)
    cache = dict(cache, pos=pos0 + lengths)
    return logits, cache


# ===========================================================================
# Accounting (roofline's MODEL_FLOPS)
# ===========================================================================

def model_flops(cfg: ArchConfig, tokens: int, kind: str = "train") -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE); 2·N·D for fwd-only."""
    n = cfg.active_param_count() if cfg.is_moe else cfg.param_count()
    mult = 6.0 if kind == "train" else 2.0
    return mult * n * tokens
