"""The granite-4.0-h block (Hugging Face ``granitemoehybrid`` with no
experts): a stack in which each layer is a Mamba-2 mixer or a
grouped-query attention layer without position embeddings, as the
configuration's ``layer_types`` names them, each followed by a SwiGLU
MLP; RMSNorm before every mixer and MLP and at the end; muP multipliers
on the embeddings, the attention scores, every residual branch and the
logits; the unembedding tied to the token embedding.  A configuration
names it with ``"arch": "granite_hybrid"``.

It gives the harness what depends on the block (``cells.ARCH_API``),
and ``state_bytes_per_slot`` for the decode-state roofline.

Weights.  The tree is the one ``repro.models.model`` serves the
``interleaved`` family from: ``mlp`` (norm and MLP of every layer),
``attn`` (norm and projections of the attention layers) and ``mamba``
(norm and mixer of the Mamba layers), each stacked over its layers in
order.  The scales are the benchmark's own: every projection into a
block has standard deviation 1/sqrt(fan-in), and the projection that
writes the residual stream is scaled so that, after the residual
multiplier, the 2 * n_layers blocks together add about ``BRANCH_GAIN``
times the scaled embedding's size, each 1/sqrt(2 * n_layers) of that.
The embedding's scale sets the tied logits' standard deviation, after
their division, to ``weights.LOGIT_STD``.  Since the unembedding is the
embedding, a residual stream that stayed near the token's own embedding
(as ``archs/dense.py``'s gain of 1 would leave it) makes every token
predict itself: in a one-period probe at the published widths (10
layers, 256 random tokens, CPU) the reference's choice was the input
token at 100% of positions with a gain of 1, 99% with 8 and 20% with
16, where bfloat16 serving read a mean gap of 0.0063 and the float8
control 0.41.
The Mamba-2 parameters that the published model initialises to a range
(``A_log``, ``dt_bias``) are drawn with standard deviation 1, so that
the heads' decay per step ranges from fast to slow; ``D`` and the norms
are ones.

Reference.  Written from the published description of the block and
sharing no code with the program: plain ``jax.numpy``, no kernels, no
cache, no batching, one sequence at a time, every matrix product at
``Precision.HIGHEST``.  The Mamba-2 mixer: input projection into the
gate z, the convolved channels xBC and dt; a causal depthwise
convolution of width ``ssm_conv`` with bias over xBC, then SiLU; dt =
softplus(dt + dt_bias); A = -exp(A_log); the selective scan
h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t + D x_t,
computed chunk by chunk (``SSD_CHUNK`` positions: within a chunk in its
quadratic form, the state carried from chunk to chunk); then
RMSNorm(y * SiLU(z)) and the output projection.  Attention: causal, no
rotary embedding, scores times ``attention_multiplier``, in blocks of
query rows.  ``precision="fp8"`` is the control, as in
``archs/dense.py``: both operands of every matrix product rounded to
float8 e4m3.  The layers run one period of ``layer_types`` at a time
in a ``lax.scan``, each layer's weights cast to float32 only inside its
step, so that a 4k-token sequence fits beside the bfloat16 weights on
one 16 GB chip.

Work counts.  What the traffic requires, from the configuration alone:
the projections of every layer, and per token the sequence mixer of
every layer: the selective scan's state update and read in each Mamba
layer (independent of the context), attention over the context in each
attention layer.  Decode bytes of KV are those of the attention layers.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.chip.weights import LOGIT_STD, padded_vocab

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
Q_BLOCK = 512               # query rows per attention block
SEQ_BUCKET = 512            # sequences are padded to a multiple of this
SSD_CHUNK = 256             # positions per chunk of the selective scan
NEG = -1e30
KV_BYTES = 2                # bfloat16 cache entries
STATE_BYTES = 4             # float32 SSM state
BRANCH_GAIN = 16.0          # all blocks' writes against the embedding's


def _sizes(m: dict) -> dict:
    di = m["ssm_expand"] * m["d_model"]
    g, n = m["ssm_ngroups"], m["ssm_state"]
    heads = di // m["ssm_headdim"]
    n_attn = list(m["layer_types"]).count("attention")
    return {"di": di, "heads": heads, "conv": di + 2 * g * n,
            "proj": 2 * di + 2 * g * n + heads,
            "n_attn": n_attn, "n_mamba": m["n_layers"] - n_attn}


def _period(types: tuple) -> int:
    n = len(types)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and types == types[:p] * (n // p))


def shapes(m: dict) -> dict:
    """path -> (shape, std) of every weight of model ``m`` (the ``model``
    entry of a configuration file); std ``None`` is a weight of ones."""
    L, d, h, hkv, dh, f = (m["n_layers"], m["d_model"], m["n_heads"],
                           m["n_kv_heads"], m["head_dim"], m["d_ff"])
    z = _sizes(m)
    la, lm, di, width = z["n_attn"], z["n_mamba"], z["di"], m["ssm_conv"]
    v = padded_vocab(m["vocab"])
    tied = m.get("tie_embeddings", False)
    embed_std = (LOGIT_STD * m["logits_scaling"] / math.sqrt(d) if tied
                 else 1.0 / m["embedding_multiplier"])
    # what each block's output projection writes, before the residual
    # multiplier, for it to add BRANCH_GAIN * embedding scale / sqrt(2 L)
    branch = (BRANCH_GAIN * m["embedding_multiplier"] * embed_std
              / (m["residual_multiplier"] * math.sqrt(2 * L)))
    tree = {
        ("embed", "tok"): ((v, d), embed_std),
        ("final_norm",): ((d,), None),
        ("mlp", "ln"): ((L, d), None),
        ("mlp", "w_gate"): ((L, d, f), 1.0 / math.sqrt(d)),
        ("mlp", "w_up"): ((L, d, f), 1.0 / math.sqrt(d)),
        ("mlp", "w_down"): ((L, f, d), branch / math.sqrt(f)),
        ("attn", "ln"): ((la, d), None),
        ("attn", "wq"): ((la, d, h, dh), 1.0 / math.sqrt(d)),
        ("attn", "wk"): ((la, d, hkv, dh), 1.0 / math.sqrt(d)),
        ("attn", "wv"): ((la, d, hkv, dh), 1.0 / math.sqrt(d)),
        ("attn", "wo"): ((la, h, dh, d), branch / math.sqrt(h * dh)),
        ("mamba", "ln"): ((lm, d), None),
        ("mamba", "w_in"): ((lm, d, z["proj"]), 1.0 / math.sqrt(d)),
        ("mamba", "conv_w"): ((lm, width, z["conv"]),
                              1.0 / math.sqrt(width)),
        ("mamba", "conv_b"): ((lm, z["conv"]), 0.1),
        ("mamba", "a_log"): ((lm, z["heads"]), 1.0),
        ("mamba", "dt_bias"): ((lm, z["heads"]), 1.0),
        ("mamba", "d_skip"): ((lm, z["heads"]), None),
        ("mamba", "norm_w"): ((lm, di), None),
        ("mamba", "w_out"): ((lm, di, d), branch / math.sqrt(di)),
    }
    if not tied:
        tree[("unembed",)] = ((v, d),
                              LOGIT_STD * m["logits_scaling"] / math.sqrt(d))
    return tree


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _mm(spec, a, b, fp8: bool):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _attend(q, k, v, scale, fp8: bool):
    """Causal grouped-query attention, scores times ``scale``.
    q (S, H, D), k/v (S, Hkv, D)."""
    s, h, d = q.shape
    hkv = k.shape[1]
    q = q.reshape(s, hkv, h // hkv, d)
    kpos = jnp.arange(s)
    out = []
    for q0 in range(0, s, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        sc = _mm("qgrd,kgd->grqk", qb, k, fp8) * scale
        qpos = q0 + jnp.arange(qb.shape[0])
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, NEG)
        p = jax.nn.softmax(sc, axis=-1)
        out.append(_mm("grqk,kgd->qgrd", p, v, fp8))
    return jnp.concatenate(out, 0).reshape(s, h, d)


def _scan(x, dt, a, b, c, fp8: bool):
    """The selective scan from a zero state, chunk by chunk.  x (S, H, P),
    dt (S, H) after softplus, a (H,) = -exp(A_log), b/c (S, H, N)."""
    s, h, p = x.shape
    q = min(SSD_CHUNK, s)
    n = b.shape[-1]

    def chunk(state, inp):
        xq, dtq, bq, cq = inp
        la = jnp.cumsum(dtq * a, axis=0)           # log decay, (Q, H)
        t = jnp.arange(q)
        seg = jnp.where((t[:, None] >= t[None, :])[:, :, None],
                        la[:, None, :] - la[None, :, :], NEG)
        w = (jnp.exp(seg) * _mm("thn,shn->tsh", cq, bq, fp8)
             * dtq[None, :, :])
        y = (_mm("tsh,shp->thp", w, xq, fp8)
             + jnp.exp(la)[:, :, None] * _mm("thn,hpn->thp", cq, state, fp8))
        keep = jnp.exp(la[-1][None, :] - la) * dtq    # (Q, H)
        state = (jnp.exp(la[-1])[:, None, None] * state
                 + _mm("shp,shn->hpn", xq * keep[:, :, None], bq, fp8))
        return state, y

    split = lambda z: z.reshape(s // q, q, *z.shape[1:])
    _, y = jax.lax.scan(chunk, jnp.zeros((h, p, n), jnp.float32),
                        (split(x), split(dt), split(b), split(c)))
    return y.reshape(s, h, p)


def _mamba(m: dict, fp8: bool, hn, lp):
    z = _sizes(m)
    s = hn.shape[0]
    di, heads, cdim = z["di"], z["heads"], z["conv"]
    g, n, width = m["ssm_ngroups"], m["ssm_state"], m["ssm_conv"]
    proj = _mm("sd,dk->sk", hn, lp["w_in"], fp8)
    gate, xbc, dt = proj[:, :di], proj[:, di:di + cdim], proj[:, di + cdim:]
    hist = jnp.concatenate([jnp.zeros((width - 1, cdim)), xbc], 0)
    conv = lp["conv_b"] + sum(hist[i:i + s] * lp["conv_w"][i]
                              for i in range(width))
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :di].reshape(s, heads, -1)
    rep = heads // g
    b = jnp.repeat(xbc[:, di:di + g * n].reshape(s, g, n), rep, axis=1)
    c = jnp.repeat(xbc[:, di + g * n:].reshape(s, g, n), rep, axis=1)
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    y = _scan(xs, dt, -jnp.exp(lp["a_log"]), b, c, fp8)
    y = (y + xs * lp["d_skip"][:, None]).reshape(s, di)
    y = _rms(y * jax.nn.silu(gate), lp["norm_w"], m["norm_eps"])
    return _mm("sk,kd->sd", y, lp["w_out"], fp8)


def _attention(m: dict, fp8: bool, hn, lp):
    q = _mm("sd,dhk->shk", hn, lp["wq"], fp8)
    k = _mm("sd,dhk->shk", hn, lp["wk"], fp8)
    v = _mm("sd,dhk->shk", hn, lp["wv"], fp8)
    o = _attend(q, k, v, m["attention_multiplier"], fp8)
    return _mm("shk,hkd->sd", o, lp["wo"], fp8)


def _period_step(m: dict, fp8: bool, kinds: tuple, x, per):
    per = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), per)
    r, eps = m["residual_multiplier"], m["norm_eps"]
    used = {"mamba": 0, "attention": 0}
    for j, kind in enumerate(kinds):
        group, mixer = (("mamba", _mamba) if kind == "mamba"
                        else ("attn", _attention))
        lp = {k: w[used[kind]] for k, w in per[group].items()}
        used[kind] += 1
        x = x + r * mixer(m, fp8, _rms(x, lp["ln"], eps), lp)
        mp = {k: w[j] for k, w in per["mlp"].items()}
        hn = _rms(x, mp["ln"], eps)
        up = jax.nn.silu(_mm("sd,df->sf", hn, mp["w_gate"], fp8)) \
            * _mm("sd,df->sf", hn, mp["w_up"], fp8)
        x = x + r * _mm("sf,fd->sd", up, mp["w_down"], fp8)
    return x, None


@functools.partial(jax.jit, static_argnames=("m_items", "precision"))
def _forward(params, tokens, m_items, precision):
    m = dict(m_items)
    fp8 = precision == "fp8"
    types = m["layer_types"]
    period = _period(types)
    rounds = len(types) // period
    x = params["embed"]["tok"][tokens].astype(jnp.float32) \
        * m["embedding_multiplier"]
    per = jax.tree_util.tree_map(
        lambda w: w.reshape(rounds, w.shape[0] // rounds, *w.shape[1:]),
        {k: params[k] for k in ("mlp", "attn", "mamba")})
    x, _ = jax.lax.scan(
        functools.partial(_period_step, m, fp8, types[:period]), x, per)
    x = _rms(x, params["final_norm"].astype(jnp.float32), m["norm_eps"])
    table = params.get("unembed", params["embed"]["tok"])
    table = table[:m["vocab"]].astype(jnp.float32)
    return _mm("sd,vd->sv", x, table, fp8) / m["logits_scaling"]


MODEL_KEYS = ("vocab", "norm_eps", "layer_types", "ssm_expand", "d_model",
              "ssm_headdim", "ssm_ngroups", "ssm_state", "ssm_conv",
              "n_layers", "embedding_multiplier", "attention_multiplier",
              "residual_multiplier", "logits_scaling")


def logits(m: dict, params: dict, tokens, precision: str = "float32"):
    """Logits (S_pad, vocab) of every position of ``tokens`` (S,) under
    model ``m`` (a configuration's ``model`` entry), the sequence padded
    at its end to a multiple of ``SEQ_BUCKET`` so that few programs
    compile; rows past ``len(tokens)`` are padding."""
    if precision not in ("float32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    s = len(tokens)
    padded = jnp.zeros((-(-s // SEQ_BUCKET) * SEQ_BUCKET,), jnp.int32)
    padded = padded.at[:s].set(jnp.asarray(tokens, jnp.int32))
    items = tuple((k, tuple(m[k]) if k == "layer_types" else m[k])
                  for k in MODEL_KEYS)
    return _forward(params, padded, items, precision)


def matmul_params(m: dict) -> int:
    """Weights multiplied per token: the projections of every layer, the
    Mamba layers' convolution taps, and the unembedding over the
    vocabulary (the embedding is a gather)."""
    d, h, hkv, dh, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["head_dim"], m["d_ff"])
    z = _sizes(m)
    mamba = d * z["proj"] + z["di"] * d + m["ssm_conv"] * z["conv"]
    attn = 2 * d * h * dh + 2 * d * hkv * dh
    return (m["n_layers"] * 3 * d * f + z["n_attn"] * attn
            + z["n_mamba"] * mamba + d * m["vocab"])


def attention_flops(m: dict, context: int) -> float:
    """The sequence mixers of one token over ``context`` positions: in
    each attention layer the scores and weighted sum over the context,
    in each Mamba layer the state's update and read (a multiply-add per
    state element each), whatever the context."""
    z = _sizes(m)
    state = z["heads"] * m["ssm_headdim"] * m["ssm_state"]
    return (4.0 * z["n_attn"] * m["n_heads"] * m["head_dim"] * context
            + 4.0 * z["n_mamba"] * state)


def kv_bytes_per_position(m: dict) -> int:
    """Keys and values of one position, over the attention layers."""
    return (_sizes(m)["n_attn"] * 2 * m["n_kv_heads"] * m["head_dim"]
            * KV_BYTES)


def state_bytes_per_slot(m: dict) -> int:
    """One sequence's recurrent state over the Mamba layers: the float32
    SSM state and the conv window of the last ``ssm_conv - 1`` inputs,
    in the served dtype."""
    z = _sizes(m)
    ssm = z["heads"] * m["ssm_headdim"] * m["ssm_state"] * STATE_BYTES
    conv = (m["ssm_conv"] - 1) * z["conv"] * jnp.dtype(m["dtype"]).itemsize
    return z["n_mamba"] * (ssm + conv)
