"""The dense decoder block: pre-norm RMSNorm, grouped-query attention
with rotary positions in the rotate-half form, a SwiGLU or plain GELU
feed-forward, a final RMSNorm and an untied unembedding.  A
configuration names it with ``"arch": "dense"``.

It gives the harness what depends on the block (``cells.ARCH_API``):
the weights' shapes and scales, the plain float32 reference and its
float8 control, and the work counts.

Weights.  The tree is the one ``repro.models.model`` serves from
(stacked layers under ``layers``).  The scales are the benchmark's own,
chosen so that the model is not chaotic at full depth: every projection
has standard deviation 1/sqrt(fan-in), the two projections that write
the residual stream (``wo``, ``w_down``) a further 1/sqrt(2 * n_layers),
and the token embedding 1.  Each block then adds about
1/sqrt(2 * n_layers) of the embedding's scale to the residual,
attention scores are of order 1, and a relative rounding error of the
weights moves the logits by an error of the same order rather than by
the whole logit, as the program's own init does at 40 layers.  A
comparison with a float32 reference can then tell bfloat16 serving from
a lower precision.  The unembedding's scale sets the logits' standard
deviation to ``weights.LOGIT_STD``.

Reference.  Written from the published description of the block and
sharing no code with the program: plain ``jax.numpy``, no kernels, no
cache, no batching, one sequence at a time.  Every matrix product runs
at ``Precision.HIGHEST``, so that the TPU computes it in float32 and not
in bfloat16 passes.  ``precision="fp8"`` is the control: the same
forward with both operands of every matrix product rounded to float8
e4m3 (each tensor scaled so that its largest magnitude maps to 448, the
format's largest), the step below the bfloat16 the configurations
state.  The correctness check has to fail it.  The layers run in one
``lax.scan`` over the stacked weights, each layer's weights cast to
float32 only inside its step, and attention in blocks of query rows, so
that a 4k-token sequence of a 3B model fits beside the bfloat16 weights
on one 16 GB chip.

Work counts.  What the traffic requires, from the configuration alone
(``work.py`` builds its sums from them).
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
NEG = -1e30
KV_BYTES = 2                # bfloat16 cache entries


def shapes(m: dict) -> dict:
    """path -> (shape, std) of every weight of the dense model ``m``
    (the ``model`` entry of a configuration file); std ``None`` is a
    norm weight of ones."""
    L, d, h, hkv, dh, f = (m["n_layers"], m["d_model"], m["n_heads"],
                           m["n_kv_heads"], m["head_dim"], m["d_ff"])
    v = padded_vocab(m["vocab"])
    out_std = 1.0 / math.sqrt(2 * L)
    tree = {
        ("embed", "tok"): ((v, d), 1.0),
        ("final_norm",): ((d,), None),
        ("unembed",): ((v, d), LOGIT_STD / math.sqrt(d)),
        ("layers", "ln1"): ((L, d), None),
        ("layers", "ln2"): ((L, d), None),
        ("layers", "wq"): ((L, d, h, dh), 1.0 / math.sqrt(d)),
        ("layers", "wk"): ((L, d, hkv, dh), 1.0 / math.sqrt(d)),
        ("layers", "wv"): ((L, d, hkv, dh), 1.0 / math.sqrt(d)),
        ("layers", "wo"): ((L, h, dh, d), out_std / math.sqrt(h * dh)),
        ("layers", "w_up"): ((L, d, f), 1.0 / math.sqrt(d)),
        ("layers", "w_down"): ((L, f, d), out_std / math.sqrt(f)),
    }
    if m["act"] == "silu":
        tree[("layers", "w_gate")] = ((L, d, f), 1.0 / math.sqrt(d))
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


def _rope(x, pos, theta):
    """x (S, heads, D) at positions pos (S,): rotate-half rotary form."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / x.shape[-1])
    ang = pos[:, None, None].astype(jnp.float32) * inv
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def _attend(q, k, v, fp8: bool):
    """Causal grouped-query attention.  q (S, H, D), k/v (S, Hkv, D)."""
    s, h, d = q.shape
    hkv = k.shape[1]
    q = q.reshape(s, hkv, h // hkv, d)
    kpos = jnp.arange(s)
    out = []
    for q0 in range(0, s, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        sc = _mm("qgrd,kgd->grqk", qb, k, fp8) / jnp.sqrt(jnp.float32(d))
        qpos = q0 + jnp.arange(qb.shape[0])
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, NEG)
        p = jax.nn.softmax(sc, axis=-1)
        out.append(_mm("grqk,kgd->qgrd", p, v, fp8))
    return jnp.concatenate(out, 0).reshape(s, h, d)


def _layer(m: dict, fp8: bool, x, lp):
    lp = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), lp)
    s = x.shape[0]
    pos = jnp.arange(s)
    hn = _rms(x, lp["ln1"], m["norm_eps"])
    q = _rope(_mm("sd,dhk->shk", hn, lp["wq"], fp8), pos, m["rope_theta"])
    k = _rope(_mm("sd,dhk->shk", hn, lp["wk"], fp8), pos, m["rope_theta"])
    v = _mm("sd,dhk->shk", hn, lp["wv"], fp8)
    x = x + _mm("shk,hkd->sd", _attend(q, k, v, fp8), lp["wo"], fp8)
    hn = _rms(x, lp["ln2"], m["norm_eps"])
    up = _mm("sd,df->sf", hn, lp["w_up"], fp8)
    if m["act"] == "silu":
        up = jax.nn.silu(_mm("sd,df->sf", hn, lp["w_gate"], fp8)) * up
    elif m["act"] == "gelu":
        up = jax.nn.gelu(up, approximate=True)
    else:
        raise ValueError(f"unknown activation {m['act']!r}")
    return x + _mm("sf,fd->sd", up, lp["w_down"], fp8), None


@functools.partial(jax.jit, static_argnames=("m_items", "precision"))
def _forward(params, tokens, m_items, precision):
    m = dict(m_items)
    fp8 = precision == "fp8"
    x = params["embed"]["tok"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(functools.partial(_layer, m, fp8), x,
                        params["layers"])
    x = _rms(x, params["final_norm"].astype(jnp.float32), m["norm_eps"])
    table = params["unembed"][:m["vocab"]].astype(jnp.float32)
    return _mm("sd,vd->sv", x, table, fp8)


def logits(m: dict, params: dict, tokens, precision: str = "float32"):
    """Logits (S_pad, vocab) of every position of ``tokens`` (S,) under
    the dense model ``m`` (a configuration's ``model`` entry), the
    sequence padded at its end to a multiple of ``SEQ_BUCKET`` so that
    few programs compile; rows past ``len(tokens)`` are padding."""
    if precision not in ("float32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    s = len(tokens)
    padded = jnp.zeros((-(-s // SEQ_BUCKET) * SEQ_BUCKET,), jnp.int32)
    padded = padded.at[:s].set(jnp.asarray(tokens, jnp.int32))
    keys = ("vocab", "norm_eps", "rope_theta", "act")
    return _forward(params, padded, tuple((k, m[k]) for k in keys),
                    precision)


def matmul_params(m: dict) -> int:
    """Weights multiplied per token: the projections of every layer and
    the unembedding over the vocabulary (the embedding is a gather)."""
    d, h, hkv, dh, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["head_dim"], m["d_ff"])
    mlp = (3 if m["act"] == "silu" else 2) * d * f
    attn = 2 * d * h * dh + 2 * d * hkv * dh
    return m["n_layers"] * (attn + mlp) + d * m["vocab"]


def attention_flops(m: dict, context: int) -> float:
    """Scores and weighted sum of one query over ``context`` keys, in
    every layer."""
    return 4.0 * m["n_layers"] * m["n_heads"] * m["head_dim"] * context


def kv_bytes_per_position(m: dict) -> int:
    """Keys and values of one position, over all layers."""
    return m["n_layers"] * 2 * m["n_kv_heads"] * m["head_dim"] * KV_BYTES
