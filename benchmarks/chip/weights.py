"""Random weights for a dense configuration, made on the device from the
seed in one jitted call, in the dtype they are served in.

The tree is the one ``repro.models.model`` serves from (stacked layers
under ``layers``).  The scales are the benchmark's own, chosen so that
the model is not chaotic at full depth: every projection has standard
deviation 1/sqrt(fan-in), the two projections that write the residual
stream (``wo``, ``w_down``) a further 1/sqrt(2 * n_layers), and the
token embedding 1.  Each block then adds about 1/sqrt(2 * n_layers) of
the embedding's scale to the residual, attention scores are of order 1,
and a relative rounding error of the weights moves the logits by an
error of the same order rather than by the whole logit, as the
program's own init does at 40 layers.  A comparison with a float32
reference can then tell bfloat16 serving from a lower precision.

The unembedding's scale sets the logits' standard deviation to
``LOGIT_STD``.  The program rounds its logits to bfloat16 before the
argmax, so two near-tied logits within one bfloat16 step of each other
can swap: at the best of 49k logits (about 4.2 standard deviations)
that step is 2^-5 anywhere in [4, 8).  With a standard deviation of 1
the best logit sits just above 4, where the step is widest against the
logits' spread; at 1.6 it sits near 6.7, where the same step is
narrower against the spread by 1.6, while a float8 control's error
grows with the spread.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LOGIT_STD = 1.6


def padded_vocab(vocab: int, multiple: int = 32) -> int:
    """Rows of the embedding tables: the vocabulary rounded up to a
    multiple of 32, as the served model pads it.  Rows past ``vocab``
    are never read by the reference and masked by the program."""
    return -(-vocab // multiple) * multiple


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


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from all 64 bits of ``seed``."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def make(m: dict, seed: int, device=None) -> dict:
    """The weights of ``m`` for ``seed``, on ``device`` (default: the
    first), in ``m["dtype"]``."""
    dtype = jnp.dtype(m["dtype"])
    table = shapes(m)

    def build(key):
        tree: dict = {}
        for i, (path, (shape, std)) in enumerate(sorted(table.items())):
            if std is None:
                leaf = jnp.ones(shape, dtype)
            else:
                leaf = (std * jax.random.normal(jax.random.fold_in(key, i),
                                                shape, jnp.float32)
                        ).astype(dtype)
            node = tree
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = leaf
        return tree

    device = device or jax.devices()[0]
    out = jax.jit(build, out_shardings=jax.sharding.SingleDeviceSharding(
        device))(seed_key(seed))
    return jax.block_until_ready(out)
