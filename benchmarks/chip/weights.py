"""Random weights for a configuration, made on the device from the seed
in one jitted call, in the dtype they are served in.  The tree and each
leaf's scale are the configuration's architecture module's
(``archs/<arch>.py``, its ``shapes``).

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

import jax
import jax.numpy as jnp

LOGIT_STD = 1.6


def padded_vocab(vocab: int, multiple: int = 32) -> int:
    """Rows of the embedding tables: the vocabulary rounded up to a
    multiple of 32, as the served model pads it.  Rows past ``vocab``
    are never read by the reference and masked by the program."""
    return -(-vocab // multiple) * multiple


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from all 64 bits of ``seed``."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def make(arch, m: dict, seed: int, device=None) -> dict:
    """The weights of model ``m`` of architecture module ``arch`` for
    ``seed``, on ``device`` (default: the first), in ``m["dtype"]``."""
    dtype = jnp.dtype(m["dtype"])
    table = arch.shapes(m)

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
