"""The work the traffic requires, counted from the configuration and the
served lengths alone.

Nothing here looks at how the program served the work: not the kernel,
not the padding, not the block table's length, not the slots left idle.
A program that stops doing work the traffic does not need therefore
raises its roofline and utilization shares; it cannot change the
yardstick.
"""
from __future__ import annotations

KV_BYTES = 2                        # bfloat16 cache entries


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


def token_flops(m: dict, context: int) -> float:
    """One token's forward, attending over ``context`` positions (its
    own included)."""
    return 2.0 * matmul_params(m) + attention_flops(m, context)


def prompt_flops(m: dict, prompt_len: int) -> float:
    """A whole prompt, causally: position p attends over p + 1."""
    return (2.0 * matmul_params(m) * prompt_len
            + attention_flops(m, 1) * prompt_len * (prompt_len + 1) / 2)


def kv_bytes_per_position(m: dict) -> int:
    """Keys and values of one position, over all layers."""
    return m["n_layers"] * 2 * m["n_kv_heads"] * m["head_dim"] * KV_BYTES


def decode_contexts(prompt_len: int, out_before: int, steps: int) -> int:
    """Positions attended, summed over ``steps`` decode steps of a
    request with ``prompt_len`` prompt tokens that had emitted
    ``out_before`` tokens: step j feeds token ``out_before + j`` at
    position ``prompt_len + out_before + j - 1`` and attends over
    ``prompt_len + out_before + j`` positions."""
    c0 = prompt_len + out_before
    return steps * c0 + steps * (steps - 1) // 2


def decode_attention_work(m: dict, contexts: int) -> tuple:
    """``(flops, bytes)`` the decode attention of ``contexts`` summed
    attended positions needs: every cached key and value read once."""
    return (attention_flops(m, 1) * contexts,
            kv_bytes_per_position(m) * contexts)


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute
    bound and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
