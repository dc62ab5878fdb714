"""The work the traffic requires, counted from the configuration and the
served lengths alone.

Nothing here looks at how the program served the work: not the kernel,
not the padding, not the block table's length, not the slots left idle.
A program that stops doing work the traffic does not need therefore
raises its roofline and utilization shares; it cannot change the
yardstick.

The counts of one token come from the configuration's architecture
module ``arch`` (``archs/<arch>.py``): ``matmul_params``,
``attention_flops`` and ``kv_bytes_per_position``.  The sums over a
prompt or over decode steps are built here from them, one context at a
time, so that a block whose attention does not grow with the context
(a window, a recurrent state) counts its FLOPs right.  The bytes of
decode attention assume that every cached position is read.
"""
from __future__ import annotations


def attention_sum(arch, m: dict, first: int, n: int) -> float:
    """Attention FLOPs of ``n`` queries over ``first``, ``first + 1``,
    ... ``first + n - 1`` positions."""
    return sum(arch.attention_flops(m, c) for c in range(first, first + n))


def token_flops(arch, m: dict, context: int) -> float:
    """One token's forward, attending over ``context`` positions (its
    own included)."""
    return 2.0 * arch.matmul_params(m) + arch.attention_flops(m, context)


def prompt_flops(arch, m: dict, prompt_len: int) -> float:
    """A whole prompt, causally: position p attends over p + 1."""
    return (2.0 * arch.matmul_params(m) * prompt_len
            + attention_sum(arch, m, 1, prompt_len))


def decode_contexts(prompt_len: int, out_before: int, steps: int) -> int:
    """Positions attended, summed over ``steps`` decode steps of a
    request with ``prompt_len`` prompt tokens that had emitted
    ``out_before`` tokens: step j feeds token ``out_before + j`` at
    position ``prompt_len + out_before + j - 1`` and attends over
    ``prompt_len + out_before + j`` positions."""
    c0 = prompt_len + out_before
    return steps * c0 + steps * (steps - 1) // 2


def decode_attention_work(arch, m: dict, prompt_len: int, out_before: int,
                          steps: int) -> tuple:
    """``(flops, bytes)`` the decode attention of those ``steps`` steps
    (see ``decode_contexts``) needs: every cached key and value read
    once."""
    return (attention_sum(arch, m, prompt_len + out_before, steps),
            arch.kv_bytes_per_position(m)
            * decode_contexts(prompt_len, out_before, steps))


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute
    bound and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
