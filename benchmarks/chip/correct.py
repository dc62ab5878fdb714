"""Whether what the timed path served is right: a comparison of the
served tokens with the float32 reference of the configuration's
architecture module (``archs/<arch>.py``, its ``logits``), and the
control that it has to fail.

Once the window has closed, a sample of the finished requests, drawn
from the seed and always holding the one that served the most tokens,
is run through the reference: each prompt with its served tokens,
teacher-forced, one forward.  At every position whose next token was
served, the gap is the reference's best logit minus the reference's
logit of the served token: 0 where the program's greedy choice is the
reference's, small where rounding tipped a near tie, and of the order of
the logits' spread where the program served a wrong token.  Random
weights make greedy ties common, so tokens alone would fail sound runs;
the gap measures how wrong a served token is, not only whether it
differs.

The number compared is the mean gap over the sample's served tokens.
The widest gap, which a served model's check would compare first, is
an extreme of the rounding noise over thousands of positions: over the
seeds it swung by 2x for the program and came to only 2-3.5x the
program's at the float8 control (PERF.md), too close for a limit.  The
mean counts how often and how far rounding tips a choice, is steady
from seed to seed, and separates bfloat16 from float8 by far more.  It
is printed with the widest gap for information.

The control puts the reference, computed in float8, in the program's
place: at each of the same positions it serves the token its own logits
put first, and its gaps are read the same way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.traffic import seed_rng

MIN_REQUESTS = 4
MIN_TOKENS = 256


def sample(finished: list, seed: int) -> list:
    """Requests to check: the one with the most served tokens, then
    others in an order drawn from the seed, until the sample holds
    ``MIN_REQUESTS`` requests and ``MIN_TOKENS`` served tokens."""
    if not finished:
        return []
    ranked = sorted(finished, key=lambda r: (-len(r.out), r.rid))
    rest = ranked[1:]
    picked = [ranked[0]] + [rest[i] for i in
                            seed_rng(seed, 2).permutation(len(rest))]
    out, tokens = [], 0
    for r in picked:
        if len(out) >= MIN_REQUESTS and tokens >= MIN_TOKENS:
            break
        out.append(r)
        tokens += len(r.out)
    return out


@jax.jit
def _gaps(ref, served):
    """Per position: reference's best logit minus its logit of the
    token ``served`` there.  An id outside the vocabulary reads inf."""
    v = ref.shape[-1]
    ok = (served >= 0) & (served < v)
    at = jnp.take_along_axis(ref, jnp.clip(served, 0, v - 1)[:, None],
                             axis=-1)[:, 0]
    return jnp.where(ok, jnp.max(ref, axis=-1) - at, jnp.inf)


@jax.jit
def _top(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def served_positions(req) -> tuple:
    """``(tokens, targets, rows)``: the forward's input (prompt and all
    but the last served token), the served token each position
    predicts, and the positions that predicted a served token."""
    prompt = np.asarray(req.prompt, np.int64)
    out = np.asarray(req.out, np.int64)
    tokens = np.concatenate([prompt, out[:-1]])
    rows = np.arange(len(prompt) - 1, len(tokens))
    return tokens, out, rows


def gaps(arch, m: dict, params: dict, requests: list,
         control: bool = False) -> dict:
    """Gap readings over ``requests`` of model ``m`` under architecture
    module ``arch``, by who served the tokens:
    ``{"program": reading}`` and, with ``control``, ``"control"``: the
    float8 reference's own choices at the same positions.  A reading
    holds the ``mean`` and the ``widest`` gap and the ``tokens``
    checked."""
    parts = {"program": []}
    if control:
        parts["control"] = []
    for req in requests:
        tokens, served, rows = served_positions(req)
        ref = arch.logits(m, params, tokens)
        target = np.zeros(ref.shape[0], np.int32)
        target[rows] = served
        parts["program"].append(
            np.asarray(_gaps(ref, jnp.asarray(target)))[rows])
        if control:
            low = _top(arch.logits(m, params, tokens, "fp8"))
            parts["control"].append(np.asarray(_gaps(ref, low))[rows])
        del ref
    out = {}
    for who, got in parts.items():
        g = np.concatenate(got) if got else np.zeros(0)
        out[who] = {"mean": float(g.mean()) if g.size else float("inf"),
                    "widest": float(g.max()) if g.size else float("inf"),
                    "tokens": int(g.size)}
    return out


def decide(reading: dict, limits: dict) -> tuple:
    """``(correct, checks)`` of one reading (the program's, or the
    control's put in its place): each number compared, beside its
    limit.  The mean gap is at most its limit; the tokens checked are
    at least theirs."""
    limit = limits["mean_gap"]["limit"]
    checks = {"mean_gap": {"value": reading["mean"], "limit": limit},
              "tokens_checked": {"value": reading["tokens"],
                                 "limit": MIN_TOKENS}}
    ok = reading["mean"] <= limit and reading["tokens"] >= MIN_TOKENS
    return ok, checks
