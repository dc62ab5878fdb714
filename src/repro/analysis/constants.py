"""Constant-bloat audit: no large arrays baked into a tick jaxpr.

A numpy array closed over at trace time becomes a jaxpr constant:
re-materialized per compile, resident per executable, and invisible in
any profile of the arguments — the classic silent memory and
compile-time regression.  Tick state must arrive through the
signature (where the donation audit sees it), so the audit walks every
tick family's consts (sub-jaxprs included) and flags anything over the
threshold.  Small iota/mask scalars are fine and expected.
"""
from __future__ import annotations

from typing import List

import jax
import numpy as np

from repro.analysis.families import TickSpec
from repro.analysis.report import Finding, info, violation

DEFAULT_THRESHOLD_BYTES = 1 << 16     # 64 KiB


def _subjaxprs(params: dict):
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for val in params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, (Jaxpr, ClosedJaxpr)):
                yield v


def iter_consts(jaxpr):
    """Every constant bound by a jaxpr, recursing into sub-jaxprs."""
    for const in getattr(jaxpr, "consts", ()) or ():
        yield const
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        for sub in _subjaxprs(eqn.params):
            yield from iter_consts(sub)


def _nbytes(const) -> int:
    arr = np.asarray(const)
    return int(arr.size) * arr.dtype.itemsize


def audit_constants(spec: TickSpec, *,
                    threshold: int = DEFAULT_THRESHOLD_BYTES
                    ) -> List[Finding]:
    closed = jax.make_jaxpr(spec.step_fn)(*spec.abstract_args)
    findings: List[Finding] = []
    total = 0
    worst = 0
    for const in iter_consts(closed):
        size = _nbytes(const)
        total += size
        worst = max(worst, size)
        if size > threshold:
            arr = np.asarray(const)
            findings.append(violation(
                "constants", spec.name,
                f"{size}-byte constant ({arr.dtype}{list(arr.shape)}) "
                f"baked into the tick jaxpr (threshold {threshold}) — "
                f"state must arrive through the signature, not a "
                f"trace-time closure"))
    if not any(f.severity == "violation" for f in findings):
        findings.append(info(
            "constants", spec.name,
            f"{total} const bytes total, largest {worst} "
            f"(threshold {threshold})"))
    return findings
