"""Host-transfer audit: nothing inside a tick talks to the host.

Static side: walk every tick family's jaxpr (recursing through
``pjit`` / ``while`` / ``cond`` / ``scan`` sub-jaxprs) and fail on any
callback or host-transfer primitive — a ``jax.debug.print`` or
``pure_callback`` smuggled into the serving tick reintroduces the
per-token host round-trip PR 1 removed.

Runtime side: drive a real (tiny) engine under
``jax.transfer_guard_device_to_host("disallow")`` — the engine's
``debug_transfers=True`` mode.  "disallow" blocks *implicit* transfers
only, so the budgeted per-tick ``jax.device_get`` sync and the pool
ledger's explicit pulls pass, while any stray ``int()`` / ``bool()`` /
``np.asarray`` on a device array raises.  One step budget, proven, not
promised: the harness also reports host syncs per tick from
``sync_stats``.
"""
from __future__ import annotations

from typing import List

import jax
import numpy as np

from repro.analysis.families import TickSpec
from repro.analysis.report import Finding, info, violation

# primitives that move data to (or run code on) the host from inside a
# compiled program; `infeed`/`outfeed` for completeness on TPU paths
FORBIDDEN_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback",
    "infeed", "outfeed",
})


def _subjaxprs(params: dict):
    """Yield every Jaxpr / ClosedJaxpr nested in an eqn's params."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for val in params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, (Jaxpr, ClosedJaxpr)):
                yield v


def iter_primitives(jaxpr):
    """Every (primitive_name, eqn) in a jaxpr, sub-jaxprs included."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)   # ClosedJaxpr -> Jaxpr
    for eqn in inner.eqns:
        yield eqn.primitive.name, eqn
        for sub in _subjaxprs(eqn.params):
            yield from iter_primitives(sub)


def audit_transfers(spec: TickSpec) -> List[Finding]:
    findings: List[Finding] = []
    closed = jax.make_jaxpr(spec.step_fn)(*spec.abstract_args)
    hits = {}
    for name, _ in iter_primitives(closed):
        if name in FORBIDDEN_PRIMITIVES:
            hits[name] = hits.get(name, 0) + 1
    for name, count in sorted(hits.items()):
        findings.append(violation(
            "transfers", spec.name,
            f"{count} `{name}` primitive(s) inside the tick jaxpr — "
            f"a host round-trip compiled into the serving hot path"))
    if not hits:
        findings.append(info(
            "transfers", spec.name,
            "no callback/host-transfer primitives in the tick jaxpr"))
    return findings


class TransferSpy:
    """Runtime enforcement of the one-budgeted-sync discipline that
    also has teeth on the CPU backend.

    ``jax.transfer_guard_device_to_host("disallow")`` (which
    ``ServingEngine(debug_transfers=True)`` arms around every tick) is
    the real guard on accelerators — but on the CPU backend host and
    device share memory, nothing "transfers", and the guard is inert.
    So the harness patches the concrete array type's conversion dunders
    for the duration of a drive loop: an ``int()`` / ``bool()`` /
    ``float()`` / ``__index__`` on a device array is an *implicit*
    device->host materialization and is recorded as a violation with
    the offending frame, unless it happens inside an explicit
    ``jax.device_get`` (the planned, budgeted syncs — ``jax.device_get``
    is wrapped to mark its extent).  This is exactly the transfer-guard
    semantics, reimplemented where XLA cannot see the copy.
    """

    _DUNDERS = ("__int__", "__bool__", "__float__", "__index__",
                "__array__")

    def __init__(self):
        self.violations: List[str] = []
        self._explicit = 0
        self._saved = {}
        self._saved_get = None

    def _frame(self) -> str:
        import traceback
        for fr in reversed(traceback.extract_stack()):
            fn = fr.filename.replace("\\", "/")
            if "/repro/" in fn and "/analysis/" not in fn:
                short = fn.split("/repro/", 1)[1]
                return f"repro/{short}:{fr.lineno} in {fr.name}"
        return "<outside repo frames>"

    def __enter__(self):
        import jax.numpy as jnp
        cls = type(jnp.zeros(()))
        self._cls = cls
        spy = self

        def wrap(name, orig):
            def guard(self_arr, *a, **kw):
                if spy._explicit == 0:
                    spy.violations.append(
                        f"implicit {name} on a device array at "
                        f"{spy._frame()}")
                return orig(self_arr, *a, **kw)
            return guard

        for name in self._DUNDERS:
            orig = cls.__dict__[name]
            self._saved[name] = orig
            setattr(cls, name, wrap(name, orig))

        self._saved_get = jax.device_get

        def explicit_get(tree):
            spy._explicit += 1
            try:
                return spy._saved_get(tree)
            finally:
                spy._explicit -= 1
        jax.device_get = explicit_get
        return self

    def __exit__(self, *exc):
        for name, orig in self._saved.items():
            setattr(self._cls, name, orig)
        jax.device_get = self._saved_get
        return False


def run_transfer_harness() -> List[Finding]:
    """Serve a real request stream with every implicit device->host
    transfer forbidden, on both layouts (the paged cell composes
    chunked prefill + speculation + over-commit, so the guard covers
    admission, fragment scheduling, eviction and resume).  The engine
    runs with ``debug_transfers=True`` (the accelerator-side guard) and
    the whole drive loop runs under :class:`TransferSpy` (the CPU-side
    equivalent)."""
    import jax.numpy as jnp
    from repro.analysis.families import (BLOCK_SIZE, FRAGMENT, MAX_SEQ,
                                         N_BLOCKS, N_SLOTS, SPEC_K,
                                         audit_config)
    from repro.models import model
    from repro.runtime.serve import Request, ServingEngine

    cfg, _ = audit_config()
    params = model.init(jax.random.PRNGKey(0), cfg, jnp.float32)

    cells = {
        "contiguous/decode": dict(),
        "paged/chunked+spec+overcommit": dict(
            paged=True, block_size=BLOCK_SIZE, n_blocks=N_BLOCKS,
            chunked_prefill=True, prefill_chunk_tokens=FRAGMENT,
            speculative=True, spec_k=SPEC_K, overcommit=True),
    }
    findings: List[Finding] = []
    for cell, kw in cells.items():
        rng = np.random.default_rng(7)
        reqs = [Request(i, rng.integers(2, 100,
                                        size=int(rng.integers(4, 12)))
                        .astype(np.int32),
                        max_new=int(rng.integers(4, 10)))
                for i in range(5)]
        eng = ServingEngine(params, cfg, n_slots=N_SLOTS, max_seq=MAX_SEQ,
                            chunk=4, debug_transfers=True, **kw)
        steps = 0
        spy = TransferSpy()
        try:
            with spy:
                pending = list(reqs)
                while pending or eng.active or eng._parked \
                        or eng._finished_instant:
                    n = eng.admit_many(pending)
                    del pending[:n]
                    eng.step()
                    steps += 1
                    assert steps < 500, \
                        "harness drive loop did not converge"
        except Exception as exc:                 # noqa: BLE001
            findings.append(violation(
                "transfers", f"harness/{cell}",
                f"engine step raised under transfer_guard_device_to_host"
                f"('disallow') after {steps} steps: "
                f"{type(exc).__name__}: {exc}"))
            continue
        if spy.violations:
            uniq = sorted(set(spy.violations))
            findings.append(violation(
                "transfers", f"harness/{cell}",
                f"{len(spy.violations)} implicit device->host "
                f"materialization(s) over {steps} steps: "
                + "; ".join(uniq[:5])
                + ("; ..." if len(uniq) > 5 else "")))
            continue
        stats = eng.sync_stats()
        findings.append(info(
            "transfers", f"harness/{cell}",
            f"{steps} guarded+spied steps, zero implicit device->host "
            f"transfers; {stats['host_syncs']} budgeted syncs over "
            f"{stats['device_ticks']} device ticks"))
    return findings
