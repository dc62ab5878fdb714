"""Transfer audit: callback primitives in jaxprs, and the TransferSpy."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.transfers import (TransferSpy, audit_transfers,
                                      iter_primitives)

F32 = jnp.float32


def _violations(findings):
    return [f for f in findings if f.severity == "violation"]


def test_callback_smuggled_into_jaxpr_fires(make_spec):
    # jax.debug.print compiles to a debug_print primitive — a host
    # round-trip inside the tick.
    def step(params, tok, cache):
        jax.debug.print("tok {}", tok)
        return tok + 1, cache

    spec = make_spec(
        step,
        (jax.ShapeDtypeStruct((8,), F32),
         jax.ShapeDtypeStruct((4,), jnp.int32),
         jax.ShapeDtypeStruct((4, 16), F32)),
        donate_argnums=(2,))
    bad = _violations(audit_transfers(spec))
    assert bad, "a callback primitive inside the tick must be a violation"
    assert any("debug_print" in f.message for f in bad)


def test_pure_callback_in_nested_scope_fires(make_spec):
    # recursion check: the callback hides inside a lax.cond branch
    def step(params, tok, cache):
        def branch(t):
            return jax.pure_callback(
                lambda x: np.asarray(x), jax.ShapeDtypeStruct(t.shape,
                                                              t.dtype), t)
        tok = jax.lax.cond(tok[0] > 0, branch, lambda t: t, tok)
        return tok, cache

    spec = make_spec(
        step,
        (jax.ShapeDtypeStruct((8,), F32),
         jax.ShapeDtypeStruct((4,), jnp.int32),
         jax.ShapeDtypeStruct((4, 16), F32)))
    bad = _violations(audit_transfers(spec))
    assert any("pure_callback" in f.message for f in bad)


def test_clean_tick_has_no_forbidden_primitives(make_spec):
    def step(params, tok, cache):
        return tok + 1, cache * params[0]

    spec = make_spec(
        step,
        (jax.ShapeDtypeStruct((8,), F32),
         jax.ShapeDtypeStruct((4,), jnp.int32),
         jax.ShapeDtypeStruct((4, 16), F32)))
    findings = audit_transfers(spec)
    assert not _violations(findings)
    # the walker still saw real primitives
    closed = jax.make_jaxpr(spec.step_fn)(*spec.abstract_args)
    assert any(name for name, _ in iter_primitives(closed))


def test_transfer_spy_catches_implicit_int():
    x = jnp.ones(())
    spy = TransferSpy()
    with spy:
        assert int(x) == 1
    assert spy.violations
    assert "__int__" in spy.violations[0]


def test_transfer_spy_catches_implicit_bool_and_float():
    x = jnp.ones(())
    spy = TransferSpy()
    with spy:
        bool(x)
        float(x)
    kinds = "".join(spy.violations)
    assert "__bool__" in kinds and "__float__" in kinds


def test_transfer_spy_allows_explicit_device_get():
    x = jnp.arange(4)
    spy = TransferSpy()
    with spy:
        host = jax.device_get(x)
        assert int(host[2]) == 2          # numpy by now: not spied
    assert spy.violations == []


def test_transfer_spy_restores_dunders_on_exit():
    x = jnp.ones(())
    with TransferSpy():
        pass
    spy = TransferSpy()
    int(x)                                 # outside any spy: no record
    assert spy.violations == []
