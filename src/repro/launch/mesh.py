"""Production meshes.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — required because the dry-run overrides the
platform device count and the smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_degraded_mesh(level: int = 0, *, multi_pod: bool = True):
    """Elastic ladder (runtime/elastic.py): each level is a pre-validated
    fallback mesh after capacity loss — EMPA's shrinking core pool."""
    ladder = [
        ((2, 16, 16), ("pod", "data", "model")),   # full fleet
        ((1, 16, 16), ("pod", "data", "model")),   # one pod lost
        ((16, 16), ("data", "model")),             # single-pod operation
        ((8, 16), ("data", "model")),              # half-pod (8 hosts lost)
        ((4, 16), ("data", "model")),              # quarter-pod
    ]
    shape, axes = ladder[level]
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_host_mesh(n: int | None = None, model_axis: int = 1):
    """Small mesh over the actually-present devices (tests, examples)."""
    n = n or len(jax.devices())
    assert n % model_axis == 0
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"),
                         (AxisType.Auto,) * 2)
