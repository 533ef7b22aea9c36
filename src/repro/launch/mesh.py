"""Mesh construction. Functions only — importing this module never touches
jax device state (required so smoke tests see 1 device while the dry-run
forces 512 host devices)."""
from __future__ import annotations

import jax


def _mk(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Assignment-fixed production mesh: 16x16 per pod, 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(data: int, model: int, pod: int = 1):
    """Arbitrary mesh for tests/examples (e.g. 4x2 on host devices)."""
    if pod > 1:
        return _mk((pod, data, model), ("pod", "data", "model"))
    return _mk((data, model), ("data", "model"))
