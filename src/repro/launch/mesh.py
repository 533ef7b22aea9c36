"""Mesh construction. Functions only — importing this module never touches
jax device state (so smoke tests see the real single device while
multi-device tests build their meshes in subprocesses)."""
from __future__ import annotations

import jax


def _mk(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh(data: int, model: int, pod: int = 1):
    """Arbitrary mesh for tests/examples (e.g. 4x2 on host devices)."""
    if pod > 1:
        return _mk((pod, data, model), ("pod", "data", "model"))
    return _mk((data, model), ("data", "model"))
