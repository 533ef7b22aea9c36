"""What the references share: seeded weights, matmuls at float32
``highest`` or in fp8 for the control, and RMS norm.

The weights are the benchmark's own: made from the seed on the device in
one jitted call, in the dtype the configuration serves, laid out as the
program's parameter tree (the layout is the one interface the program and
the references share; ``run.py`` refuses a program whose tree differs).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

F8_MAX = 448.0          # largest finite float8_e4m3fn


@dataclass(frozen=True)
class Spec:
    shape: Tuple[int, ...]
    init: str               # normal | gain | ones | a_log | dt_bias
    std: float = 1.0


def seed_key(seed: int):
    """A PRNG key from any non-negative seed that fits in 64 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _leaf(spec: Spec, key, dtype):
    if spec.init == "normal":
        x = jax.random.normal(key, spec.shape, jnp.float32) * spec.std
    elif spec.init == "gain":           # norm gains near 1
        x = 1.0 + 0.1 * jax.random.normal(key, spec.shape, jnp.float32)
    elif spec.init == "ones":
        x = jnp.ones(spec.shape, jnp.float32)
    elif spec.init == "a_log":          # A = -exp(a_log) in [-16, -1]
        x = jnp.log(jax.random.uniform(key, spec.shape, jnp.float32, 1.0, 16.0))
    elif spec.init == "dt_bias":        # softplus(dt_bias) in [1e-3, 1e-1]
        u = jax.random.uniform(key, spec.shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        x = dt + jnp.log(-jnp.expm1(-dt))
    else:
        raise ValueError(f"unknown init {spec.init!r}")
    return x.astype(dtype)


def make_params(specs, seed: int, dtype):
    """The whole weight tree from the seed, in one jitted call."""
    leaves, treedef = jax.tree.flatten(
        specs, is_leaf=lambda s: isinstance(s, Spec))

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(
            treedef, [_leaf(s, k, dtype) for s, k in zip(leaves, keys)])

    return build(seed_key(seed))


def shapes(specs, dtype):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(dtype)),
                        specs, is_leaf=lambda s: isinstance(s, Spec))


def fp8(x):
    """Round to float8_e4m3fn with one scale per tensor, back to f32."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def ein(spec: str, a, b, low: bool = False):
    """A matmul in float32 at ``highest`` precision, or (``low``) on
    operands rounded to fp8: the control's precision."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if low:
        a, b = fp8(a), fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x, w, eps: float):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def table(t, low: bool):
    t = t.astype(jnp.float32)
    return fp8(t) if low else t
