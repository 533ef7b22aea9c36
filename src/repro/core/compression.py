"""Gradient compression: int8 block quantization with error feedback.

Used on the parameter-server push path (DESIGN.md §2): the pushed vector is
quantized per block of 256 values with an f32 scale (≈2x byte reduction vs
bf16, 4x vs f32, wire format int8+scales); the quantization residual is
carried in an error-feedback buffer so the compression is unbiased over
time (Seide et al. style).

This is the pure-jnp reference; kernels/quantize.py is the Pallas TPU
mirror validated against it. ``make_compressor`` picks between the two
(kernel on a TPU backend, jit'd reference elsewhere) and
``CompressedPush`` is the wire format the software PS moves.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256


@dataclass(frozen=True)
class CompressedPush:
    """Wire format of a compressed PS push: int8 payload + one f32 scale
    per block, ~4x fewer bytes than the dense f32 vector."""
    q: np.ndarray           # int8, padded length (multiple of BLOCK)
    scales: np.ndarray      # f32, len(q) // BLOCK
    dense_nbytes: int       # size of the vector this stands in for

    @property
    def wire_nbytes(self) -> int:
        return self.q.nbytes + self.scales.nbytes


def pad_to_block(n: int, block: int = BLOCK) -> int:
    return -(-n // block) * block


def quantize_int8(x, block: int = BLOCK):
    """x (N,) with N % block == 0 -> (q int8 (N,), scale f32 (N/block,))."""
    xb = x.astype(jnp.float32).reshape(-1, block)
    amax = jnp.max(jnp.abs(xb), axis=1)
    scale = amax / 127.0
    q = jnp.round(xb / jnp.maximum(scale[:, None], 1e-30))
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return q.reshape(-1), scale


def dequantize_int8(q, scale, block: int = BLOCK):
    qb = q.astype(jnp.float32).reshape(-1, block)
    return (qb * scale[:, None]).reshape(-1)


def compress_with_feedback(x, err, block: int = BLOCK):
    """Quantize (x + err); return (q, scale, new_err, wire_view).

    ``wire_view`` is the dequantized value that actually travels — callers
    aggregate it (numerics match the wire format exactly); the residual
    goes back into the feedback buffer."""
    y = x.astype(jnp.float32) + err
    q, scale = quantize_int8(y, block)
    wire = dequantize_int8(q, scale, block)
    return q, scale, y - wire, wire


def wire_bytes(n: int, block: int = BLOCK) -> int:
    """Bytes on the wire for an n-element compressed push."""
    return n + 4 * (n // block)


def make_compressor(block: int = BLOCK, use_tpu: bool = None):
    """Build the push-path compressor ``(fn, path)`` with ``fn(x, err)
    -> (q, scales, new_err)``: the fused Pallas kernel when running on a
    TPU backend (``path`` "pallas"), the jit'd jnp reference otherwise
    ("jnp"; the two are validated against each other in
    tests/test_compression.py). ``x`` must be a multiple of ``block``
    long — the PS shard layout guarantees this."""
    if use_tpu is None:
        use_tpu = jax.default_backend() == "tpu"
    if use_tpu:
        from repro.kernels.autotune import tuned_quantize_block
        from repro.kernels.quantize import quantize_ef

        jfn = jax.jit(lambda x, e, blk: quantize_ef(
            x, e, qblock=block, block=blk), static_argnums=(2,))

        def compress(x, e):
            # tuned grid block resolved outside the jit (cached per shape)
            blk = tuned_quantize_block(int(x.shape[0]), block, x.dtype)
            return jfn(x, e, blk)
        return compress, "pallas"
    # one definition of the scheme: drop the wire view (its math is part
    # of the residual anyway, so nothing extra is computed under jit)
    return jax.jit(
        lambda x, e: compress_with_feedback(x, e, block)[:3]), "jnp"
