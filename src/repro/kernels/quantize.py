"""Pallas TPU int8 block quantization with fused error feedback.

One pass over the push vector: y = x + err; per-block absmax scale;
q = round(y/scale); err' = y - q*scale. Used before the PS push to halve
(vs bf16) / quarter (vs f32) collective bytes.

Layout: the flat vector is viewed as (F / qblock, qblock), one
quantization block per row, so the absmax is a lane reduction. The
scales leave the kernel lane-dense as a (1, F / qblock) row: a grid
block holds 128 rows (one lane tile of scales, and four int8 sublane
tiles of ``q``), i.e. ``QTILE`` elements. F is zero-padded to a whole
``QTILE`` when it is not one already; zero blocks quantize to zero.

Oracle: kernels/ref.py:quantize_ref (== core/compression.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.grid import LANES, fit_block, pad_to

QBLOCK = 256
QTILE = LANES * QBLOCK      # elements per grid-block row group


def _column_to_row(col):
    """(R, 1) -> (1, R) through an aligned (R, 128) transpose."""
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], LANES)))[:1]


def _row_to_column(row):
    """(1, R) -> (R, 1), the inverse of ``_column_to_row``."""
    return jnp.transpose(jnp.broadcast_to(row, (LANES, row.shape[1])))[:, :1]


def _quant_kernel(x_ref, e_ref, q_ref, s_ref, ne_ref):
    y = x_ref[...].astype(jnp.float32) + e_ref[...].astype(jnp.float32)
    scale = jnp.max(jnp.abs(y), axis=1, keepdims=True) / 127.0   # (R, 1)
    qv = jnp.clip(jnp.round(y / jnp.maximum(scale, 1e-30)), -127, 127)
    q_ref[...] = qv.astype(jnp.int8)
    s_ref[...] = _column_to_row(scale)
    ne_ref[...] = (y - qv * scale).astype(ne_ref.dtype)


def _grid(f: int, qblock: int, block: int):
    """(padded length, rows, rows per grid block) for a flat length."""
    fp = pad_to(f, LANES * qblock)
    block = fit_block(fp, block, multiple=LANES * qblock)
    return fp, fp // qblock, block // qblock


def quantize_ef(x, err, *, qblock: int = QBLOCK, block: int = QTILE,
                interpret: bool = False):
    """x/err (F,) -> (q int8 (F,), scales (F/qblock,), new_err (F,)).
    ``block`` is in elements, a multiple of ``128 * qblock``."""
    f = x.shape[0]
    fp, rows, brows = _grid(f, qblock, block)
    if fp != f:
        x, err = jnp.pad(x, (0, fp - f)), jnp.pad(err, (0, fp - f))
    tile = pl.BlockSpec((brows, qblock), lambda i: (i, 0))
    q, s, ne = pl.pallas_call(
        _quant_kernel,
        grid=(rows // brows,),
        in_specs=[tile, tile],
        out_specs=[tile, pl.BlockSpec((1, brows), lambda i: (0, i)), tile],
        out_shape=[
            jax.ShapeDtypeStruct((rows, qblock), jnp.int8),
            jax.ShapeDtypeStruct((1, rows), jnp.float32),
            jax.ShapeDtypeStruct((rows, qblock), err.dtype),
        ],
        interpret=interpret,
    )(x.reshape(rows, qblock), err.reshape(rows, qblock))
    return (q.reshape(-1)[:f], s.reshape(-1)[: f // qblock],
            ne.reshape(-1)[:f])


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = (q_ref[...].astype(jnp.float32)
                  * _row_to_column(s_ref[...])).astype(x_ref.dtype)


def dequantize(q, scales, *, qblock: int = QBLOCK, block: int = QTILE,
               interpret: bool = False):
    f = q.shape[0]
    fp, rows, brows = _grid(f, qblock, block)
    if fp != f:
        q = jnp.pad(q, (0, fp - f))
        scales = jnp.pad(scales, (0, rows - scales.shape[0]))
    tile = pl.BlockSpec((brows, qblock), lambda i: (i, 0))
    x = pl.pallas_call(
        _dequant_kernel,
        grid=(rows // brows,),
        in_specs=[tile, pl.BlockSpec((1, brows), lambda i: (0, i))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows, qblock), jnp.float32),
        interpret=interpret,
    )(q.reshape(rows, qblock), scales.reshape(1, rows))
    return x.reshape(-1)[:f]
