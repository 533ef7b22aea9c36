"""Pallas TPU fused parameter-server shard aggregation + solver update.

The paper calls the PS "a throughput-critical system" whose receiving
threads aggregate incoming partitions and update global weights. On TPU
the shard owner's aggregation + optimizer update is HBM-bandwidth-bound;
fusing mean-aggregation with the (elementwise) solver update makes it a
single read-modify-write pass over the shard instead of several.

Supports the DLaaS solver updates: sgd, momentum, adam (bias-corrected),
and the EASGD center rule. The flat vectors are viewed as (rows, 128)
lane-dense tiles; blocks of (n_learners, rows, 128) are reduced over
learners in VMEM. Adam's bias corrections are step-dependent scalars: they
are computed outside the kernel and read from SMEM (the TPU lowering has
no ``powf``).

Oracle: kernels/ref.py:ps_aggregate_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.grid import LANES, TILE, fit_block, pad_to


def _agg_kernel(bc_ref, g_ref, p_ref, m_ref, v_ref,
                po_ref, mo_ref, vo_ref, *,
                solver: str, lr: float, b1: float, b2: float, eps: float,
                momentum: float, beta: float):
    g = jnp.mean(g_ref[...].astype(jnp.float32), axis=0)     # (rows, 128)
    p = p_ref[...].astype(jnp.float32)
    if solver == "sgd":
        po_ref[...] = (p - lr * g).astype(po_ref.dtype)
        mo_ref[...] = m_ref[...]
        vo_ref[...] = v_ref[...]
    elif solver == "momentum":
        m = momentum * m_ref[...].astype(jnp.float32) + g
        po_ref[...] = (p - lr * m).astype(po_ref.dtype)
        mo_ref[...] = m.astype(mo_ref.dtype)
        vo_ref[...] = v_ref[...]
    elif solver == "adam":
        m = b1 * m_ref[...].astype(jnp.float32) + (1 - b1) * g
        v = b2 * v_ref[...].astype(jnp.float32) + (1 - b2) * g * g
        mh = m / bc_ref[0]
        vh = v / bc_ref[1]
        po_ref[...] = (p - lr * mh / (jnp.sqrt(vh) + eps)).astype(
            po_ref.dtype)
        mo_ref[...] = m.astype(mo_ref.dtype)
        vo_ref[...] = v.astype(vo_ref.dtype)
    elif solver == "easgd_center":
        # g_ref holds per-learner (x_i - center) diffs; center += beta*mean
        po_ref[...] = (p + beta * g).astype(po_ref.dtype)
        mo_ref[...] = m_ref[...]
        vo_ref[...] = v_ref[...]
    elif solver == "average":
        # model averaging: the pushed slots carry weights, not grads
        po_ref[...] = g.astype(po_ref.dtype)
        mo_ref[...] = m_ref[...]
        vo_ref[...] = v_ref[...]
    else:
        raise ValueError(solver)


def ps_aggregate(grads, params, m, v, step, *, solver: str = "adam",
                 lr: float = 1e-3, b1: float = 0.9, b2: float = 0.999,
                 eps: float = 1e-8, momentum: float = 0.9,
                 beta: float = 0.9, block: int = 8 * TILE,
                 interpret: bool = False):
    """grads (NL, F); params/m/v (F,); step scalar (1-based).

    Returns (new_params, new_m, new_v): one fused aggregation+update pass.
    ``block`` is in elements, a multiple of ``TILE`` (one (8, 128) f32
    tile); F is zero-padded to a whole tile when it is not one already
    (software-PS shards always are).
    """
    nl, f = grads.shape
    fp = pad_to(f, TILE)
    if fp != f:
        pad = ((0, 0), (0, fp - f))
        grads = jnp.pad(grads, pad)
        params, m, v = (jnp.pad(a, pad[1]) for a in (params, m, v))
    block = fit_block(fp, block, multiple=TILE)
    rows, brows = fp // LANES, block // LANES
    step = jnp.asarray(step, jnp.float32)
    bias = jnp.stack([1 - b1 ** step, 1 - b2 ** step])
    kernel = functools.partial(
        _agg_kernel, solver=solver, lr=lr, b1=b1, b2=b2, eps=eps,
        momentum=momentum, beta=beta)
    vec = pl.BlockSpec((brows, LANES), lambda i: (i, 0))
    outs = pl.pallas_call(
        kernel,
        grid=(rows // brows,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((nl, brows, LANES), lambda i: (0, i, 0)),
            vec, vec, vec,
        ],
        out_specs=[vec, vec, vec],
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), a.dtype)
                   for a in (params, m, v)],
        interpret=interpret,
    )(bias, grads.reshape(nl, rows, LANES),
      *(a.reshape(rows, LANES) for a in (params, m, v)))
    return tuple(o.reshape(-1)[:f] for o in outs)
