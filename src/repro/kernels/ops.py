"""Jit'd public wrappers over the Pallas kernels.

Each wrapper handles layout folding (model layouts -> kernel layouts),
dtype plumbing, and the TPU/interpret switch: on a TPU backend the Mosaic
kernel runs; elsewhere ``interpret=True`` executes the kernel body in
Python (correctness-equivalent, used by tests and CPU smoke).

Block sizes default to the autotuner (kernels/autotune.py): the tuned
choice is resolved *outside* the jit boundary and passed in as a static
argument, so measurement probes never run mid-trace and the cache makes
repeat shapes free. Passing an explicit block pins it (tests, parity
sweeps)."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm as _gmm

from repro.kernels import autotune
from repro.kernels import flash_attention as _fa
from repro.kernels import ps_aggregate as _agg
from repro.kernels import quantize as _q
from repro.kernels import ssd_scan as _ssd


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def _flash_attention_jit(q, k, v, *, causal: bool, block_q: int,
                         block_k: int):
    b, s, h, hd = q.shape
    kv = k.shape[2]
    if kv != h:
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    o = _fa.flash_attention_fwd(fold(q), fold(k), fold(v), causal=causal,
                                block_q=block_q, block_k=block_k,
                                interpret=not _on_tpu())
    return o.reshape(b, h, s, hd).transpose(0, 2, 1, 3)


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None):
    """q (B,S,H,hd); k/v (B,S,KV,hd) -> (B,S,H,hd). Repeats GQA heads,
    folds to the kernel layout, unfolds back. Blocks autotune per shape
    unless pinned."""
    b, s, h, hd = q.shape
    if block_q is None or block_k is None:
        tq, tk = autotune.tuned_flash_blocks(b * h, s, k.shape[1], hd,
                                             q.dtype)
        block_q = block_q or tq
        block_k = block_k or tk
    return _flash_attention_jit(q, k, v, causal=causal,
                                block_q=block_q, block_k=block_k)


def _block(size: int, most: int) -> int:
    """``most`` where it tiles ``size`` evenly, else the whole of it."""
    return most if size % most == 0 else size


def grouped_matmul(lhs, rhs, group_sizes):
    """lhs (m, k), rhs (G, k, n), group_sizes (G,) -> (m, n) in lhs's
    dtype: each group's rows, in order, times its own rhs[g], as
    ``jax.lax.ragged_dot`` computes them, but reading only the weights of
    groups that have rows (megablox's grouped matmul, every row in one
    m-tile: meant for the few rows of a decode step). Rows past the
    groups are left unwritten: the caller masks them."""
    m, k = lhs.shape
    n = rhs.shape[2]
    return _gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
                tiling=(m, _block(k, 2048), _block(n, 2048)),
                interpret=not _on_tpu())


@partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, a_log, b, c, *, chunk: int = 128):
    """Model layout: x (B,S,H,P), dt (B,S,H) post-softplus, a_log (H,),
    b/c (B,S,G,N). Returns y (B,S,H,P) (no D-skip/gating)."""
    nb, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    hg = h // g
    a = -jnp.exp(a_log.astype(jnp.float32))
    xdt = (x.astype(jnp.float32) * dt[..., None]).transpose(0, 2, 1, 3)
    ldec = (dt * a).transpose(0, 2, 1)[..., None]       # (B,H,S,1)
    bh_ = jnp.repeat(b, hg, axis=2).transpose(0, 2, 1, 3)
    ch_ = jnp.repeat(c, hg, axis=2).transpose(0, 2, 1, 3)
    fold = lambda t: t.reshape((nb * h,) + t.shape[2:])
    y = _ssd.ssd_scan_fwd(fold(xdt).astype(x.dtype), fold(ldec),
                          fold(bh_).astype(x.dtype),
                          fold(ch_).astype(x.dtype),
                          chunk=chunk, interpret=not _on_tpu())
    return y.reshape(nb, h, s, p).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("solver", "lr", "b1", "b2", "eps",
                                   "momentum", "beta", "block"))
def _ps_aggregate_jit(grads, params, m, v, step, *, solver, lr, b1, b2,
                      eps, momentum, beta, block):
    return _agg.ps_aggregate(grads, params, m, v, step, solver=solver,
                             lr=lr, b1=b1, b2=b2, eps=eps,
                             momentum=momentum, beta=beta, block=block,
                             interpret=not _on_tpu())


def ps_aggregate(grads, params, m, v, step, *, solver="adam", lr=1e-3,
                 b1=0.9, b2=0.999, eps=1e-8, momentum=0.9, beta=0.9,
                 block: Optional[int] = None):
    if block is None:
        nl, f = grads.shape
        block = autotune.tuned_ps_block(nl, f, grads.dtype)
    return _ps_aggregate_jit(grads, params, m, v, step, solver=solver,
                             lr=lr, b1=b1, b2=b2, eps=eps,
                             momentum=momentum, beta=beta, block=block)


@partial(jax.jit, static_argnames=("block",))
def _quantize_ef_jit(x, err, *, block):
    return _q.quantize_ef(x, err, block=block, interpret=not _on_tpu())


def quantize_ef(x, err, *, block: Optional[int] = None):
    if block is None:
        block = autotune.tuned_quantize_block(x.shape[0], _q.QBLOCK,
                                              x.dtype)
    return _quantize_ef_jit(x, err, block=block)


@partial(jax.jit, static_argnames=("block",))
def _dequantize_jit(q, scales, *, block):
    return _q.dequantize(q, scales, block=block, interpret=not _on_tpu())


def dequantize(q, scales, *, block: Optional[int] = None):
    if block is None:
        block = autotune.tuned_quantize_block(q.shape[0], _q.QBLOCK,
                                              q.dtype)
    return _dequantize_jit(q, scales, block=block)
