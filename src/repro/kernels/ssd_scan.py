"""Pallas TPU Mamba-2 SSD chunked scan (forward).

Grid (batch·heads, chunks); the chunk dimension iterates sequentially and
the inter-chunk state (head_dim x d_state) is carried in VMEM scratch —
the same carried-scratch pattern as the flash kernel's online softmax.
Inside a chunk the recurrence is evaluated as a masked quadratic form
(MXU-friendly), per the SSD duality.

The ops.py wrapper precomputes xdt = x·dt and ldec = dt·A (per-head log
decay) and expands B/C groups to heads, so the kernel is a pure 4-input
scan. Oracle: repro.models.mamba.ssd_scan_ref via kernels/ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(xdt_ref, ldec_ref, b_ref, c_ref, y_ref, state_ref, *,
                chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    xdt = xdt_ref[0].astype(jnp.float32)             # (Q, P)
    l = ldec_ref[0, 0].astype(jnp.float32)           # (1, Q) log decays
    b = b_ref[0].astype(jnp.float32)                 # (Q, N)
    c = c_ref[0].astype(jnp.float32)                 # (Q, N)

    # in-chunk prefix sums as a matmul with an upper-triangular ones
    # matrix (the TPU lowering has no cumsum): cum[t] = sum_{s<=t} l[s]
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    upper = (ti <= si).astype(jnp.float32)
    cum_row = jnp.dot(jnp.broadcast_to(l, (8, chunk)), upper,
                      preferred_element_type=jnp.float32)
    cum_s = jnp.broadcast_to(cum_row[:1], (chunk, chunk))   # [t, s] = cum[s]
    cum_t = jnp.transpose(cum_s)                             # [t, s] = cum[t]
    cum = cum_t[:, :1]                                       # (Q, 1)
    total = jnp.sum(l)                                       # cum[Q-1]
    # intra-chunk quadratic term
    dec = jnp.where(ti >= si, jnp.exp(cum_t - cum_s), 0.0)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y_intra = jax.lax.dot(scores * dec, xdt,
                          preferred_element_type=jnp.float32)
    # inter-chunk: incoming state, decayed to each position
    state = state_ref[...]                           # (P, N)
    y_inter = jax.lax.dot_general(c, state, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_inter = y_inter * jnp.exp(cum)
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)
    # state update: S' = exp(cum_Q) S + sum_s exp(cum_Q - cum_s) xdt_s b_s^T
    tail = jnp.exp(total - cum)                      # (Q, 1)
    s_chunk = jax.lax.dot_general(
        xdt * tail, b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (P, N)
    state_ref[...] = state * jnp.exp(total) + s_chunk


def ssd_scan_fwd(xdt, ldec, b, c, *, chunk: int = 128,
                 interpret: bool = False):
    """xdt (BH, S, P); ldec (BH, S, 1); b/c (BH, S, N) -> y (BH, S, P).

    BH folds batch x heads; ldec = dt * A (negative log decays);
    xdt = x * dt. Returns the SSD output (no D-skip, no gating)."""
    bh, s, p = xdt.shape
    n = b.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda b_, i: (b_, i, 0)),
            # each chunk's decays as one lane-dense (1, Q) row
            pl.BlockSpec((1, 1, 1, chunk), lambda b_, i: (b_, i, 0, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, i: (b_, i, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, i: (b_, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda b_, i: (b_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, p), xdt.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xdt, ldec.reshape(bh, nc, 1, chunk), b, c)
