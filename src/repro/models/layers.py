"""Shared layers: param definitions, norms, RoPE/M-RoPE, MLP, embedding,
chunked cross-entropy. All functional (pytrees in, arrays out)."""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed.sharding import Dist


# ---------------------------------------------------------------------------
# Parameter definition machinery
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParamDef:
    """Abstract parameter: shape + logical dim names + init spec."""
    shape: Tuple[int, ...]
    dims: Tuple[str, ...]        # logical names, see distributed/sharding.py
    init: str = "normal"         # normal | zeros | ones | const:<v>
    scale: float = 1.0           # fan-in style scale multiplier
    # elements each output sums over; 0 = shape[-2], the input dim of a
    # plain matmul weight (set it where the contraction spans other
    # dims, and to 1 for a lookup table)
    fan_in: int = 0

    def __post_init__(self):
        assert len(self.shape) == len(self.dims), (self.shape, self.dims)


def is_pdef(x) -> bool:
    return isinstance(x, ParamDef)


def abstract_params(defs, dtype) -> dict:
    """ShapeDtypeStruct tree for abstract lowering (no allocation)."""
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.dtype(dtype)),
        defs, is_leaf=is_pdef)


def init_params(defs, rng, dtype) -> dict:
    """Materialise small parameter trees (smoke/examples only)."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_pdef)
    keys = jax.random.split(rng, len(leaves))
    out = []
    for d, k in zip(leaves, keys):
        if d.init == "zeros":
            a = jnp.zeros(d.shape, dtype)
        elif d.init == "ones":
            a = jnp.ones(d.shape, dtype)
        elif d.init.startswith("const:"):
            a = jnp.full(d.shape, float(d.init[6:]), dtype)
        else:
            fan_in = d.fan_in or (d.shape[-2] if len(d.shape) >= 2
                                  else d.shape[-1])
            std = d.scale / math.sqrt(max(1, fan_in))
            a = (jax.random.normal(k, d.shape, jnp.float32) * std).astype(dtype)
        out.append(a)
    return jax.tree.unflatten(treedef, out)


def param_dims(defs):
    """Pytree of dim-name tuples (same structure as params)."""
    return jax.tree.map(lambda d: d.dims, defs, is_leaf=is_pdef)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x, w, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def layer_norm(x, w, b, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------


def _rope_angles(positions, half: int, theta: float):
    """positions (...,) -> cos/sin (..., half)."""
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, positions, theta: float,
               mrope_sections: Optional[Tuple[int, ...]] = None):
    """x (B, S, H, hd); positions (B, S) or (3, B, S) for M-RoPE."""
    hd = x.shape[-1]
    half = hd // 2
    if mrope_sections is None:
        cos, sin = _rope_angles(positions, half, theta)      # (B,S,half)
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    else:
        assert sum(mrope_sections) == half, (mrope_sections, half)
        cs, ss = [], []
        for i, sec in enumerate(mrope_sections):
            # section i rotates with positions[i] (t/h/w)
            freq_lo = sum(mrope_sections[:i])
            freqs = jnp.exp(-math.log(theta)
                            * (jnp.arange(sec) + freq_lo).astype(jnp.float32)
                            / half)
            ang = positions[i].astype(jnp.float32)[..., None] * freqs
            cs.append(jnp.cos(ang))
            ss.append(jnp.sin(ang))
        cos = jnp.concatenate(cs, -1)[:, :, None, :]
        sin = jnp.concatenate(ss, -1)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    dt = x.dtype
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], -1).astype(dt)


def sinusoid_positions(seq: int, d_model: int, dtype=jnp.float32):
    """Whisper-style sinusoidal embeddings (S, D)."""
    half = d_model // 2
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half) / max(1, half - 1))
    ang = pos * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def gated_mlp(x, wg, wu, wd, dist: Dist):
    """SwiGLU MLP. x (B,S,D); wg/wu (D,F); wd (F,D). F sharded over TP
    (fsdp_tp) or replicated with seq-sharded activations (zero3_sp)."""
    h = jax.nn.silu(x @ wg) * (x @ wu)
    if dist.has_mesh:
        if dist.seq_parallel and x.shape[1] % dist.model_size == 0 \
                and x.shape[1] > 1:
            h = dist.constrain(h, P(dist.batch_axes, "model", None))
        else:
            h = dist.constrain(h, P(dist.batch_axes, None, dist.tp_axis))
    return h @ wd


# ---------------------------------------------------------------------------
# Embedding (vocab-sharded, Megatron masked-gather + psum)
# ---------------------------------------------------------------------------


def embed_tokens(tokens, table, dist: Dist, vocab_sharded: bool = True):
    """tokens (B, S) int32; table (V, D) sharded over vocab ("model")."""
    if not dist.has_mesh or not vocab_sharded:
        return jnp.take(table, tokens, axis=0)

    mesh = dist.mesh
    bt = dist.batch_axes

    def _local(tok, tab):
        nshard = jax.lax.psum(1, "model")
        vloc = tab.shape[0]
        lo = jax.lax.axis_index("model") * vloc
        idx = tok - lo
        ok = (idx >= 0) & (idx < vloc)
        got = jnp.take(tab, jnp.clip(idx, 0, vloc - 1), axis=0)
        got = jnp.where(ok[..., None], got, jnp.zeros_like(got))
        del nshard
        return jax.lax.psum(got, "model")

    fn = jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(bt, None), P("model", None)),
        out_specs=P(bt, None, None), check_vma=False)
    return fn(tokens, table)


# ---------------------------------------------------------------------------
# Chunked softmax cross-entropy (never materialises (B,S,V))
# ---------------------------------------------------------------------------


def chunked_xent(h, w_head, labels, dist: Dist, chunk: int = 512,
                 z_loss: float = 0.0, vocab_sharded: bool = True):
    """h (B,S,D) -> scalar mean CE. w_head (D,V) vocab-sharded.

    Scans over sequence chunks; logits for one chunk only live transiently
    (and are recomputed in backward via jax.checkpoint).
    """
    B, S, D = h.shape
    chunk = min(chunk, S)
    n = S // chunk
    assert S % chunk == 0, (S, chunk)

    hc = h.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)      # (n,B,c,D)
    lc = labels.reshape(B, n, chunk).transpose(1, 0, 2)       # (n,B,c)

    @jax.checkpoint
    def body(carry, xs):
        hh, ll = xs
        logits = (hh.astype(w_head.dtype) @ w_head).astype(jnp.float32)
        if dist.has_mesh:
            logits = dist.constrain(
                logits, P(dist.batch_axes, None,
                          "model" if vocab_sharded else None))
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ll[..., None], axis=-1)[..., 0]
        nll = lse - gold
        if z_loss:
            nll = nll + z_loss * jnp.square(lse)
        return carry + jnp.sum(nll), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, lc))
    return total / (B * S)


def last_token_logits(h_last, w_head, dist: Dist, vocab_sharded: bool = True):
    """h_last (B, 1, D) -> logits (B, 1, V)."""
    logits = (h_last @ w_head).astype(jnp.float32)
    if dist.has_mesh:
        logits = dist.constrain(
            logits, P(dist.batch_axes, None,
                      "model" if vocab_sharded else None))
    return logits
