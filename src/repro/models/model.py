"""Model facade: one entry point per architecture.

``Model`` dispatches to the family implementation and owns:
  * abstract/init parameter trees + their shardings,
  * ``loss`` / ``prefill`` / ``decode`` pure functions,
  * ``input_specs`` / ``cache_specs`` — ShapeDtypeStruct stand-ins for
    lowering (weak-type-correct, shardable, no allocation),
  * ``cache_axes`` — each cache leaf's slot and sequence axes, declared
    with its shape in ``_cache_layout``,
  * matching ``input_shardings`` / ``cache_shardings``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec
from repro.distributed.sharding import Dist, tree_specs, tree_shardings
from repro.models import encdec as ed
from repro.models import mamba as mam
from repro.models import transformer as tf
from repro.models.layers import abstract_params, init_params


@dataclass
class Model:
    cfg: ArchConfig
    dist: Dist
    opts: Optional[Dict[str, Any]] = None

    # ---- params -----------------------------------------------------------
    def param_defs(self):
        if self.cfg.family == "encdec":
            return ed.encdec_param_defs(self.cfg, self.dist)
        return tf.decoder_param_defs(self.cfg, self.dist)

    def abstract_params(self):
        return abstract_params(self.param_defs(), self.cfg.dtype)

    def init(self, rng):
        return init_params(self.param_defs(), rng, self.cfg.dtype)

    def param_specs(self):
        return tree_specs(self.dist, self.param_defs())

    def param_shardings(self):
        return tree_shardings(self.dist, self.param_defs())

    # ---- compute ----------------------------------------------------------
    def loss(self, params, batch):
        if self.cfg.family == "encdec":
            return ed.encdec_loss(params, batch, self.cfg, self.dist,
                                  self.opts)
        return tf.lm_loss(params, batch, self.cfg, self.dist, self.opts)

    def prefill(self, params, batch):
        if self.cfg.family == "encdec":
            return ed.encdec_prefill(params, batch, self.cfg, self.dist,
                                     self.opts)
        return tf.lm_prefill(params, batch, self.cfg, self.dist, self.opts)

    def decode(self, params, cache, batch):
        return self.decode_counted(params, cache, batch)[:2]

    def decode_counted(self, params, cache, batch):
        """``decode`` and the step's MoE counts: (rows computed, held
        experts touched) summed over layers, or None without MoE."""
        if self.cfg.family == "encdec":
            return ed.encdec_decode(params, cache, batch, self.cfg,
                                    self.dist, self.opts) + (None,)
        return tf.lm_decode(params, cache, batch, self.cfg, self.dist,
                            self.opts)

    # ---- input specs ------------------------------------------------------
    def input_specs(self, shape: ShapeSpec) -> Dict[str, Any]:
        """ShapeDtypeStructs for one step of the given shape."""
        c = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        act = jnp.dtype(c.dtype)
        if shape.kind == "train":
            if c.family == "encdec":
                half = S // 2
                return {
                    "enc_embeds": jax.ShapeDtypeStruct((B, half, c.d_model),
                                                       act),
                    "tokens": jax.ShapeDtypeStruct((B, half), i32),
                    "labels": jax.ShapeDtypeStruct((B, half), i32),
                }
            out = {"labels": jax.ShapeDtypeStruct((B, S), i32)}
            if c.frontend != "none":
                out["embeds"] = jax.ShapeDtypeStruct((B, S, c.d_model), act)
            else:
                out["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
            if c.mrope:
                out["positions"] = jax.ShapeDtypeStruct((3, B, S), i32)
            return out
        if shape.kind == "prefill":
            if c.family == "encdec":
                half = S // 2
                return {
                    "enc_embeds": jax.ShapeDtypeStruct((B, half, c.d_model),
                                                       act),
                    "tokens": jax.ShapeDtypeStruct((B, half), i32),
                }
            out = {}
            if c.frontend != "none":
                out["embeds"] = jax.ShapeDtypeStruct((B, S, c.d_model), act)
            else:
                out["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
            if c.mrope:
                out["positions"] = jax.ShapeDtypeStruct((3, B, S), i32)
            return out
        # decode: one new token against a seq_len cache
        return {"tokens": jax.ShapeDtypeStruct((B, 1), i32)}

    def input_sharding_specs(self, shape: ShapeSpec) -> Dict[str, Any]:
        d = self.dist
        if not d.has_mesh:
            return {k: P() for k in self.input_specs(shape)}
        bt = d.batch_axes
        out = {}
        for k, v in self.input_specs(shape).items():
            if k == "positions":
                out[k] = P(None, bt, None)
            elif v.ndim == 3:
                out[k] = P(bt, None, None)
            else:
                out[k] = P(bt, None)
        return out

    # ---- cache layout -----------------------------------------------------
    def _cache_layout(self, B: int, S: int) -> Dict[str, Tuple[Any, Any, Any]]:
        """The one declaration of each family's cache: leaf ->
        (ShapeDtypeStruct, slot axis, sequence axis or None). The slot
        axis holds one request's (batch row's) state; the sequence axis
        one row per position. ``pos`` is a shared scalar with neither."""
        c = self.cfg
        from repro.models.transformer import _cache_dtype
        sds = jax.ShapeDtypeStruct

        def kv(lead, tail):
            # one row per position (the KV heads side by side for the
            # decoders: attention reads the rows as stored)
            n = len(lead)
            return (sds(lead + (B, S) + tail, _cache_dtype(c)), n, n + 1)

        def state(lead):
            # each Mamba layer's SSM state and causal-conv tail
            d_in, nheads, gn, k = mam.mamba_dims(c)
            n = len(lead)
            return {
                "ssm": (sds(lead + (B, nheads, c.ssm.head_dim,
                                    c.ssm.d_state), jnp.float32), n, None),
                "conv": (sds(lead + (B, k - 1, d_in + 2 * gn),
                             jnp.dtype(c.dtype)), n, None)}

        pos = {"pos": (sds((), jnp.int32), None, None)}
        L = c.n_layers
        if c.family == "encdec":
            heads = kv((L,), (c.n_kv_heads, c.hd))
            return {"k": heads, "v": heads, "cross_k": heads,
                    "cross_v": heads, **pos}
        if c.family == "ssm":
            return {**state((L,)), **pos}
        rows = (c.n_kv_heads * c.hd,)
        if c.family == "hybrid":
            per = c.attn_period
            np_ = L // per
            return {"k": kv((np_,), rows), "v": kv((np_,), rows),
                    **state((np_, per - 1)), **pos}
        return {"k": kv((L,), rows), "v": kv((L,), rows), **pos}

    def cache_specs(self, B: int, S: int) -> Dict[str, Any]:
        """ShapeDtypeStructs of a B-row cache of S positions."""
        return {k: leaf[0] for k, leaf in self._cache_layout(B, S).items()}

    def cache_axes(self) -> Dict[str, Tuple[Optional[int], Optional[int]]]:
        """Each cache leaf's (slot axis, sequence axis or None): where one
        request's cache sits in a batched one, and which axis grows
        with the sequence."""
        return {k: leaf[1:] for k, leaf in self._cache_layout(1, 8).items()}

    def cache_sharding_specs(self, B: int) -> Dict[str, Any]:
        """Cache PartitionSpecs. Batch over data axes when divisible, else
        the sequence dim takes every mesh axis (long-context, B=1)."""
        c = self.cfg
        d = self.dist
        if not d.has_mesh:
            return {k: P() for k in self.cache_specs(B, 8)}
        bt = d.batch_axes                      # resolved for B by the step
        seq_ax = "model" if bt else tuple(d.axis_names)
        heads_ax = None
        if c.ssm is not None:
            d_in, nheads, gn, k = mam.mamba_dims(c)
            if nheads % d.model_size == 0 and d.tp_axis:
                heads_ax = "model"
        out = {}
        for key, (spec, slot, seq) in self._cache_layout(B, 8).items():
            dims = [None] * spec.ndim
            if slot is not None:
                dims[slot] = bt
            if seq is not None:
                dims[seq] = seq_ax
            if key == "ssm":                   # (..., B, heads, P, N)
                dims[slot + 1] = heads_ax
            out[key] = P(*dims)
        return out

    def cache_shardings(self, B: int):
        if not self.dist.has_mesh:
            return None
        return {k: NamedSharding(self.dist.mesh, s)
                for k, s in self.cache_sharding_specs(B).items()}


def make_model(cfg: ArchConfig, dist: Optional[Dist] = None,
               opts: Optional[Dict[str, Any]] = None) -> Model:
    return Model(cfg, dist or Dist(), opts)
