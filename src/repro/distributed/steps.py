"""Step builders: train / prefill / decode with full sharding annotations.

These are the functions the trainer executes (and the multi-device tests
lower on a host mesh). The train step here is the pjit-native path (grad
psum over the batch axes is inserted by SPMD; optimizer state shards per
opt_state_specs — the paper's PS partition scheme as a resident layout). The explicit parameter-server
push/pull solvers (paper-faithful modes) live in core/solvers.py and wrap
the same loss function.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec
from repro.distributed.sharding import Dist, tree_specs
from repro.models.model import Model
from repro.optim.optimizers import (OptConfig, apply_updates, init_opt_state,
                                    opt_state_specs)


def default_optimizer(cfg: ArchConfig) -> OptConfig:
    """Adafactor for huge models (factored stats), AdamW otherwise."""
    if cfg.n_params() > 30e9:
        return OptConfig(name="adafactor", lr=1e-3)
    return OptConfig(name="adamw", lr=1e-3)


def expert_grad_tie(cfg: ArchConfig, model: Model):
    """Gradient-tying transform for replicated ('virtual') MoE experts.

    When E < model-axis size, each expert is replicated R times and copies
    receive different tokens; averaging copy gradients keeps the copies
    mathematically tied to the paper-listed E-expert model."""
    from repro.models.moe import replication_factor
    if cfg.moe is None:
        return lambda g: g
    r = replication_factor(cfg.moe, model.dist)
    if r == 1:
        return lambda g: g

    def tie(tree_path_leaf):
        def fix(path, g):
            names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
            if any(n in ("wg", "wu", "wd") for n in names) and \
               any(n == "moe" or n == "blocks" for n in names):
                # expert axis is the first non-scan dim; copies adjacent
                for ax, size in enumerate(g.shape):
                    # find the virtual-expert dim: first dim divisible by r
                    # that matches Ev = E * r
                    if size == cfg.moe.n_experts * r:
                        s = g.shape
                        gr = g.reshape(s[:ax] + (cfg.moe.n_experts, r)
                                       + s[ax + 1:])
                        gm = jnp.mean(gr, axis=ax + 1, keepdims=True)
                        return jnp.broadcast_to(gm, gr.shape).reshape(s)
                return g
            return g
        return jax.tree_util.tree_map_with_path(fix, tree_path_leaf)
    return tie


def build_train_step(model: Model, opt_cfg: OptConfig,
                     grad_accum: int = 1):
    """Returns train_step(params, opt_state, batch) -> (params, state, loss)."""
    tie = expert_grad_tie(model.cfg, model)

    def loss_fn(params, batch):
        return model.loss(params, batch)

    def _constrain_grads(grads):
        """Pin gradients to the parameter sharding so XLA reduce-scatters
        partial grads into shards instead of all-reducing full replicas."""
        dist = model.dist
        if not dist.has_mesh:
            return grads
        from repro.distributed.sharding import tree_specs
        specs = tree_specs(dist, model.param_defs())
        return jax.tree.map(lambda g, s: dist.constrain(g, s), grads, specs)

    def train_step(params, opt_state, batch):
        if grad_accum == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            grads = _constrain_grads(grads)
        else:
            def split(x):
                return x.reshape((grad_accum, x.shape[0] // grad_accum)
                                 + x.shape[1:])
            mb = jax.tree.map(split, batch)

            def acc_body(carry, mbatch):
                l, g = jax.value_and_grad(loss_fn)(params, mbatch)
                return (carry[0] + l,
                        jax.tree.map(jnp.add, carry[1], g)), None
            zero_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(
                acc_body, (jnp.zeros((), jnp.float32), zero_g), mb)
            loss = loss / grad_accum
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
        grads = tie(grads)
        new_params, new_state = apply_updates(opt_cfg, params, grads,
                                              opt_state)
        return new_params, new_state, loss

    return train_step


def build_prefill_step(model: Model):
    def prefill_step(params, batch):
        return model.prefill(params, batch)
    return prefill_step


def build_decode_step(model: Model):
    def decode_step(params, cache, batch):
        return model.decode(params, cache, batch)
    return decode_step


# ---------------------------------------------------------------------------
# Jit wrappers with shardings (used by the trainer)
# ---------------------------------------------------------------------------


def _ns(dist: Dist, spec_tree):
    if not dist.has_mesh:
        return None
    return jax.tree.map(lambda s: NamedSharding(dist.mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def jit_train_step(model: Model, opt_cfg: OptConfig, shape: ShapeSpec,
                   grad_accum: int = 1):
    dist = model.dist
    pspecs = model.param_specs()
    ospecs = opt_state_specs(opt_cfg, model.param_defs(), dist)
    bspecs = model.input_sharding_specs(shape)
    fn = build_train_step(model, opt_cfg, grad_accum)
    if not dist.has_mesh:
        # the step replaces params and optimizer state: donating them
        # keeps one copy of each on the device, not two
        return jax.jit(fn, donate_argnums=(0, 1))
    return jax.jit(
        fn,
        in_shardings=(_ns(dist, pspecs), _ns(dist, ospecs),
                      _ns(dist, bspecs)),
        out_shardings=(_ns(dist, pspecs), _ns(dist, ospecs),
                       NamedSharding(dist.mesh, P())),
        donate_argnums=(0, 1),
    )


def jit_prefill_step(model: Model, shape: ShapeSpec):
    dist = model.dist
    fn = build_prefill_step(model)
    if not dist.has_mesh:
        return jax.jit(fn)
    pspecs = model.param_specs()
    bspecs = model.input_sharding_specs(shape)
    B = shape.global_batch
    cspecs = model.cache_sharding_specs(B)
    vs = P(dist.batch_axes, None, None)
    return jax.jit(
        fn,
        in_shardings=(_ns(dist, pspecs), _ns(dist, bspecs)),
        out_shardings=(NamedSharding(dist.mesh, vs), _ns(dist, cspecs)),
    )


def jit_decode_step(model: Model, shape: ShapeSpec):
    dist = model.dist
    fn = build_decode_step(model)
    if not dist.has_mesh:
        return jax.jit(fn, donate_argnums=(1,))
    pspecs = model.param_specs()
    B = shape.global_batch
    cspecs = model.cache_sharding_specs(B)
    bspecs = {"tokens": P(dist.batch_axes, None)}
    vs = P(dist.batch_axes, None, None)
    return jax.jit(
        fn,
        in_shardings=(_ns(dist, pspecs), _ns(dist, cspecs),
                      _ns(dist, bspecs)),
        out_shardings=(NamedSharding(dist.mesh, vs), _ns(dist, cspecs)),
        donate_argnums=(1,),
    )


def abstract_inputs(model: Model, shape: ShapeSpec,
                    opt_cfg: Optional[OptConfig] = None):
    """(args...) ShapeDtypeStructs for lowering the right step kind."""
    aps = model.abstract_params()
    if shape.kind == "train":
        oc = opt_cfg or default_optimizer(model.cfg)
        opt = jax.eval_shape(lambda p: init_opt_state(oc, p), aps)
        return (aps, opt, model.input_specs(shape))
    if shape.kind == "prefill":
        return (aps, model.input_specs(shape))
    cache = model.cache_specs(shape.global_batch, shape.seq_len)
    return (aps, cache, model.input_specs(shape))
