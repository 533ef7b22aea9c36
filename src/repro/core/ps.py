"""Parameter-server primitives (paper §Parameter Server), TPU-adapted.

The paper's PS client "evenly divides the entire model based on the number
of available servers and sends partitions by partition ID"; the same
partitions from all learners meet at one server, which aggregates and
returns. Here the learners ARE the servers: the model is flattened and
chunked over the ``data`` axis, so

    push  = reduce-scatter   (partitions meet at their owner)
    aggregate+update         (runs where the shard lives)
    pull  = all-gather       (updated partitions return to all learners)

moving 2·(L−1)/L·|model| bytes per learner — the paper's O(L) scheme. The
``broadcast`` mode implements the O(L²) all-to-all strawman the paper
argues against (every learner all-gathers every other learner's full
vector) so the asymptotics are measurable from compiled HLO.

Every primitive has a mesh implementation (shard_map + lax collectives)
and a local one (leading learner axis on one device) with identical math,
so solver behaviour is unit-testable in-process.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class PSContext:
    mesh: Optional[object]        # jax Mesh or None
    n_learners: int
    axis: str = "data"

    @property
    def use_mesh(self) -> bool:
        return self.mesh is not None and self.n_learners > 1


def shard_len(n_flat: int, n_learners: int) -> int:
    assert n_flat % n_learners == 0
    return n_flat // n_learners


# ---------------------------------------------------------------------------
# pull: sharded center -> full params everywhere
# ---------------------------------------------------------------------------


def pull(center, ctx: PSContext):
    """center: (F/L,)-per-owner [mesh: (F,) array sharded P(axis)] -> (F,)."""
    if not ctx.use_mesh:
        return center.reshape(-1)

    def body(c):
        return jax.lax.all_gather(c, ctx.axis, axis=0, tiled=True)

    return jax.shard_map(body, mesh=ctx.mesh, in_specs=P(ctx.axis),
                         out_specs=P(None), check_vma=False)(center)


# ---------------------------------------------------------------------------
# push (mean aggregation): per-learner vectors -> mean on every learner
# ---------------------------------------------------------------------------


def push_mean(vstack, mode: str, ctx: PSContext):
    """vstack (NL, F) per-learner -> (F,) mean, via PS or broadcast."""
    if not ctx.use_mesh:
        return jnp.mean(vstack, axis=0)
    nl = ctx.n_learners

    if mode == "ps":
        def body(v):
            # v (1, F) local learner. reduce-scatter -> own chunk of sum
            chunk = jax.lax.psum_scatter(v[0], ctx.axis, scatter_dimension=0,
                                         tiled=True) / nl
            return jax.lax.all_gather(chunk, ctx.axis, axis=0, tiled=True)
        return jax.shard_map(body, mesh=ctx.mesh,
                             in_specs=P(ctx.axis, None),
                             out_specs=P(None), check_vma=False)(vstack)

    # broadcast: every learner receives every other learner's FULL vector
    def body(v):
        allv = jax.lax.all_gather(v[0], ctx.axis, axis=0)   # (NL, F) each!
        return jnp.mean(allv, axis=0)
    return jax.shard_map(body, mesh=ctx.mesh, in_specs=P(ctx.axis, None),
                         out_specs=P(None), check_vma=False)(vstack)


# ---------------------------------------------------------------------------
# push + server update + pull (PSGD-style: optimizer runs on the shard owner)
# ---------------------------------------------------------------------------


def push_update_pull(gstack, center, opt_state, update_fn, mode: str,
                     ctx: PSContext):
    """gstack (NL, F) grads; center sharded params; update_fn(p, g, s).

    Returns (new_center [sharded like center], new_opt, full_params (F,)).
    """
    if not ctx.use_mesh:
        g = jnp.mean(gstack, axis=0)
        flat = center.reshape(-1)
        new, opt = update_fn(flat, g, opt_state)
        return new.reshape(center.shape), opt, new

    nl = ctx.n_learners
    if mode == "ps":
        def body(g, c, *opt_leaves):
            st = jax.tree.unflatten(opt_def, opt_leaves)
            chunk = jax.lax.psum_scatter(g[0], ctx.axis, scatter_dimension=0,
                                         tiled=True) / nl
            new_c, new_st = update_fn(c, chunk, st)
            full = jax.lax.all_gather(new_c, ctx.axis, axis=0, tiled=True)
            return (new_c, full) + tuple(jax.tree.leaves(new_st))

        opt_leaves, opt_def = jax.tree.flatten(opt_state)
        opt_specs = tuple(P(ctx.axis) if getattr(l, "ndim", 0) > 0 else P()
                          for l in opt_leaves)
        out = jax.shard_map(
            body, mesh=ctx.mesh,
            in_specs=(P(ctx.axis, None), P(ctx.axis)) + opt_specs,
            out_specs=(P(ctx.axis), P(None)) + opt_specs,
            check_vma=False)(gstack, center, *opt_leaves)
        new_center, full = out[0], out[1]
        new_opt = jax.tree.unflatten(opt_def, out[2:])
        return new_center, new_opt, full

    # broadcast: replicated center + opt; every learner updates redundantly
    g = push_mean(gstack, "broadcast", ctx)
    flat = center.reshape(-1)
    new, opt = update_fn(flat, g, opt_state)
    return new.reshape(center.shape), opt, new


# ---------------------------------------------------------------------------
# Downpour: sequential arrival-order application at the shard owner
# ---------------------------------------------------------------------------


def downpour_round(gstack, center, opt_state, update_fn, ctx: PSContext):
    """Async-PS simulation (DESIGN.md §2): each learner's accumulated grads
    are applied SEQUENTIALLY at the PS (arrival order = learner index); each
    learner pulls the params as of its own arrival prefix — preserving
    Downpour's staleness semantics on a synchronous SPMD substrate.

    Returns (new_center, new_opt, per_learner_params (NL, F)).
    """
    if not ctx.use_mesh:
        flat = center.reshape(-1)

        def body(carry, g):
            p, st = carry
            p, st = update_fn(p, g, st)
            return (p, st), p
        (new, opt), prefixes = jax.lax.scan(body, (flat, opt_state), gstack)
        return new.reshape(center.shape), opt, prefixes

    nl = ctx.n_learners

    def body(g, c, *opt_leaves):
        st = jax.tree.unflatten(opt_def, opt_leaves)
        # each learner chunks its grad by destination owner, all_to_all:
        chunks = g[0].reshape(nl, -1)                       # (owners, C)
        recv = jax.lax.all_to_all(chunks, ctx.axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        recv = recv.reshape(nl, -1)                         # (learners, C)

        def seq(carry, gi):
            p, s = carry
            p, s = update_fn(p, gi, s)
            return (p, s), p
        (new_c, new_st), prefixes = jax.lax.scan(seq, (c, st), recv)
        # prefixes (NL, C): row i = my chunk after learner i's push.
        # all_to_all returns row i to learner i, gathered over owners.
        back = jax.lax.all_to_all(prefixes, ctx.axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        mine = back.reshape(1, -1)                          # (1, F)
        return (new_c, mine) + tuple(jax.tree.leaves(new_st))

    opt_leaves, opt_def = jax.tree.flatten(opt_state)
    opt_specs = tuple(P(ctx.axis) if getattr(l, "ndim", 0) > 0 else P()
                      for l in opt_leaves)
    out = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(ctx.axis, None), P(ctx.axis)) + opt_specs,
        out_specs=(P(ctx.axis), P(ctx.axis, None)) + opt_specs,
        check_vma=False)(gstack, center, *opt_leaves)
    new_center, prefixes = out[0], out[1]
    new_opt = jax.tree.unflatten(opt_def, out[2:])
    return new_center, new_opt, prefixes
