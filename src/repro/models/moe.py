"""Mixture-of-Experts with sort-based expert-parallel dispatch.

TPU adaptation notes (see DESIGN.md §5):
  * experts are sharded over the "model" mesh axis (EP). When the expert
    count is below the axis size, experts are *replicated* R = axis/E times
    ("virtual experts", DeepSeek-EP style hot-expert replication); the
    router spreads tokens round-robin over copies and the training step
    ties copy gradients, so the model stays exactly the paper-listed E.
  * dispatch is sort-based (argsort by expert id + capacity clip), NOT the
    GShard one-hot einsum whose dispatch matmul costs ~2·T·E·C·d FLOPs —
    300× the expert FLOPs at kimi-k2 scale.
  * the prefill/train path sequence-shards tokens over "model", dispatches
    with one all_to_all to expert owners and one back; the decode path
    (seq=1) keeps tokens replicated over "model", computes local experts
    only and psums the combine.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, MoEConfig
from repro.distributed.sharding import Dist
from repro.models.layers import ParamDef


def replication_factor(moe: MoEConfig, dist: Dist) -> int:
    ms = dist.model_size
    if ms <= 1 or dist.expert_axis is None:
        return 1
    if moe.n_experts >= ms:
        assert moe.n_experts % ms == 0, (moe.n_experts, ms)
        return 1
    assert ms % moe.n_experts == 0, (moe.n_experts, ms)
    return ms // moe.n_experts


def moe_param_defs(cfg: ArchConfig, dist: Dist, scan_dims=()) -> dict:
    moe = cfg.moe
    r = replication_factor(moe, dist)
    ev = moe.n_experts * r
    lead = tuple(scan_dims)
    ldim = tuple("layers" for _ in lead)
    d, fe = cfg.d_model, moe.d_ff_expert
    return {
        "router": ParamDef(lead + (d, moe.n_experts),
                           ldim + ("embed", "expert_out")),
        "wg": ParamDef(lead + (ev, d, fe), ldim + ("expert", "embed", "eff")),
        "wu": ParamDef(lead + (ev, d, fe), ldim + ("expert", "embed", "eff")),
        "wd": ParamDef(lead + (ev, fe, d), ldim + ("expert", "eff", "embed")),
    }


def _capacity(n_tokens: int, top_k: int, ev: int, cf: float) -> int:
    c = int(math.ceil(n_tokens * top_k * cf / ev))
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def _route(x2d, router_w, moe: MoEConfig, r: int):
    """x2d (T, D) -> (expert_v (T*k,), gate (T*k,), token (T*k,))."""
    t = x2d.shape[0]
    logits = (x2d.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                   # (T, E)
    gate, idx = jax.lax.top_k(probs, moe.top_k)               # (T, k)
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)
    if r > 1:
        # round-robin over the R copies of each expert, balanced by slot id
        slot = (jnp.arange(t)[:, None] * moe.top_k
                + jnp.arange(moe.top_k)[None, :]) % r
        idx = idx * r + slot
    token = jnp.broadcast_to(jnp.arange(t)[:, None], idx.shape)
    return idx.reshape(-1), gate.reshape(-1), token.reshape(-1)


def _fill_buffers(x2d, expert_v, gate, token, ev: int, cap: int):
    """Sort-based capacity dispatch -> (buf (ev*cap, D), slot bookkeeping)."""
    tk = expert_v.shape[0]
    order = jnp.argsort(expert_v)                       # stable
    se = expert_v[order]
    # rank of each routed pair within its expert
    starts = jnp.searchsorted(se, jnp.arange(ev), side="left")
    rank = jnp.arange(tk) - starts[se]
    keep = rank < cap
    slot = jnp.where(keep, se * cap + rank, ev * cap)   # OOB -> dropped
    d = x2d.shape[-1]
    buf = jnp.zeros((ev * cap, d), x2d.dtype)
    buf = buf.at[slot].set(x2d[token[order]], mode="drop")
    tok_of_slot = jnp.full((ev * cap,), tk, jnp.int32)
    tok_of_slot = tok_of_slot.at[slot].set(token[order], mode="drop")
    gate_of_slot = jnp.zeros((ev * cap,), jnp.float32)
    gate_of_slot = gate_of_slot.at[slot].set(gate[order], mode="drop")
    return buf, tok_of_slot, gate_of_slot


def _expert_mlp(buf_e, wg, wu, wd):
    """buf_e (E_l, T_e, D); weights (E_l, D, Fe)/(E_l, Fe, D)."""
    h = jnp.einsum("etd,edf->etf", buf_e, wg)
    h = jax.nn.silu(h) * jnp.einsum("etd,edf->etf", buf_e, wu)
    return jnp.einsum("etf,efd->etd", h, wd)


def _combine(y_slots, tok_of_slot, gate_of_slot, n_tokens: int):
    d = y_slots.shape[-1]
    out = jnp.zeros((n_tokens + 1, d), jnp.float32)
    contrib = y_slots.astype(jnp.float32) * gate_of_slot[:, None]
    out = out.at[tok_of_slot].add(contrib, mode="drop")
    return out[:n_tokens]


# ---------------------------------------------------------------------------
# Local (single shard) path — also the smoke/CPU path
# ---------------------------------------------------------------------------


def _moe_single(x, params, moe: MoEConfig, r: int):
    b, s, d = x.shape
    ev = moe.n_experts * r
    x2d = x.reshape(-1, d)
    cap = _capacity(x2d.shape[0], moe.top_k, ev, moe.capacity_factor)
    ei, gi, ti = _route(x2d, params["router"], moe, r)
    buf, tos, gos = _fill_buffers(x2d, ei, gi, ti, ev, cap)
    y = _expert_mlp(buf.reshape(ev, cap, d), params["wg"], params["wu"],
                    params["wd"]).reshape(ev * cap, d)
    out = _combine(y, tos, gos, x2d.shape[0])
    return out.reshape(b, s, d).astype(x.dtype)


# ---------------------------------------------------------------------------
# Sharded paths
# ---------------------------------------------------------------------------


def _gather_fsdp(w, dist_axes):
    if dist_axes:
        w = jax.lax.all_gather(w, dist_axes, axis=1, tiled=True)
    return w


def moe_block(x, params, cfg: ArchConfig, dist: Dist):
    """x (B, S, D) -> (B, S, D). Chooses the dispatch strategy by shape."""
    moe = cfg.moe
    r = replication_factor(moe, dist)
    if not dist.has_mesh or dist.expert_axis is None:
        return _moe_single(x, params, moe, r)

    b, s, d = x.shape
    ms = dist.model_size
    ev = moe.n_experts * r
    e_local = ev // ms
    bt = dist.batch_axes
    fsdp = dist.fsdp_axes
    if fsdp:
        # experts already occupy "model"; weights FSDP over the rest
        fsdp = tuple(a for a in fsdp if a != "model") or None
    mesh = dist.mesh

    wspec_g = P("model", fsdp, None)     # (Ev, D, Fe): E over model, D fsdp
    wspec_d = P("model", None, fsdp)     # (Ev, Fe, D)

    if s % ms == 0 and s > 1:
        # ---- train/prefill: sequence-sharded tokens + all_to_all EP ------
        def body(xl, rw, wg, wu, wd):
            bl, sl, _ = xl.shape
            wg = _gather_fsdp(wg, fsdp)
            wu = _gather_fsdp(wu, fsdp)
            wd = jax.lax.all_gather(wd, fsdp, axis=2, tiled=True) if fsdp else wd
            x2d = xl.reshape(-1, d)
            t = x2d.shape[0]
            cap = _capacity(t, moe.top_k, ev, moe.capacity_factor)
            ei, gi, ti = _route(x2d, rw, moe, r)
            buf, tos, gos = _fill_buffers(x2d, ei, gi, ti, ev, cap)
            # (Ev*cap, D) -> (ms, E_l, cap, D); dim0 = destination device
            buf = buf.reshape(ms, e_local, cap, d)
            recv = jax.lax.all_to_all(buf, "model", split_axis=0,
                                      concat_axis=0, tiled=True)
            # dim0 now = source device; group tokens per local expert
            recv = recv.reshape(ms, e_local, cap, d).transpose(1, 0, 2, 3)
            recv = recv.reshape(e_local, ms * cap, d)
            y = _expert_mlp(recv, wg, wu, wd)
            y = y.reshape(e_local, ms, cap, d).transpose(1, 0, 2, 3)
            y = jax.lax.all_to_all(y, "model", split_axis=0,
                                   concat_axis=0, tiled=True)
            y = y.reshape(ev * cap, d)
            out = _combine(y, tos, gos, t)
            return out.reshape(bl, sl, d).astype(xl.dtype)

        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(bt, "model", None), P(None, None),
                      wspec_g, wspec_g, wspec_d),
            out_specs=P(bt, "model", None), check_vma=False)
        return fn(x, params["router"], params["wg"], params["wu"],
                  params["wd"])

    # ---- decode: tokens replicated over "model", local experts + psum ----
    def body_dec(xl, rw, wg, wu, wd):
        bl, sl, _ = xl.shape
        wg = _gather_fsdp(wg, fsdp)
        wu = _gather_fsdp(wu, fsdp)
        wd = jax.lax.all_gather(wd, fsdp, axis=2, tiled=True) if fsdp else wd
        x2d = xl.reshape(-1, d)
        t = x2d.shape[0]
        cap = _capacity(t, moe.top_k, ev, moe.capacity_factor)
        ei, gi, ti = _route(x2d, rw, moe, r)
        my = jax.lax.axis_index("model")
        mine = (ei // e_local) == my
        # non-local choices -> dropped here (handled by their owner shard)
        ei_l = jnp.where(mine, ei % e_local, e_local)
        gi_l = jnp.where(mine, gi, 0.0)
        buf, tos, gos = _fill_buffers(x2d, ei_l, gi_l, ti, e_local, cap)
        # slots routed to the sentinel expert e_local were padded into the
        # buffer tail by construction of _fill_buffers' OOB slot.
        y = _expert_mlp(buf.reshape(e_local, cap, d), wg, wu, wd)
        out = _combine(y.reshape(e_local * cap, d), tos, gos, t)
        out = jax.lax.psum(out, "model")
        return out.reshape(bl, sl, d).astype(xl.dtype)

    fn = jax.shard_map(
        body_dec, mesh=mesh,
        in_specs=(P(bt, None, None), P(None, None),
                  wspec_g, wspec_g, wspec_d),
        out_specs=P(bt, None, None), check_vma=False)
    return fn(x, params["router"], params["wg"], params["wu"], params["wd"])
