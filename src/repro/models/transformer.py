"""Decoder-only LM (dense / MoE / VLM) and the Jamba-style hybrid.

All models are functional: ``*_param_defs`` build ParamDef trees (abstract,
for dry-run + sharding), ``lm_loss`` / ``lm_prefill`` / ``lm_decode`` are
pure functions. Layers are scanned (stacked leading dim) so HLO size is
independent of depth; the hybrid scans over periods of ``attn_period``
layers (1 attention + N-1 mamba, per Jamba).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed.sharding import Dist, dim_shardable
from repro.models import mamba as mam
from repro.models.attention import (decode_attention, flash_attention_ref,
                                    repeat_kv)
from repro.models.layers import (ParamDef, apply_rope, chunked_xent,
                                 embed_tokens, gated_mlp, last_token_logits,
                                 layer_norm, rms_norm)
from repro.models.moe import moe_block, moe_param_defs

DEFAULT_OPTS: Dict[str, Any] = {
    "remat": "full",       # none | dots | full
    "xent_chunk": 512,
    "q_chunk": 512,
    "k_chunk": 1024,
}


def _opt(opts, key):
    return (opts or {}).get(key, DEFAULT_OPTS[key])


# ---------------------------------------------------------------------------
# Param defs
# ---------------------------------------------------------------------------


def norm_param_defs(cfg: ArchConfig, scan_dims=()) -> dict:
    ld = tuple("layers" for _ in scan_dims)
    defs = {"w": ParamDef(scan_dims + (cfg.d_model,), ld + ("norm",),
                          init="ones")}
    if cfg.family == "encdec":   # whisper uses LayerNorm
        defs["b"] = ParamDef(scan_dims + (cfg.d_model,), ld + ("norm",),
                             init="zeros")
    return defs


def norm_apply(x, p, cfg: ArchConfig):
    if "b" in p:
        return layer_norm(x, p["w"], p["b"], cfg.norm_eps)
    return rms_norm(x, p["w"], cfg.norm_eps)


def attn_param_defs(cfg: ArchConfig, scan_dims=()) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ld = tuple("layers" for _ in scan_dims)
    defs = {
        "wq": ParamDef(scan_dims + (d, h, hd), ld + ("embed", "heads", "hd"),
                       fan_in=d),
        "wk": ParamDef(scan_dims + (d, kv, hd), ld + ("embed", "kv", "hd"),
                       fan_in=d),
        "wv": ParamDef(scan_dims + (d, kv, hd), ld + ("embed", "kv", "hd"),
                       fan_in=d),
        "wo": ParamDef(scan_dims + (h, hd, d), ld + ("heads", "hd", "embed"),
                       fan_in=h * hd),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef(scan_dims + (h, hd), ld + ("heads", "hd"),
                              init="zeros")
        defs["bk"] = ParamDef(scan_dims + (kv, hd), ld + ("kv", "hd"),
                              init="zeros")
        defs["bv"] = ParamDef(scan_dims + (kv, hd), ld + ("kv", "hd"),
                              init="zeros")
    return defs


def mlp_param_defs(cfg: ArchConfig, scan_dims=()) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ld = tuple("layers" for _ in scan_dims)
    return {
        "wg": ParamDef(scan_dims + (d, f), ld + ("embed", "ff")),
        "wu": ParamDef(scan_dims + (d, f), ld + ("embed", "ff")),
        "wd": ParamDef(scan_dims + (f, d), ld + ("ff", "embed")),
    }


def decoder_param_defs(cfg: ArchConfig, dist: Dist) -> dict:
    L = cfg.n_layers
    if cfg.family == "hybrid":
        return _hybrid_param_defs(cfg, dist)
    block: Dict[str, Any] = {
        "ln1": norm_param_defs(cfg, (L,)),
        "ln2": norm_param_defs(cfg, (L,)),
    }
    if cfg.family == "ssm":
        block = {"ln1": norm_param_defs(cfg, (L,)),
                 "mamba": mam.mamba_param_defs(cfg, (L,))}
    else:
        block["attn"] = attn_param_defs(cfg, (L,))
        if cfg.is_moe and cfg.moe.layout == "all":
            block["moe"] = moe_param_defs(cfg, dist, (L,))
        else:
            block["mlp"] = mlp_param_defs(cfg, (L,))
    defs = {
        "blocks": block,
        "final_norm": norm_param_defs(cfg),
        "head": ParamDef((cfg.d_model, cfg.vocab_size), ("embed", "vocab")),
    }
    if cfg.frontend == "none":
        defs["embed"] = ParamDef((cfg.vocab_size, cfg.d_model),
                                 ("vocab", "embed"), fan_in=1)
    else:
        # stub frontends feed precomputed embeddings; keep a (tiny) text
        # embedding for decode steps over generated tokens.
        defs["embed"] = ParamDef((cfg.vocab_size, cfg.d_model),
                                 ("vocab", "embed"), fan_in=1)
    return defs


def _hybrid_param_defs(cfg: ArchConfig, dist: Dist) -> dict:
    per = cfg.attn_period                      # 8 for jamba
    np_ = cfg.n_layers // per                  # periods (9)
    n_moe = per // 2                           # odd local indices
    n_mlp = per - n_moe
    block = {
        "attn": attn_param_defs(cfg, (np_,)),
        "attn_ln": norm_param_defs(cfg, (np_,)),
        "mamba": mam.mamba_param_defs(cfg, (np_, per - 1)),
        "mamba_ln": norm_param_defs(cfg, (np_, per - 1)),
        "ffn_ln": norm_param_defs(cfg, (np_, per)),
        "moe": moe_param_defs(cfg, dist, (np_, n_moe)),
        "mlp": mlp_param_defs(cfg, (np_, n_mlp)),
    }
    return {
        "embed": ParamDef((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                          fan_in=1),
        "blocks": block,
        "final_norm": norm_param_defs(cfg),
        "head": ParamDef((cfg.d_model, cfg.vocab_size), ("embed", "vocab")),
    }


# ---------------------------------------------------------------------------
# Attention sub-blocks
# ---------------------------------------------------------------------------


def _cache_dtype(cfg: ArchConfig):
    """bf16 caches in production; full precision when the model is f32
    (smoke) so decode matches prefill bit-for-bit-ish."""
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.dtype(cfg.dtype)


def _use_sp(cfg: ArchConfig, dist: Dist, seq: int) -> bool:
    """zero3_sp sequence-parallel activations (attention families only:
    the SSD scan needs its full sequence per shard)."""
    return (dist.seq_parallel and cfg.family in ("dense", "moe", "vlm")
            and seq % dist.model_size == 0 and seq > 1)


def _res_spec(cfg: ArchConfig, dist: Dist, seq: int) -> P:
    sp = _use_sp(cfg, dist, seq)
    return P(dist.batch_axes, "model" if sp else None, None)


def _heads_axis(cfg: ArchConfig, dist: Dist):
    if dist.has_mesh and dist.tp_axis and cfg.n_heads % dist.model_size == 0:
        return "model"
    return None


def _qkv(h, p, cfg: ArchConfig, dist: Dist, positions):
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    sections = cfg.mrope_sections if cfg.mrope else None
    q = apply_rope(q, positions, cfg.rope_theta, sections)
    k = apply_rope(k, positions, cfg.rope_theta, sections)
    return q, k, v


def attn_train(h, p, cfg: ArchConfig, dist: Dist, positions, opts,
               causal: bool = True):
    """Full-sequence attention; returns (out, (k, v)) for caching."""
    ha = _heads_axis(cfg, dist)
    bt = dist.batch_axes
    q, k, v = _qkv(h, p, cfg, dist, positions)
    if _use_sp(cfg, dist, h.shape[1]):
        # zero3_sp: queries sequence-sharded, heads replicated; k/v are
        # gathered inside the shard_map. No psum on the wo contraction.
        from repro.models.attention import sp_flash_attention
        sspec = P(bt, "model", None, None)
        q = dist.constrain(q, sspec)
        k = dist.constrain(k, sspec)
        v = dist.constrain(v, sspec)
        out = sp_flash_attention(q, k, v, dist, causal=causal,
                                 q_chunk=_opt(opts, "q_chunk"),
                                 k_chunk=_opt(opts, "k_chunk"))
        out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
        out = dist.constrain(out, P(bt, "model", None))
        return out, (_cache_rows(k, cfg), _cache_rows(v, cfg))
    if dist.has_mesh:
        q = dist.constrain(q, P(bt, None, ha, None))
        k = dist.constrain(k, P(bt, None, None, None))
        v = dist.constrain(v, P(bt, None, None, None))
    kr = repeat_kv(k, cfg.n_heads)
    vr = repeat_kv(v, cfg.n_heads)
    if dist.has_mesh:
        kr = dist.constrain(kr, P(bt, None, ha, None))
        vr = dist.constrain(vr, P(bt, None, ha, None))
    out = flash_attention_ref(q, kr, vr, causal=causal,
                              q_chunk=_opt(opts, "q_chunk"),
                              k_chunk=_opt(opts, "k_chunk"))
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if dist.has_mesh:
        out = dist.constrain(out, P(bt, None, None))
    return out, (_cache_rows(k, cfg), _cache_rows(v, cfg))


def _cache_rows(x, cfg: ArchConfig):
    """(B,S,KV,hd) -> the cache's rows (B,S,KV*hd) in the cache dtype."""
    return x.reshape(x.shape[:2] + (-1,)).astype(_cache_dtype(cfg))


def write_rows(cache, rows, pos):
    """Write one new row per layer and batch row into the cache, in place.

    cache (L,B,S,W); rows (L,B,W); pos: the write position, a scalar or
    one per batch row. Each write is a dynamic_update_slice of the donated
    buffer, so nothing but the rows moves."""
    rows = rows.astype(cache.dtype)[:, :, None]
    if jnp.ndim(pos) == 0:
        return jax.lax.dynamic_update_slice(cache, rows, (0, 0, pos, 0))
    for b in range(cache.shape[1]):
        cache = jax.lax.dynamic_update_slice(
            cache, rows[:, b:b + 1], (0, b, pos[b], 0))
    return cache


def attn_decode(h, p, cfg: ArchConfig, dist: Dist, pos, kc, vc):
    """h (B,1,D); kc/vc (B,S,KV*hd), read only; pos: the token's position,
    a scalar or one per batch row. Returns (out, k_row, v_row): the
    token's rows (B,KV*hd) in the cache dtype, for the caller to write at
    pos."""
    bsz = h.shape[0]
    positions = jnp.broadcast_to(jnp.reshape(pos, (-1, 1)),
                                 (bsz, 1)).astype(jnp.int32)
    if cfg.mrope:
        positions = jnp.broadcast_to(positions, (3,) + positions.shape)
    q, k, v = _qkv(h, p, cfg, dist, positions)
    # rounded to the cache dtype first: the numbers later steps read back
    k, v = _cache_rows(k, cfg), _cache_rows(v, cfg)
    out = decode_attention(q, kc, vc, pos, k, v)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if dist.has_mesh:
        out = dist.constrain(out, P(dist.batch_axes, None, None))
    return out, k[:, 0], v[:, 0]


def ffn_apply(h, bp, cfg: ArchConfig, dist: Dist):
    if "moe" in bp:
        return moe_block(h, bp["moe"], cfg, dist)
    return gated_mlp(h, bp["mlp"]["wg"], bp["mlp"]["wu"], bp["mlp"]["wd"],
                     dist)


# ---------------------------------------------------------------------------
# Homogeneous decoder stack (dense / moe / ssm / vlm)
# ---------------------------------------------------------------------------


def _remat(fn, opts):
    mode = _opt(opts, "remat")
    if mode == "none":
        return fn
    if mode == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def _stack_forward(h, params, cfg: ArchConfig, dist: Dist, positions, opts,
                   collect_cache: bool):
    """Scan over layers. Returns (h, cache_stacks or None)."""

    def body(carry, bp):
        hh = carry
        if cfg.family == "ssm":
            x = norm_apply(hh, bp["ln1"], cfg)
            out, state, tail = mam.mamba_block(x, bp["mamba"], cfg, dist)
            hh = hh + out
            ys = (state.astype(jnp.float32), tail) if collect_cache else None
            return hh, ys
        x = norm_apply(hh, bp["ln1"], cfg)
        a, (k, v) = attn_train(x, bp["attn"], cfg, dist, positions, opts)
        hh = hh + a
        if dist.has_mesh:
            hh = dist.constrain(hh, _res_spec(cfg, dist, hh.shape[1]))
        x = norm_apply(hh, bp["ln2"], cfg)
        hh = hh + ffn_apply(x, bp, cfg, dist)
        if dist.has_mesh:
            hh = dist.constrain(hh, _res_spec(cfg, dist, hh.shape[1]))
        ys = (k, v) if collect_cache else None
        return hh, ys

    h, caches = jax.lax.scan(_remat(body, opts), h, params["blocks"])
    return h, caches


def _inputs_to_h(params, batch, cfg: ArchConfig, dist: Dist):
    """Resolve tokens/embeds input to hidden states + positions."""
    if "embeds" in batch:
        h = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        vs = dim_shardable(dist, cfg.vocab_size, "vocab")
        h = embed_tokens(batch["tokens"], params["embed"], dist, vs)
    b, s = h.shape[0], h.shape[1]
    if "positions" in batch:
        positions = batch["positions"]
    else:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        if cfg.mrope:
            positions = jnp.broadcast_to(positions, (3, b, s))
    if dist.has_mesh:
        h = dist.constrain(h, _res_spec(cfg, dist, h.shape[1]))
    return h, positions


def lm_loss(params, batch, cfg: ArchConfig, dist: Dist, opts=None):
    """Next-token CE loss. batch: tokens|embeds, labels[, positions]."""
    if cfg.family == "hybrid":
        return _hybrid_loss(params, batch, cfg, dist, opts)
    h, positions = _inputs_to_h(params, batch, cfg, dist)
    h, _ = _stack_forward(h, params, cfg, dist, positions, opts,
                          collect_cache=False)
    if dist.has_mesh:
        h = dist.constrain(h, P(dist.batch_axes, None, None))
    h = norm_apply(h, params["final_norm"], cfg)
    vs = dim_shardable(dist, cfg.vocab_size, "vocab")
    return chunked_xent(h, params["head"], batch["labels"], dist,
                        chunk=min(_opt(opts, "xent_chunk"), h.shape[1]),
                        vocab_sharded=vs)


def lm_prefill(params, batch, cfg: ArchConfig, dist: Dist, opts=None):
    """Prefill: build caches, return last-position logits + cache pytree."""
    if cfg.family == "hybrid":
        return _hybrid_prefill(params, batch, cfg, dist, opts)
    h, positions = _inputs_to_h(params, batch, cfg, dist)
    seq = h.shape[1]
    h, caches = _stack_forward(h, params, cfg, dist, positions, opts,
                               collect_cache=True)
    h = norm_apply(h, params["final_norm"], cfg)
    vs = dim_shardable(dist, cfg.vocab_size, "vocab")
    logits = last_token_logits(h[:, -1:], params["head"], dist, vs)
    if cfg.family == "ssm":
        cache = {"ssm": caches[0], "conv": caches[1],
                 "pos": jnp.int32(seq)}
    else:
        k, v = caches                     # (L,B,S,KV*hd)
        cache = {"k": k, "v": v, "pos": jnp.int32(seq)}
    return logits, cache


def lm_decode(params, cache, batch, cfg: ArchConfig, dist: Dist, opts=None):
    """One decode step. batch: token (B,1) [or embeds]. cache["pos"] is the
    write position: a scalar shared by the batch, or one per batch row.

    The KV cache goes through the layer scan read-only; each layer returns
    only its new rows, written in place after the scan. Returns
    (logits (B,1,V), new cache)."""
    if cfg.family == "hybrid":
        return _hybrid_decode(params, cache, batch, cfg, dist, opts)
    if "embeds" in batch:
        h = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        vs = dim_shardable(dist, cfg.vocab_size, "vocab")
        h = embed_tokens(batch["tokens"], params["embed"], dist, vs)
    pos = cache["pos"]

    if cfg.family == "ssm":
        def body(hh, xs):
            bp, state, tail = xs
            x = norm_apply(hh, bp["ln1"], cfg)
            out, state, tail = mam.mamba_decode(x, bp["mamba"], cfg, dist,
                                                state, tail)
            return hh + out, (state, tail)
        h, (ssm, conv) = jax.lax.scan(
            body, h, (params["blocks"], cache["ssm"], cache["conv"]))
        new_cache = {"ssm": ssm, "conv": conv, "pos": pos + 1}
    else:
        def body(hh, xs):
            bp, kc, vc = xs
            x = norm_apply(hh, bp["ln1"], cfg)
            a, k, v = attn_decode(x, bp["attn"], cfg, dist, pos, kc, vc)
            hh = hh + a
            x = norm_apply(hh, bp["ln2"], cfg)
            hh = hh + ffn_apply(x, bp, cfg, dist)
            return hh, (k, v)
        h, (k, v) = jax.lax.scan(
            body, h, (params["blocks"], cache["k"], cache["v"]))
        new_cache = {"k": write_rows(cache["k"], k, pos),
                     "v": write_rows(cache["v"], v, pos), "pos": pos + 1}

    h = norm_apply(h, params["final_norm"], cfg)
    vs = dim_shardable(dist, cfg.vocab_size, "vocab")
    logits = last_token_logits(h, params["head"], dist, vs)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Hybrid (Jamba): periods of [attn, mamba x (per-1)], alternating MoE FFN
# ---------------------------------------------------------------------------


def _hybrid_period(hh, bp, cfg, dist, positions, opts, collect):
    """One period: layer j==0 attention, j>0 mamba; FFN after each mixer
    (MoE at odd local j)."""
    per = cfg.attn_period
    ys_attn = None
    ys_mamba = []

    def ffn_at(hh, j):
        x = norm_apply(hh, jax.tree.map(lambda a: a[j], bp["ffn_ln"]), cfg)
        if j % 2 == 1:
            sub = jax.tree.map(lambda a: a[(j - 1) // 2], bp["moe"])
            return hh + moe_block(x, sub, cfg, dist)
        sub = jax.tree.map(lambda a: a[j // 2], bp["mlp"])
        return hh + gated_mlp(x, sub["wg"], sub["wu"], sub["wd"], dist)

    # j = 0: attention
    x = norm_apply(hh, bp["attn_ln"], cfg)
    a, kv = attn_train(x, bp["attn"], cfg, dist, positions, opts)
    hh = ffn_at(hh + a, 0)
    if collect:
        ys_attn = kv
    # j = 1..per-1: mamba
    for j in range(1, per):
        mp = jax.tree.map(lambda a: a[j - 1], bp["mamba"])
        ln = jax.tree.map(lambda a: a[j - 1], bp["mamba_ln"])
        x = norm_apply(hh, ln, cfg)
        out, state, tail = mam.mamba_block(x, mp, cfg, dist)
        hh = ffn_at(hh + out, j)
        if collect:
            ys_mamba.append((state, tail))
    if collect:
        states = jnp.stack([s for s, _ in ys_mamba])
        tails = jnp.stack([t for _, t in ys_mamba])
        return hh, (ys_attn[0], ys_attn[1], states, tails)
    return hh, None


def _hybrid_loss(params, batch, cfg, dist, opts):
    h, positions = _inputs_to_h(params, batch, cfg, dist)

    def body(hh, bp):
        hh, _ = _hybrid_period(hh, bp, cfg, dist, positions, opts, False)
        return hh, None

    h, _ = jax.lax.scan(_remat(body, opts), h, params["blocks"])
    h = norm_apply(h, params["final_norm"], cfg)
    vs = dim_shardable(dist, cfg.vocab_size, "vocab")
    return chunked_xent(h, params["head"], batch["labels"], dist,
                        chunk=min(_opt(opts, "xent_chunk"), h.shape[1]),
                        vocab_sharded=vs)


def _hybrid_prefill(params, batch, cfg, dist, opts):
    h, positions = _inputs_to_h(params, batch, cfg, dist)
    seq = h.shape[1]

    def body(hh, bp):
        hh, ys = _hybrid_period(hh, bp, cfg, dist, positions, opts, True)
        return hh, ys

    h, (k, v, states, tails) = jax.lax.scan(
        _remat(body, opts), h, params["blocks"])
    h = norm_apply(h, params["final_norm"], cfg)
    vs = dim_shardable(dist, cfg.vocab_size, "vocab")
    logits = last_token_logits(h[:, -1:], params["head"], dist, vs)
    cache = {"k": k, "v": v, "ssm": states, "conv": tails,
             "pos": jnp.int32(seq)}
    return logits, cache


def _hybrid_decode(params, cache, batch, cfg, dist, opts):
    vs = dim_shardable(dist, cfg.vocab_size, "vocab")
    h = embed_tokens(batch["tokens"], params["embed"], dist, vs)
    pos = cache["pos"]
    per = cfg.attn_period

    def body(hh, xs):
        bp, kc, vc, states, tails = xs

        def ffn_at(hh, j):
            x = norm_apply(hh, jax.tree.map(lambda a: a[j], bp["ffn_ln"]),
                           cfg)
            if j % 2 == 1:
                sub = jax.tree.map(lambda a: a[(j - 1) // 2], bp["moe"])
                return hh + moe_block(x, sub, cfg, dist)
            sub = jax.tree.map(lambda a: a[j // 2], bp["mlp"])
            return hh + gated_mlp(x, sub["wg"], sub["wu"], sub["wd"], dist)

        x = norm_apply(hh, bp["attn_ln"], cfg)
        a, k, v = attn_decode(x, bp["attn"], cfg, dist, pos, kc, vc)
        hh = ffn_at(hh + a, 0)
        new_states, new_tails = [], []
        for j in range(1, per):
            mp = jax.tree.map(lambda a: a[j - 1], bp["mamba"])
            ln = jax.tree.map(lambda a: a[j - 1], bp["mamba_ln"])
            x = norm_apply(hh, ln, cfg)
            out, st, tl = mam.mamba_decode(
                x, mp, cfg, dist, states[j - 1], tails[j - 1])
            hh = ffn_at(hh + out, j)
            new_states.append(st)
            new_tails.append(tl)
        return hh, (k, v, jnp.stack(new_states), jnp.stack(new_tails))

    h, (k, v, ssm, conv) = jax.lax.scan(
        body, h, (params["blocks"], cache["k"], cache["v"],
                  cache["ssm"], cache["conv"]))
    h = norm_apply(h, params["final_norm"], cfg)
    logits = last_token_logits(h, params["head"], dist, vs)
    return logits, {"k": write_rows(cache["k"], k, pos),
                    "v": write_rows(cache["v"], v, pos),
                    "ssm": ssm, "conv": conv, "pos": pos + 1}
