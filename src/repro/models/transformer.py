"""Decoder-only LM (dense / MoE / VLM) and the hybrid (Jamba, Granite 4.0-H).

All models are functional: ``*_param_defs`` build ParamDef trees (abstract,
for lowering + sharding), ``lm_loss`` / ``lm_prefill`` / ``lm_decode`` are
pure functions. Layers are scanned (stacked leading dim) so HLO size is
independent of depth; the hybrid scans over periods of ``attn_period``
layers (1 attention at ``attn_index`` + N-1 mamba), each layer followed by
its FFN: MoE (plus a shared expert where configured) at every layer or at
odd ones, a gated MLP at the rest.
"""
from __future__ import annotations

from dataclasses import dataclass
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


def mlp_param_defs(cfg: ArchConfig, scan_dims=(), d_ff: int = 0) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
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
    n_moe = sum(cfg.is_moe_layer(j) for j in range(per))
    block = {
        "attn": attn_param_defs(cfg, (np_,)),
        "attn_ln": norm_param_defs(cfg, (np_,)),
        "mamba": mam.mamba_param_defs(cfg, (np_, per - 1)),
        "mamba_ln": norm_param_defs(cfg, (np_, per - 1)),
        "ffn_ln": norm_param_defs(cfg, (np_, per)),
    }
    if n_moe:
        block["moe"] = moe_param_defs(cfg, dist, (np_, n_moe))
        if cfg.moe.d_ff_shared:
            block["shared"] = mlp_param_defs(cfg, (np_, n_moe),
                                             cfg.moe.d_ff_shared)
    if n_moe < per:
        block["mlp"] = mlp_param_defs(cfg, (np_, per - n_moe))
    defs = {
        "embed": ParamDef((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                          fan_in=1),
        "blocks": block,
        "final_norm": norm_param_defs(cfg),
    }
    if not cfg.tie_embeddings:
        defs["head"] = ParamDef((cfg.d_model, cfg.vocab_size),
                                ("embed", "vocab"))
    return defs


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
    if not cfg.nope:
        sections = cfg.mrope_sections if cfg.mrope else None
        q = apply_rope(q, positions, cfg.rope_theta, sections)
        k = apply_rope(k, positions, cfg.rope_theta, sections)
    if cfg.attn_scale:
        # attention scales scores by head_dim ** -0.5: the query carries
        # the rest of the configured scale
        q = q * (cfg.attn_scale * cfg.hd ** 0.5)
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


def _slot(cache, *idx):
    """The layer at leading indices idx of a stacked state cache: (layer,)
    for a plain stack, (period q, mamba layer m) for the hybrid's."""
    n = len(idx)
    lead = idx + (0,) * (cache.ndim - n)
    return jax.lax.dynamic_slice(cache, lead,
                                 (1,) * n + cache.shape[n:])[(0,) * n]


def _put(cache, x, *idx):
    """cache with the layer at leading indices idx set to x, in its dtype."""
    n = len(idx)
    lead = idx + (0,) * (cache.ndim - n)
    return jax.lax.dynamic_update_slice(
        cache, x[(None,) * n].astype(cache.dtype), lead)


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
    """-> (out, counts): the MoE layer's counts (``moe_block``), or None
    for a gated MLP."""
    if "moe" in bp:
        return moe_block(h, bp["moe"], cfg, dist)
    return gated_mlp(h, bp["mlp"]["wg"], bp["mlp"]["wu"], bp["mlp"]["wd"],
                     dist), None


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
        f, _ = ffn_apply(x, bp, cfg, dist)
        hh = hh + f
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
    only its new rows, written in place after the scan. The SSM and conv
    states ride in the scan's carry, each layer's read and written in
    place, so the donated state cache is updated with no stacked copy.

    Returns (logits (B,1,V), new cache, counts): counts is the MoE layers'
    (rows computed, held experts touched) summed over layers, (2,) int32,
    or None without MoE."""
    if cfg.family == "hybrid":
        return _hybrid_decode(params, cache, batch, cfg, dist, opts)
    if "embeds" in batch:
        h = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        vs = dim_shardable(dist, cfg.vocab_size, "vocab")
        h = embed_tokens(batch["tokens"], params["embed"], dist, vs)
    pos = cache["pos"]
    counts = None

    if cfg.family == "ssm":
        def body(carry, xs):
            hh, ssm, conv = carry
            bp, i = xs
            x = norm_apply(hh, bp["ln1"], cfg)
            out, state, tail = mam.mamba_decode(x, bp["mamba"], cfg, dist,
                                                _slot(ssm, i), _slot(conv, i))
            return (hh + out, _put(ssm, state, i), _put(conv, tail, i)), None
        n = cache["ssm"].shape[0]
        (h, ssm, conv), _ = jax.lax.scan(
            body, (h, cache["ssm"], cache["conv"]),
            (params["blocks"], jnp.arange(n, dtype=jnp.int32)))
        new_cache = {"ssm": ssm, "conv": conv, "pos": pos + 1}
    else:
        def body(hh, xs):
            bp, kc, vc = xs
            x = norm_apply(hh, bp["ln1"], cfg)
            a, k, v = attn_decode(x, bp["attn"], cfg, dist, pos, kc, vc)
            hh = hh + a
            x = norm_apply(hh, bp["ln2"], cfg)
            f, c = ffn_apply(x, bp, cfg, dist)
            hh = hh + f
            return hh, ((k, v) if c is None else (k, v, c))
        h, ys = jax.lax.scan(
            body, h, (params["blocks"], cache["k"], cache["v"]))
        k, v = ys[:2]
        if len(ys) > 2:
            counts = jnp.sum(ys[2], axis=0)
        new_cache = {"k": write_rows(cache["k"], k, pos),
                     "v": write_rows(cache["v"], v, pos), "pos": pos + 1}

    h = norm_apply(h, params["final_norm"], cfg)
    vs = dim_shardable(dist, cfg.vocab_size, "vocab")
    logits = last_token_logits(h, params["head"], dist, vs)
    return logits, new_cache, counts


# ---------------------------------------------------------------------------
# Hybrid: periods of attention at attn_index and mamba elsewhere, each layer
# followed by its FFN. Consecutive layers of one kind (mixer and FFN) run as
# one scan, so a period compiles about one layer of each kind.
# ---------------------------------------------------------------------------


def _at(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _times(x, m: float):
    """x * m, or x itself where m is 1 (the program stays as it was)."""
    return x if m == 1.0 else x * m


def _split_experts(blocks):
    """(blocks without the routed experts' weights, those weights with
    their layers flattened into the expert axis, or None): the scans
    slice the rest, and the grouped products read the experts as stored
    (``moe_block``'s ``layer``)."""
    if "moe" not in blocks:
        return blocks, None
    moe = dict(blocks["moe"])
    experts = {k: moe.pop(k) for k in ("wg", "wu", "wd")}
    experts = {k: w.reshape((-1,) + w.shape[-2:]) for k, w in experts.items()}
    return dict(blocks, moe=moe), experts


@dataclass(frozen=True)
class _Mixers:
    """How a path runs the mixers of a hybrid period.

    attn(x, p) -> (out, kv); mamba(x, p, st) -> (out, st'): one layer,
    where st is that mamba layer's state as ``read(extra, m)`` gives it
    and ``write(extra, m, st')`` puts back -> (extra, kept), ``kept``
    stacked over the period's mamba layers (None keeps nothing).
    ``extra`` is the path's own carry through the layers."""
    attn: Any
    mamba: Any
    read: Any
    write: Any


def _runs(cfg: ArchConfig):
    """The period's layers as runs [first, end) of one kind: (first, end,
    attention?, MoE FFN?)."""
    runs = []
    for j in range(cfg.attn_period):
        kind = (cfg.is_attn_layer(j), cfg.is_moe_layer(j))
        if runs and runs[-1][2:] == kind and not kind[0]:
            runs[-1] = (runs[-1][0], j + 1) + kind
        else:
            runs.append((j, j + 1) + kind)
    return runs


def _hybrid_period(hh, extra, bp, q, experts, cfg: ArchConfig, dist: Dist,
                   mix: _Mixers):
    """Period ``q``. ``bp``: its blocks without the routed experts, which
    come stacked for every period in ``experts`` (``_split_experts``).
    Each mixer's and each FFN's output joins the residual times
    ``residual_multiplier``. Returns (hh, extra, the attention layer's
    kv, the mamba layers' kept states stacked, MoE counts summed over the
    period or None)."""
    per, a, rm = cfg.attn_period, cfg.attn_index, cfg.residual_multiplier
    moe_at = [cfg.is_moe_layer(j) for j in range(per)]
    n_moe = sum(moe_at)

    def layer(hh, extra, j, j0, attn, moe):
        # j: this layer (an int, or traced inside a run from j0); the
        # indices into the stacks move with j inside a run
        i_ffn = sum(moe_at[:j0]) if moe else j0 - sum(moe_at[:j0])
        i_ffn += j - j0
        kv = kept = counts = None
        if attn:
            with jax.named_scope("attention"):
                x = norm_apply(hh, bp["attn_ln"], cfg)
                out, kv = mix.attn(x, bp["attn"])
        else:
            m = j - (j0 > a)
            with jax.named_scope("ssd"):
                x = norm_apply(hh, _at(bp["mamba_ln"], m), cfg)
                out, st = mix.mamba(x, _at(bp["mamba"], m), mix.read(extra, m))
            extra, kept = mix.write(extra, m, st)
        hh = hh + _times(out, rm)
        x = norm_apply(hh, _at(bp["ffn_ln"], j), cfg)
        if moe:
            out, counts = moe_block(x, dict(experts, **_at(bp["moe"], i_ffn)),
                                    cfg, dist, layer=q * n_moe + i_ffn)
            if "shared" in bp:
                with jax.named_scope("moe.shared"):
                    sh = _at(bp["shared"], i_ffn)
                    out = out + gated_mlp(x, sh["wg"], sh["wu"], sh["wd"],
                                          dist)
        else:
            sub = _at(bp["mlp"], i_ffn)
            out = gated_mlp(x, sub["wg"], sub["wu"], sub["wd"], dist)
        return hh + _times(out, rm), extra, (kv, kept, counts)

    kv, kept, counts = None, [], []
    for j0, j1, attn, moe in _runs(cfg):
        if j1 - j0 == 1:
            hh, extra, (k, st, c) = layer(hh, extra, j0, j0, attn, moe)
            st = jax.tree.map(lambda t: t[None], st)
        else:
            def body(carry, j, j0=j0, attn=attn, moe=moe):
                hh, extra, ys = layer(*carry, j, j0, attn, moe)
                return (hh, extra), ys
            (hh, extra), (k, st, c) = jax.lax.scan(
                body, (hh, extra), jnp.arange(j0, j1, dtype=jnp.int32))
            c = None if c is None else jnp.sum(c, axis=0)
        kv = k if attn else kv
        if not attn and st is not None:
            kept.append(st)
        if c is not None:
            counts.append(c)
    kept = (jax.tree.map(lambda *t: jnp.concatenate(t), *kept)
            if kept else None)
    return hh, extra, kv, kept, (sum(counts) if counts else None)


def _hybrid_embed(params, batch, cfg, dist):
    h, positions = _inputs_to_h(params, batch, cfg, dist)
    return _times(h, cfg.embedding_multiplier), positions


def _hybrid_head(h, params, cfg, dist):
    """Final norm and the output head (the embedding table where tied),
    the logits divided by ``logits_scaling``: (normed h, head (D, V))."""
    h = norm_apply(h, params["final_norm"], cfg)
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    return _times(h, 1.0 / cfg.logits_scaling), w


def _hybrid_full_seq(params, batch, cfg, dist, opts, keep: bool):
    """The stack over whole sequences (loss and prefill): (h, kv, states)
    with each period's attention kv and mamba (state, tail) when ``keep``."""
    h, positions = _hybrid_embed(params, batch, cfg, dist)
    mix = _Mixers(
        attn=lambda x, p: attn_train(x, p, cfg, dist, positions, opts),
        mamba=lambda x, p, st: (lambda o, s, t: (o, (s, t)))(
            *mam.mamba_block(x, p, cfg, dist)),
        read=lambda extra, m: None,
        write=lambda extra, m, st: (extra, st if keep else None))
    blocks, experts = _split_experts(params["blocks"])
    n = jax.tree.leaves(blocks)[0].shape[0]

    def body(hh, xs):
        bp, q = xs
        hh, _, kv, kept, _ = _hybrid_period(hh, None, bp, q, experts, cfg,
                                            dist, mix)
        return hh, ((kv, kept) if keep else None)

    return jax.lax.scan(_remat(body, opts), h,
                        (blocks, jnp.arange(n, dtype=jnp.int32)))


def _hybrid_loss(params, batch, cfg, dist, opts):
    h, _ = _hybrid_full_seq(params, batch, cfg, dist, opts, keep=False)
    h, w = _hybrid_head(h, params, cfg, dist)
    vs = dim_shardable(dist, cfg.vocab_size, "vocab")
    return chunked_xent(h, w, batch["labels"], dist,
                        chunk=min(_opt(opts, "xent_chunk"), h.shape[1]),
                        vocab_sharded=vs)


def _hybrid_prefill(params, batch, cfg, dist, opts):
    seq = batch["tokens"].shape[1] if "tokens" in batch \
        else batch["embeds"].shape[1]
    h, ((k, v), (states, tails)) = _hybrid_full_seq(params, batch, cfg, dist,
                                                    opts, keep=True)
    h, w = _hybrid_head(h, params, cfg, dist)
    vs = dim_shardable(dist, cfg.vocab_size, "vocab")
    logits = last_token_logits(h[:, -1:], w, dist, vs)
    cache = {"k": k, "v": v, "ssm": states, "conv": tails,
             "pos": jnp.int32(seq)}
    return logits, cache


def _hybrid_decode(params, cache, batch, cfg, dist, opts):
    """The SSM and conv states ride through the scans in their carry, each
    layer's read and written in place; the KV cache is read-only in the
    scans and takes its new rows after them."""
    vs = dim_shardable(dist, cfg.vocab_size, "vocab")
    h = _times(embed_tokens(batch["tokens"], params["embed"], dist, vs),
               cfg.embedding_multiplier)
    pos = cache["pos"]
    blocks, experts = _split_experts(params["blocks"])
    n = jax.tree.leaves(blocks)[0].shape[0]

    def body(carry, xs):
        hh, ssm, conv = carry
        bp, kc, vc, q = xs

        def attn(x, p):
            a, k, v = attn_decode(x, p, cfg, dist, pos, kc, vc)
            return a, (k, v)

        def mamba(x, p, st):
            out, s, t = mam.mamba_decode(x, p, cfg, dist, *st)
            return out, (s, t)

        mix = _Mixers(
            attn=attn, mamba=mamba,
            read=lambda e, m: (_slot(e[0], q, m), _slot(e[1], q, m)),
            write=lambda e, m, st: ((_put(e[0], st[0], q, m),
                                     _put(e[1], st[1], q, m)), None))
        hh, (ssm, conv), (k, v), _, counts = _hybrid_period(
            hh, (ssm, conv), bp, q, experts, cfg, dist, mix)
        return (hh, ssm, conv), ((k, v) if counts is None else (k, v, counts))

    (h, ssm, conv), ys = jax.lax.scan(
        body, (h, cache["ssm"], cache["conv"]),
        (blocks, cache["k"], cache["v"], jnp.arange(n, dtype=jnp.int32)))
    k, v = ys[:2]
    counts = jnp.sum(ys[2], axis=0) if len(ys) > 2 else None
    h, w = _hybrid_head(h, params, cfg, dist)
    logits = last_token_logits(h, w, dist, vs)
    return logits, {"k": write_rows(cache["k"], k, pos),
                    "v": write_rows(cache["v"], v, pos),
                    "ssm": ssm, "conv": conv, "pos": pos + 1}, counts
