"""Whisper-style encoder-decoder backbone (conv frontend stubbed).

``input_specs()`` provides precomputed frame embeddings (B, S_enc, D) per the
assignment; positions are sinusoidal (no RoPE, faithful to Whisper). The
decoder carries a causal self-attention cache and a fixed cross-attention
cache computed from the encoder output at prefill time.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed.sharding import Dist, dim_shardable
from repro.models.attention import (decode_attention, flash_attention_ref,
                                    repeat_kv)
from repro.models.layers import (ParamDef, chunked_xent, embed_tokens,
                                 last_token_logits, sinusoid_positions)
from repro.models.transformer import (_cache_dtype, _cache_rows,
                                      attn_param_defs, mlp_param_defs,
                                      norm_apply, norm_param_defs, _remat,
                                      _heads_axis, _opt)


def encdec_param_defs(cfg: ArchConfig, dist: Dist) -> dict:
    L = cfg.n_layers
    enc_block = {
        "ln1": norm_param_defs(cfg, (L,)),
        "attn": attn_param_defs(cfg, (L,)),
        "ln2": norm_param_defs(cfg, (L,)),
        "mlp": mlp_param_defs(cfg, (L,)),
    }
    dec_block = {
        "ln1": norm_param_defs(cfg, (L,)),
        "self_attn": attn_param_defs(cfg, (L,)),
        "ln2": norm_param_defs(cfg, (L,)),
        "cross_attn": attn_param_defs(cfg, (L,)),
        "ln3": norm_param_defs(cfg, (L,)),
        "mlp": mlp_param_defs(cfg, (L,)),
    }
    return {
        "embed": ParamDef((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                          fan_in=1),
        "enc_blocks": enc_block,
        "enc_norm": norm_param_defs(cfg),
        "dec_blocks": dec_block,
        "final_norm": norm_param_defs(cfg),
        "head": ParamDef((cfg.d_model, cfg.vocab_size), ("embed", "vocab")),
    }


def _proj_qkv(h, p, cfg):
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
    return q, k, v


def _sp_ok(dist, seq):
    return (dist.seq_parallel and seq % dist.model_size == 0 and seq > 1)


def _attn_full(h, p, cfg, dist, opts, causal, kv_h=None):
    """Self (kv_h None) or cross (kv_h = encoder states) attention."""
    ha = _heads_axis(cfg, dist)
    bt = dist.batch_axes
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    src = h if kv_h is None else kv_h
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    if _sp_ok(dist, h.shape[1]) and _sp_ok(dist, src.shape[1]):
        # zero3_sp: whisper's 20 heads don't divide the model axis; shard
        # the sequence instead (same fix as qwen2-vl, see §Perf)
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
        cd = _cache_dtype(cfg)
        return out, (k.astype(cd), v.astype(cd))
    if dist.has_mesh:
        q = dist.constrain(q, P(bt, None, ha, None))
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
    cd = _cache_dtype(cfg)
    return out, (k.astype(cd), v.astype(cd))


def _encode(params, enc_embeds, cfg, dist, opts):
    h = enc_embeds.astype(jnp.dtype(cfg.dtype))
    h = h + sinusoid_positions(h.shape[1], cfg.d_model, h.dtype)
    if dist.has_mesh:
        sax = "model" if _sp_ok(dist, h.shape[1]) else None
        h = dist.constrain(h, P(dist.batch_axes, sax, None))

    def body(hh, bp):
        x = norm_apply(hh, bp["ln1"], cfg)
        a, _ = _attn_full(x, bp["attn"], cfg, dist, opts, causal=False)
        hh = hh + a
        x = norm_apply(hh, bp["ln2"], cfg)
        m = bp["mlp"]
        hh = hh + (jax.nn.silu(x @ m["wg"]) * (x @ m["wu"])) @ m["wd"]
        return hh, None

    h, _ = jax.lax.scan(_remat(body, opts), h, params["enc_blocks"])
    return norm_apply(h, params["enc_norm"], cfg)


def _decode_stack(params, tokens, enc_h, cfg, dist, opts, collect):
    vs = dim_shardable(dist, cfg.vocab_size, "vocab")
    h = embed_tokens(tokens, params["embed"], dist, vs)
    h = h + sinusoid_positions(h.shape[1], cfg.d_model, h.dtype)
    if dist.has_mesh:
        sax = "model" if _sp_ok(dist, h.shape[1]) else None
        h = dist.constrain(h, P(dist.batch_axes, sax, None))

    def body(hh, bp):
        x = norm_apply(hh, bp["ln1"], cfg)
        a, kv_self = _attn_full(x, bp["self_attn"], cfg, dist, opts,
                                causal=True)
        hh = hh + a
        x = norm_apply(hh, bp["ln2"], cfg)
        a, kv_cross = _attn_full(x, bp["cross_attn"], cfg, dist, opts,
                                 causal=False, kv_h=enc_h)
        hh = hh + a
        x = norm_apply(hh, bp["ln3"], cfg)
        m = bp["mlp"]
        hh = hh + (jax.nn.silu(x @ m["wg"]) * (x @ m["wu"])) @ m["wd"]
        ys = (kv_self + kv_cross) if collect else None
        return hh, ys

    h, caches = jax.lax.scan(_remat(body, opts), h, params["dec_blocks"])
    return norm_apply(h, params["final_norm"], cfg), caches


def encdec_loss(params, batch, cfg: ArchConfig, dist: Dist, opts=None):
    enc_h = _encode(params, batch["enc_embeds"], cfg, dist, opts)
    h, _ = _decode_stack(params, batch["tokens"], enc_h, cfg, dist, opts,
                         collect=False)
    if dist.has_mesh:
        h = dist.constrain(h, P(dist.batch_axes, None, None))
    vs = dim_shardable(dist, cfg.vocab_size, "vocab")
    return chunked_xent(h, params["head"], batch["labels"], dist,
                        chunk=min(_opt(opts, "xent_chunk"), h.shape[1]),
                        vocab_sharded=vs)


def encdec_prefill(params, batch, cfg: ArchConfig, dist: Dist, opts=None):
    enc_h = _encode(params, batch["enc_embeds"], cfg, dist, opts)
    h, caches = _decode_stack(params, batch["tokens"], enc_h, cfg, dist,
                              opts, collect=True)
    sk, sv, ck, cv = caches
    vs = dim_shardable(dist, cfg.vocab_size, "vocab")
    logits = last_token_logits(h[:, -1:], params["head"], dist, vs)
    cache = {"k": sk, "v": sv, "cross_k": ck, "cross_v": cv,
             "pos": jnp.int32(batch["tokens"].shape[1])}
    return logits, cache


def cache_update(cache, new, pos):
    """Write new (B,1,KV,hd) at position pos along seq dim."""
    return jax.lax.dynamic_update_slice_in_dim(
        cache, new.astype(cache.dtype), pos, axis=1)


def encdec_decode(params, cache, batch, cfg: ArchConfig, dist: Dist,
                  opts=None):
    vs = dim_shardable(dist, cfg.vocab_size, "vocab")
    h = embed_tokens(batch["tokens"], params["embed"], dist, vs)
    pos = cache["pos"]
    # decoder position embedding for the new token
    sin = sinusoid_positions(cache["k"].shape[2] + 1, cfg.d_model, h.dtype)
    h = h + jax.lax.dynamic_slice_in_dim(sin, pos, 1, axis=0)[None]

    def body(hh, xs):
        bp, kc, vc, ck, cv = xs
        x = norm_apply(hh, bp["ln1"], cfg)
        q = jnp.einsum("bsd,dhk->bshk", x, bp["self_attn"]["wq"])
        kn = jnp.einsum("bsd,dhk->bshk", x, bp["self_attn"]["wk"])
        vn = jnp.einsum("bsd,dhk->bshk", x, bp["self_attn"]["wv"])
        kc = cache_update(kc, kn, pos)
        vc = cache_update(vc, vn, pos)
        a = decode_attention(q, _cache_rows(kc, cfg), _cache_rows(vc, cfg),
                             pos + 1)
        hh = hh + jnp.einsum("bshk,hkd->bsd", a, bp["self_attn"]["wo"])
        x = norm_apply(hh, bp["ln2"], cfg)
        q = jnp.einsum("bsd,dhk->bshk", x, bp["cross_attn"]["wq"])
        a = decode_attention(q, _cache_rows(ck, cfg), _cache_rows(cv, cfg),
                             ck.shape[1])
        hh = hh + jnp.einsum("bshk,hkd->bsd", a, bp["cross_attn"]["wo"])
        x = norm_apply(hh, bp["ln3"], cfg)
        m = bp["mlp"]
        hh = hh + (jax.nn.silu(x @ m["wg"]) * (x @ m["wu"])) @ m["wd"]
        return hh, (kc, vc)

    h, (k, v) = jax.lax.scan(
        body, h, (params["dec_blocks"], cache["k"], cache["v"],
                  cache["cross_k"], cache["cross_v"]))
    h = norm_apply(h, params["final_norm"], cfg)
    logits = last_token_logits(h, params["head"], dist, vs)
    new_cache = dict(cache, k=k, v=v, pos=pos + 1)
    return logits, new_cache
