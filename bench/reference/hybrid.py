"""Plain float32 forward pass of IBM Granite 4.0-H (``granitemoehybrid``).

With h0 = embedding_multiplier * embed[tokens], each layer computes

    h = h + residual_multiplier * mixer(RMSNorm(h))
    x = RMSNorm(h)
    h = h + residual_multiplier * (sum_{e in top-k} g_e SwiGLU_e(x) + SwiGLU_shared(x))

and logits = RMSNorm(h) @ embed.T / logits_scaling (the head is the
embedding table). ``layer_types`` says which mixer each layer has:

* attention: GQA with no position embedding (NoPE), scores scaled by
  ``attention_multiplier``, causal softmax over all positions;
* mamba: Mamba-2 (SSD) as in ``bench/reference/ssm.py``: z, u, B, C and dt
  from x, a causal depthwise convolution and silu on u, B, C, the state
  space model in its quadratic form over the whole sequence (no chunks, no
  carried state; computed a block of heads at a time so that it fits),
  then RMSNorm(y * silu(z)) and the output projection.

Routing follows the published gate: the top-k router logits, their softmax
as the gates. This chip holds experts [0, num_local_experts) of the
``published_num_local_experts`` the router scores; every held expert is
computed densely for every token and weighted by its gate (0 where not
picked). What experts held elsewhere would add is left out, as in the
program. The shared expert is computed in full.

Where this departs from the published model (and so does the repo's):
the depthwise convolution has no bias (published ``mamba_conv_bias``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.common import Spec, ein, rms_norm, table
from bench.reference.ssm import _conv, _segsum

# sequences per reference pass (each row holds max_seq positions of logits)
REF_BATCH = 1
# heads of the SSD quadratic form evaluated at once (heads x T x T floats)
HEAD_BLOCK = 32
# the embedding table's norm per row (times embedding_multiplier, 0.6)
EMBED_STD = 0.05

# configuration-file key -> ArchConfig field the program must agree on
PROGRAM_FIELDS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "mamba_d_state": "ssm.d_state",
    "mamba_d_head": "ssm.head_dim",
    "mamba_expand": "ssm.expand",
    "mamba_chunk_size": "ssm.chunk_size",
    "mamba_n_groups": "ssm.n_groups",
    "mamba_d_conv": "ssm.conv_kernel",
    "intermediate_size": "moe.d_ff_expert",
    "shared_intermediate_size": "moe.d_ff_shared",
    "num_experts_per_tok": "moe.top_k",
    "published_num_local_experts": "moe.n_experts",
    "num_local_experts": "held_experts",
    "attention_multiplier": "attn_scale",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "logits_scaling": "logits_scaling",
    "tie_word_embeddings": "tie_embeddings",
}


def pattern(c: dict):
    """(period, attention index) of ``layer_types`` over the layers run:
    one attention layer in each period, the rest mamba."""
    kinds = c["layer_types"][:c["num_hidden_layers"]]
    at = [i for i, k in enumerate(kinds) if k == "attention"]
    per = at[1] - at[0] if len(at) > 1 else len(kinds)
    if (not at or len(kinds) % per
            or kinds != [("attention" if i % per == at[0] else "mamba")
                         for i in range(len(kinds))]):
        raise ValueError("layer_types is not whole periods of one attention "
                         "layer among mamba layers")
    return per, at[0]


def _dims(c: dict):
    d = c["hidden_size"]
    din = c["mamba_expand"] * d
    nh = din // c["mamba_d_head"]
    if c["mamba_n_groups"] != 1 or nh != c.get("mamba_n_heads", nh):
        raise ValueError("the reference covers one B/C group, heads = "
                         "expand * hidden / head size")
    return d, din, nh, c["mamba_d_state"], c["mamba_d_conv"]


def param_specs(c: dict) -> dict:
    d, din, nh, n, k = _dims(c)
    per, _ = pattern(c)
    P_, M = c["num_hidden_layers"] // per, per - 1
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // H
    E, Ea = c["num_local_experts"], c["published_num_local_experts"]
    F, Fs, V = (c["intermediate_size"], c["shared_intermediate_size"],
                c["vocab_size"])
    s = 1.0 / math.sqrt(d)
    return {
        "blocks": {
            "attn": {"wq": Spec((P_, d, H, hd), "normal", s),
                     "wk": Spec((P_, d, KV, hd), "normal", s),
                     "wv": Spec((P_, d, KV, hd), "normal", s),
                     "wo": Spec((P_, H, hd, d), "normal", 1 / math.sqrt(H * hd))},
            "attn_ln": {"w": Spec((P_, d), "gain")},
            "ffn_ln": {"w": Spec((P_, per, d), "gain")},
            "mamba": {
                "A_log": Spec((P_, M, nh), "a_log"),
                "D_skip": Spec((P_, M, nh), "ones"),
                "conv_B": Spec((P_, M, k, n), "normal", 1 / math.sqrt(k)),
                "conv_C": Spec((P_, M, k, n), "normal", 1 / math.sqrt(k)),
                "conv_x": Spec((P_, M, k, din), "normal", 1 / math.sqrt(k)),
                "dt_bias": Spec((P_, M, nh), "dt_bias"),
                "norm": Spec((P_, M, din), "gain"),
                "out_proj": Spec((P_, M, din, d), "normal", 1 / math.sqrt(din)),
                "wB": Spec((P_, M, d, n), "normal", s),
                "wC": Spec((P_, M, d, n), "normal", s),
                "wdt": Spec((P_, M, d, nh), "normal", s),
                "wx": Spec((P_, M, d, din), "normal", s),
                "wz": Spec((P_, M, d, din), "normal", s),
            },
            "mamba_ln": {"w": Spec((P_, M, d), "gain")},
            "moe": {"router": Spec((P_, per, d, Ea), "normal", s),
                    "wg": Spec((P_, per, E, d, F), "normal", s),
                    "wu": Spec((P_, per, E, d, F), "normal", s),
                    "wd": Spec((P_, per, E, F, d), "normal", 1 / math.sqrt(F))},
            "shared": {"wg": Spec((P_, per, d, Fs), "normal", s),
                       "wu": Spec((P_, per, d, Fs), "normal", s),
                       "wd": Spec((P_, per, Fs, d), "normal", 1 / math.sqrt(Fs))},
        },
        # small beside the layers' outputs: with random weights a tied
        # table that dominates the residual puts each token's own logit
        # first, whatever the layers compute
        "embed": Spec((V, d), "normal", EMBED_STD / math.sqrt(d)),
        "final_norm": {"w": Spec((d,), "gain")},
    }


def _at(tree, i: int):
    return jax.tree.map(lambda a: a[i], tree)


def _attention(x, a, c: dict, low: bool):
    H = c["num_attention_heads"]
    T = x.shape[1]
    q = ein("btd,dhk->bthk", x, a["wq"], low)
    k = ein("btd,dhk->bthk", x, a["wk"], low)
    v = ein("btd,dhk->bthk", x, a["wv"], low)
    k = jnp.repeat(k, H // k.shape[2], axis=2)
    v = jnp.repeat(v, H // v.shape[2], axis=2)
    s = ein("bqhk,bshk->bhqs", q, k, low) * c["attention_multiplier"]
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = ein("bhqs,bshk->bqhk", jax.nn.softmax(s, axis=-1), v, low)
    return ein("bthk,hkd->btd", o, a["wo"], low)


def _mamba(x, m, c: dict, low: bool):
    d, din, nh, n, k = _dims(c)
    P = c["mamba_d_head"]
    B, T = x.shape[:2]
    z = ein("btd,de->bte", x, m["wz"], low)
    u = jax.nn.silu(_conv(ein("btd,de->bte", x, m["wx"], low), m["conv_x"]))
    b = jax.nn.silu(_conv(ein("btd,dn->btn", x, m["wB"], low), m["conv_B"]))
    cc = jax.nn.silu(_conv(ein("btd,dn->btn", x, m["wC"], low), m["conv_C"]))
    dt = jax.nn.softplus(ein("btd,dh->bth", x, m["wdt"], low)
                         + m["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(m["A_log"].astype(jnp.float32))
    cb = ein("btn,bsn->bts", cc, b, low)
    uh = u.reshape(B, T, nh, P)
    nb = nh // HEAD_BLOCK if nh % HEAD_BLOCK == 0 else 1
    hb = nh // nb

    def heads(xs):                          # one block of heads
        dt_b, A_b, u_b = xs                 # (B,T,hb), (hb,), (B,T,hb,P)
        decay = jnp.exp(_segsum(dt_b * A_b))                    # (B,hb,T,T)
        return ein("bhts,bshp->bthp", decay * cb[:, None], u_b * dt_b[..., None],
                   low)

    y = jax.lax.map(heads, (jnp.moveaxis(dt.reshape(B, T, nb, hb), 2, 0),
                            A.reshape(nb, hb),
                            jnp.moveaxis(uh.reshape(B, T, nb, hb, P), 2, 0)))
    y = jnp.moveaxis(y, 0, 2).reshape(B, T, nh, P)
    y = y + uh * m["D_skip"].astype(jnp.float32)[:, None]
    y = rms_norm(y.reshape(B, T, din) * jax.nn.silu(z), m["norm"],
                 c["rms_norm_eps"])
    return ein("bte,ed->btd", y, m["out_proj"], low)


def _swiglu(x, w, low: bool):
    g = jax.nn.silu(ein("btd,df->btf", x, w["wg"], low))
    return ein("btf,fd->btd", g * ein("btd,df->btf", x, w["wu"], low),
               w["wd"], low)


def gates(x, router, c: dict, low: bool = False):
    """(B,T,D) -> (gate of each held expert (B,T,E_held), top-k picks
    (B,T,k)): the softmax of the top-k router logits over every expert."""
    scores = ein("btd,de->bte", x, router, low)
    top, idx = jax.lax.top_k(scores, c["num_experts_per_tok"])
    g = jax.nn.softmax(top, axis=-1)
    held = jnp.arange(c["num_local_experts"])
    return jnp.sum(jnp.where(idx[..., None] == held, g[..., None], 0.0),
                   axis=-2), idx


def moe(x, p, c: dict, low: bool = False):
    """This chip's part of one MoE layer, without the shared expert."""
    g, _ = gates(x, p["router"], c, low)
    h = jax.nn.silu(ein("btd,edf->btef", x, p["wg"], low)) \
        * ein("btd,edf->btef", x, p["wu"], low)
    return ein("btef,efd->btd", h * g[..., None], p["wd"], low)


def layers(params, tokens, c: dict, low: bool = False, on_layer=None):
    """tokens (B, T) -> the hidden states after the last layer. With
    ``on_layer(i, x, ffn params)``, called with each layer's FFN input."""
    eps, rm = c["rms_norm_eps"], c["residual_multiplier"]
    per, a = pattern(c)
    h = jnp.take(table(params["embed"], low), tokens, axis=0) \
        * c["embedding_multiplier"]

    def period(h, p, i0=0):
        for j in range(per):
            if j == a:
                x = rms_norm(h, p["attn_ln"]["w"], eps)
                out = _attention(x, p["attn"], c, low)
            else:
                mi = j - (j > a)
                x = rms_norm(h, p["mamba_ln"]["w"][mi], eps)
                out = _mamba(x, _at(p["mamba"], mi), c, low)
            h = h + rm * out
            x = rms_norm(h, p["ffn_ln"]["w"][j], eps)
            if on_layer is not None:
                on_layer(i0 + j, x, {"moe": _at(p["moe"], j),
                                     "shared": _at(p["shared"], j)})
            h = h + rm * (moe(x, _at(p["moe"], j), c, low)
                          + _swiglu(x, _at(p["shared"], j), low))
        return h, None

    if on_layer is not None:                # eager, layer by layer
        for q in range(c["num_hidden_layers"] // per):
            h, _ = period(h, _at(params["blocks"], q), q * per)
        return h
    h, _ = jax.lax.scan(period, h, params["blocks"])
    return h


def logits(params, tokens, c: dict, low: bool = False):
    """tokens (B, T) int32 -> logits (B, T, V) float32."""
    h = rms_norm(layers(params, tokens, c, low), params["final_norm"]["w"],
                 c["rms_norm_eps"])
    return ein("btd,vd->btv", h, params["embed"], low) / c["logits_scaling"]
