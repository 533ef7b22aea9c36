"""Plain float32 forward pass of the repo's dense decoder block.

Per layer: h += Wo·attn(RoPE(Wq x), RoPE(Wk x), Wv x) with x = RMSNorm(h),
causal softmax scaled by head_dim^-1/2 over all positions; then
h += Wd·(silu(Wg x) * Wu x) with x = RMSNorm(h). Then RMSNorm and an
untied output head. RoPE rotates the two halves of every head with
frequencies theta^(-i/(head_dim/2)).

Where this departs from the published StableLM-2 block (and so does the
repo's model, which this reference has to agree with): RMSNorm without a
bias where StableLM-2 has LayerNorm; rotary over the whole head where it
rotates 25 % of it; no bias on the q/k/v projections where it has one.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.common import Spec, ein, rms_norm, table

# sequences per reference pass (each row holds max_seq positions of logits)
REF_BATCH = 2

# configuration-file key -> ArchConfig field the program must agree on
PROGRAM_FIELDS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "norm_eps": "norm_eps",
}


def param_specs(c: dict) -> dict:
    d, L, F, V = (c["hidden_size"], c["num_hidden_layers"],
                  c["intermediate_size"], c["vocab_size"])
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // H
    s = 1.0 / math.sqrt(d)
    return {
        "blocks": {
            "attn": {"wq": Spec((L, d, H, hd), "normal", s),
                     "wk": Spec((L, d, KV, hd), "normal", s),
                     "wv": Spec((L, d, KV, hd), "normal", s),
                     "wo": Spec((L, H, hd, d), "normal", 1 / math.sqrt(H * hd))},
            "ln1": {"w": Spec((L, d), "gain")},
            "ln2": {"w": Spec((L, d), "gain")},
            "mlp": {"wd": Spec((L, F, d), "normal", 1 / math.sqrt(F)),
                    "wg": Spec((L, d, F), "normal", s),
                    "wu": Spec((L, d, F), "normal", s)},
        },
        "embed": Spec((V, d), "normal", 1.0),
        "final_norm": {"w": Spec((d,), "gain")},
        "head": Spec((d, V), "normal", s),
    }


def _rope(x, theta: float):
    """x (B, T, H, hd); positions 0..T-1."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs       # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(params, tokens, c: dict, low: bool = False):
    """tokens (B, T) int32 -> logits (B, T, V) float32."""
    eps, theta = c["norm_eps"], c["rope_theta"]
    H = c["num_attention_heads"]
    h = jnp.take(table(params["embed"], low), tokens, axis=0)
    T = tokens.shape[1]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(h, p):
        a = p["attn"]
        x = rms_norm(h, p["ln1"]["w"], eps)
        q = _rope(ein("btd,dhk->bthk", x, a["wq"], low), theta)
        k = _rope(ein("btd,dhk->bthk", x, a["wk"], low), theta)
        v = ein("btd,dhk->bthk", x, a["wv"], low)
        k = jnp.repeat(k, H // k.shape[2], axis=2)
        v = jnp.repeat(v, H // v.shape[2], axis=2)
        s = ein("bqhk,bshk->bhqs", q, k, low) * q.shape[-1] ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        o = ein("bhqs,bshk->bqhk", jax.nn.softmax(s, axis=-1), v, low)
        h = h + ein("bthk,hkd->btd", o, a["wo"], low)
        m = p["mlp"]
        x = rms_norm(h, p["ln2"]["w"], eps)
        g = jax.nn.silu(ein("btd,df->btf", x, m["wg"], low))
        u = ein("btd,df->btf", x, m["wu"], low)
        return h + ein("btf,fd->btd", g * u, m["wd"], low), None

    h, _ = jax.lax.scan(layer, h, params["blocks"])
    h = rms_norm(h, params["final_norm"]["w"], eps)
    return ein("btd,dv->btv", h, params["head"], low)
