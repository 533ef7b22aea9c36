"""Plain float32 forward pass of the repo's Mamba-2 (SSD) block.

Per layer, with x = RMSNorm(h): z = Wz x, u = Wx x, B = WB x, C = WC x,
dt = softplus(Wdt x + dt_bias); u, B, C each pass a causal depthwise
convolution of width K and silu. With A = -exp(A_log) per head, the state
space model is evaluated in its quadratic (attention-like) form over the
whole sequence, with no chunks and no carried state:

    y_t = sum_{s<=t} (C_t . B_s) exp(sum_{k=s+1..t} dt_k A) dt_s u_s + D u_t

then y = RMSNorm(y * silu(z)) and h += Wout y. Then RMSNorm and an untied
output head. One group of B/C (n_groups 1).

Where this departs from the published Mamba-2 1.3B (and so does the repo's
model): the convolution has no bias; the output head is not tied to the
embedding.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.common import Spec, ein, rms_norm, table

# sequences per reference pass (the decay matrix is heads x T x T floats)
REF_BATCH = 1

PROGRAM_FIELDS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "padded_vocab_size": "vocab_size",
    "state_size": "ssm.d_state",
    "head_dim": "ssm.head_dim",
    "expand": "ssm.expand",
    "chunk_size": "ssm.chunk_size",
    "n_groups": "ssm.n_groups",
    "conv_kernel": "ssm.conv_kernel",
    "norm_eps": "norm_eps",
}


def _dims(c: dict):
    d = c["hidden_size"]
    din = c["expand"] * d
    if c["n_groups"] != 1:
        raise ValueError("the reference covers n_groups == 1")
    return d, din, din // c["head_dim"], c["state_size"], c["conv_kernel"]


def param_specs(c: dict) -> dict:
    d, din, nh, n, k = _dims(c)
    L, V = c["num_hidden_layers"], c["padded_vocab_size"]
    s = 1.0 / math.sqrt(d)
    return {
        "blocks": {
            "ln1": {"w": Spec((L, d), "gain")},
            "mamba": {
                "A_log": Spec((L, nh), "a_log"),
                "D_skip": Spec((L, nh), "ones"),
                "conv_B": Spec((L, k, n), "normal", 1 / math.sqrt(k)),
                "conv_C": Spec((L, k, n), "normal", 1 / math.sqrt(k)),
                "conv_x": Spec((L, k, din), "normal", 1 / math.sqrt(k)),
                "dt_bias": Spec((L, nh), "dt_bias"),
                "norm": Spec((L, din), "gain"),
                "out_proj": Spec((L, din, d), "normal", 1 / math.sqrt(din)),
                "wB": Spec((L, d, n), "normal", s),
                "wC": Spec((L, d, n), "normal", s),
                "wdt": Spec((L, d, nh), "normal", s),
                "wx": Spec((L, d, din), "normal", s),
                "wz": Spec((L, d, din), "normal", s),
            },
        },
        "embed": Spec((V, d), "normal", 1.0),
        "final_norm": {"w": Spec((d,), "gain")},
        "head": Spec((d, V), "normal", s),
    }


def _conv(x, w):
    """Causal depthwise convolution: x (B, T, C), w (K, C)."""
    k, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, j:j + T] * w[j].astype(jnp.float32) for j in range(k))


def _segsum(a):
    """a (B, T, H) -> (B, H, T, T): sum_{k=s+1..t} a_k for s <= t, -inf above."""
    T = a.shape[1]
    x = jnp.broadcast_to(jnp.moveaxis(a, 1, -1)[..., None], a.shape[:1]
                         + (a.shape[2], T, T))          # [b,h,t,s] = a_t
    strict = jnp.tril(jnp.ones((T, T), bool), -1)
    x = jnp.cumsum(jnp.where(strict, x, 0.0), axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), x, -jnp.inf)


def logits(params, tokens, c: dict, low: bool = False):
    """tokens (B, T) int32 -> logits (B, T, V) float32."""
    eps = c["norm_eps"]
    d, din, nh, n, k = _dims(c)
    P = c["head_dim"]
    h = jnp.take(table(params["embed"], low), tokens, axis=0)
    B, T = tokens.shape

    def layer(h, p):
        m = p["mamba"]
        x = rms_norm(h, p["ln1"]["w"], eps)
        z = ein("btd,de->bte", x, m["wz"], low)
        u = jax.nn.silu(_conv(ein("btd,de->bte", x, m["wx"], low), m["conv_x"]))
        b = jax.nn.silu(_conv(ein("btd,dn->btn", x, m["wB"], low), m["conv_B"]))
        cc = jax.nn.silu(_conv(ein("btd,dn->btn", x, m["wC"], low), m["conv_C"]))
        dt = jax.nn.softplus(ein("btd,dh->bth", x, m["wdt"], low)
                             + m["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(m["A_log"].astype(jnp.float32))
        decay = jnp.exp(_segsum(dt * A))                       # (B,H,T,T)
        cb = ein("btn,bsn->bts", cc, b, low)
        uh = u.reshape(B, T, nh, P)
        y = ein("bhts,bshp->bthp", decay * cb[:, None], uh * dt[..., None], low)
        y = y + uh * m["D_skip"].astype(jnp.float32)[:, None]
        y = rms_norm(y.reshape(B, T, din) * jax.nn.silu(z), m["norm"], eps)
        return h + ein("bte,ed->btd", y, m["out_proj"], low), None

    h, _ = jax.lax.scan(layer, h, params["blocks"])
    h = rms_norm(h, params["final_norm"]["w"], eps)
    return ein("btd,dv->btv", h, params["head"], low)
