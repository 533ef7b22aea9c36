"""Mamba-2 block (SSD — state-space duality, arXiv:2405.21060).

Chunked-scan formulation: within a chunk the recurrence is computed as a
masked quadratic "attention" term (MXU-friendly), between chunks a small
state (B, H, P, N) is carried by a scan. This pure jnp path is what the
models run on every backend, and the oracle of the Pallas kernel in
kernels/ssd_scan.py, which tiles the same computation but no model calls.

Sharding: heads (d_inner) over "model" (TP); B/C (n_groups=1) replicated;
the inter-chunk state is tiny. Decode carries (ssm_state, conv_tail).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, SSMConfig
from repro.distributed.sharding import Dist
from repro.models.layers import ParamDef, rms_norm


def mamba_dims(cfg: ArchConfig) -> Tuple[int, int, int, int]:
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    nheads = d_in // s.head_dim
    gn = s.n_groups * s.d_state
    return d_in, nheads, gn, s.conv_kernel


def mamba_param_defs(cfg: ArchConfig, scan_dims: Tuple[int, ...] = ()) -> dict:
    d = cfg.d_model
    s = cfg.ssm
    d_in, nheads, gn, k = mamba_dims(cfg)
    ld = tuple("layers" for _ in scan_dims)
    return {
        "wz": ParamDef(scan_dims + (d, d_in), ld + ("embed", "ff")),
        "wx": ParamDef(scan_dims + (d, d_in), ld + ("embed", "ff")),
        "wB": ParamDef(scan_dims + (d, gn), ld + ("embed", "bc")),
        "wC": ParamDef(scan_dims + (d, gn), ld + ("embed", "bc")),
        "wdt": ParamDef(scan_dims + (d, nheads), ld + ("embed", "heads")),
        "conv_x": ParamDef(scan_dims + (k, d_in), ld + ("conv", "ff"),
                           init="const:0.25"),
        "conv_B": ParamDef(scan_dims + (k, gn), ld + ("conv", "bc"),
                           init="const:0.25"),
        "conv_C": ParamDef(scan_dims + (k, gn), ld + ("conv", "bc"),
                           init="const:0.25"),
        "A_log": ParamDef(scan_dims + (nheads,), ld + ("heads",),
                          init="const:0.0"),
        "dt_bias": ParamDef(scan_dims + (nheads,), ld + ("heads",),
                            init="const:-2.0"),
        "D_skip": ParamDef(scan_dims + (nheads,), ld + ("heads",),
                           init="ones"),
        "norm": ParamDef(scan_dims + (d_in,), ld + ("ff",), init="ones"),
        "out_proj": ParamDef(scan_dims + (d_in, d), ld + ("ff", "embed")),
    }


def causal_conv(x, w):
    """Depthwise causal conv: x (B,S,C), w (K,C) -> (B,S,C)."""
    k = w.shape[0]
    out = x * w[k - 1]
    for i in range(k - 1):
        shift = k - 1 - i
        out = out + jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, :-shift] * w[i]
    return out


def ssd_scan_ref(x, dt, a_log, b, c, chunk: int):
    """Chunked SSD scan.

    x (B,S,H,P); dt (B,S,H) (post-softplus); a_log (H,) (A = -exp(a_log));
    b/c (B,S,G,N). Returns y (B,S,H,P) and final state (B,H,P,N).
    """
    with jax.named_scope("ssd_scan_ref"):
        seq = x.shape[1]
        chunk = min(chunk, seq)
        pad = (-seq) % chunk
        if pad:
            # dt=0 padding steps are identities: decay exp(0)=1, xdt=0,
            # so neither the outputs nor the carried state are affected.
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
            b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
            c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
        y, state = _ssd_inner(x, dt, a_log, b, c, chunk)
        return (y[:, :seq] if pad else y), state


def _ssd_inner(x, dt, a_log, b, c, chunk):
    nb, seq, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    hg = h // g
    chunk = min(chunk, seq)
    assert seq % chunk == 0
    nc = seq // chunk

    A = -jnp.exp(a_log.astype(jnp.float32))                  # (H,) < 0
    dt = dt.astype(jnp.float32)
    xdt = x.astype(jnp.float32) * dt[..., None]              # (B,S,H,P)

    def split(t, extra):
        return t.reshape((nb, nc, chunk) + extra).transpose(
            (1, 0, 2) + tuple(range(3, 3 + len(extra))))

    xc = split(xdt, (h, p))         # (nc,B,Q,H,P)
    dtc = split(dt, (h,))           # (nc,B,Q,H)
    bc_ = split(b.astype(jnp.float32), (g, n))
    cc_ = split(c.astype(jnp.float32), (g, n))

    def body(state, xs):
        xq, dq, bq, cq = xs          # per-chunk
        l = dq * A                   # (B,Q,H) log decays
        cum = jnp.cumsum(l, axis=1)  # inclusive
        # intra-chunk: att[t,s] = exp(cum_t - cum_s) for s <= t
        dec = cum[:, :, None, :] - cum[:, None, :, :]        # (B,Q,Q,H)
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        dec = jnp.where(tri[None, :, :, None], dec, -jnp.inf)
        dec = jnp.exp(dec)
        scores = jnp.einsum("bqgn,bsgn->bqsg", cq, bq)       # (B,Q,Q,G)
        scores = jnp.repeat(scores, hg, axis=3)              # (B,Q,Q,H)
        y_intra = jnp.einsum("bqsh,bshp->bqhp", scores * dec, xq)
        # inter-chunk: contribution of the incoming state, decayed to t
        ch = jnp.repeat(cq, hg, axis=2)                      # (B,Q,H,N)
        y_inter = jnp.einsum("bqhn,bhpn->bqhp", ch, state)
        y_inter = y_inter * jnp.exp(cum)[..., None]
        # new state: sum_s exp(cum_Q - cum_s) xdt_s B_s + exp(cum_Q) state
        tail = jnp.exp(cum[:, -1:, :] - cum)                 # (B,Q,H)
        bh = jnp.repeat(bq, hg, axis=2)                      # (B,Q,H,N)
        s_chunk = jnp.einsum("bqhp,bqh,bqhn->bhpn", xq, tail, bh)
        state = state * jnp.exp(cum[:, -1, :])[..., None, None] + s_chunk
        return state, (y_intra + y_inter)

    state0 = jnp.zeros((nb, h, p, n), jnp.float32)
    state, ys = jax.lax.scan(body, state0, (xc, dtc, bc_, cc_))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(nb, seq, h, p)
    return y.astype(x.dtype), state


def ssd_decode_step(state, x1, dt1, a_log, b1, c1):
    """One-token recurrence. state (B,H,P,N); x1 (B,H,P); dt1 (B,H);
    b1/c1 (B,G,N). Returns (y (B,H,P), new state)."""
    h = x1.shape[1]
    g = b1.shape[1]
    hg = h // g
    A = -jnp.exp(a_log.astype(jnp.float32))
    a = jnp.exp(dt1.astype(jnp.float32) * A)                 # (B,H)
    bh = jnp.repeat(b1, hg, axis=1)                          # (B,H,N)
    ch = jnp.repeat(c1, hg, axis=1)
    xdt = x1.astype(jnp.float32) * dt1[..., None]
    state = state * a[..., None, None] + jnp.einsum(
        "bhp,bhn->bhpn", xdt, bh)
    y = jnp.einsum("bhpn,bhn->bhp", state, ch)
    return y.astype(x1.dtype), state


# ---------------------------------------------------------------------------
# Full block
# ---------------------------------------------------------------------------


def mamba_block(x, params, cfg: ArchConfig, dist: Dist):
    """Train/prefill path. x (B,S,D) -> (y (B,S,D), final_state, conv_tail)."""
    s = cfg.ssm
    d_in, nheads, gn, k = mamba_dims(cfg)
    nb, seq, _ = x.shape
    z = x @ params["wz"]
    xi = x @ params["wx"]
    bi = x @ params["wB"]
    ci = x @ params["wC"]
    dt_raw = x @ params["wdt"]

    conv_in = jnp.concatenate([xi, bi, ci], axis=-1)
    xi = jax.nn.silu(causal_conv(xi, params["conv_x"]))
    bi = jax.nn.silu(causal_conv(bi, params["conv_B"]))
    ci = jax.nn.silu(causal_conv(ci, params["conv_C"]))

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))
    xh = xi.reshape(nb, seq, nheads, s.head_dim)
    bg = bi.reshape(nb, seq, s.n_groups, s.d_state)
    cg = ci.reshape(nb, seq, s.n_groups, s.d_state)
    y, state = ssd_scan_ref(xh, dt, params["A_log"], bg, cg, s.chunk_size)
    y = y + xh * params["D_skip"].astype(x.dtype)[None, None, :, None]
    y = y.reshape(nb, seq, d_in)
    y = rms_norm(y * jax.nn.silu(z), params["norm"], cfg.norm_eps)
    out = y @ params["out_proj"]
    conv_tail = conv_in[:, -(k - 1):, :] if seq >= k - 1 else jnp.pad(
        conv_in, ((0, 0), (k - 1 - seq, 0), (0, 0)))
    return out, state.astype(jnp.float32), conv_tail


def mamba_decode(x, params, cfg: ArchConfig, dist: Dist, ssm_state, conv_tail):
    """Decode path. x (B,1,D); states carried. Returns (y, new states)."""
    s = cfg.ssm
    d_in, nheads, gn, k = mamba_dims(cfg)
    nb = x.shape[0]
    x1 = x[:, 0]
    z = x1 @ params["wz"]
    xi = x1 @ params["wx"]
    bi = x1 @ params["wB"]
    ci = x1 @ params["wC"]
    dt_raw = x1 @ params["wdt"]

    new_in = jnp.concatenate([xi, bi, ci], axis=-1)          # (B, convdim)
    full = jnp.concatenate([conv_tail, new_in[:, None, :]], axis=1)  # (B,K,·)
    w = jnp.concatenate(
        [params["conv_x"], params["conv_B"], params["conv_C"]], axis=-1)
    conv_out = jnp.einsum("bkc,kc->bc", full, w)
    xi, bi, ci = jnp.split(
        jax.nn.silu(conv_out), [d_in, d_in + gn], axis=-1)
    conv_tail = full[:, 1:, :]

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))
    xh = xi.reshape(nb, nheads, s.head_dim)
    bg = bi.reshape(nb, s.n_groups, s.d_state)
    cg = ci.reshape(nb, s.n_groups, s.d_state)
    y, ssm_state = ssd_decode_step(ssm_state, xh, dt, params["A_log"], bg, cg)
    y = y + xh * params["D_skip"].astype(x.dtype)[None, :, None]
    y = y.reshape(nb, d_in)
    y = rms_norm(y * jax.nn.silu(z), params["norm"], cfg.norm_eps)
    out = (y @ params["out_proj"])[:, None, :]
    return out, ssm_state, conv_tail
