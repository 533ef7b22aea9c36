"""Attention: chunked flash reference (jnp, O(S) memory), decode attention.

The Pallas TPU kernel (kernels/flash_attention.py) implements the same
online-softmax tiling; on CPU (dry-run, smoke) the chunked jnp path below is
lowered instead.

Layouts (see DESIGN.md §5):
  * train/prefill: q/k/v all carry the full head count (GQA kv heads are
    repeated by the caller) so the head dim shards cleanly over "model"
    for ANY kv count; the repeated k/v is itself head-sharded so the
    per-device footprint matches q.
  * decode: q is one token; k/v stay in compact (B, S, KV*hd) cache rows,
    each query head spread block-diagonally over a row. The cache's
    sequence dim is sharded for long contexts and the softmax reductions
    over S become SPMD partial-softmax combines (the TPU flash-decoding
    analogue).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import Dist

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal: bool,
                        q_offset: int = 0,
                        q_chunk: int = 512, k_chunk: int = 1024):
    """q/k/v (B, S, H, hd) (same H; GQA pre-repeated) -> (B, Sq, H, hd).

    Online-softmax over k chunks, scanned over q chunks. For causal
    attention with q_offset, query position i attends to kv positions
    <= i + q_offset.
    """
    with jax.named_scope("pallas_flash_attention"):
        sq, sk = q.shape[1], k.shape[1]
        q_chunk = min(q_chunk, sq)
        k_chunk = min(k_chunk, sk)
        pq, pk = (-sq) % q_chunk, (-sk) % k_chunk
        if pq:
            q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        if pk:
            k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        out = _flash_vjp(q, k, v, jnp.asarray(q_offset, jnp.int32),
                         causal, q_chunk, k_chunk, sk)
        return out[:, :sq] if pq else out


def _flash_inner(q, k, v, causal, q_offset, q_chunk, k_chunk, sk_valid):
    B, Sq, H, hd = q.shape
    _, Sk, _, _ = k.shape
    assert Sq % q_chunk == 0 and Sk % k_chunk == 0
    nq, nk = Sq // q_chunk, Sk // k_chunk
    scale = hd ** -0.5

    kc = k.reshape(B, nk, k_chunk, H, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, k_chunk, H, hd).transpose(1, 0, 2, 3, 4)
    qc = q.reshape(B, nq, q_chunk, H, hd).transpose(1, 0, 2, 3, 4)

    def q_body(_, qi_xs):
        qi, iq = qi_xs                              # (B,cq,H,hd)
        qpos = iq * q_chunk + jnp.arange(q_chunk) + q_offset

        def kv_body(carry, kv_xs):
            acc, m, l = carry
            kj, vj, jk = kv_xs
            kpos = jk * k_chunk + jnp.arange(k_chunk)
            s = jnp.einsum("bqhd,bchd->bhqc", qi, kj,
                           preferred_element_type=jnp.float32) * scale
            mask = kpos[None, :] < sk_valid                  # kv padding
            if causal:
                mask = mask & (qpos[:, None] >= kpos[None, :])
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhqc,bchd->bhqd", p.astype(vj.dtype), vj,
                            preferred_element_type=jnp.float32)
            acc = acc * corr[..., None] + pv
            return (acc, m_new, l_new), None

        acc0 = jnp.zeros((B, H, q_chunk, hd), jnp.float32)
        m0 = jnp.full((B, H, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_body, (acc0, m0, l0), (kc, vc, jnp.arange(nk)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.astype(q.dtype)            # (B,H,cq,hd)

    _, outs = jax.lax.scan(q_body, None, (qc, jnp.arange(nq)))
    # (nq,B,H,cq,hd) -> (B,Sq,H,hd)
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, Sq, H, hd)
    return out


def repeat_kv(k, n_heads: int):
    """(B,S,KV,hd) -> (B,S,H,hd) by repeating each kv head H//KV times."""
    kv = k.shape[2]
    if kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // kv, axis=2)


def decode_attention(q, k_cache, v_cache, length, k_new=None, v_new=None):
    """Single-step attention against a compact cache, read as it is stored.

    q (B,1,H,hd); k_cache/v_cache (B,S,KV*hd): one row per position, its KV
    heads side by side as the projection makes them. length: valid length,
    a scalar or one per batch row (entries at positions >= length are
    masked). k_new/v_new (B,1,KV*hd), when given, is the step's own row:
    attended after the valid rows, without being written into the cache.

    Each query head is spread over a whole row, zero outside its KV head's
    hd columns, so both contractions run over rows in their stored layout
    (no per-head relayout of the cache) and the zero blocks add exact
    zeros. Sequence-dim sharding of the cache turns the softmax reductions
    into SPMD partial combines.
    """
    B, _, H, hd = q.shape
    _, S, width = k_cache.shape
    KV = width // hd
    g = H // KV
    f32 = jnp.float32
    scale = hd ** -0.5
    eye = jnp.eye(KV, dtype=q.dtype)
    qf = q.reshape(B, KV, g, hd)
    q_rows = (qf[:, :, :, None, :] * eye[:, None, :, None]).reshape(
        B, H, width)
    s = jnp.einsum("bhc,bsc->bhs", q_rows, k_cache,
                   preferred_element_type=f32) * scale
    valid = jnp.arange(S) < jnp.reshape(length, (-1, 1, 1))
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    if k_new is not None:
        s_new = jnp.einsum("bkgd,bkd->bkg", qf, k_new.reshape(B, KV, hd),
                           preferred_element_type=f32).reshape(B, H, 1) * scale
        m = jnp.maximum(m, s_new)
    p = jnp.exp(s - m)
    total = jnp.sum(p, axis=-1, keepdims=True)
    if k_new is not None:
        p_new = jnp.exp(s_new - m)
        total = total + p_new
    rows = jnp.einsum("bhs,bsc->bhc", (p / total).astype(v_cache.dtype),
                      v_cache, preferred_element_type=f32)
    out = jnp.sum(rows.reshape(B, KV, g, KV, hd)
                  * eye.astype(f32)[:, None, :, None], axis=3)
    if v_new is not None:
        out = out + (p_new / total).reshape(B, KV, g, 1) \
            * v_new.reshape(B, KV, 1, hd).astype(f32)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def sp_flash_attention(q, k, v, dist, *, causal: bool,
                       q_chunk: int = 512, k_chunk: int = 1024):
    """Sequence-parallel attention (zero3_sp policy): q is sharded over
    "model" on the SEQUENCE dim (heads replicated — works for ANY head
    count, incl. whisper's 20 / qwen2-vl's 12); COMPACT k/v (KV heads,
    unrepeated — GQA pays for itself on the wire) are all-gathered inside
    a shard_map and repeated locally; each shard runs the flash reference
    on its sequence slice with the right causal offset. No attention
    psum: the wo projection contracts full (unsharded) heads.

    q (B, S, H, hd); k/v (B, S, KV, hd); S % model-axis == 0.
    """
    bt = dist.batch_axes
    mesh = dist.mesh
    n_heads = q.shape[2]

    def body(ql, kl, vl):
        kf = jax.lax.all_gather(kl, "model", axis=1, tiled=True)
        vf = jax.lax.all_gather(vl, "model", axis=1, tiled=True)
        kf = repeat_kv(kf, n_heads)
        vf = repeat_kv(vf, n_heads)
        off = jax.lax.axis_index("model") * ql.shape[1]
        with jax.named_scope("pallas_flash_attention"):
            return _flash_vjp(ql, kf, vf, off.astype(jnp.int32), causal,
                              min(q_chunk, ql.shape[1]),
                              min(k_chunk, kf.shape[1]), kf.shape[1])

    spec = P(bt, "model", None, None)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def _flash_fwd_lse(q, k, v, causal, q_offset, q_chunk, k_chunk, sk_valid):
    """Forward identical to _flash_inner but also returns the row LSE
    (needed by the flash backward)."""
    B, Sq, H, hd = q.shape
    _, Sk, _, _ = k.shape
    nq, nk = Sq // q_chunk, Sk // k_chunk
    scale = hd ** -0.5
    kc = k.reshape(B, nk, k_chunk, H, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, k_chunk, H, hd).transpose(1, 0, 2, 3, 4)
    qc = q.reshape(B, nq, q_chunk, H, hd).transpose(1, 0, 2, 3, 4)

    def q_body(_, qi_xs):
        qi, iq = qi_xs
        qpos = iq * q_chunk + jnp.arange(q_chunk) + q_offset

        def kv_body(carry, kv_xs):
            acc, m, l = carry
            kj, vj, jk = kv_xs
            kpos = jk * k_chunk + jnp.arange(k_chunk)
            s = jnp.einsum("bqhd,bchd->bhqc", qi, kj,
                           preferred_element_type=jnp.float32) * scale
            mask = kpos[None, :] < sk_valid
            if causal:
                mask = mask & (qpos[:, None] >= kpos[None, :])
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhqc,bchd->bhqd", p.astype(vj.dtype), vj,
                            preferred_element_type=jnp.float32)
            acc = acc * corr[..., None] + pv
            return (acc, m_new, l_new), None

        acc0 = jnp.zeros((B, H, q_chunk, hd), jnp.float32)
        m0 = jnp.full((B, H, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_body, (acc0, m0, l0), (kc, vc, jnp.arange(nk)))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return None, (out.astype(q.dtype), lse)

    _, (outs, lses) = jax.lax.scan(q_body, None, (qc, jnp.arange(nq)))
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, Sq, H, hd)
    lse = lses.transpose(1, 2, 0, 3).reshape(B, H, Sq)
    return out, lse


from functools import partial as _partial


@_partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_vjp(q, k, v, q_offset, causal, q_chunk, k_chunk, sk_valid):
    out, _ = _flash_fwd_lse(q, k, v, causal, q_offset, q_chunk, k_chunk,
                            sk_valid)
    return out


def _flash_vjp_fwd(q, k, v, q_offset, causal, q_chunk, k_chunk, sk_valid):
    out, lse = _flash_fwd_lse(q, k, v, causal, q_offset, q_chunk, k_chunk,
                              sk_valid)
    return out, (q, k, v, out, lse, q_offset)


def _flash_vjp_bwd(causal, q_chunk, k_chunk, sk_valid, res, do):
    """Flash backward: O(S) memory — per (q-block, kv-block) tile the P
    matrix is recomputed from (q, k, lse); only dq/dk/dv accumulate.
    Runs inside the pallas scope: on TPU this is the bwd Pallas kernel."""
    with jax.named_scope("pallas_flash_attention"):
        q, k, v, out, lse, q_offset = res
        B, Sq, H, hd = q.shape
        _, Sk, _, _ = k.shape
        nq, nk = Sq // q_chunk, Sk // k_chunk
        scale = hd ** -0.5
        dof = do.astype(jnp.float32)
        delta = jnp.sum(dof * out.astype(jnp.float32), axis=-1)  # (B,Sq,H)
        delta = delta.transpose(0, 2, 1)                          # (B,H,Sq)

        qc = q.reshape(B, nq, q_chunk, H, hd).transpose(1, 0, 2, 3, 4)
        doc = do.reshape(B, nq, q_chunk, H, hd).transpose(1, 0, 2, 3, 4)
        lc = lse.reshape(B, H, nq, q_chunk).transpose(2, 0, 1, 3)
        dc = delta.reshape(B, H, nq, q_chunk).transpose(2, 0, 1, 3)
        kc = k.reshape(B, nk, k_chunk, H, hd).transpose(1, 0, 2, 3, 4)
        vc = v.reshape(B, nk, k_chunk, H, hd).transpose(1, 0, 2, 3, 4)

        def kv_body(dq_acc, kv_xs):
            kj, vj, jk = kv_xs
            kpos = jk * k_chunk + jnp.arange(k_chunk)

            def q_body(carry, q_xs):
                dkj, dvj = carry
                qi, doi, lsei, di, iq = q_xs
                qpos = iq * q_chunk + jnp.arange(q_chunk) + q_offset
                s = jnp.einsum("bqhd,bchd->bhqc", qi, kj,
                               preferred_element_type=jnp.float32) * scale
                mask = kpos[None, :] < sk_valid
                if causal:
                    mask = mask & (qpos[:, None] >= kpos[None, :])
                p = jnp.where(mask[None, None],
                              jnp.exp(s - lsei[..., None]), 0.0)
                dvj = dvj + jnp.einsum("bhqc,bqhd->bchd", p, dof_cast(doi))
                dp = jnp.einsum("bqhd,bchd->bhqc", dof_cast(doi), vj)
                ds = p * (dp - di[..., None]) * scale
                dq_i = jnp.einsum("bhqc,bchd->bqhd", ds, kj)
                dkj = dkj + jnp.einsum("bhqc,bqhd->bchd", ds, qi)
                return (dkj, dvj), dq_i

            z = jnp.zeros((B, k_chunk, H, hd), jnp.float32)
            (dkj, dvj), dq_chunks = jax.lax.scan(
                q_body, (z, z), (qc, doc, lc, dc, jnp.arange(nq)))
            dq_acc = dq_acc + dq_chunks.transpose(1, 0, 2, 3, 4).reshape(
                B, Sq, H, hd)
            return dq_acc, (dkj, dvj)

        dq0 = jnp.zeros((B, Sq, H, hd), jnp.float32)
        dq, (dks, dvs) = jax.lax.scan(kv_body, dq0,
                                      (kc, vc, jnp.arange(nk)))
        dk = dks.transpose(1, 0, 2, 3, 4).reshape(B, Sk, H, hd)
        dv = dvs.transpose(1, 0, 2, 3, 4).reshape(B, Sk, H, hd)
        import numpy as _np
        from jax import dtypes as _dtypes
        dq_off = _np.zeros(_np.shape(q_offset), _dtypes.float0)
        return (dq.astype(q.dtype), dk.astype(k.dtype),
                dv.astype(v.dtype), dq_off)


def dof_cast(x):
    return x.astype(jnp.float32)


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)

