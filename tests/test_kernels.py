"""Per-kernel allclose vs the pure-jnp oracles, sweeping shapes & dtypes
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.models.attention import flash_attention_ref
from repro.models.mamba import ssd_scan_ref


def _rand(key, shape, dtype, scale=1.0):
    x = jax.random.normal(jax.random.PRNGKey(key), shape) * scale
    return x.astype(dtype)


@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 128, 2, 2, 64),
    (2, 256, 4, 2, 64),       # GQA
    (1, 128, 4, 1, 128),      # MQA
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, S, H, KV, hd, causal, dtype):
    q = _rand(0, (B, S, H, hd), dtype)
    k = _rand(1, (B, S, KV, hd), dtype)
    v = _rand(2, (B, S, KV, hd), dtype)
    out_k = ops.flash_attention(q, k, v, causal=causal,
                                block_q=64, block_k=64)
    from repro.models.attention import repeat_kv
    out_r = flash_attention_ref(q, repeat_kv(k, H), repeat_kv(v, H),
                                causal=causal, q_chunk=64, k_chunk=64)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("B,S,H,P,G,N,chunk", [
    (1, 128, 2, 16, 1, 16, 32),
    (2, 256, 4, 32, 2, 16, 64),
    (1, 128, 4, 64, 1, 32, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan(B, S, H, P, G, N, chunk, dtype):
    x = _rand(0, (B, S, H, P), dtype, 0.5)
    dt = jax.nn.softplus(_rand(1, (B, S, H), jnp.float32))
    a_log = jnp.zeros((H,))
    b = _rand(2, (B, S, G, N), dtype, 0.3)
    c = _rand(3, (B, S, G, N), dtype, 0.3)
    y_k = ops.ssd_scan(x, dt, a_log, b, c, chunk=chunk)
    y_r, _ = ssd_scan_ref(x, dt, a_log, b, c, chunk=chunk)
    tol = 1e-3 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("solver", ["sgd", "momentum", "adam",
                                    "easgd_center", "average"])
@pytest.mark.parametrize("nl,f", [(2, 2048), (8, 4096)])
def test_ps_aggregate(solver, nl, f):
    g = _rand(0, (nl, f), jnp.float32)
    p = _rand(1, (f,), jnp.float32)
    m = _rand(2, (f,), jnp.float32, 0.1)
    v = jnp.abs(_rand(3, (f,), jnp.float32, 0.1))
    pk, mk, vk = ops.ps_aggregate(g, p, m, v, 3, solver=solver, lr=0.01)
    pr, mr, vr = ref.ps_aggregate_ref(g, p, m, v, 3, solver=solver,
                                      lr=0.01)
    np.testing.assert_allclose(pk, pr, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(mk, mr, atol=1e-6)
    np.testing.assert_allclose(vk, vr, atol=1e-6)


@pytest.mark.parametrize("sizes", [
    [0, 3, 0, 0, 2, 1, 0, 0, 0, 0, 0, 0],     # some groups empty
    [0] * 12,                                  # no row in any group
    [1] * 12,                                  # every group one row
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_matches_ragged_dot(sizes, dtype):
    """Rows inside the groups equal ``ragged_dot``'s; rows past them are
    left to the caller."""
    rows = _rand(0, (16, 256), dtype)
    w = _rand(1, (12, 256, 128), dtype)
    g = jnp.asarray(sizes, jnp.int32)
    n = sum(sizes)
    got = ops.grouped_matmul(rows, w, g)
    want = jax.lax.ragged_dot(rows, w, g)
    assert got.shape == want.shape and got.dtype == want.dtype
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got[:n], np.float32),
                               np.asarray(want[:n], np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("solver", ["sgd", "momentum", "adam",
                                    "easgd_center", "average"])
def test_ps_aggregate_np_matches_ref_over_rounds(solver):
    """The in-place numpy twin (software-PS CPU hot path) tracks the
    jnp oracle across multiple aggregation rounds, state included."""
    rng = np.random.RandomState(0)
    p = rng.randn(1536).astype(np.float32)
    m = np.zeros(1536, np.float32)
    v = np.zeros(1536, np.float32)
    pr, mr, vr = jnp.array(p), jnp.array(m), jnp.array(v)
    for step in range(1, 12):
        g = rng.randn(3, 1536).astype(np.float32)
        ref.ps_aggregate_np(g, p, m, v, step, solver=solver, lr=0.01)
        pr, mr, vr = ref.ps_aggregate_ref(jnp.array(g), pr, mr, vr,
                                          step, solver=solver, lr=0.01)
    np.testing.assert_allclose(p, np.asarray(pr), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(m, np.asarray(mr), atol=1e-5)
    np.testing.assert_allclose(v, np.asarray(vr), atol=1e-5)


def test_ps_aggregate_block_fallback_non_pow2():
    """Shard lengths are multiples of 256, not 4096: the kernel grid
    must fall back to a dividing block size instead of asserting."""
    f = 2048 + 256                                     # 9 * 256
    g = _rand(0, (2, f), jnp.float32)
    p = _rand(1, (f,), jnp.float32)
    m = jnp.zeros((f,), jnp.float32)
    v = jnp.zeros((f,), jnp.float32)
    pk, _, _ = ops.ps_aggregate(g, p, m, v, 1, solver="sgd", lr=0.1)
    pr, _, _ = ref.ps_aggregate_ref(g, p, m, v, 1, solver="sgd", lr=0.1)
    np.testing.assert_allclose(pk, pr, atol=1e-6)


def test_flash_ref_oracle_matches_folded():
    """kernels/ref.py flash_ref (folded layout) is self-consistent with
    the model-layout reference."""
    q = _rand(0, (4, 128, 64), jnp.float32)
    k = _rand(1, (4, 128, 64), jnp.float32)
    v = _rand(2, (4, 128, 64), jnp.float32)
    a = ref.flash_ref(q, k, v, causal=True)
    from repro.kernels.flash_attention import flash_attention_fwd
    b = flash_attention_fwd(q, k, v, causal=True, block_q=64, block_k=64,
                            interpret=True)
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_ssd_kernel_long_state_carry():
    """State must carry correctly across many chunks (decay ordering)."""
    B, S, H, P, N = 1, 512, 1, 8, 8
    x = _rand(0, (B, S, H, P), jnp.float32, 0.3)
    dt = jnp.full((B, S, H), 0.5)
    a_log = jnp.full((H,), -1.0)       # slow decay: long-range coupling
    b = _rand(1, (B, S, 1, N), jnp.float32, 0.3)
    c = _rand(2, (B, S, 1, N), jnp.float32, 0.3)
    y64 = ops.ssd_scan(x, dt, a_log, b, c, chunk=64)
    y128 = ops.ssd_scan(x, dt, a_log, b, c, chunk=128)
    # chunk size must not change the result
    np.testing.assert_allclose(np.asarray(y64), np.asarray(y128),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("S", [96, 128])
def test_flash_custom_vjp_grads_match_naive(causal, S):
    """The O(S)-memory flash backward must match naive-attention grads."""
    def naive(q, k, v):
        _, s_, _, hd = q.shape
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
        if causal:
            mask = jnp.tril(jnp.ones((s_, s_), bool))
            s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    q = _rand(0, (2, S, 4, 32), jnp.float32)
    k = _rand(1, (2, S, 4, 32), jnp.float32)
    v = _rand(2, (2, S, 4, 32), jnp.float32)
    w = _rand(3, (2, S, 4, 32), jnp.float32)
    f1 = lambda q, k, v: jnp.sum(flash_attention_ref(
        q, k, v, causal=causal, q_chunk=64, k_chunk=64) * w)
    f2 = lambda q, k, v: jnp.sum(naive(q, k, v) * w)
    o1, g1 = jax.value_and_grad(f1, argnums=(0, 1, 2))(q, k, v)
    o2, g2 = jax.value_and_grad(f2, argnums=(0, 1, 2))(q, k, v)
    assert abs(float(o1 - o2)) < 1e-2
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-3)
