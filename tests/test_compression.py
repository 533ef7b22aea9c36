"""int8 compression: error bounds, error-feedback convergence/
unbiasedness properties, jnp-vs-Pallas parity, and the push-path
compressor the software-PS client uses.

Only the property-based tests need hypothesis; everything else runs
even where it is not installed (the guard is per-test, not module-wide,
so the parity sweeps keep covering bare environments)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False

    def given(*a, **k):             # keep decorated defs importable
        return lambda f: f

    settings = given

    class st:                       # noqa: N801 — stand-in namespace
        integers = floats = staticmethod(lambda *a, **k: None)

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="hypothesis not installed")

from repro.core.compression import (BLOCK, CompressedPush,
                                    compress_with_feedback,
                                    dequantize_int8, make_compressor,
                                    quantize_int8, wire_bytes)


@needs_hypothesis
@given(st.integers(0, 2 ** 31 - 1), st.floats(0.01, 100.0))
@settings(max_examples=30, deadline=None)
def test_quant_error_bound(seed, scale):
    x = jax.random.normal(jax.random.PRNGKey(seed), (1024,)) * scale
    q, s = quantize_int8(x)
    back = dequantize_int8(q, s)
    # per-block error <= scale/2 = amax/254
    xb = np.asarray(x).reshape(-1, BLOCK)
    amax = np.abs(xb).max(axis=1)
    err = np.abs(np.asarray(back).reshape(-1, BLOCK) - xb)
    assert (err <= amax[:, None] / 127.0 * 0.5 + 1e-7).all()


def test_error_feedback_unbiased_over_time():
    """With error feedback, the ACCUMULATED transmitted signal converges
    to the accumulated true signal (compression is unbiased over time)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (512,))
    err = jnp.zeros_like(x)
    sent = jnp.zeros_like(x)
    for _ in range(50):
        q, s, err, wire = compress_with_feedback(x, err)
        sent = sent + wire
    # mean transmitted per round -> x
    np.testing.assert_allclose(np.asarray(sent / 50), np.asarray(x),
                               atol=np.abs(np.asarray(x)).max() / 100)


@needs_hypothesis
@given(st.integers(0, 2 ** 31 - 1), st.floats(0.05, 50.0),
       st.integers(20, 60))
@settings(max_examples=15, deadline=None)
def test_error_feedback_unbiased_property(seed, scale, rounds):
    """Property form of the unbiasedness claim: for any signal scale
    and horizon, the mean transmitted vector converges to the true
    vector at a 1/rounds rate (the residual is bounded by the feedback
    buffer, which the quantization error bound caps)."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (512,)) * scale
    err = jnp.zeros_like(x)
    sent = jnp.zeros_like(x)
    for _ in range(rounds):
        _, _, err, wire = compress_with_feedback(x, err)
        sent = sent + wire
    # mean(sent) - x == -err/rounds, and |err| <= per-block amax/127
    amax = float(jnp.max(jnp.abs(x)))
    np.testing.assert_allclose(np.asarray(sent / rounds), np.asarray(x),
                               atol=1.01 * amax / 127.0 / rounds + 1e-7)


def test_make_compressor_matches_quantize_ref():
    """The push-path compressor (jit'd reference on CPU) returns
    exactly what kernels/ref.py:quantize_ref defines."""
    from repro.kernels.ref import quantize_ref
    fn, path = make_compressor()
    assert path == "jnp"
    x = jax.random.normal(jax.random.PRNGKey(0), (2048,))
    e = jax.random.normal(jax.random.PRNGKey(1), (2048,)) * 0.1
    q, s, err = fn(x, e)
    qr, sr, er = quantize_ref(x, e)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(err), np.asarray(er),
                               atol=1e-6)


def test_compressed_push_wire_accounting():
    p = CompressedPush(q=np.zeros(1024, np.int8),
                       scales=np.zeros(4, np.float32),
                       dense_nbytes=4096)
    assert p.wire_nbytes == 1024 + 16
    assert p.dense_nbytes / p.wire_nbytes > 3.9


def test_wire_bytes():
    assert wire_bytes(1024) == 1024 + 4 * 4   # int8 + f32 scale per block


def test_quantize_kernel_matches_ref_sweep():
    from repro.kernels import ops, ref
    for n in (256, 1024, 8192):
        for seed in (0, 1):
            x = jax.random.normal(jax.random.PRNGKey(seed), (n,))
            e = jax.random.normal(jax.random.PRNGKey(seed + 7), (n,)) * .1
            qk, sk, ek = ops.quantize_ef(x, e)
            qr, sr, er = ref.quantize_ref(x, e)
            np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
            np.testing.assert_allclose(sk, sr, rtol=1e-6)
            np.testing.assert_allclose(ek, er, atol=1e-6)
