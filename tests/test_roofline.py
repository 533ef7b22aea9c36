"""Roofline HLO analyzer: dot FLOPs, while trip counts, collective
formulas, group parsing — validated against analytically-known modules."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.perf import JobPerf
from repro.analysis.roofline import Op, _group_info, analyze_hlo_text


def _hlo_of(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_dot_flops_exact():
    a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    txt = _hlo_of(lambda x, y: x @ y, a, b)
    got = analyze_hlo_text(txt)["flops_per_device"]
    assert got == 2 * 64 * 128 * 32


def test_scan_trip_count_multiplies():
    a = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((24, 64, 64), jnp.float32)

    def fn(x, ws):
        def body(h, wi):
            return jnp.tanh(h @ wi), None
        h, _ = jax.lax.scan(body, x, ws)
        return h
    txt = _hlo_of(fn, a, w)
    got = analyze_hlo_text(txt)["flops_per_device"]
    want = 24 * 2 * 64 * 64 * 64
    assert abs(got - want) / want < 0.05, (got, want)


def test_nested_scan_trip_counts():
    a = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((4, 3, 32, 32), jnp.float32)

    def fn(x, ws):
        def outer(h, wrow):
            def inner(h2, wi):
                return h2 @ wi, None
            h, _ = jax.lax.scan(inner, h, wrow)
            return h, None
        h, _ = jax.lax.scan(outer, x, ws)
        return h
    txt = _hlo_of(fn, a, w)
    got = analyze_hlo_text(txt)["flops_per_device"]
    want = 12 * 2 * 32 ** 3
    assert abs(got - want) / want < 0.05, (got, want)


def test_roofline_times_use_the_named_device_peaks():
    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    txt = _hlo_of(lambda x: x @ x, a)
    tpu = analyze_hlo_text(txt, device_kind="TPU v5 lite")
    assert tpu["device_kind"] == "TPU v5 lite"
    assert tpu["compute_s"] == pytest.approx(tpu["flops_per_device"]
                                             / 197e12)
    assert tpu["memory_s"] == pytest.approx(tpu["hbm_bytes_per_device"]
                                            / 819e9)
    # a kind with no published peaks gets counts, never v5e's ceiling
    other = analyze_hlo_text(txt, device_kind="cpu")
    assert other["flops_per_device"] == tpu["flops_per_device"]
    assert other["compute_s"] is None and other["memory_s"] is None


def _job_perf_of(txt):
    """A JobPerf fed ``txt``, once its background analysis has ended."""
    perf = JobPerf("job-perf-test")
    perf.start_async(lambda: txt)
    for _ in range(600):
        if perf.snapshot()["state"] != "running":
            break
        time.sleep(0.05)
    return perf


def test_job_perf_names_the_device_kind_it_ran_on():
    a = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    txt = _hlo_of(lambda x: x @ x, a)
    snap = _job_perf_of(txt).snapshot(measured_per_s=10.0)
    assert snap["state"] == "ready", snap
    assert snap["device_kind"] == jax.devices()[0].device_kind
    assert snap["peaks"] == "no peaks"          # the CPU is not in PEAKS
    assert snap["flops_per_step_per_device"] == 2 * 64 ** 3
    assert "pct_of_attainable" not in snap


def test_group_info_parsing():
    def op(line):
        return Op("x", "all-reduce", 0, [], [], line)
    # explicit groups
    s, crosses = _group_info(op("replica_groups={{0,1,2,3}}"))
    assert s == 4 and not crosses
    s, crosses = _group_info(op("replica_groups={{0,256}}"))
    assert s == 2 and crosses
    # iota form: 16 groups of 16 over 256 — contiguous, single pod
    s, crosses = _group_info(op("replica_groups=[16,16]<=[256]"))
    assert s == 16 and not crosses
    # iota with transpose over 512: groups stride across pods
    s, crosses = _group_info(op("replica_groups=[256,2]<=[2,256]T(1,0)"))
    assert s == 2 and crosses


def test_memory_bytes_reasonable_for_elementwise():
    a = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    txt = _hlo_of(lambda x: x * 2.0 + 1.0, a)
    got = analyze_hlo_text(txt)["hbm_bytes_per_device"]
    # read + write = 8 MB; allow fusion-accounting factor 2
    assert 4e6 <= got <= 2e7, got


def _flash_program():
    from repro.models.attention import flash_attention_ref
    qkv = jax.ShapeDtypeStruct((1, 64, 2, 16), jnp.float32)
    return _hlo_of(lambda q, k, v: flash_attention_ref(
        q, k, v, causal=True, q_chunk=32, k_chunk=32), qkv, qkv, qkv)


def _ssd_program():
    from repro.models.mamba import ssd_scan_ref
    f32 = jnp.float32
    x = jax.ShapeDtypeStruct((1, 32, 2, 8), f32)
    dt = jax.ShapeDtypeStruct((1, 32, 2), f32)
    a_log = jax.ShapeDtypeStruct((2,), f32)
    bc = jax.ShapeDtypeStruct((1, 32, 1, 4), f32)
    return _hlo_of(lambda *args: ssd_scan_ref(*args, chunk=16),
                   x, dt, a_log, bc, bc)


@pytest.mark.parametrize("scope,program", [
    ("flash_attention_ref", _flash_program),
    ("ssd_scan_ref", _ssd_program),
])
def test_scoped_jnp_code_is_counted(scope, program):
    """Named scopes are names, not kernels: status.perf counts the bytes
    of every op in them, as the plain analysis of the program does."""
    txt = program()
    assert f"/{scope}/" in txt              # the ops carry the scope name
    plain = analyze_hlo_text(txt)["hbm_bytes_per_device"]
    perf = _job_perf_of(txt)
    assert perf.state == "ready", perf.snapshot()
    assert plain > 0
    assert perf.analysis["hbm_bytes_per_device"] == plain
