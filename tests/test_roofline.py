"""Roofline HLO analyzer: dot FLOPs, while trip counts, collective
formulas, group parsing — validated against analytically-known modules."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.roofline import (Op, _group_info, analyze_hlo_text,
                                     model_flops, parse_module)
from repro.configs.base import SHAPES_BY_NAME
from repro.configs.registry import get_arch


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


def test_job_perf_names_the_device_kind_it_ran_on():
    from repro.analysis.perf import JobPerf
    a = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    txt = _hlo_of(lambda x: x @ x, a)
    perf = JobPerf("job-perf-test")
    perf.start_async(lambda: txt)
    for _ in range(600):
        if perf.snapshot()["state"] != "running":
            break
        time.sleep(0.05)
    snap = perf.snapshot(measured_per_s=10.0)
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


def test_model_flops_formulas():
    cfg = get_arch("stablelm-1.6b")
    tr = model_flops(cfg, SHAPES_BY_NAME["train_4k"])
    # 6ND dominates: 6 * 1.64e9 * 1.05e6 ~ 1.03e16
    assert 0.9e16 < tr < 1.4e16
    pf = model_flops(cfg, SHAPES_BY_NAME["prefill_32k"])
    dc = model_flops(cfg, SHAPES_BY_NAME["decode_32k"])
    assert dc < pf < tr
    moe = get_arch("kimi-k2-1t-a32b")
    # active params ~32B -> train flops ~ 6*32e9*1.05e6 ~ 2e17
    assert 1e17 < model_flops(moe, SHAPES_BY_NAME["train_4k"]) < 6e17


def test_kernel_scope_accounting_reduces_bytes():
    a = jax.ShapeDtypeStruct((4, 256, 64), jnp.float32)

    def fn(q):
        from repro.kernels.flash_attention import flash_attention_fwd
        with jax.named_scope("pallas_flash_attention"):
            # emulate scope-internal traffic with plain ops
            s = jnp.einsum("bqd,bkd->bqk", q, q)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bqk,bkd->bqd", p, q)
    txt = _hlo_of(fn, a)
    full = analyze_hlo_text(txt)["hbm_bytes_per_device"]
    fused = analyze_hlo_text(
        txt, kernel_scopes=("pallas_flash_attention",)
    )["hbm_bytes_per_device"]
    assert fused < full
