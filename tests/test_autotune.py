"""Autotuner (kernels/autotune.py): deterministic choices, on-disk
cache round-trip across processes, tuned-vs-pinned parity against the
jnp oracles, and the perf-gate verdict logic in benchmarks/run.py."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, ops, ref
from repro.kernels.grid import TILE, fit_block
from repro.kernels.quantize import QTILE

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def tuner_cache(tmp_path, monkeypatch):
    """Point the autotune cache at a private temp file so tests neither
    see nor pollute the shared default cache."""
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("DLAAS_AUTOTUNE_CACHE", str(path))
    monkeypatch.delenv("DLAAS_AUTOTUNE", raising=False)
    monkeypatch.delenv("DLAAS_AUTOTUNE_MEASURE", raising=False)
    yield path
    autotune._caches.pop(str(path), None)


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(jax.random.PRNGKey(key), shape)
            * scale).astype(dtype)


# ---------------------------------------------------------------------------
# determinism + cache


def test_choice_deterministic_and_cached(tuner_cache):
    b1 = autotune.tuned_ps_block(4, 1 << 14)
    b2 = autotune.tuned_ps_block(4, 1 << 14)          # in-memory hit
    assert b1 == b2
    data = json.loads(tuner_cache.read_text())
    (key, rec), = data.items()
    assert key.startswith("ps_aggregate|4x16384|")
    assert rec["choice"] == b1
    assert rec["source"] in ("predicted", "measured")
    # a cold cache re-derives the identical choice (ranking is pure)
    autotune.get_cache().clear()
    assert autotune.tuned_ps_block(4, 1 << 14) == b1


def test_cache_round_trip_across_processes(tuner_cache):
    blk = autotune.tuned_ps_block(4, 1 << 14)
    # poison the persisted choice with a different legal block: if the
    # child returns it, the choice really came from the disk cache, not
    # from re-tuning to the same deterministic answer
    data = json.loads(tuner_cache.read_text())
    (key, rec), = data.items()
    poison = 1024 if blk != 1024 else 2048
    rec["choice"], rec["source"] = poison, "poisoned"
    tuner_cache.write_text(json.dumps(data))
    env = dict(os.environ,
               DLAAS_AUTOTUNE_CACHE=str(tuner_cache),
               PYTHONPATH=str(ROOT / "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, "-c",
         "from repro.kernels import autotune\n"
         "print('CHOICE', autotune.tuned_ps_block(4, 1 << 14))"],
        capture_output=True, text=True, env=env, timeout=180)
    assert out.returncode == 0, out.stderr
    assert f"CHOICE {poison}" in out.stdout, (out.stdout, out.stderr)


def test_cache_merge_on_write(tmp_path):
    """Two concurrent writers (distinct in-memory instances on the same
    path, as two processes would be) must not clobber each other."""
    p = str(tmp_path / "c.json")
    a, b = autotune.AutotuneCache(p), autotune.AutotuneCache(p)
    a.put("k1", {"choice": 1})
    b.put("k2", {"choice": 2})
    fresh = autotune.AutotuneCache(p)
    assert fresh.get("k1")["choice"] == 1
    assert fresh.get("k2")["choice"] == 2


def test_flash_choice_tuple_survives_disk_round_trip(tuner_cache):
    c1 = autotune.tuned_flash_blocks(2, 128, 128, 64)
    assert isinstance(c1, tuple) and len(c1) == 2
    # evict the in-memory mirror: the next call re-reads the JSON file,
    # where the tuple became a list
    autotune._caches.pop(str(tuner_cache), None)
    c2 = autotune.tuned_flash_blocks(2, 128, 128, 64)
    assert isinstance(c2, tuple) and c2 == c1


def test_disabled_falls_back_to_fit_block(tuner_cache, monkeypatch):
    monkeypatch.setenv("DLAAS_AUTOTUNE", "0")
    assert autotune.tuned_ps_block(4, 1 << 14) == \
        fit_block(1 << 14, TILE, multiple=TILE)
    assert autotune.tuned_quantize_block(1 << 13) == QTILE
    assert not tuner_cache.exists()


def test_forced_measurement_keeps_a_measured_choice(tuner_cache,
                                                    monkeypatch):
    monkeypatch.setenv("DLAAS_AUTOTUNE_MEASURE", "1")
    blk = autotune.tuned_ps_block(2, 4 * TILE)
    assert blk in (TILE, 2 * TILE, 4 * TILE)
    (_, rec), = json.loads(tuner_cache.read_text()).items()
    assert rec["source"] == "measured"
    assert rec["measured_us"]          # top-K candidates were timed
    assert str(blk) in rec["measured_us"]


def test_failing_measurement_is_an_error(tuner_cache):
    def measure_s(c):
        raise RuntimeError(f"candidate {c} did not compile")

    with pytest.raises(RuntimeError, match="did not compile"):
        autotune.tune("probe", (8,), "float32", candidates=[1, 2, 4],
                      predict_us=float, measure_s=measure_s, default=1)
    assert not tuner_cache.exists()             # nothing cached


def test_under_trace_sees_jit_tracing():
    seen = []

    @jax.jit
    def f(x):
        seen.append(autotune._under_trace())
        return x + 1

    assert not autotune._under_trace()
    f(jnp.zeros(2))
    assert seen == [True]


# ---------------------------------------------------------------------------
# tuned-path parity vs the jnp oracles (block=None -> autotuned)


def test_ps_aggregate_tuned_matches_ref(tuner_cache):
    nl, f = 4, 3 * 1024
    g = _rand(0, (nl, f))
    p = _rand(1, (f,))
    m = _rand(2, (f,), scale=0.1)
    v = jnp.abs(_rand(3, (f,), scale=0.1))
    pk, mk, vk = ops.ps_aggregate(g, p, m, v, 3, solver="adam", lr=0.01)
    pr, mr, vr = ref.ps_aggregate_ref(g, p, m, v, 3, solver="adam",
                                      lr=0.01)
    np.testing.assert_allclose(pk, pr, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(mk, mr, atol=1e-6)
    np.testing.assert_allclose(vk, vr, atol=1e-6)
    assert any(k.startswith("ps_aggregate|")
               for k in json.loads(tuner_cache.read_text()))


def test_quantize_tuned_matches_ref(tuner_cache):
    f = 1 << 13
    x = _rand(0, (f,))
    e = jnp.zeros_like(x)
    qk, sk, ek = ops.quantize_ef(x, e)
    qr, sr, er = ref.quantize_ref(x, e)
    np.testing.assert_allclose(np.asarray(ops.dequantize(qk, sk)),
                               np.asarray(ref.dequantize_ref(qr, sr)),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ek), np.asarray(er),
                               atol=1e-5, rtol=1e-5)
    assert any(k.startswith("quantize_ef|")
               for k in json.loads(tuner_cache.read_text()))


def test_flash_attention_tuned_matches_ref(tuner_cache):
    from repro.models.attention import flash_attention_ref, repeat_kv
    q = _rand(0, (1, 128, 2, 64))
    k = _rand(1, (1, 128, 2, 64))
    v = _rand(2, (1, 128, 2, 64))
    out_t = ops.flash_attention(q, k, v, causal=True)   # autotuned blocks
    out_r = flash_attention_ref(q, repeat_kv(k, 2), repeat_kv(v, 2),
                                causal=True, q_chunk=64, k_chunk=64)
    np.testing.assert_allclose(np.asarray(out_t), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)
    assert any(key.startswith("flash_attention|")
               for key in json.loads(tuner_cache.read_text()))


# ---------------------------------------------------------------------------
# perf-gate verdicts (benchmarks/run.py compare())


def _benchrun():
    spec = importlib.util.spec_from_file_location(
        "benchrun_for_tests", ROOT / "benchmarks" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


BASE = {"backends": {"software-ps": {"steps_per_s": 10.0}},
        "modes": {"int8": {"steps_per_s": 8.0, "compression_ratio": 3.9}},
        "loads": {"1": {"req_per_s": 12.0}}}


def test_gate_pass():
    br = _benchrun()
    fresh = json.loads(json.dumps(BASE))
    fresh["backends"]["software-ps"]["steps_per_s"] = 6.0   # >= 0.5x
    res = br.compare(BASE, fresh, 0.5)
    assert res["verdict"] == "PASS"
    assert len(res["checks"]) == 4
    assert all(c["ok"] for c in res["checks"])


def test_gate_regress_names_the_metric():
    br = _benchrun()
    fresh = json.loads(json.dumps(BASE))
    fresh["modes"]["int8"]["steps_per_s"] = 3.0             # < 0.5 * 8.0
    res = br.compare(BASE, fresh, 0.5)
    assert res["verdict"] == "REGRESS"
    bad = [c for c in res["checks"] if not c["ok"]]
    assert [c["metric"] for c in bad] == ["modes.int8.steps_per_s"]


def test_gate_missing_baseline_and_missing_fresh_metric():
    br = _benchrun()
    assert br.compare(None, BASE, 0.5)["verdict"] == "MISSING_BASELINE"
    assert br.compare({}, BASE, 0.5)["verdict"] == "MISSING_BASELINE"
    # a fresh run that lost a metric entirely is a regression
    fresh = json.loads(json.dumps(BASE))
    del fresh["loads"]
    res = br.compare(BASE, fresh, 0.5)
    assert res["verdict"] == "REGRESS"
    assert any(c["metric"] == "loads.1.req_per_s" and c["fresh"] is None
               for c in res["checks"])
