"""The hybrid (Granite 4.0-H) program against the plain float32 reference
at smoke size on the CPU: prefill and decode, the engine's slot cache,
dropless routing under total imbalance, the expert shares adding up to
the uncut layer, the fp8 control, and a serve cell driven end to end."""
import dataclasses
import json
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import compare, harness  # noqa: E402
from bench.drivers import open_loop_serve as drv  # noqa: E402
from bench.reference import common, hybrid  # noqa: E402
from bench.work import hybrid as work  # noqa: E402
from repro.configs.registry import resolve_arch  # noqa: E402
from repro.distributed.sharding import Dist  # noqa: E402
from repro.models import make_model  # noqa: E402
from repro.models.moe import _capacity, moe_block  # noqa: E402
from repro.serving.engine import InferenceEngine  # noqa: E402

# granite-4.0-h-small-smoke as a configuration file states it
SMOKE = dict(
    hidden_size=64, num_hidden_layers=10,
    layer_types=["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
    num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
    rms_norm_eps=1e-5, mamba_d_state=16, mamba_d_head=16, mamba_expand=2,
    mamba_chunk_size=32, mamba_n_groups=1, mamba_d_conv=4,
    intermediate_size=64, shared_intermediate_size=64, num_experts_per_tok=2,
    published_num_local_experts=4, num_local_experts=4,
    attention_multiplier=1 / 128, embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=16.0, tie_word_embeddings=True,
    dtype="float32")
SEED = 2**35 + 11
# the reference's logits have a spread of about 0.003 (its small embedding
# table, tied to the head); float32 program and reference agree to about
# 1e-8 of that
ATOL = 1e-6


def _arch(shards=1, **moe):
    a = resolve_arch("granite-4.0-h-small-smoke")
    return dataclasses.replace(a, expert_shards=shards,
                               moe=dataclasses.replace(a.moe, **moe))


def _conf(shards=1):
    return dict(SMOKE, num_local_experts=4 // shards)


def _pad_seq(cache, length):
    """The engine's padding of a prefill cache to its max_seq."""
    out = dict(cache)
    for k in ("k", "v"):
        pads = [(0, 0)] * out[k].ndim
        pads[2] = (0, length - out[k].shape[2])
        out[k] = jnp.pad(out[k], pads)
    return out


def test_published_file_matches_the_program_and_its_pattern():
    conf = json.loads((ROOT / "bench/configs/granite-4.0-h-small.json").read_text())
    arch = harness.register_config(conf)
    harness.check_program_config(arch, conf, hybrid)
    a = resolve_arch(arch)
    assert hybrid.pattern(conf) == (a.attn_period, a.attn_index) == (10, 5)
    assert (a.n_layers, a.held_experts, a.moe.n_experts) == (10, 18, 72)
    assert conf["published_num_hidden_layers"] == len(conf["layer_types"]) == 40
    assert set(conf["reduced"]) == {"num_hidden_layers", "num_local_experts"}


@pytest.mark.parametrize("shards", [1, 2])
def test_reference_matches_prefill_then_decode(shards):
    a, conf = _arch(shards), _conf(shards)
    for key, field in hybrid.PROGRAM_FIELDS.items():
        obj = a
        for part in field.split("."):
            obj = getattr(obj, part)
        assert conf[key] == obj, key
    model = make_model(a, None, {"remat": "none"})
    specs = hybrid.param_specs(conf)
    assert common.shapes(specs, "float32") == jax.eval_shape(
        model.init, jax.random.PRNGKey(0))
    params = common.make_params(specs, SEED, "float32")
    T, P = 45, 37            # prompt past the SSD chunk of 32
    toks = np.random.default_rng(0).integers(0, 256, (2, T)).astype(np.int32)
    want = np.asarray(hybrid.logits(params, jnp.asarray(toks), conf))
    with jax.default_matmul_precision("highest"):
        last, cache = model.prefill(params, {"tokens": jnp.asarray(toks[:, :P])})
        got = [np.asarray(last[:, -1])]
        cache = _pad_seq(cache, T)
        for t in range(P, T - 1):
            lg, cache = model.decode(params, cache,
                                     {"tokens": jnp.asarray(toks[:, t:t + 1])})
            got.append(np.asarray(lg[:, -1]))
    np.testing.assert_allclose(np.stack(got, 1), want[:, P - 1:T - 1],
                               atol=ATOL, rtol=0)


def test_engine_slot_cache_matches_the_reference():
    """Prompts of three lengths served through the engine's prefill,
    splice and slot-batched decode (3 requests on 2 slots: the third
    joins mid-flight): the reference puts every served token first."""
    a, conf = _arch(2), _conf(2)
    eng = InferenceEngine(a, capacity=2, max_seq=64, endpoint_id="ep-hybrid")
    eng.start(None)
    params = common.make_params(hybrid.param_specs(conf), SEED, "float32")
    eng.params = params
    rng = np.random.default_rng(3)
    reqs = [eng.submit(rng.integers(0, 256, n).astype(np.int32), max_new=m)
            for n, m in ((9, 6), (33, 5), (20, 7))]
    th = threading.Thread(target=eng.run, daemon=True)
    th.start()
    for r in reqs:
        assert r.wait(180), r.status
    eng.drain()
    th.join(30)
    assert not th.is_alive()
    items = [(r.prompt, r.tokens) for r in reqs]
    assert [len(t) for _, t in items] == [6, 5, 7]
    cmp = compare.Comparator(hybrid, conf, 64, hybrid.REF_BATCH)
    gap, n = cmp.widest_gap(params, items)
    assert n == 18 and gap < ATOL, gap


def test_dropless_under_total_imbalance():
    """Every token's first choice is expert 0: the layer computes all of
    their rows (a capacity of 1.25 x the even share would drop most) and
    agrees with the reference's dense layer."""
    a, conf = _arch(1, n_experts=8, top_k=2), dict(
        _conf(1), published_num_local_experts=8, num_local_experts=8)
    p = common.make_params(hybrid.param_specs(conf), SEED, "float32")
    moe = jax.tree.map(lambda w: w[0, 0], p["blocks"]["moe"])
    moe["router"] = moe["router"].at[0, 0].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 64))
    x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 1.0)
    _, idx = hybrid.gates(x, moe["router"], conf)
    assert bool(jnp.all(idx[..., 0] == 0))
    t = x.shape[0] * x.shape[1]
    assert _capacity(t, 2, 8, 1.25) < t          # the clip would drop
    with jax.default_matmul_precision("highest"):
        out, counts = moe_block(x, moe, a, Dist())
    assert counts.tolist() == [2 * t, len(set(np.asarray(idx).ravel()))]
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(hybrid.moe(x, moe, conf)),
                               atol=1e-4, rtol=1e-4)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips hold two of eight experts each: each share is the
    program's layer given its experts first (the router's columns turned
    to match); the shares' parts, with the shared expert counted once,
    add up to the uncut reference layer."""
    full = dict(_conf(1), published_num_local_experts=8, num_local_experts=8,
                num_experts_per_tok=3)
    p = common.make_params(hybrid.param_specs(full), SEED + 1, "float32")
    moe = jax.tree.map(lambda w: w[0, 0], p["blocks"]["moe"])
    shared = jax.tree.map(lambda w: w[0, 0], p["blocks"]["shared"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 64))
    share = _arch(4, n_experts=8, top_k=3)
    assert share.held_experts == 2
    total, rows = 0.0, 0
    with jax.default_matmul_precision("highest"):
        for i in range(4):
            mine = slice(2 * i, 2 * i + 2)
            part = {"router": jnp.roll(moe["router"], -2 * i, axis=1),
                    **{k: moe[k][mine] for k in ("wg", "wu", "wd")}}
            out, counts = moe_block(x, part, share, Dist())
            total, rows = total + out, rows + int(counts[0])
        total = total + hybrid._swiglu(x, shared, False)
    want = hybrid.moe(x, moe, full) + hybrid._swiglu(x, shared, False)
    assert rows == 3 * 2 * 16
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_comparator_reads_zero_for_the_reference_and_more_for_fp8():
    conf = _conf(2)
    params = common.make_params(hybrid.param_specs(conf), 7, "float32")
    rng = np.random.default_rng(1)
    fwd = jax.jit(lambda t: hybrid.logits(params, t, conf))
    items = []
    for n in (9, 20, 31):                  # greedy continuation by the reference
        seq = list(rng.integers(0, 256, n).astype(np.int32))
        for _ in range(6):
            row = np.zeros((1, 48), np.int32)
            row[0, :len(seq)] = seq
            seq.append(int(jnp.argmax(fwd(jnp.asarray(row))[0, len(seq) - 1])))
        items.append((np.asarray(seq[:n], np.int32), seq[n:]))
    cmp = compare.Comparator(hybrid, conf, 48, 1)
    gap, n = cmp.widest_gap(params, items)
    assert n == 18 and gap < ATOL
    ctrl, n = cmp.control_gap(params, items)
    assert n == 18 and ctrl > 100 * ATOL


def test_least_bytes_of_the_cell_step():
    """Weights outside the routed experts and the state of 8 live slots
    at the cell's configuration: 3.13 GB and 0.61 GB."""
    conf = json.loads((ROOT / "bench/configs/granite-4.0-h-small.json").read_text())
    arch = resolve_arch(harness.register_config(conf))
    tree = make_model(arch).abstract_params()
    routed = sum(w.size for w in jax.tree.leaves(
        {k: tree["blocks"]["moe"][k] for k in ("wg", "wu", "wd")}))
    outside = sum(w.size for w in jax.tree.leaves(tree)) - routed
    assert sum(work.weight_params(conf).values()) == outside == 1565270400
    ssm = make_model(arch).cache_specs(1, 8)
    per_slot = 2 * sum(ssm[k].size * ssm[k].dtype.itemsize for k in ("ssm", "conv"))
    assert work.state_bytes_per_slot(conf) == per_slot
    assert work.decode_least_bytes(conf, 8) == 2 * outside + 8 * per_slot


TRAFFIC = {"kind": "open_loop_serve", "arrival": {"cv": 2.0}, "arrangement_seed": 1,
           "prompt": {"median": 16, "sigma": 0.8, "min": 8, "max": 48, "grid": 8},
           "output": {"median": 6, "sigma": 0.8, "min": 4, "max": 12, "grid": 1}}


def test_sound_hybrid_serve_run_is_correct():
    """The open-loop driver end to end on the CPU at smoke size, past the
    chip check, with two expert shards cut through ``arch_overrides``: a
    sound run is correct, and the control is far past its limit."""
    conf = dict(_conf(2), name="granite-smoke-half", arch="granite-4.0-h-small-smoke",
                family="hybrid", arch_overrides={"expert_shards": 2},
                serving={"capacity": 2, "max_seq": 64, "max_queue": 64})
    cell = harness.Cell("granite-smoke-half.chat", 1, conf, TRAFFIC,
                        {"rate_rps": 4.0, "widest_logit_gap_limit": ATOL}, [], [])
    out, checks, correct, _, setup_s = drv.run(
        cell, seed=2**33 + 5, seconds=2.0, trace=False, t_start=time.time(),
        clog=harness.CompileLog(), check_chip=False)
    assert correct, checks
    assert checks["widest_logit_gap"]["tokens"] > 0
    assert out.failed == 0 and out.window_compiles == 0
    assert out.counters["decode_steps"] > 0 and setup_s > 0


def test_roofline_reader_reads_its_one_cell_and_refuses_two(monkeypatch):
    """The roofline reader takes its configuration from the one cell its
    ``BENCHMARK.json`` entry lists, and raises rather than measure a
    second cell against the first one's bytes."""
    reader = harness.metric_reader("decode_roofline_share.serve")
    conf = reader._config()
    assert conf["arch"] == "granite-4.0-h-small"
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    metric = next(m for m in spec["per_layer"] if m["name"] == reader.NAME)
    metric["workloads"] = metric["workloads"] + ["stablelm-1.6b.serve-chat"]
    load = harness.load_json
    monkeypatch.setattr(harness, "load_json", lambda p: (
        spec if Path(p).name == "BENCHMARK.json" else load(p)))
    with pytest.raises(RuntimeError, match="lists 2"):
        reader._config()
