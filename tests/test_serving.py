"""Serving subsystem: continuous-batching correctness (mid-flight join
token-identical to sequential decode), admission queue overflow +
deadlines, and the managed endpoint lifecycle through the control plane."""
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import resolve_arch
from repro.platform.cluster import UserError
from repro.serving.engine import (EndpointClosed, InferenceEngine,
                                  QueueFull)
from util_poll import assert_holds_for, wait_until

ARCH = "stablelm-1.6b-smoke"
MAX_SEQ = 32


@pytest.fixture(scope="module")
def cfg():
    return resolve_arch(ARCH)


@pytest.fixture(scope="module")
def engine(cfg):
    eng = InferenceEngine(cfg, capacity=2, max_seq=MAX_SEQ, max_queue=16,
                          default_max_new=6, endpoint_id="ep-test")
    eng.start(None)
    return eng


def _serve(eng, reqs, timeout=180.0):
    t = threading.Thread(target=eng.run, daemon=True)
    t.start()
    for r in reqs:
        assert r.wait(timeout), f"request {r.req_id} stuck: {r.status}"
    eng.drain()
    t.join(20)
    assert not t.is_alive()
    return reqs


def _sequential_reference(model, params, prompt, max_new):
    """Greedy B=1 decode with the plain model functions and a scalar
    position — the oracle a mid-flight-joined request must match token
    for token."""
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode)
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompt)[None]})
    # each leaf padded out to the shape the cache specs give it at
    # MAX_SEQ: a KV cache along its sequence axis, a state not at all
    specs = model.cache_specs(1, MAX_SEQ)
    cache = {k: v if k == "pos" else jnp.pad(
        v, [(0, want - have) for have, want in zip(v.shape, specs[k].shape)])
        for k, v in cache.items()}
    toks = [int(jnp.argmax(logits[0, -1]))]
    while len(toks) < max_new:
        logits, cache = decode(
            params, cache,
            {"tokens": jnp.asarray([[toks[-1]]], dtype=jnp.int32)})
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks


# ---------------------------------------------------------------------------
# continuous-batching correctness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", [ARCH, "mamba2-1.3b-smoke"])
def test_midflight_join_token_identical(arch):
    """5 requests over 2 slots with staggered lengths: 3 of them join
    mid-flight into freed slots. Every output must be token-identical
    to decoding that request alone (same seed, greedy)."""
    cfg = resolve_arch(arch)
    engine = InferenceEngine(cfg, capacity=2, max_seq=MAX_SEQ,
                             default_max_new=6, endpoint_id="ep-join")
    engine.start(None)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, size=8).astype(np.int32)
               for _ in range(5)]
    max_news = [3, 6, 4, 5, 7]          # staggered retirement → joins
    reqs = [engine.submit(p, max_new=m)
            for p, m in zip(prompts, max_news)]
    _serve(engine, reqs)
    stats = engine.stats()
    # with 5 requests on 2 slots the engine must actually have batched
    assert stats["mean_batch_occupancy"] > 0.5
    for p, m, r in zip(prompts, max_news, reqs):
        assert r.status == "DONE"
        assert len(r.tokens) == m
        ref = _sequential_reference(engine.model, engine.params, p, m)
        assert r.tokens == ref, (r.tokens, ref)


def test_decode_step_writes_only_each_slots_new_row(cfg):
    """One decode step over slots at different depths changes, in every
    layer, exactly row pos[b] of slot b's K and V, live or free, and bit
    for bit nothing else; each live slot's token is the oracle's."""
    eng = InferenceEngine(cfg, capacity=3, max_seq=MAX_SEQ,
                          endpoint_id="ep-rows")
    eng.start(None)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 11)]
    reqs = [eng.submit(p, max_new=4) for p in prompts]
    while (batch := eng._take_batch()) is not None:   # slot 2 stays free
        eng._admit(*batch)
    before = jax.tree.map(np.asarray, eng._cache)
    pos = before["pos"]
    assert list(pos) == [5, 11, 0]
    eng._decode_once()
    after = jax.tree.map(np.asarray, eng._cache)
    np.testing.assert_array_equal(after["pos"], pos + 1)
    for key in ("k", "v"):
        changed = np.any(before[key] != after[key], axis=-1)  # (L, slot, S)
        want = np.zeros_like(changed)
        want[:, np.arange(eng.capacity), pos] = True
        np.testing.assert_array_equal(changed, want)
    for p, r in zip(prompts, reqs):
        assert r.tokens == _sequential_reference(eng.model, eng.params, p, 2)


def test_stats_say_the_decode_step_runs_in_place(cfg):
    """stats() and status.perf carry the decode program's temporary bytes
    and whether every cache leaf is aliased to its donated input, from
    the background compile that start() schedules."""
    eng = InferenceEngine(cfg, capacity=2, max_seq=MAX_SEQ,
                          endpoint_id="ep-inplace")
    assert eng.stats()["decode_cache_aliased"] is None
    eng.start(None)
    assert wait_until(lambda: eng.perf.state == "ready", timeout=120), \
        eng.perf.error
    st, perf = eng.stats(), eng.perf_status()
    assert st["decode_cache_aliased"] is True
    assert 0 < st["decode_temp_bytes"] < 2 ** 20
    assert perf["decode_cache_aliased"] is True
    assert perf["decode_temp_bytes"] == st["decode_temp_bytes"]


def test_eos_retires_early(cfg):
    """A slot whose argmax hits eos retires before max_new."""
    eng = InferenceEngine(cfg, capacity=1, max_seq=MAX_SEQ,
                          default_max_new=8, endpoint_id="ep-eos")
    eng.start(None)
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, cfg.vocab_size, size=6).astype(np.int32)
    free_run = eng.submit(prompt, max_new=8)
    _serve(eng, [free_run])
    # pick the second generated token as "eos" and rerun: generation
    # must stop right there
    eos = free_run.tokens[1]
    eng2 = InferenceEngine(cfg, capacity=1, max_seq=MAX_SEQ,
                           default_max_new=8, eos_id=eos,
                           endpoint_id="ep-eos2")
    eng2.start(None)
    r = eng2.submit(prompt, max_new=8)
    _serve(eng2, [r])
    assert r.tokens == free_run.tokens[:2]


def test_request_timestamps_and_prefill_span(cfg):
    """Each request carries its queue wait and time to first token; the
    tracer's prefill span ends once the first tokens are on the host;
    no per-step decode event crowds the trace."""
    from repro.observability.trace import Tracer
    tracer = Tracer()
    eng = InferenceEngine(cfg, capacity=1, max_seq=MAX_SEQ,
                          endpoint_id="ep-ts", tracer=tracer)
    eng.start(None)
    rng = np.random.RandomState(5)
    reqs = [eng.submit(rng.randint(0, cfg.vocab_size, size=6)
                       .astype(np.int32), max_new=3) for _ in range(3)]
    _serve(eng, reqs)
    for r in reqs:
        assert r.status == "DONE"
        assert 0 <= r.queue_s <= r.ttft_s <= r.finished_ts - r.submitted
    # one slot: the third request waits for the first two
    assert reqs[2].queue_s > reqs[0].queue_s
    spans = tracer.store.spans(tracer.trace_of("ep-ts"))
    prefills = sorted((s for s in spans if s.name == "prefill"),
                      key=lambda s: s.start)
    assert len(prefills) == len(reqs)
    for sp, r in zip(prefills, reqs):
        assert sp.end >= r.first_token_ts
    by_req = {s.attrs["req_id"]: s for s in spans if s.name == "request"}
    for r in reqs:
        assert by_req[r.req_id].attrs["queue_s"] == r.queue_s
        assert by_req[r.req_id].attrs["ttft_s"] == r.ttft_s
    assert not [s for s in spans if s.name == "decode"]


def test_decode_rate_excludes_idle_time(cfg):
    """decode_rate() divides the steps by the time spent in them, not by
    the time the engine sat idle between them."""
    eng = InferenceEngine(cfg, capacity=1, max_seq=MAX_SEQ,
                          endpoint_id="ep-rate")
    eng.start(None)
    assert eng.decode_rate() is None
    rng = np.random.RandomState(6)

    def serve_one():
        r = eng.submit(rng.randint(0, cfg.vocab_size, size=6)
                       .astype(np.int32), max_new=6)
        assert r.wait(180), r.status

    t = threading.Thread(target=eng.run, daemon=True)
    t.start()
    serve_one()                            # compiles every program used
    steps0 = eng.stats()["decode_steps"]
    decode_s0 = steps0 / eng.decode_rate()
    idle_s = 1.0
    t0 = time.perf_counter()
    serve_one()
    time.sleep(idle_s)                     # no live slot: the engine idles
    serve_one()
    eng.drain()
    t.join(20)
    assert not t.is_alive()
    busy_s = time.perf_counter() - t0 - idle_s
    steps = eng.stats()["decode_steps"]
    assert steps == 3 * steps0
    # host time inside the later steps: none of the idle second in it
    assert 0 < steps / eng.decode_rate() - decode_s0 <= busy_s


def test_program_names(cfg, engine):
    """The engine names its jitted programs itself, so the benchmark's
    trace readers find decode and prefill after any refactor."""
    from repro.serving.engine import DECODE_PROGRAM, PREFILL_PROGRAM
    assert "decode" in DECODE_PROGRAM and "prefill" in PREFILL_PROGRAM
    toks = jnp.zeros((engine.capacity, 1), jnp.int32)
    text = engine._decode.lower(engine.params, engine._cache, toks).as_text()
    assert f"module @jit_{DECODE_PROGRAM} " in text
    text = engine._prefill.lower(
        engine.params, {"tokens": jnp.zeros((1, 8), jnp.int32)}).as_text()
    assert f"module @jit_{PREFILL_PROGRAM} " in text


# ---------------------------------------------------------------------------
# admission queue: overflow + deadlines
# ---------------------------------------------------------------------------


def test_admission_queue_overflow(cfg):
    eng = InferenceEngine(cfg, capacity=1, max_seq=MAX_SEQ, max_queue=2,
                          default_max_new=2, endpoint_id="ep-q")
    # engine not running: submissions pile up in the bounded queue
    p = np.arange(4, dtype=np.int32) + 1
    eng.submit(p)
    eng.submit(p)
    with pytest.raises(QueueFull):
        eng.submit(p)
    st = eng.stats()
    assert st["rejected_total"] == 1
    assert st["queue_depth"] == 2
    assert st["requests_total"] == 3


def test_deadline_expires_queued_request(cfg):
    eng = InferenceEngine(cfg, capacity=1, max_seq=MAX_SEQ,
                          default_max_new=2, endpoint_id="ep-dl")
    p = np.arange(4, dtype=np.int32) + 1
    req = eng.submit(p, deadline_s=0.01)
    # deadline passes while queued (poll the actual expiry condition)
    assert wait_until(lambda: time.time() > req.deadline, timeout=5)
    eng.start(None)
    t = threading.Thread(target=eng.run, daemon=True)
    t.start()
    assert req.wait(30)
    assert req.status == "EXPIRED"
    assert eng.stats()["expired_total"] == 1
    eng.drain()
    t.join(10)


def test_submit_validation(cfg, engine):
    with pytest.raises(UserError):
        engine.submit([])                          # empty prompt
    with pytest.raises(UserError):
        engine.submit(np.arange(4), max_new=MAX_SEQ)   # exceeds max_seq
    with pytest.raises(UserError):
        engine.submit([cfg.vocab_size + 7])        # out-of-vocab token


def test_encoder_decoder_arch_refused_with_user_error():
    with pytest.raises(UserError, match="whisper-large-v3-smoke"):
        InferenceEngine(resolve_arch("whisper-large-v3-smoke"))


def test_release_frees_buffers_and_fails_queued(cfg):
    eng = InferenceEngine(cfg, capacity=1, max_seq=MAX_SEQ,
                          default_max_new=2, endpoint_id="ep-rel")
    req = eng.submit(np.arange(4, dtype=np.int32) + 1)
    eng.start(None)
    assert eng._cache is not None
    eng.release()
    assert eng._cache is None and eng.params is None
    assert req.status == "FAILED"
    with pytest.raises(EndpointClosed):
        eng.submit([1, 2])


# ---------------------------------------------------------------------------
# endpoint lifecycle through the control plane
# ---------------------------------------------------------------------------

TRAIN_MANIFEST = ("name: serve-src\nlearners: 1\ngpus: 1\nsteps: 3\n"
                  "batch_docs: 2\ncheckpoint_every: 100\n"
                  "data:\n  n_docs: 32\n  seq_len: 16\n"
                  "framework:\n  name: repro-lm\n  arch: stablelm-1.6b-smoke\n")


@pytest.fixture(scope="module")
def core():
    from repro.service.core import DLaaSCore
    c = DLaaSCore(tempfile.mkdtemp(prefix="dlaas_serving_"),
                  tick_interval=0.005)
    yield c
    c.close()


def _wait_state(core, eid, want, timeout=180.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        st = core.endpoint_status(eid)
        if st["state"] == want:
            return st
        time.sleep(0.05)
    raise AssertionError(
        f"endpoint never reached {want}: {core.endpoint_status(eid)}")


def test_endpoint_lifecycle_from_training(core):
    """deploy-from-training answers predicts with trained weights, then
    DRAINING→STOPPED releases buffers and unregisters metrics."""
    mid = core.deploy_model(TRAIN_MANIFEST)["model_id"]
    tid = core.create_training(mid)["training_id"]
    assert core.wait_for(tid, timeout=240) == "COMPLETED"

    out = core.deploy_endpoint(from_training=tid, capacity=2, max_new=4)
    eid = out["endpoint_id"]
    assert out["arch"] == "stablelm-1.6b-smoke"
    _wait_state(core, eid, "READY")

    rng = np.random.RandomState(0)
    res = [core.predict(eid, rng.randint(0, 100, size=8), max_new=4)
           for _ in range(3)]
    for r in res:
        assert len(r["tokens"]) == 4
        assert 0 <= r["queue_s"] <= r["ttft_s"] <= r["latency_s"]
    # the endpoint serves the *trained* weights, deterministically:
    # the same prompt through a second from-training endpoint matches
    again = core.predict(eid, np.arange(5) + 1, max_new=3)["tokens"]
    assert core.predict(eid, np.arange(5) + 1,
                        max_new=3)["tokens"] == again

    st = core.endpoint_status(eid)
    assert st["state"] == "READY"
    stats = st["stats"]
    assert stats["completed_total"] == 5
    assert stats["rejected_total"] == 0
    assert stats["p50_latency_s"] is not None
    assert stats["mean_batch_occupancy"] > 0

    core.stop_endpoint(eid)
    st = _wait_state(core, eid, "STOPPED")
    # teardown satellite: stats snapshotted, KV buffers freed, metrics
    # unregistered
    assert st["stats"]["completed_total"] == 5
    ep = core.endpoints[eid]
    assert ep.engine.released and ep.engine._cache is None
    assert core.metrics.metrics(eid) == []
    # a stopped endpoint answers no more predicts
    with pytest.raises(EndpointClosed):
        core.predict(eid, [1, 2], max_new=2)


def test_deploy_validation(core):
    with pytest.raises(ValueError):
        core.deploy_endpoint()                       # neither source
    with pytest.raises(ValueError):
        core.deploy_endpoint(arch="no-such-arch")
    with pytest.raises(KeyError):
        core.deploy_endpoint(from_training="training-99999")


def test_endpoint_pause_resume(core):
    """Endpoints share the training lifecycle hooks: pause gates the
    serve loop at a batch-step boundary, resume reopens it."""
    out = core.deploy_endpoint(arch="stablelm-1.6b-smoke", capacity=1,
                               max_new=2)
    eid = out["endpoint_id"]
    _wait_state(core, eid, "READY")
    core.predict(eid, [1, 2, 3], max_new=2)        # warm the jits
    core.pause_training(eid)
    req = core.endpoints[eid].engine.submit([4, 5, 6], max_new=2)
    assert_holds_for(lambda: not req.done.is_set(),
                     desc="paused endpoint must hold the request")
    core.resume_training(eid)
    assert req.wait(60) and req.status == "DONE"
    core.stop_endpoint(eid)
    _wait_state(core, eid, "STOPPED")


def test_endpoint_is_a_metered_job(core):
    """Endpoints flow through the same scheduler/queue as trainings:
    they appear as jobs with a tenant, and admission control rejects
    what the quota can never fit."""
    from repro.platform.queue import QuotaExceeded
    core.register_tenant("svc-team", quota_gpus=1)
    out = core.deploy_endpoint(arch="stablelm-1.6b-smoke", capacity=1,
                               tenant="svc-team", gpus=1, max_new=2)
    eid = out["endpoint_id"]
    assert core.lcm.job_spec(eid).get("tenant") == "svc-team"
    with pytest.raises(QuotaExceeded):
        core.deploy_endpoint(arch="stablelm-1.6b-smoke", tenant="svc-team",
                             gpus=2)
    _wait_state(core, eid, "READY")
    # a second endpoint fits the quota but must wait for the first:
    # it sits QUEUED — and stopping it must actually remove it from
    # the scheduler queue, not just flag the engine draining
    held = core.deploy_endpoint(arch="stablelm-1.6b-smoke", capacity=1,
                                tenant="svc-team", gpus=1,
                                max_new=2)["endpoint_id"]
    assert core.endpoint_status(held)["state"] == "DEPLOYING"
    core.stop_endpoint(held)
    _wait_state(core, held, "STOPPED", timeout=30)
    core.stop_endpoint(eid)
    _wait_state(core, eid, "STOPPED")
