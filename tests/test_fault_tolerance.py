"""End-to-end fault tolerance (paper §Fault-Tolerance):
learner crash -> scheduler restart -> resume from checkpoint;
storage transient failures -> exponential backoff; ZK quorum;
chaos drills (node kill/drain under training and serving)."""
import time

import numpy as np
import pytest

from repro.core.cursor import GlobalCursor
from repro.core.software_ps import SoftwareParameterServer
from repro.platform.cluster import (Cluster, Node, Resources, RUNNING,
                                    Scheduler)
from repro.platform.faults import FaultEvent, FaultInjector, FaultSchedule, KILL
from repro.platform.lcm import JobSpec, LifecycleManager
from repro.platform.metrics import MetricsService
from repro.platform.storage import (LocalFSStore, ObjectStore,
                                    StorageManager, TransientError,
                                    with_backoff)
from repro.platform.zookeeper import ZooKeeper
from repro.runtime.learner import LearnerJobConfig, make_learner_body
from repro.service.core import DLaaSCore
from util_poll import wait_until


def _stack(tmp_path):
    zk = ZooKeeper()
    cluster = Cluster([Node(f"n{i}", Resources(cpus=8, gpus=4,
                                               memory_mb=32000))
                       for i in range(3)])
    sched = Scheduler(cluster)
    lcm = LifecycleManager(zk, sched)
    storage = StorageManager()
    storage.register("results", LocalFSStore(str(tmp_path / "results")))
    metrics = MetricsService()
    return zk, sched, lcm, storage, metrics


def _drive(sched, lcm, job_id, timeout=60.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        sched.tick()
        st = lcm.monitor(job_id)
        if st in ("COMPLETED", "FAILED", "KILLED"):
            return st
        time.sleep(0.02)
    return lcm.job_state(job_id)


def test_crash_restart_resumes_from_checkpoint(tmp_path):
    zk, sched, lcm, storage, metrics = _stack(tmp_path)
    cfg = LearnerJobConfig(
        job_id="ft1", framework="repro-mlp",
        framework_cfg={"d_in": 16, "n_classes": 4},
        n_learners=2, steps=40, lr=0.3, checkpoint_every=10,
        checkpoint_dir=str(tmp_path / "ckpt"),
        fail_at_step={0: 17})           # learner 0 crashes at step 17
    import jax
    from jax.flatten_util import ravel_pytree
    from repro.runtime.learner import PLUGINS
    plugin = PLUGINS["repro-mlp"](cfg.framework_cfg)
    flat0, _ = ravel_pytree(plugin.init_params(0))
    ps = SoftwareParameterServer(np.asarray(flat0), n_shards=4,
                                 n_learners=2, optimizer="sgd", lr=0.3)
    cursor = GlobalCursor(zk, "/jobs/ft1/cursor", dataset_size=512)
    results = {}
    body = make_learner_body(cfg, ps, cursor, storage, metrics, results)
    spec = JobSpec(job_id="ft1", learners=2, learner_body=body,
                   ps_body=lambda wd: None)
    lcm.submit(spec)

    st = _drive(sched, lcm, "ft1", timeout=90)
    assert st == "COMPLETED"
    app = sched.apps["ft1-learners"]
    assert any(t.restarts > 0 for t in app.tasks.values()), \
        "the injected crash must have caused a restart"
    # learner-0 resumed from a checkpoint, not step 0: its post-restart log
    logs_touched = metrics.series("ft1", "loss").steps
    assert max(logs_touched) >= 39
    ev = metrics.events("ft1", "checkpoint")
    assert ev, "checkpoints were persisted"
    # trained model uploaded despite the crash
    data = storage.download("results", "ft1", "trained_model.npy")
    assert len(data) > 0


def test_user_error_fails_job_without_restart(tmp_path):
    zk, sched, lcm, storage, metrics = _stack(tmp_path)
    cfg = LearnerJobConfig(
        job_id="ft2", framework="repro-mlp",
        framework_cfg={"d_in": 8, "n_classes": 2},
        n_learners=1, steps=20, user_error_at=3,
        checkpoint_dir=None)
    from jax.flatten_util import ravel_pytree
    from repro.runtime.learner import PLUGINS
    plugin = PLUGINS["repro-mlp"](cfg.framework_cfg)
    flat0, _ = ravel_pytree(plugin.init_params(0))
    ps = SoftwareParameterServer(np.asarray(flat0), n_shards=2,
                                 n_learners=1, optimizer="sgd", lr=0.1)
    cursor = GlobalCursor(zk, "/jobs/ft2/cursor", dataset_size=128)
    body = make_learner_body(cfg, ps, cursor, storage, metrics)
    lcm.submit(JobSpec(job_id="ft2", learners=1, learner_body=body))
    st = _drive(sched, lcm, "ft2", timeout=30)
    assert st == "FAILED"
    app = sched.apps["ft2-learners"]
    assert all(t.restarts == 0 for t in app.tasks.values())


# ---------------------------------------------------------------------------
# chaos acceptance: seeded fault injection against live jobs
# ---------------------------------------------------------------------------


class _Throttled:
    """Watchdog proxy that slows the learner to one step per ``delay``
    seconds, so the scheduler gets many ticks inside the training window
    and a step-triggered fault always lands on a RUNNING job."""

    def __init__(self, wd, delay):
        self._wd = wd
        self._delay = delay

    def heartbeat(self, *a, **kw):
        time.sleep(self._delay)
        return self._wd.heartbeat(*a, **kw)

    def __getattr__(self, name):
        return getattr(self._wd, name)


def test_chaos_kill_node_under_ps_learners_mid_round(tmp_path):
    """Kill the node hosting the software-PS learners mid-BSP-round
    (step-progress trigger through the LCM hook). The learners are
    requeued, resume from the last checkpoint on another node and the
    job completes with no lost steps and the model uploaded."""
    zk, sched, lcm, storage, metrics = _stack(tmp_path)
    cfg = LearnerJobConfig(
        job_id="chaos1", framework="repro-mlp",
        framework_cfg={"d_in": 16, "n_classes": 4},
        n_learners=2, steps=40, lr=0.3, checkpoint_every=5,
        checkpoint_dir=str(tmp_path / "ckpt"))
    from jax.flatten_util import ravel_pytree
    from repro.runtime.learner import PLUGINS
    plugin = PLUGINS["repro-mlp"](cfg.framework_cfg)
    flat0, _ = ravel_pytree(plugin.init_params(0))
    ps = SoftwareParameterServer(np.asarray(flat0), n_shards=4,
                                 n_learners=2, optimizer="sgd", lr=0.3)
    cursor = GlobalCursor(zk, "/jobs/chaos1/cursor", dataset_size=512)
    inner = make_learner_body(cfg, ps, cursor, storage, metrics)
    body = lambda wd, idx: inner(_Throttled(wd, 0.01), idx)

    # deterministic placement: the PS app then both learners best-fit
    # onto n0, so the schedule can name its victim up front
    sched.faults = FaultInjector(FaultSchedule([
        FaultEvent(KILL, "n0", at_step=15, job_id="chaos1")]),
        lcm=lcm, metrics=metrics)
    lcm.submit(JobSpec(job_id="chaos1", learners=2, learner_body=body,
                       ps_body=lambda wd: None))
    st = _drive(sched, lcm, "chaos1", timeout=120)
    assert st == "COMPLETED"
    assert sched.faults.done() and sched.faults.fired[0]["applied"]
    assert not sched.cluster.nodes["n0"].alive
    app = sched.apps["chaos1-learners"]
    assert any(t.restarts > 0 for t in app.tasks.values()), \
        "the node kill must have restarted the learners"
    # checkpoint-resume, no lost work: the final step was reached and
    # the trained model was uploaded despite the mid-round kill
    assert max(metrics.series("chaos1", "loss").steps) >= cfg.steps - 1
    assert metrics.events("chaos1", "checkpoint")
    assert len(storage.download("results", "chaos1",
                                "trained_model.npy")) > 0
    assert metrics.counters("cluster").get("faults_kill") == 1


CHAOS_PJIT_MANIFEST = """
name: chaos-pjit
learners: 1
gpus: 2
steps: 60
checkpoint_every: 10
lr: 0.1
optimizer: sgd
seed: 0
batch_docs: 4
data:
  n_docs: 128
  seq_len: 16
framework:
  name: repro-lm
  arch: stablelm-1.6b-smoke
  distribution: pjit
"""


def test_chaos_drain_node_under_pjit_gang(tmp_path):
    """Drain the node under a running pjit gang: the whole gang is
    requeued like a preemption, re-places on the remaining node, restores
    its checkpoint and completes — the elastic shrink path end-to-end."""
    cluster = Cluster([Node(f"g{i}", Resources(cpus=16, gpus=2,
                                               memory_mb=64000))
                       for i in range(2)])
    core = DLaaSCore(str(tmp_path), cluster=cluster)
    try:
        mid = core.deploy_model(CHAOS_PJIT_MANIFEST)["model_id"]
        tid = core.create_training(mid)["training_id"]
        assert wait_until(
            lambda: core.metrics.checkpoints(tid)
            and core.training_status(tid)["steps_done"] >= 20,
            timeout=120), "no mid-training checkpoint in time"
        core.pause_training(tid)       # hold the gang at a step boundary
        app = core.scheduler.apps[f"{tid}-workers"]
        victim = next(t.node for t in app.tasks.values()
                      if t.state == RUNNING)
        core.drain_node(victim)
        # the re-placed gang restores the checkpoint on the other node
        assert wait_until(
            lambda: any("resumed from checkpoint" in l
                        for l in core.training_logs(tid)),
            timeout=120), "drained pjit gang did not resume"
        assert all(t.node != victim for t in app.tasks.values())
        core.resume_training(tid)
        assert core.wait_for(tid, timeout=240) == "COMPLETED"
        assert core.training_status(tid)["steps_done"] >= 60
        # the drained node ended up cordoned and fully freed
        n = core.cluster.nodes[victim]
        assert n.draining and n.free.gpus == n.capacity.gpus
        assert len(core.download_model(tid)) > 0
    finally:
        core.close()


def test_chaos_kill_serving_node_mid_request(tmp_path):
    """Kill the node under a serving endpoint while a request is in
    flight: the engine re-queues the request, the endpoint gang
    reincarnates on the surviving node and the request completes —
    zero lost requests."""
    cluster = Cluster([Node(f"s{i}", Resources(cpus=16, gpus=1,
                                               memory_mb=64000))
                       for i in range(2)])
    core = DLaaSCore(str(tmp_path), cluster=cluster)
    try:
        eid = core.deploy_endpoint(arch="stablelm-1.6b-smoke")["endpoint_id"]
        assert wait_until(
            lambda: core.endpoint_status(eid)["state"] == "READY",
            timeout=120), "endpoint never became READY"
        core.predict(eid, [1, 2, 3], max_new=2)        # warm the jits
        core.pause_training(eid)       # hold serving at a batch boundary
        req = core.endpoints[eid].engine.submit([4, 5, 6], max_new=2)
        app = core.scheduler.apps[f"{eid}-servers"]
        victim = next(t.node for t in app.tasks.values()
                      if t.state == RUNNING)
        core.inject_faults(events=[
            FaultEvent(KILL, victim, at_tick=core.cluster.clock + 1)])
        # server task reincarnates on the surviving node
        assert wait_until(
            lambda: any(t.state == RUNNING and t.node != victim
                        for t in app.tasks.values()),
            timeout=60), "endpoint was not re-placed after the kill"
        core.resume_training(eid)
        assert req.wait(120) and req.status == "DONE", req.status
        assert core.scheduler.faults.done()
        assert core.endpoint_status(eid)["state"] == "READY"
        core.stop_endpoint(eid)
    finally:
        core.close()


def test_objectstore_backoff_retries(tmp_path):
    store = ObjectStore(str(tmp_path / "os"))
    store.put("c", "k", b"v")
    store.inject_failures(3)
    sleeps = []
    out = with_backoff(lambda: store.get("c", "k"), retries=5,
                       sleep=sleeps.append)
    assert out == b"v"
    assert len(sleeps) == 3
    assert sleeps == sorted(sleeps)          # exponential growth
    store.inject_failures(10)
    with pytest.raises(TransientError):
        with_backoff(lambda: store.get("c", "k"), retries=2,
                     sleep=sleeps.append)


def test_objectstore_auth(tmp_path):
    from repro.platform.storage import AuthError
    store = ObjectStore(str(tmp_path / "os2"),
                        credentials={"alice": "pw"})
    with pytest.raises(AuthError):
        store.put("c", "k", b"v")
    store.authenticate("alice", "pw")
    store.put("c", "k", b"v")
    assert store.get("c", "k") == b"v"
    with pytest.raises(AuthError):
        store.authenticate("alice", "wrong")
