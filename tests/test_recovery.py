"""Durable control plane: WAL journal, crash-recovery replay, idempotent
submission (the FfDL resiliency pillar — stateless services over durable
metadata; a dead control plane is a restart, not a data loss)."""
import json
import threading
import time
import zlib

import pytest

from repro.platform.faults import FaultEvent
from repro.platform.journal import Journal
from repro.platform.zookeeper import (ConnectionLoss, NoNodeError,
                                      ZooKeeper, zk_retry)
from repro.service.core import DLaaSCore
from util_poll import wait_until

MANIFEST = """
name: parity
learners: 1
gpus: 1
memory: 512MiB
steps: 300
lr: 0.2
checkpoint_every: 50
framework:
  name: repro-mlp
  d_in: 16
  n_classes: 4
"""


# --------------------------------------------------------------- journal
def test_journal_roundtrip(tmp_path):
    j = Journal(str(tmp_path / "j"))
    j.append({"seq": 0, "op": "create", "path": "/a", "data": "1"})
    j.append({"seq": 1, "op": "set", "path": "/a", "data": "2"})
    j.close()
    snap, records, dropped = Journal(str(tmp_path / "j")).load()
    assert snap is None and dropped == 0
    assert [r["seq"] for r in records] == [0, 1]


def test_journal_torn_tail_dropped_and_truncated(tmp_path):
    j = Journal(str(tmp_path / "j"))
    for i in range(3):
        j.append({"seq": i, "op": "set", "path": "/a", "data": str(i)})
    j.close()
    # simulate a crash mid-append: half a record, no trailing newline
    with open(j.log_path, "a") as fh:
        fh.write("deadbeef {\"seq\": 3, \"op\"")
    j2 = Journal(str(tmp_path / "j"))
    snap, records, dropped = j2.load()
    assert [r["seq"] for r in records] == [0, 1, 2]
    assert dropped == 1
    # the torn bytes were truncated away: appends stay readable
    j2.append({"seq": 3, "op": "set", "path": "/a", "data": "3"})
    j2.close()
    _, records, dropped = Journal(str(tmp_path / "j")).load()
    assert [r["seq"] for r in records] == [0, 1, 2, 3]
    assert dropped == 0


def test_journal_crc_corruption_stops_scan(tmp_path):
    j = Journal(str(tmp_path / "j"))
    for i in range(4):
        j.append({"seq": i, "op": "set", "path": "/a", "data": str(i)})
    j.close()
    lines = j.log_path.read_text().splitlines(keepends=True)
    lines[1] = lines[1].replace("seq", "sXq", 1)   # payload no longer
    j.log_path.write_text("".join(lines))          # matches its crc
    _, records, dropped = Journal(str(tmp_path / "j")).load()
    # everything after the corrupt record is unordered wrt the mutation
    # stream — replay keeps only the prefix
    assert [r["seq"] for r in records] == [0]
    assert dropped == 1


def test_journal_snapshot_dedups_by_seq(tmp_path):
    """A crash between snapshot-publish and log-truncation must not
    double-apply: records folded into the snapshot are filtered out."""
    j = Journal(str(tmp_path / "j"))
    for i in range(5):
        j.append({"seq": i, "op": "set", "path": "/a", "data": str(i)})
    # publish a snapshot covering seq<=2, but keep the old log intact
    # (as if the truncation step never ran)
    payload = json.dumps({"last_seq": 2, "tree": {}},
                         sort_keys=True, separators=(",", ":"))
    j.snap_path.write_text(json.dumps(
        {"crc": zlib.crc32(payload.encode()), "state": payload}))
    snap, records, _ = Journal(str(tmp_path / "j")).load()
    assert snap["last_seq"] == 2
    assert [r["seq"] for r in records] == [3, 4]


# ----------------------------------------------------------- zk + journal
def test_zk_replay_rebuilds_tree(tmp_path):
    zk = ZooKeeper(journal=str(tmp_path / "j"))
    zk.create("/a/b", b"hello", makepath=True)
    zk.set("/a/b", b"world")
    zk.create("/a/seq-", b"s", sequential=True)
    zk.increment("/ctr", 7)
    s = zk.session()
    zk.create("/a/alive", b"", ephemeral=True, session=s, makepath=True)
    zk.create("/gone", b"", makepath=True)
    zk.delete("/gone")
    zk.detach_journal()

    zk2 = ZooKeeper(journal=str(tmp_path / "j"))
    assert zk2.get("/a/b")[0] == b"world"
    assert zk2.get("/ctr")[0] == b"7"
    assert not zk2.exists("/gone")
    # ephemerals die with their session — the recovered process has none
    assert not zk2.exists("/a/alive")
    # sequential counter continuity: no collision with the replayed node
    p = zk2.create("/a/seq-", b"s2", sequential=True)
    assert p.rsplit("/", 1)[1] not in ("seq-0000000000",)
    zk2.detach_journal()


def test_zk_snapshot_compaction_roundtrip(tmp_path):
    zk = ZooKeeper(journal=Journal(str(tmp_path / "j"), compact_every=5))
    for i in range(12):
        zk.create(f"/n{i}", str(i).encode(), makepath=True)
    zk.detach_journal()
    zk2 = ZooKeeper(journal=str(tmp_path / "j"))
    assert zk2.journal_stats["snapshot"] == 1
    for i in range(12):
        assert zk2.get(f"/n{i}")[0] == str(i).encode()
    zk2.detach_journal()


def test_binary_data_survives_replay(tmp_path):
    blob = bytes(range(256))
    zk = ZooKeeper(journal=str(tmp_path / "j"))
    zk.create("/bin", blob, makepath=True)
    zk.detach_journal()
    zk2 = ZooKeeper(journal=str(tmp_path / "j"))
    assert zk2.get("/bin")[0] == blob
    zk2.detach_journal()


# ------------------------------------------------------ quorum resilience
def test_zk_retry_rides_out_transient_loss():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ConnectionLoss("quorum lost")
        return "ok"

    naps = []
    assert zk_retry(flaky, sleep=naps.append) == "ok"
    assert len(naps) == 2
    assert naps[1] > naps[0]            # exponential

    with pytest.raises(ConnectionLoss):
        zk_retry(lambda: (_ for _ in ()).throw(ConnectionLoss("down")),
                 retries=3, sleep=lambda s: None)


def test_tick_paths_survive_quorum_loss_and_recovery():
    """Watchdog heartbeats and LCM reads keep working across a quorum
    outage shorter than the retry budget: 2/3 replicas die, a healer
    thread restores one, and the in-flight writes land."""
    from repro.platform.cluster import (Cluster, Node, Resources,
                                        Scheduler)
    from repro.platform.lcm import LifecycleManager
    from repro.platform.watchdog import Watchdog

    zk = ZooKeeper(replicas=3)
    cluster = Cluster([Node("n0", Resources(cpus=8, gpus=2,
                                            memory_mb=4096))])
    lcm = LifecycleManager(zk, Scheduler(cluster))
    wd = Watchdog(zk, "job-q", "learner-0")
    wd.heartbeat(1)

    zk.kill_replica(0)
    zk.kill_replica(1)                  # majority gone: writes fail
    healer = threading.Timer(0.15, lambda: zk.restore_replica(0))
    healer.start()
    wd.heartbeat(2)                     # blocks in zk_retry, then lands
    wd.set_status("RUNNING")
    assert lcm.member_statuses("job-q")["learner-0"]["heartbeat"][
        "step"] == 2
    healer.join()


# ------------------------------------------------- end-to-end crash drill
def _wait_terminal(core, tid, timeout=90):
    assert wait_until(
        lambda: core.lcm.job_state(tid) in ("COMPLETED", "FAILED"),
        timeout=timeout), f"job stuck in {core.lcm.job_state(tid)}"
    return core.lcm.job_state(tid)


@pytest.mark.slow
def test_crash_recovery_drill_with_loss_parity(tmp_path):
    """The acceptance drill: SIGKILL-equivalent core teardown
    mid-training, a fresh DLaaSCore on the same workdir replays the
    journal, the job completes via checkpoint-resume with the SAME final
    loss as an uninterrupted same-seed run, billing carries over, and a
    replayed Idempotency-Key returns the original ids."""
    # --- uninterrupted baseline (same seed == same manifest)
    base = DLaaSCore(workdir=str(tmp_path / "base"))
    mid = base.deploy_model(MANIFEST)["model_id"]
    tid = base.create_training(mid, user="alice")["training_id"]
    assert _wait_terminal(base, tid) == "COMPLETED"
    base_loss = base.training_status(tid)["last_loss"]
    base.close()

    # --- crash run: core dies (via the chaos-drill event) at step 120
    wd = str(tmp_path / "crash")
    c1 = DLaaSCore(workdir=wd)
    mid1 = c1.deploy_model(MANIFEST, idempotency_key="dep-1")["model_id"]
    tid1 = c1.create_training(mid1, user="alice",
                              idempotency_key="sub-1")["training_id"]
    c1.inject_faults(events=[FaultEvent("crash_core", "",
                                        at_step=120, job_id=tid1)])
    assert wait_until(lambda: c1.crashed, timeout=60), "crash never fired"
    pre_usage = dict(c1.usage)
    pre_gpu_s = c1.scheduler.tenant_snapshots().get(
        "alice", {}).get("gpu_seconds", 0.0)

    # --- recovery: same workdir, fresh core
    c2 = DLaaSCore(workdir=wd)
    rep = c2.recovery_report()
    assert rep["recovered"]
    assert tid1 in (rep["trainings"]["resumed"]
                    + rep["trainings"]["requeued"])
    assert rep["trainings"]["abandoned"] == []
    # billing never resets: metering + tenant gpu-seconds carried over
    assert c2.usage == pre_usage
    post_gpu_s = c2.scheduler.tenant_snapshots().get(
        "alice", {}).get("gpu_seconds", 0.0)
    assert post_gpu_s >= pre_gpu_s - 1e-6
    # replayed keys return the ORIGINAL ids — no duplicate, no re-bill
    assert c2.deploy_model(MANIFEST,
                           idempotency_key="dep-1")["model_id"] == mid1
    assert c2.create_training(mid1, user="alice",
                              idempotency_key="sub-1")[
        "training_id"] == tid1
    assert c2.usage == pre_usage        # replay is not metered
    assert len(c2.list_trainings()) == 1

    # --- the job completes via checkpoint-resume with loss parity
    assert _wait_terminal(c2, tid1) == "COMPLETED"
    loss = c2.training_status(tid1)["last_loss"]
    assert loss == pytest.approx(base_loss, rel=1e-6), \
        (loss, base_loss)
    # recovery counters landed in MetricsService
    counters = c2.metrics.counters("platform")
    assert counters["recoveries_total"] >= 1
    assert counters["recovery_journal_records"] > 0
    c2.close()


@pytest.mark.slow
def test_endpoint_redeploys_after_crash(tmp_path):
    """A READY endpoint returns to READY on the recovered core and
    answers a predict."""
    wd = str(tmp_path / "w")
    c1 = DLaaSCore(workdir=wd)
    eid = c1.deploy_endpoint(arch="stablelm-1.6b-smoke", user="bob",
                             idempotency_key="ep-1")["endpoint_id"]
    assert wait_until(
        lambda: c1.endpoint_status(eid)["state"] == "READY", timeout=60)
    out1 = c1.predict(eid, [1, 2, 3], max_new=4)
    c1.crash()

    c2 = DLaaSCore(workdir=wd)
    assert eid in c2.recovery_report()["endpoints"]["redeployed"]
    assert wait_until(
        lambda: c2.endpoint_status(eid)["state"] == "READY", timeout=60)
    out2 = c2.predict(eid, [1, 2, 3], max_new=4)
    assert out2["tokens"]
    # same weights (fresh-init arch endpoints re-seed identically)
    assert out2["tokens"] == out1["tokens"]
    # replaying the deploy returns the original endpoint, not a second
    assert c2.deploy_endpoint(arch="stablelm-1.6b-smoke", user="bob",
                              idempotency_key="ep-1")[
        "endpoint_id"] == eid
    assert len(c2.endpoints) == 1
    c2.close()


def test_idempotent_submission_no_duplicates(tmp_path):
    """Same key == same job, exactly one submission, exactly one bill —
    and a NEW key still creates a new job."""
    core = DLaaSCore(workdir=str(tmp_path / "w"))
    mid = core.deploy_model(MANIFEST)["model_id"]
    r1 = core.create_training(mid, user="alice", idempotency_key="k")
    usage_after_first = core.usage["alice"]
    r2 = core.create_training(mid, user="alice", idempotency_key="k")
    assert r2["training_id"] == r1["training_id"]
    assert core.usage["alice"] == usage_after_first
    assert len(core.list_trainings()) == 1
    r3 = core.create_training(mid, user="alice", idempotency_key="k2")
    assert r3["training_id"] != r1["training_id"]
    assert core.metrics.counters("platform")[
        "idempotent_replays_total"] >= 1
    for tid in (r1["training_id"], r3["training_id"]):
        _wait_terminal(core, tid)
    core.close()


def test_rest_api_recovery_and_idempotency_header(tmp_path):
    """Idempotency-Key rides the HTTP header; GET /v1/recovery reports."""
    import urllib.request
    from repro.service.rest import DLaaSServer

    def req(url, method="GET", body=None, key=None):
        data = json.dumps(body).encode() if body is not None else None
        r = urllib.request.Request(url, data=data, method=method)
        r.add_header("Authorization", "Bearer alice")
        if key:
            r.add_header("Idempotency-Key", key)
        if data:
            r.add_header("Content-Type", "application/json")
        with urllib.request.urlopen(r) as resp:
            return json.loads(resp.read())

    with DLaaSServer(str(tmp_path / "w")) as srv:
        out = req(f"{srv.url}/v1/models", "POST",
                  {"manifest": MANIFEST}, key="m-1")
        out2 = req(f"{srv.url}/v1/models", "POST",
                   {"manifest": MANIFEST}, key="m-1")
        assert out2["model_id"] == out["model_id"]
        t1 = req(f"{srv.url}/v1/trainings", "POST",
                 {"model_id": out["model_id"]}, key="t-1")
        t2 = req(f"{srv.url}/v1/trainings", "POST",
                 {"model_id": out["model_id"]}, key="t-1")
        assert t2["training_id"] == t1["training_id"]
        rec = req(f"{srv.url}/v1/recovery")
        assert rec == {"recovered": False}
        _wait_terminal(srv.core, t1["training_id"])


def test_recovery_settles_pending_idempotency_keys(tmp_path):
    """A key left 'pending' by a crash completes on recovery when its
    job record landed, and is dropped when it did not — the client retry
    either replays or cleanly resubmits, never duplicates."""
    wd = str(tmp_path / "w")
    c1 = DLaaSCore(workdir=wd)
    mid = c1.deploy_model(MANIFEST)["model_id"]
    tid = c1.create_training(mid, user="alice",
                             idempotency_key="settled")["training_id"]
    # forge the crash window: reservation durable, completion lost
    # (crash between launch and _idem_complete) ...
    c1.zk.set(c1._idem_path("settled"), json.dumps(
        {"key": "settled", "kind": "training", "id": tid,
         "status": "pending"}).encode())
    # ... and one whose job record never landed at all
    c1.zk.create(c1._idem_path("orphan"), json.dumps(
        {"key": "orphan", "kind": "training", "id": "training-99999",
         "status": "pending"}).encode(), makepath=True)
    c1.crash()

    c2 = DLaaSCore(workdir=wd)
    idem = c2.recovery_report()["idempotency"]
    assert idem["completed"] == 1 and idem["dropped"] == 1
    assert c2.create_training(mid, user="alice",
                              idempotency_key="settled")[
        "training_id"] == tid
    with pytest.raises(NoNodeError):
        c2.zk.get(c2._idem_path("orphan"))
    _wait_terminal(c2, tid)
    c2.close()
