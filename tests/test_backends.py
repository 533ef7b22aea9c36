"""Execution-backend layer: parity between software-ps and pjit on the
same manifest + seed, checkpoint restorability, lifecycle hooks, and the
PR-1 preemption acceptance scenario rerun with ``distribution: pjit``."""
import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.checkpoint.checkpoint import CheckpointManager
from repro.platform.cluster import Cluster, Node, Resources
from repro.runtime.backend import BACKENDS, get_backend
from repro.service.core import DLaaSCore
from repro.service.rest import DLaaSServer

PARITY_MANIFEST = """
name: parity-lm
learners: 1
gpus: 1
steps: 25
checkpoint_every: 10
lr: 0.1
optimizer: sgd
seed: 3
batch_docs: 4
data:
  n_docs: 128
  seq_len: 16
framework:
  name: repro-lm
  arch: stablelm-1.6b-smoke
"""


def _req(url, method="GET", body=None, token="tester"):
    data = json.dumps(body).encode() if body is not None else None
    r = urllib.request.Request(url, data=data, method=method)
    r.add_header("Authorization", f"Bearer {token}")
    if data:
        r.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(r) as resp:
        return json.loads(resp.read())


def test_backend_registry():
    assert set(BACKENDS) >= {"software-ps", "pjit"}
    from repro.platform.cluster import UserError
    with pytest.raises(UserError):
        get_backend("horovod")


def test_backend_parity_same_manifest_same_seed(tmp_path):
    """Acceptance: the same manifest + seed trained on both backends
    reaches comparable loss and leaves a restorable checkpoint."""
    finals = {}
    for backend in ("software-ps", "pjit"):
        core = DLaaSCore(str(tmp_path / backend))
        try:
            mid = core.deploy_model(PARITY_MANIFEST)["model_id"]
            out = core.create_training(
                mid, overrides={"distribution": backend})
            assert out["backend"] == backend
            tid = out["training_id"]
            assert core.wait_for(tid, timeout=240) == "COMPLETED"
            status = core.training_status(tid)
            assert status["backend"] == backend
            assert status["steps_done"] >= 25
            rec = core.trainings[tid]
            finals[backend] = rec["results"]["final_loss"]

            # the checkpoint each backend wrote is valid and restorable
            ckpt = CheckpointManager(f"{core.workdir}/ckpt/{tid}")
            last = ckpt.latest_valid()
            assert last is not None
            if backend == "software-ps":
                params = rec["results"]["params"]
                tree, extra = ckpt.restore(
                    last, {"flat": np.zeros_like(params)})
                assert int(extra["step"]) == last
                assert tree["flat"].shape == params.shape
            else:
                # restore through the real elastic path: a fresh Trainer
                from repro.configs.registry import resolve_arch
                from repro.distributed.sharding import Dist
                from repro.optim.optimizers import OptConfig
                from repro.runtime.trainer import Trainer, TrainerConfig
                tc = TrainerConfig(batch=4, seq=16,
                                   ckpt_dir=f"{core.workdir}/ckpt/{tid}",
                                   job_id="probe")
                tr = Trainer(resolve_arch("stablelm-1.6b-smoke"),
                             Dist(), OptConfig(name="sgd", lr=0.1),
                             tc).init(0)
                tr._restore_latest()
                assert tr.step == last
        finally:
            core.close()
    # same model, data, optimizer and seed -> comparable loss
    assert abs(finals["software-ps"] - finals["pjit"]) < 0.2, finals


def test_backend_lifecycle_hooks(tmp_path):
    """checkpoint/pause/resume hooks flow from the backend protocol to
    the running job (observed at step boundaries)."""
    core = DLaaSCore(str(tmp_path))
    try:
        mid = core.deploy_model(
            "name: hooks\nlearners: 1\nsteps: 400\n"
            "checkpoint_every: 100000\n"           # periodic ckpt off
            "framework:\n  name: repro-mlp\n  d_in: 16\n"
            "  n_classes: 4\n")["model_id"]
        tid = core.create_training(mid)["training_id"]
        t0 = time.time()
        while core.training_status(tid)["steps_done"] < 5 \
                and time.time() - t0 < 60:
            time.sleep(0.01)
        assert core.training_status(tid)["steps_done"] >= 5

        core.checkpoint_training(tid)              # on-demand checkpoint
        t0 = time.time()
        while not core.metrics.events(tid, "checkpoint") \
                and time.time() - t0 < 30:
            time.sleep(0.01)
        assert core.metrics.events(tid, "checkpoint"), \
            "on-demand checkpoint was never taken"

        core.pause_training(tid)
        time.sleep(0.2)                            # drain in-flight step
        s1 = core.training_status(tid)["steps_done"]
        time.sleep(0.3)
        s2 = core.training_status(tid)["steps_done"]
        assert s2 <= s1 + 1, "paused job kept stepping"
        core.resume_training(tid)
        assert core.wait_for(tid, timeout=120) == "COMPLETED"
    finally:
        core.close()


def test_software_ps_int8_dataplane_end_to_end(tmp_path):
    """A software-ps training with framework.compression: int8 trains
    to a comparable loss, reports the data plane through the status
    surface, and moves ≥3.5x fewer push bytes on the wire."""
    finals = {}
    for comp in ("none", "int8"):
        core = DLaaSCore(str(tmp_path / comp))
        try:
            mid = core.deploy_model(PARITY_MANIFEST)["model_id"]
            out = core.create_training(
                mid, overrides={"compression": comp, "ps_shards": 2})
            tid = out["training_id"]
            assert core.wait_for(tid, timeout=240) == "COMPLETED"
            dp = core.training_status(tid)["data_plane"]
            assert dp["compression"] == comp
            assert dp["ps_shards"] == 2
            assert dp["agg_rounds"] >= 25
            assert dp["agg_ms_per_round"] is not None
            if comp == "int8":
                assert dp["compression_ratio"] >= 3.5
                assert dp["bytes_pushed_wire"] * 3.5 <= \
                    dp["bytes_pushed_dense"]
            else:
                assert dp["bytes_pushed_wire"] == dp["bytes_pushed_dense"]
            # loss series, not the last sample: the step loss is noisy
            vals = core.metrics.series(tid, "loss").values
            finals[comp] = sum(vals[-8:]) / 8
        finally:
            core.close()
    assert abs(finals["int8"] - finals["none"]) < 0.3, finals


def test_rest_rejects_unknown_distribution(tmp_path):
    with DLaaSServer(str(tmp_path)) as srv:
        mid = _req(f"{srv.url}/v1/models", "POST",
                   {"manifest": PARITY_MANIFEST})["model_id"]
        with pytest.raises(urllib.error.HTTPError) as ei:
            _req(f"{srv.url}/v1/trainings", "POST",
                 {"model_id": mid,
                  "overrides": {"distribution": "horovod"}})
        assert ei.value.code == 400
        body = json.loads(ei.value.read())
        assert "horovod" in body["error"]


def test_pjit_rejects_non_zoo_framework(tmp_path):
    core = DLaaSCore(str(tmp_path))
    try:
        mid = core.deploy_model(
            "name: x\nframework:\n  name: repro-mlp\n")["model_id"]
        from repro.platform.cluster import UserError
        with pytest.raises(UserError) as ei:
            core.create_training(mid, overrides={"distribution": "pjit"})
        assert "repro-lm" in str(ei.value)
    finally:
        core.close()


# ---------------------------------------------------------------------------
# acceptance: the PR-1 preemption scenario rerun on the pjit backend
# ---------------------------------------------------------------------------

PJIT_CONTENTION = """
name: contention-pjit
learners: 1
gpus: 2
steps: 120
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

HI_MANIFEST = """
name: hi-prio
learners: 1
gpus: 2
steps: 30
lr: 0.2
framework:
  name: repro-mlp
  d_in: 16
  n_classes: 4
"""


def test_pjit_preemption_checkpoint_resume(tmp_path):
    """A pjit training submitted through REST is preempted by a
    higher-priority job, requeues as PREEMPTED (still reporting its
    backend), resumes from its checkpoint and completes. The smoke
    model steps in ~ms, so the backend's pause hook holds the job at a
    step boundary to make the eviction window deterministic."""
    cluster = Cluster([Node("n0", Resources(cpus=16, gpus=2,
                                            memory_mb=64000))])
    with DLaaSServer(str(tmp_path), cluster=cluster) as srv:
        mid = _req(f"{srv.url}/v1/models", "POST",
                   {"manifest": PJIT_CONTENTION})["model_id"]
        lo = _req(f"{srv.url}/v1/trainings", "POST",
                  {"model_id": mid, "tenant": "research",
                   "priority": 0})["training_id"]
        core = srv.core
        # wait until mid-training with a checkpoint on disk
        t0 = time.time()
        while time.time() - t0 < 90:
            if core.metrics.checkpoints(lo) and \
                    core.training_status(lo)["steps_done"] >= 20:
                break
            time.sleep(0.01)
        assert core.metrics.checkpoints(lo), "no checkpoint in time"
        core.pause_training(lo)        # hold at the next step boundary

        hid = _req(f"{srv.url}/v1/models", "POST",
                   {"manifest": HI_MANIFEST})["model_id"]
        hi = _req(f"{srv.url}/v1/trainings", "POST",
                  {"model_id": hid, "tenant": "prod",
                   "priority": 10})["training_id"]

        # the 2-GPU node is full: placing prod's job must evict the gang
        saw_preempted = False
        t0 = time.time()
        while time.time() - t0 < 60:
            st = _req(f"{srv.url}/v1/trainings/{lo}")
            if st["status"] == "PREEMPTED":
                saw_preempted = True
                # backend still reported while evicted
                assert st["backend"] == "pjit"
                break
            time.sleep(0.01)
        assert saw_preempted, "pjit job was never PREEMPTED"
        assert core.wait_for(hi, timeout=90) == "COMPLETED"

        # re-placed gang restores the checkpoint (leader logs it even
        # while still paused), then the resume hook lets it finish
        t0 = time.time()
        while time.time() - t0 < 90:
            logs = _req(f"{srv.url}/v1/trainings/{lo}/logs")["logs"]
            if any("resumed from checkpoint" in l for l in logs):
                break
            time.sleep(0.01)
        assert any("resumed from checkpoint" in l for l in logs), \
            "preempted pjit job did not resume from its checkpoint"
        core.resume_training(lo)
        assert core.wait_for(lo, timeout=180) == "COMPLETED"

        st = _req(f"{srv.url}/v1/trainings/{lo}")
        assert st["backend"] == "pjit"
        assert st["steps_done"] >= 120
        # the trained model is downloadable despite the eviction
        blob = urllib.request.urlopen(
            f"{srv.url}/v1/trainings/{lo}/model").read()
        assert len(blob) > 0
