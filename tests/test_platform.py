"""Scheduler/cluster/LCM/watchdog: placement, failure recovery, the
paper's GPU-unresponsive incident (reproduced AND fixed), LCM decoupling."""
import time

import pytest

from repro.platform.cluster import (App, Cluster, FAILED, FINISHED, Node,
                                    Resources, RUNNING, Scheduler, STAGING,
                                    UserError)
from repro.platform.lcm import JobSpec, LifecycleManager
from repro.platform.watchdog import JOB_DONE, Watchdog
from repro.platform.zookeeper import ZooKeeper


def mk_cluster(n=3, gpus=4):
    return Cluster([Node(f"n{i}", Resources(cpus=8, gpus=gpus,
                                            memory_mb=32000))
                    for i in range(n)])


def test_placement_and_release():
    c = mk_cluster(2, gpus=2)
    s = Scheduler(c)
    app = App("a", Resources(cpus=1, gpus=2, memory_mb=100), count=2)
    s.submit(app)
    s.tick()
    nodes = {t.node for t in app.tasks.values()}
    assert len(nodes) == 2              # 2 GPUs each: must spread
    for t in app.tasks.values():
        s.task_finished(t.task_id)
    assert c.idle_fraction() == 1.0


def test_queue_when_full_then_schedule():
    c = mk_cluster(1, gpus=2)
    s = Scheduler(c)
    a1 = s.submit(App("a1", Resources(gpus=2), count=1))
    a2 = s.submit(App("a2", Resources(gpus=2), count=1))
    s.tick()
    states = sorted(t.state for t in
                    list(a1.tasks.values()) + list(a2.tasks.values()))
    assert states == [RUNNING, STAGING]
    for t in a1.tasks.values():
        s.task_finished(t.task_id)
    s.tick()
    assert all(t.state == RUNNING for t in a2.tasks.values())


def test_node_failure_reschedules():
    c = mk_cluster(2, gpus=2)
    s = Scheduler(c)
    app = s.submit(App("a", Resources(gpus=1), count=1))
    s.tick()
    node = next(iter(app.tasks.values())).node
    c.fail_node(node)
    s.tick()
    t = next(iter(app.tasks.values()))
    assert t.state == RUNNING and t.node != node
    assert t.restarts == 1


def test_colloquium_incident_without_health_checks():
    """Paper: 'our resource manager failed to recognize [unresponsive
    GPUs] and kept scheduling jobs to this node. As a result, a few jobs
    failed to start.'"""
    c = mk_cluster(1, gpus=4)
    c.make_gpu_unresponsive("n0")
    s = Scheduler(c, health_checks=False)
    app = s.submit(App("a", Resources(gpus=1), count=2, max_restarts=0))
    s.tick()
    assert all(t.state == FAILED for t in app.tasks.values())
    assert all("unresponsive" in t.message for t in app.tasks.values())


def test_health_checker_fixes_incident():
    """With the health checker (the paper's future work), the bad node is
    drained and tasks land on a healthy one."""
    c = mk_cluster(2, gpus=4)
    c.make_gpu_unresponsive("n0")
    s = Scheduler(c, health_checks=True)
    app = s.submit(App("a", Resources(gpus=1), count=2))
    s.tick()
    assert all(t.state == RUNNING and t.node == "n1"
               for t in app.tasks.values())
    assert any("drained n0" in e for e in s.health.events)


def test_user_error_not_restarted():
    c = mk_cluster()
    s = Scheduler(c)

    def bad(task):
        raise UserError("syntax error in user model")

    app = s.submit(App("a", Resources(gpus=0), count=1, run=bad))
    s.tick()
    for _ in range(50):
        if app.tasks["a.0"].state == FAILED:
            break
        time.sleep(0.02)
    t = app.tasks["a.0"]
    assert t.state == FAILED and t.restarts == 0


def test_infra_error_restarted_up_to_max():
    c = mk_cluster()
    s = Scheduler(c)
    calls = []

    def flaky(task):
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("transient")

    app = s.submit(App("a", Resources(gpus=0), count=1, run=flaky,
                       max_restarts=5))
    for _ in range(100):
        s.tick()
        if app.tasks["a.0"].state == FINISHED:
            break
        time.sleep(0.02)
    assert app.tasks["a.0"].state == FINISHED
    assert len(calls) == 3


# ---------------------------------------------------------------------------
# LCM
# ---------------------------------------------------------------------------


def _drive(s, lcm, job_id, timeout=10.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        s.tick()
        st = lcm.monitor(job_id)
        if st in ("COMPLETED", "FAILED", "KILLED"):
            return st
        time.sleep(0.02)
    return lcm.job_state(job_id)


def test_lcm_full_lifecycle():
    zk = ZooKeeper()
    s = Scheduler(mk_cluster())
    lcm = LifecycleManager(zk, s)
    spec = JobSpec(job_id="j1", learners=2,
                   learner_body=lambda wd, idx: wd.log("hi"),
                   ps_body=lambda wd: None)
    lcm.submit(spec)
    assert _drive(s, lcm, "j1") == "COMPLETED"
    st = lcm.member_statuses("j1")
    assert st["learner-0"]["status"] == JOB_DONE
    lcm.gc("j1")
    assert lcm.member_statuses("j1") == {}


def test_lcm_state_write_survives_a_racing_create():
    """Two threads writing a job's key (the tick thread's monitor and a
    status call's) can both find it missing; the second create then
    finds it made and sets it instead of failing."""
    zk = ZooKeeper()
    lcm = LifecycleManager(zk, Scheduler(mk_cluster()))
    exists = zk.exists
    path = "/dlaas/jobs/j1/progress"

    def racing_exists(p):
        if p == path and not exists(p):
            zk.create(p, b"{}", makepath=True)    # the other thread's write
            return False
        return exists(p)

    zk.exists = racing_exists
    lcm._set("j1", "progress", {"alive": 1})
    assert zk.get(path)[0] == b'{"alive": 1}'


def test_lcm_detects_crash_via_ephemeral():
    zk = ZooKeeper()
    s = Scheduler(mk_cluster())
    lcm = LifecycleManager(zk, s)

    crashed = []

    def body(wd, idx):
        if idx == 0 and not crashed:
            crashed.append(1)
            wd.crash()                     # ephemeral disappears silently
            raise RuntimeError("container crash")
        time.sleep(0.1)

    spec = JobSpec(job_id="j2", learners=2, learner_body=body,
                   ps_body=lambda wd: None)
    lcm.submit(spec)
    st = _drive(s, lcm, "j2", timeout=15)
    assert st == "COMPLETED"               # restarted learner finished
    # the scheduler restarted the crashed learner
    app = s.apps["j2-learners"]
    assert any(t.restarts > 0 for t in app.tasks.values())


def test_lcm_statelessness_and_decoupling():
    """Kill the LCM mid-job: training proceeds; a recovered LCM resumes
    monitoring from ZK state (paper's decoupling claim)."""
    zk = ZooKeeper()
    s = Scheduler(mk_cluster())
    lcm = LifecycleManager(zk, s)
    done = []

    def body(wd, idx):
        time.sleep(0.3)
        done.append(idx)

    lcm.submit(JobSpec(job_id="j3", learners=2, learner_body=body,
                       ps_body=lambda wd: None))
    s.tick()
    del lcm                                 # LCM 'crashes'
    time.sleep(0.5)                         # job keeps running without it
    assert sorted(done) == [0, 1]
    lcm2 = LifecycleManager.recover(zk, s)
    st = _drive(s, lcm2, "j3")
    assert st == "COMPLETED"
