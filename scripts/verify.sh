#!/usr/bin/env bash
# Tier-1 verification + docs link-check. Plain shell so any CI can call
# it:   bash scripts/verify.sh
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== docs link-check: every repo path referenced in README.md and" \
     "docs/ARCHITECTURE.md must exist =="
missing=0
for doc in README.md docs/ARCHITECTURE.md; do
    # backtick-quoted repo paths: src/..., tests/..., examples/..., etc.
    for p in $(grep -o '`[A-Za-z0-9_./-]*`' "$doc" | tr -d '`' \
               | grep -E '^(src|tests|examples|benchmarks|docs|scripts)/' \
               | sed 's:/$::' | sort -u); do
        if [ ! -e "$p" ]; then
            echo "MISSING: $p (referenced in $doc)"
            missing=1
        fi
    done
    # top-level files referenced in docs
    for p in $(grep -o '`[A-Za-z0-9_.-]*\.\(md\|txt\|ini\|yml\)`' "$doc" \
               | tr -d '`' | sort -u); do
        case "$p" in
            manifest.yml|m.yml) continue ;;   # illustrative names
        esac
        if [ ! -e "$p" ]; then
            echo "MISSING: $p (referenced in $doc)"
            missing=1
        fi
    done
done
if [ "$missing" -ne 0 ]; then
    echo "docs link-check FAILED"
    exit 1
fi
echo "docs link-check OK"

echo "== exception hygiene: no swallowed exceptions (except ...: pass) =="
python - <<'EOF'
import pathlib
import re
import sys

# 'except:'/'except Exception:' followed by a bare 'pass' silently eats
# scheduler and learner bugs (PR 2 satellite); narrow except clauses
# (e.g. NoNodeError) stay allowed.
pat = re.compile(
    r"except(\s+(Exception|BaseException))?\s*(as\s+\w+\s*)?"
    r":\s*(\n\s*)?pass\b")
bad = []
for root in ("src", "benchmarks"):
    for p in sorted(pathlib.Path(root).rglob("*.py")):
        text = p.read_text()
        for m in pat.finditer(text):
            line = text[: m.start()].count("\n") + 1
            bad.append(f"{p}:{line}")
if bad:
    print("swallowed exceptions (except ...: pass) at:")
    print("\n".join(f"  {b}" for b in bad))
    sys.exit(1)
print("except-pass check OK")
EOF

echo "== logging hygiene: no bare print() in src/ outside the CLI" \
     "(everything routes through the structured 'repro' logger) =="
python - <<'EOF'
import ast
import pathlib
import sys

# the CLI prints to stdout by contract; everything else must log so the
# job/trace context filter and the per-job log hub see it
ALLOW = {"src/repro/service/cli.py"}
bad = []
for p in sorted(pathlib.Path("src").rglob("*.py")):
    if p.as_posix() in ALLOW:
        continue
    tree = ast.parse(p.read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Name) \
                and node.func.id == "print":
            bad.append(f"{p}:{node.lineno}")
if bad:
    print("bare print() outside the CLI (use logging.getLogger"
          "('repro.<area>')):")
    print("\n".join(f"  {b}" for b in bad))
    sys.exit(1)
print("print-free check OK")
EOF

echo "== ps-dataplane benchmark smoke (compression none vs int8) =="
# tiny invocation of the data-plane bench: proves both wire formats
# train end-to-end; writes to a temp file so the committed
# BENCH_ps_dataplane.json (full 30-step run) is not clobbered
PS_DATAPLANE_STEPS=6 PS_DATAPLANE_OUT="$(mktemp /tmp/ps_dataplane.XXXXXX.json)" \
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python benchmarks/run.py ps-dataplane

echo "== serving smoke (deploy smoke arch, N predicts, drain; fails on" \
     "any rejected request at smoke load) =="
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'EOF'
import tempfile
import time

import numpy as np

from repro.service.core import DLaaSCore

core = DLaaSCore(tempfile.mkdtemp(prefix="verify_serving_"),
                 tick_interval=0.005)
try:
    eid = core.deploy_endpoint(arch="stablelm-1.6b-smoke", capacity=2,
                               max_queue=16, max_new=4)["endpoint_id"]
    t0 = time.time()
    while core.endpoint_status(eid)["state"] != "READY":
        if time.time() - t0 > 300:
            raise SystemExit("serving smoke FAILED: endpoint not READY")
        time.sleep(0.1)
    rng = np.random.RandomState(0)
    for i in range(6):
        out = core.predict(eid, rng.randint(0, 100, size=8), max_new=4)
        assert len(out["tokens"]) == 4, out
    core.stop_endpoint(eid)
    t0 = time.time()
    while True:
        st = core.endpoint_status(eid)
        if st["state"] == "STOPPED":
            break
        if time.time() - t0 > 60:
            raise SystemExit("serving smoke FAILED: endpoint not STOPPED")
        time.sleep(0.1)
    stats = st["stats"]
    assert stats["rejected_total"] == 0, \
        f"serving smoke FAILED: rejected requests at smoke load: {stats}"
    assert stats["completed_total"] == 6, stats
    print("serving smoke OK:",
          {k: stats[k] for k in ("completed_total", "p50_latency_s",
                                 "mean_batch_occupancy")})
finally:
    core.close()
EOF

echo "== observability smoke: scrape /metrics during a training," \
     "validate Prometheus text + dlaas_ families, live follow streams," \
     "single-trace timeline =="
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'EOF'
import json
import tempfile
import time
import urllib.request

from repro.observability.export import parse_prometheus_text
from repro.service.rest import DLaaSServer

MANIFEST = ("name: obs-smoke\nlearners: 2\ngpus: 1\nsteps: 60\n"
            "checkpoint_every: 20\nframework:\n  name: repro-mlp\n"
            "  d_in: 16\n  n_classes: 4\n")


def req(url, method="GET", body=None):
    data = json.dumps(body).encode() if body is not None else None
    r = urllib.request.Request(url, data=data, method=method)
    r.add_header("Authorization", "Bearer verify")
    if data:
        r.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(r) as resp:
        return resp.read()


with DLaaSServer(tempfile.mkdtemp(prefix="verify_obs_")) as srv:
    base = srv.url
    mid = json.loads(req(f"{base}/v1/models", "POST",
                         {"manifest": MANIFEST}))["model_id"]
    tid = json.loads(req(f"{base}/v1/trainings", "POST",
                         {"model_id": mid}))["training_id"]
    # scrape DURING the run: wait for PROCESSING, then hit /metrics
    t0 = time.time()
    while True:
        st = json.loads(req(f"{base}/v1/trainings/{tid}"))["status"]
        if st == "PROCESSING":
            break
        if st in ("COMPLETED", "FAILED", "KILLED") \
                or time.time() - t0 > 300:
            raise SystemExit(f"obs smoke FAILED: never PROCESSING ({st})")
        time.sleep(0.02)
    # a Prometheus scraper negotiates on the exact Content-Type
    with urllib.request.urlopen(f"{base}/metrics") as resp:
        ctype = resp.headers.get("Content-Type")
        text = resp.read().decode()
    if ctype != "text/plain; version=0.0.4; charset=utf-8":
        raise SystemExit(f"obs smoke FAILED: /metrics Content-Type "
                         f"{ctype!r} is not the 0.0.4 exposition")
    parsed = parse_prometheus_text(text)       # raises on malformed text
    fams = parsed["families"]
    for want in ("dlaas_queue_depth", "dlaas_cluster_nodes",
                 "dlaas_cluster_gpus_free", "dlaas_journal_seq",
                 "dlaas_journal_compactions_total", "dlaas_trace_spans",
                 "dlaas_platform_events_total", "dlaas_slo_burn_rate",
                 "dlaas_slo_objective", "dlaas_alerts_active",
                 "dlaas_alerts_fired_total",
                 "dlaas_alerts_remediations_total"):
        if want not in fams:
            raise SystemExit(f"obs smoke FAILED: /metrics missing "
                             f"{want}; has {sorted(fams)}")
    # live streams while the job runs: loss records + structured logs
    raw = req(f"{base}/v1/trainings/{tid}/metrics?follow=1&max_s=3")
    mlines = [json.loads(l) for l in raw.splitlines() if l.strip()]
    if not (mlines and mlines[0]["type"] == "snapshot"
            and any(r.get("metric") == "loss" for r in mlines[1:])):
        raise SystemExit(f"obs smoke FAILED: metrics?follow=1 streamed "
                         f"no live loss records ({len(mlines)} lines)")
    raw = req(f"{base}/v1/trainings/{tid}/logs?follow=1&max_s=3")
    llines = [json.loads(l) for l in raw.splitlines() if l.strip()]
    if not any("step=" in r.get("line", "") for r in llines):
        raise SystemExit("obs smoke FAILED: logs?follow=1 streamed no "
                         f"training lines ({len(llines)} records)")
    t0 = time.time()
    while json.loads(req(f"{base}/v1/trainings/{tid}"))["status"] \
            != "COMPLETED":
        if time.time() - t0 > 300:
            raise SystemExit("obs smoke FAILED: training never finished")
        time.sleep(0.1)
    # one trace, phases tile the lifetime without overlap
    tl = json.loads(req(f"{base}/v1/trainings/{tid}/timeline"))
    names = [s["name"] for s in tl["spans"]]
    for want in ("job", "submit", "queue_wait", "place", "run",
                 "checkpoint_publish"):
        if want not in names:
            raise SystemExit(f"obs smoke FAILED: timeline missing "
                             f"{want!r} span: {names}")
    phases = sorted((s for s in tl["spans"]
                     if s["name"] in ("queue_wait", "place", "run",
                                      "preempted")),
                    key=lambda s: s["start"])
    for a, b in zip(phases, phases[1:]):
        if a["end"] is None or a["end"] > b["start"] + 1e-9:
            raise SystemExit(f"obs smoke FAILED: overlapping phases "
                             f"{a['name']}->{b['name']}")
    print(f"observability smoke OK: {len(fams)} families, "
          f"{len(mlines)} live metric lines, {len(llines)} live log "
          f"records, {len(tl['spans'])} spans in one trace")
EOF

echo "== perf regression gate: fresh trajectory benches vs committed" \
     "BENCH_*.json (tolerance GATE_TOLERANCE, default 0.5) =="
# re-runs the backends/ps-dataplane/serving benches into a temp dir and
# requires every rate metric to reach GATE_TOLERANCE x its committed
# baseline; exit 1 on regression. The band is wide on purpose (container
# speed varies several-fold) — override with e.g. GATE_TOLERANCE=0.25
# on very slow CI hosts, or GATE_BENCHES=ps_dataplane to subset.
GATE_TOLERANCE="${GATE_TOLERANCE:-0.5}" \
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python benchmarks/run.py gate

echo "== backend-parity + manifest test groups =="
JAX_PLATFORMS=cpu PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m pytest -x -q tests/test_backends.py tests/test_manifest.py

echo "== chaos drill: seeded kill/drain replay + 2-node node-kill for" \
     "both backends + serving-node kill (zero lost requests) =="
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'EOF'
import tempfile
import time

from repro.platform.cluster import (Cluster, Node, Resources, RUNNING,
                                    Scheduler)
from repro.platform.faults import (DRAIN, FaultEvent, FaultInjector,
                                   FaultSchedule, KILL)
from repro.service.core import DLaaSCore


def wait_until(cond, timeout=300.0, desc="condition"):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if cond():
            return
        time.sleep(0.02)
    raise SystemExit(f"chaos drill FAILED: timed out waiting for {desc}")


# -- determinism: the same seed must replay the identical transition log
def drill(seed):
    c = Cluster([Node(f"n{i}", Resources(cpus=8, gpus=2, memory_mb=16000))
                 for i in range(2)])
    s = Scheduler(c)
    s.faults = FaultInjector(FaultSchedule.seeded(
        seed, sorted(c.nodes), n_events=4, horizon=10,
        kinds=(KILL, DRAIN)))
    for _ in range(12):
        s.tick()
    assert s.faults.done()
    return list(c.transitions)


log = drill(29)
assert log and log == drill(29), \
    "chaos drill FAILED: seeded drill did not replay tick-exact"
print(f"replay OK: {len(log)} transitions, identical across two runs")

PS_MANIFEST = ("name: chaos-ps\nlearners: 2\ngpus: 1\nsteps: 40\n"
               "checkpoint_every: 5\nframework:\n  name: repro-mlp\n"
               "  d_in: 16\n  n_classes: 4\n")
PJIT_MANIFEST = ("name: chaos-pjit\nlearners: 1\ngpus: 2\nsteps: 40\n"
                 "batch_docs: 2\ncheckpoint_every: 10\n"
                 "data:\n  n_docs: 32\n  seq_len: 16\n"
                 "framework:\n  name: repro-lm\n  arch: stablelm-1.6b-smoke\n"
                 "  distribution: pjit\n")


# -- both backends: kill the busy node mid-run; the job must resume
# from its checkpoint on the surviving node and complete
def backend_drill(dist):
    c = Cluster([Node(f"c{i}", Resources(cpus=16, gpus=2,
                                         memory_mb=64000))
                 for i in range(2)])
    core = DLaaSCore(tempfile.mkdtemp(prefix=f"verify_chaos_{dist}_"),
                     tick_interval=0.005, cluster=c)
    try:
        man = PJIT_MANIFEST if dist == "pjit" else PS_MANIFEST
        mid = core.deploy_model(man)["model_id"]
        tid = core.create_training(mid)["training_id"]
        wait_until(lambda: core.training_status(tid)["steps_done"] >= 10
                   and core.metrics.checkpoints(tid),
                   desc=f"{dist}: 10 steps + a checkpoint")
        core.pause_training(tid)      # gate at a step boundary
        gid = f"{tid}-workers" if dist == "pjit" else f"{tid}-learners"
        app = core.scheduler.apps[gid]
        victim = [t.node for t in app.tasks.values()
                  if t.state == RUNNING and t.node][0]
        core.inject_faults(events=[
            FaultEvent(KILL, victim, at_tick=core.cluster.clock + 1)])
        wait_until(lambda: core.scheduler.faults.done(),
                   desc=f"{dist}: fault fired")
        wait_until(lambda: any("resumed from checkpoint" in l
                               for l in core.training_logs(tid)),
                   desc=f"{dist}: checkpoint resume on survivor")
        core.resume_training(tid)
        if core.wait_for(tid, timeout=300) != "COMPLETED":
            raise SystemExit(f"chaos drill FAILED: {dist} job did not "
                             f"complete after node kill")
        st = core.training_status(tid)
        assert st["steps_done"] >= 40, st
        assert not core.cluster.nodes[victim].alive
        print(f"{dist} drill OK: killed {victim}, resumed from "
              f"checkpoint, {st['steps_done']} steps done")
    finally:
        core.close()


backend_drill("software-ps")
backend_drill("pjit")


# -- serving: kill the endpoint's node with requests queued; the engine
# must re-queue them and answer every one after re-placement
def serving_drill():
    c = Cluster([Node(f"s{i}", Resources(cpus=8, gpus=1,
                                         memory_mb=16000))
                 for i in range(2)])
    core = DLaaSCore(tempfile.mkdtemp(prefix="verify_chaos_srv_"),
                     tick_interval=0.005, cluster=c)
    try:
        eid = core.deploy_endpoint(arch="stablelm-1.6b-smoke", capacity=2,
                                   max_new=2)["endpoint_id"]
        wait_until(lambda: core.endpoint_status(eid)["state"] == "READY",
                   desc="endpoint READY")
        core.predict(eid, [1, 2, 3], max_new=2)        # warm the jits
        core.pause_training(eid)      # hold the serve loop
        eng = core.endpoints[eid].engine
        reqs = [eng.submit([4, 5, 6], max_new=2),
                eng.submit([7, 8], max_new=2)]
        app = core.scheduler.apps[f"{eid}-servers"]
        victim = [t.node for t in app.tasks.values()
                  if t.state == RUNNING][0]
        core.inject_faults(events=[
            FaultEvent(KILL, victim, at_tick=core.cluster.clock + 1)])
        wait_until(lambda: any(t.state == RUNNING and t.node != victim
                               for t in app.tasks.values()),
                   desc="endpoint re-placed on survivor")
        core.resume_training(eid)
        for r in reqs:
            if not r.wait(180) or r.status != "DONE":
                raise SystemExit("chaos drill FAILED: lost request "
                                 f"{r.req_id}: {r.status}")
        wait_until(lambda: core.endpoint_status(eid)["state"] == "READY",
                   desc="endpoint READY after kill")
        core.stop_endpoint(eid)
        print(f"serving drill OK: killed {victim}, zero lost requests")
    finally:
        core.close()


serving_drill()
print("chaos drill OK")
EOF

echo "== health drill: seeded straggler -> burn/anomaly alert ->" \
     "auto-restart remediation -> completion with loss parity," \
     "deterministic across two runs =="
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'EOF'
import json
import tempfile
import time
import urllib.request

from repro.platform.faults import FaultSchedule
from repro.service.rest import DLaaSServer

MANIFEST = ("name: health-drill\nlearners: 2\ngpus: 1\nsteps: 40\n"
            "checkpoint_every: 5\nlr: 0.3\nframework:\n"
            "  name: repro-mlp\n  d_in: 16\n  n_classes: 4\n"
            "  distribution: software-ps\n")
SEED = 11


def req(url):
    r = urllib.request.Request(url)
    r.add_header("Authorization", "Bearer verify")
    with urllib.request.urlopen(r) as resp:
        return json.loads(resp.read())


def run(inject):
    """One training; returns (final_loss, straggler alert sequence,
    remediation log, HTTP /v1/alerts report, timeline span names)."""
    with DLaaSServer(tempfile.mkdtemp(prefix="verify_health_"),
                     tick_interval=0.005, durable=False) as srv:
        core = srv.core
        core.health.cooldown_s = 1.0
        mid = core.deploy_model(MANIFEST)["model_id"]
        tid = core.create_training(mid)["training_id"]
        if inject:
            sched = FaultSchedule.seeded_straggler(
                SEED, tid, 2, at_step=3, seconds=0.08)
            core.inject_faults(events=sched.events)
            t0 = time.time()
            while not any(
                    r["action"] == "restart_learner"
                    for r in core.health.alerts.remediations()):
                if time.time() - t0 > 300:
                    raise SystemExit("health drill FAILED: straggler "
                                     "remediation never ran")
                time.sleep(0.02)
        if core.wait_for(tid, timeout=300) != "COMPLETED":
            raise SystemExit(f"health drill FAILED: job did not "
                             f"complete ({core.lcm.job_state(tid)})")
        loss = core.metrics.series(tid, "loss").values[-1]
        rep = req(f"{srv.url}/v1/alerts")
        fired = rep["history"] + rep["active"]
        # the deterministic slice: seeded straggler alerts + what the
        # controller did about them (throughput/latency SLO alerts are
        # timing-dependent and excluded on purpose)
        alerts, seen = [], set()
        for a in sorted(fired, key=lambda a: a["seq"]):
            k = (a["name"], a["scope"])
            if a["name"] == "straggler" and k not in seen:
                seen.add(k)
                alerts.append(k)
        rems, seen = [], set()
        for r in rep["remediations"]:
            k = (r["action"], r["scope"], r.get("task", ""))
            if r["action"] == "restart_learner" and k not in seen:
                seen.add(k)
                rems.append(k)
        names = [s["name"]
                 for s in core.training_timeline(tid)["spans"]]
        return loss, alerts, rems, rep, names, tid


base_loss, _, _, _, _, _ = run(inject=False)
loss1, alerts1, rems1, rep1, names1, tid = run(inject=True)
loss2, alerts2, rems2, _, _, _ = run(inject=True)

victim = FaultSchedule.seeded_straggler(SEED, tid, 2).events[0].member
scope = f"{tid}/learner-{victim}"
if alerts1 != [("straggler", scope)]:
    raise SystemExit(f"health drill FAILED: expected one straggler "
                     f"alert on {scope}, got {alerts1}")
if rems1 != [("restart_learner", scope,
              f"{tid}-learners.{victim}")]:
    raise SystemExit(f"health drill FAILED: remediation log "
                     f"{rems1} did not requeue the victim learner")
if (alerts1, rems1) != (alerts2, rems2):
    raise SystemExit(f"health drill FAILED: seeded drill not "
                     f"deterministic: {(alerts1, rems1)} vs "
                     f"{(alerts2, rems2)}")
# the alert reached BOTH surfaces: /v1/alerts and the job timeline
if not any(a["name"] == "straggler" and a["scope"] == scope
           for a in rep1["history"] + rep1["active"]):
    raise SystemExit("health drill FAILED: straggler missing from "
                     "/v1/alerts")
for want in ("alert", "remediation"):
    if want not in names1:
        raise SystemExit(f"health drill FAILED: no {want!r} event in "
                         f"the job timeline: {sorted(set(names1))}")
# loss parity: the remediated run converges like the unfaulted one
if loss1 > max(2 * base_loss, base_loss + 0.3):
    raise SystemExit(f"health drill FAILED: loss {loss1:.4f} vs "
                     f"unfaulted baseline {base_loss:.4f}")
print(f"health drill OK: straggler {scope} alerted + requeued, "
      f"deterministic across two seeded runs, loss {loss1:.4f} vs "
      f"baseline {base_loss:.4f}")
EOF

echo "== storage hygiene: production object-store I/O must go through" \
     "StorageManager's with_backoff wrappers, never raw Store methods =="
python - <<'EOF'
import pathlib
import re
import sys

# direct store calls skip the exponential-backoff retry the paper
# requires for Object Store access; everything in src/ must route
# through StorageManager.download/upload (platform/storage.py)
pat = re.compile(
    r"(\bget_store\s*\(|\bstore\.(put|get|list|delete|exists)\s*\(|"
    r"\.stores\[)")
bad = []
for p in sorted(pathlib.Path("src").rglob("*.py")):
    if p.as_posix() == "src/repro/platform/storage.py":
        continue
    text = p.read_text()
    for m in pat.finditer(text):
        line = text[: m.start()].count("\n") + 1
        bad.append(f"{p}:{line}: {m.group(0)}")
if bad:
    print("raw object-store access outside the backoff wrapper:")
    print("\n".join(f"  {b}" for b in bad))
    sys.exit(1)
print("storage backoff-path check OK")
EOF

echo "== crash-recovery drill: hard-kill (SIGKILL) a core subprocess" \
     "mid-training, recover a fresh core on the same workdir =="
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'EOF'
import json
import os
import pathlib
import signal
import subprocess
import sys
import tempfile
import time

WORKDIR = tempfile.mkdtemp(prefix="verify_crash_")
MARKER = pathlib.Path(WORKDIR) / "marker.json"

# -- phase 1: a real OS process builds state, then is SIGKILLed --------
CHILD = r'''
import json, pathlib, sys, time
from repro.service.core import DLaaSCore

workdir = sys.argv[1]
MANIFEST = ("name: crash-drill\nlearners: 1\ngpus: 1\nsteps: 2000\n"
            "checkpoint_every: 100\nframework:\n  name: repro-mlp\n"
            "  d_in: 16\n  n_classes: 4\n")
core = DLaaSCore(workdir, tick_interval=0.005)
eid = core.deploy_endpoint(arch="stablelm-1.6b-smoke", max_new=2,
                           idempotency_key="drill-ep")["endpoint_id"]
t0 = time.time()
while core.endpoint_status(eid)["state"] != "READY":
    if time.time() - t0 > 300:
        raise SystemExit("child: endpoint never READY")
    time.sleep(0.1)
pre = core.predict(eid, [1, 2, 3], max_new=2)["tokens"]
mid = core.deploy_model(MANIFEST)["model_id"]
tid = core.create_training(mid, user="alice",
                           idempotency_key="drill-sub")["training_id"]
t0 = time.time()
while not core.metrics.checkpoints(tid):
    if time.time() - t0 > 300:
        raise SystemExit("child: no checkpoint landed")
    time.sleep(0.05)
core.pause_training(tid)     # hold mid-flight so the kill is mid-job
pathlib.Path(workdir, "marker.json").write_text(json.dumps(
    {"tid": tid, "eid": eid, "mid": mid, "pre_tokens": pre}))
time.sleep(600)              # parent SIGKILLs us here
'''
child = subprocess.Popen([sys.executable, "-c", CHILD, WORKDIR])
t0 = time.time()
while not MARKER.exists():
    if child.poll() is not None:
        raise SystemExit("crash drill FAILED: child died before marker "
                         f"(rc={child.returncode})")
    if time.time() - t0 > 600:
        child.kill()
        raise SystemExit("crash drill FAILED: child never wrote marker")
    time.sleep(0.1)
ids = json.loads(MARKER.read_text())
os.kill(child.pid, signal.SIGKILL)       # no shutdown hook runs
child.wait()

# -- phase 2: fresh core, same workdir — replay + recover --------------
from repro.service.core import DLaaSCore

core = DLaaSCore(WORKDIR, tick_interval=0.005)
try:
    rep = core.recovery_report()
    tid, eid = ids["tid"], ids["eid"]
    assert rep["recovered"], rep
    if tid not in rep["trainings"]["resumed"] + rep["trainings"]["requeued"]:
        raise SystemExit(f"crash drill FAILED: {tid} not relaunched: {rep}")
    if eid not in rep["endpoints"]["redeployed"]:
        raise SystemExit(f"crash drill FAILED: {eid} not redeployed: {rep}")
    # replayed Idempotency-Key returns the ORIGINAL job, no duplicate
    again = core.create_training(ids["mid"], user="alice",
                                 idempotency_key="drill-sub")
    assert again["training_id"] == tid, again
    if core.wait_for(tid, timeout=600) != "COMPLETED":
        raise SystemExit("crash drill FAILED: training did not complete "
                         f"after recovery: {core.lcm.job_state(tid)}")
    t0 = time.time()
    while core.endpoint_status(eid)["state"] != "READY":
        if time.time() - t0 > 300:
            raise SystemExit("crash drill FAILED: endpoint not READY "
                             "after recovery")
        time.sleep(0.1)
    post = core.predict(eid, [1, 2, 3], max_new=2)["tokens"]
    assert post == ids["pre_tokens"], (post, ids["pre_tokens"])
    # the recovered job's timeline continues the submission-time trace
    # and records the recovery pass as an event
    tl = core.training_timeline(tid)
    names = [s["name"] for s in tl["spans"]]
    if "recovery" not in names:
        raise SystemExit(f"crash drill FAILED: no recovery event in the "
                         f"recovered timeline: {names}")
    rec = core._zget(f"/dlaas/jobs/{tid}/record") or {}
    if rec.get("trace_id") and tl["trace_id"] != rec["trace_id"]:
        raise SystemExit(f"crash drill FAILED: timeline trace "
                         f"{tl['trace_id']} != persisted "
                         f"{rec['trace_id']}")
    print(f"crash-recovery drill OK: journal {rep['journal']}, "
          f"{tid} completed after SIGKILL, {eid} serving again, "
          f"idempotent replay returned the original ids, recovery "
          f"event in the persisted trace {tl['trace_id']}")
finally:
    core.close()
EOF

echo "== tier-1 tests (-rs: every skip must name its reason) =="
JAX_PLATFORMS=cpu PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m pytest -x -q -rs "$@"
