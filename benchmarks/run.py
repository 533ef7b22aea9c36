"""Benchmark harness — one benchmark per paper claim/table.

Prints ``name,us_per_call,derived`` CSV. Benches run on the real single
CPU device; anything needing multiple devices (collective byte counts)
spawns a subprocess with forced host devices, mirroring the dry-run.

  ps_vs_broadcast_L{4,8}   paper §Learner Coordination: O(L) vs O(L^2)
                           bytes from compiled HLO (derived = byte ratio)
  software_ps_round        paper §Parameter Server throughput-critical path
  solver_*                 paper §PS solvers: rounds to reach loss<0.05
  scheduler_colloquium     paper §Usage Study: 45 users / 135 jobs burst
  cursor_claims            paper §Global Cursor: claims/s (8 threads)
  kernel_*                 Pallas kernels (interpret) vs jnp oracle
  checkpoint_save/restore  paper §Fault tolerance: MB/s
  quantize_throughput      gradient compression: MB/s + compression ratio
  rest_api                 paper §API layer: requests/s
  roofline_table           §Roofline summary over results/dryrun artifacts
  backends                 execution backends (software-ps vs pjit) on one
                           smoke manifest: steps/s + time-to-first-
                           checkpoint -> BENCH_backends.json at repo root
  ps_dataplane             software-PS data plane: compression none vs
                           int8 on the same smoke manifest: steps/s,
                           bytes on wire, fused-aggregation ms/round,
                           final-loss delta -> BENCH_ps_dataplane.json
                           (env: PS_DATAPLANE_STEPS, PS_DATAPLANE_OUT
                           for the scripts/verify.sh smoke invocation)
  serving                  inference endpoint (serving subsystem) under
                           closed-loop client load at 2-3 offered
                           concurrencies: req/s, p50/p99 latency, mean
                           batch occupancy -> BENCH_serving.json
                           (env: SERVING_LOADS, SERVING_REQUESTS,
                           SERVING_OUT)

Pass bench-name substrings as argv to run a subset, e.g.
``python benchmarks/run.py backends`` or
``python benchmarks/run.py ps-dataplane``.

``python benchmarks/run.py gate`` is the perf regression gate: it
re-runs the three trajectory benches (backends, ps_dataplane, serving)
into a temp dir and compares every rate metric against the committed
BENCH_*.json baselines with a wide tolerance band
(``GATE_TOLERANCE``, default 0.5 — container speed varies several-fold
between runs, so the gate catches collapses, not noise). Exit 1 iff a
metric regresses; the final ``GATE {...}`` line is machine-readable.
``GATE_BENCHES`` subsets the gated files.
"""
import json
import subprocess
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

ROWS = []


def emit(name, us, derived=""):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}", flush=True)


def timeit(fn, n=5, warmup=1):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


# ---------------------------------------------------------------------------


def bench_ps_vs_broadcast():
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, re, json
sys.path.insert(0, %r)
import jax, jax.numpy as jnp
from repro.core.solvers import SolverConfig, make_solver
from repro.optim.optimizers import OptConfig
from repro.launch.mesh import make_mesh
from repro.analysis.roofline import analyze_hlo_text

out = {}
for nl in (4, 8):
    mesh = make_mesh(data=nl, model=1)
    D = 4096
    loss = lambda p, b: jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)
    p0 = {"w": jnp.zeros((D,))}
    batches = {"x": jnp.zeros((1, nl, 4, D)), "y": jnp.zeros((1, nl, 4))}
    res = {}
    for mode in ("ps", "broadcast"):
        s = make_solver(loss, p0, OptConfig(name="sgd"),
                        SolverConfig(name="psgd", push_mode=mode), nl,
                        mesh=mesh)
        st = s.init_state(p0)
        txt = jax.jit(s._round).lower(st, batches).compile().as_text()
        a = analyze_hlo_text(txt)
        res[mode] = a["ici_bytes_per_device"]
    out[nl] = res
print("RESULT " + json.dumps(out))
""" % str(ROOT / "src")
    t0 = time.perf_counter()
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600)
    us = (time.perf_counter() - t0) * 1e6
    line = [l for l in p.stdout.splitlines() if l.startswith("RESULT")]
    if not line:
        emit("ps_vs_broadcast", us, f"ERROR:{p.stderr[-200:]}")
        return
    res = json.loads(line[0][7:])
    for nl, r in sorted(res.items()):
        ratio = r["broadcast"] / max(r["ps"], 1)
        emit(f"ps_vs_broadcast_L{nl}", us / len(res),
             f"bytes_ps={r['ps']:.0f};bytes_bc={r['broadcast']:.0f};"
             f"ratio={ratio:.2f}")


def bench_software_ps():
    from repro.core.software_ps import SoftwareParameterServer
    f = 1 << 20
    init = np.zeros(f, np.float32)
    ps = SoftwareParameterServer(init, n_shards=4, n_learners=4,
                                 optimizer="adam", lr=1e-3)
    for i in range(4):
        ps.join(i)
    g = [np.random.randn(f).astype(np.float32) for _ in range(4)]

    def round_():
        ts = [threading.Thread(target=ps.push, args=(i, g[i]))
              for i in range(4)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        ps.pull(0)

    us = timeit(round_, n=5)
    mbps = (4 * g[0].nbytes + init.nbytes) / (us / 1e6) / 1e6
    emit("software_ps_round", us, f"agg_MBps={mbps:.0f}")


def bench_solvers():
    import jax
    import jax.numpy as jnp
    from repro.core.solvers import SolverConfig, make_solver
    from repro.optim.optimizers import OptConfig
    D, NL, B = 16, 4, 16
    W = jax.random.normal(jax.random.PRNGKey(0), (D,))
    loss = lambda p, b: jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)
    p0 = {"w": jnp.zeros((D,))}

    def batches(rng, h):
        xs = jax.random.normal(rng, (h, NL, B, D))
        return {"x": xs, "y": xs @ W}

    for scfg in (SolverConfig(name="psgd"),
                 SolverConfig(name="psgd", compress=True),
                 SolverConfig(name="modelavg", comm_every=4),
                 SolverConfig(name="easgd", comm_every=4),
                 SolverConfig(name="downpour", comm_every=4)):
        s = make_solver(loss, p0, OptConfig(name="sgd", lr=0.1), scfg, NL)
        st = s.init_state(p0)
        rng = jax.random.PRNGKey(1)
        rounds = 0
        t0 = time.perf_counter()
        m = {"loss": 1e9}
        while float(m["loss"]) > 0.05 and rounds < 400:
            rng, k = jax.random.split(rng)
            st, m = s.round(st, batches(k, scfg.rounds_h))
            rounds += 1
        us = (time.perf_counter() - t0) / max(rounds, 1) * 1e6
        tag = scfg.name + ("_q8" if scfg.compress else "")
        emit(f"solver_{tag}", us,
             f"rounds_to_0.05={rounds};steps={rounds * scfg.rounds_h};"
             f"wire_B_per_round={s.wire_bytes_per_round()}")


def bench_scheduler():
    import tempfile

    from repro.service.core import DLaaSCore, default_cluster
    wd = tempfile.mkdtemp(prefix="dlaas_bench_")
    core = DLaaSCore(wd, cluster=default_cluster(16, 8),
                     tick_interval=0.002)
    MAN = ("name: b\nlearners: 1\ngpus: %d\nsteps: 1\n"
           "framework:\n  name: repro-mlp\n  d_in: 8\n  n_classes: 2\n")
    try:
        t0 = time.perf_counter()
        tids = []
        lock = threading.Lock()

        def user(u):
            mid = core.deploy_model(MAN % (1 + u % 3),
                                    user=f"u{u}")["model_id"]
            got = [core.create_training(mid, user=f"u{u}")["training_id"]
                   for _ in range(3)]
            with lock:
                tids.extend(got)

        ts = [threading.Thread(target=user, args=(u,)) for u in range(15)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        done = sum(1 for t in tids
                   if core.wait_for(t, timeout=240) == "COMPLETED")
        dt = time.perf_counter() - t0
        emit("scheduler_colloquium", dt / max(len(tids), 1) * 1e6,
             f"jobs={len(tids)};completed={done};makespan_s={dt:.1f};"
             f"jobs_per_s={len(tids) / dt:.1f}")
    finally:
        core.close()


def bench_cursor():
    from repro.core.cursor import GlobalCursor
    from repro.platform.zookeeper import ZooKeeper
    cur = GlobalCursor(ZooKeeper(), "/c", 10 ** 9)
    n = 2000

    def claims():
        ts = []
        for _ in range(8):
            t = threading.Thread(
                target=lambda: [cur.next_chunk(16)
                                for _ in range(n // 8)])
            ts.append(t)
        [t.start() for t in ts]
        [t.join() for t in ts]

    us = timeit(claims, n=3)
    emit("cursor_claims", us / n, f"claims_per_s={n / (us / 1e6):.0f}")


def bench_kernels():
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.models.attention import flash_attention_ref, repeat_kv

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 256, 4, 64))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 2, 64))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 256, 2, 64))
    o1 = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    o2 = flash_attention_ref(q, repeat_kv(k, 4), repeat_kv(v, 4),
                             causal=True, q_chunk=64, k_chunk=64)
    err = float(jnp.max(jnp.abs(o1 - o2)))
    us = timeit(lambda: jax.block_until_ready(
        ops.flash_attention(q, k, v, causal=True, block_q=64,
                            block_k=64)), n=3)
    emit("kernel_flash_attn_interp", us, f"allclose_err={err:.2e}")

    x = jax.random.normal(jax.random.PRNGKey(3), (1, 256, 4, 32)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(4),
                                           (1, 256, 4)))
    b = jax.random.normal(jax.random.PRNGKey(5), (1, 256, 1, 16)) * 0.3
    c = jax.random.normal(jax.random.PRNGKey(6), (1, 256, 1, 16)) * 0.3
    from repro.models.mamba import ssd_scan_ref
    y1 = ops.ssd_scan(x, dt, jnp.zeros(4), b, c, chunk=64)
    y2, _ = ssd_scan_ref(x, dt, jnp.zeros(4), b, c, chunk=64)
    err = float(jnp.max(jnp.abs(y1 - y2)))
    us = timeit(lambda: jax.block_until_ready(
        ops.ssd_scan(x, dt, jnp.zeros(4), b, c, chunk=64)), n=3)
    emit("kernel_ssd_scan_interp", us, f"allclose_err={err:.2e}")

    g = jax.random.normal(jax.random.PRNGKey(7), (4, 1 << 16))
    p = jax.random.normal(jax.random.PRNGKey(8), (1 << 16,))
    m = jnp.zeros(1 << 16)
    us = timeit(lambda: jax.block_until_ready(
        ops.ps_aggregate(g, p, m, m, 1, solver="adam")), n=3)
    emit("kernel_ps_aggregate_interp", us,
         f"elems_per_s={(1 << 16) / (us / 1e6):.2e}")


def bench_checkpoint():
    import tempfile

    import jax.numpy as jnp
    from repro.checkpoint.checkpoint import CheckpointManager
    d = tempfile.mkdtemp(prefix="ckpt_bench_")
    tree = {"w": jnp.zeros((1 << 22,), jnp.float32)}      # 16 MB
    cm = CheckpointManager(d, async_save=False)
    us_save = timeit(lambda: cm.save(1, tree), n=3)
    emit("checkpoint_save_16MB", us_save,
         f"MBps={16 / (us_save / 1e6):.0f}")
    us_restore = timeit(lambda: cm.restore(1, tree), n=3)
    emit("checkpoint_restore_16MB", us_restore,
         f"MBps={16 / (us_restore / 1e6):.0f}")


def bench_quantize():
    import jax
    import jax.numpy as jnp
    from repro.core.compression import compress_with_feedback, wire_bytes
    x = jax.random.normal(jax.random.PRNGKey(0), (1 << 22,))
    e = jnp.zeros_like(x)
    fn = jax.jit(lambda x, e: compress_with_feedback(x, e))
    jax.block_until_ready(fn(x, e))
    us = timeit(lambda: jax.block_until_ready(fn(x, e)), n=5)
    ratio = (x.size * 4) / wire_bytes(x.size)
    emit("quantize_throughput", us,
         f"MBps={x.size * 4 / (us / 1e6) / 1e6:.0f};"
         f"compression={ratio:.2f}x")


def bench_rest_api():
    import tempfile
    import urllib.request

    from repro.service.rest import DLaaSServer
    wd = tempfile.mkdtemp(prefix="dlaas_rest_")
    with DLaaSServer(wd) as srv:
        man = ("name: x\nlearners: 1\nsteps: 1\n"
               "framework:\n  name: repro-mlp\n")
        body = json.dumps({"manifest": man}).encode()

        def call():
            req = urllib.request.Request(
                f"{srv.url}/v1/models", data=body, method="POST")
            req.add_header("Content-Type", "application/json")
            urllib.request.urlopen(req).read()

        us = timeit(call, n=20)
        emit("rest_api_deploy", us, f"rps={1e6 / us:.0f}")


def bench_backends():
    """Backend trajectory: the same smoke manifest trained through both
    execution backends (runtime/backend.py); emits BENCH_backends.json
    at the repo root with steps/s and time-to-first-checkpoint
    (``BACKENDS_OUT`` redirects it, e.g. for the perf gate)."""
    import os
    import tempfile

    from repro.service.core import DLaaSCore
    out_path = Path(os.environ.get("BACKENDS_OUT",
                                   ROOT / "BENCH_backends.json"))
    MAN = ("name: bench-backends\nlearners: 1\ngpus: 1\nsteps: 30\n"
           "checkpoint_every: 10\nlr: 0.1\noptimizer: sgd\nseed: 0\n"
           "batch_docs: 4\n"
           "data:\n  n_docs: 128\n  seq_len: 16\n"
           "framework:\n  name: repro-lm\n  arch: stablelm-1.6b-smoke\n"
           "  distribution: %s\n")
    out = {}
    for backend in ("software-ps", "pjit"):
        core = DLaaSCore(tempfile.mkdtemp(prefix=f"bench_{backend}_"),
                         tick_interval=0.005)
        try:
            mid = core.deploy_model(MAN % backend)["model_id"]
            t0 = time.time()
            tid = core.create_training(mid)["training_id"]
            status = core.wait_for(tid, timeout=300)
            wall = time.time() - t0
            evs = core.metrics.events(tid, "checkpoint")
            ttfc = evs[0]["ts"] - t0 if evs else None
            loss = core.metrics.series(tid, "loss")
            steps = len(loss.values)
            row = {"status": status, "steps": steps,
                   "wall_s": round(wall, 3),
                   "steps_per_s": round(steps / wall, 2),
                   "time_to_first_checkpoint_s":
                       round(ttfc, 3) if ttfc is not None else None,
                   "final_loss": (round(loss.values[-1], 4)
                                  if loss.values else None)}
            out[backend] = row
            emit(f"backend_{backend}", wall / max(steps, 1) * 1e6,
                 f"steps_per_s={row['steps_per_s']};"
                 f"ttfc_s={row['time_to_first_checkpoint_s']};"
                 f"final_loss={row['final_loss']}")
        finally:
            core.close()
    out_path.write_text(
        json.dumps({"manifest": "repro-lm/stablelm-1.6b smoke, 30 steps",
                    "note": ("both backends measured in one process on "
                             "the same machine — compare within a file, "
                             "not across commits: container speed varies "
                             "several-fold between runs, and the jax "
                             "persistent compile cache (.jax_cache) "
                             "makes repeat invocations warm-start"),
                    "backends": out}, indent=1) + "\n")


def bench_ps_dataplane():
    """Data-plane trajectory: the backends smoke manifest through the
    software-PS with compression none vs int8. Emits
    BENCH_ps_dataplane.json with steps/s, bytes on the wire (pre/post
    compression), fused-aggregation ms/round and the compressed-vs-
    uncompressed final-loss delta. ``PS_DATAPLANE_STEPS`` /
    ``PS_DATAPLANE_OUT`` shrink + redirect it for CI smoke runs."""
    import os
    import tempfile

    from repro.service.core import DLaaSCore
    steps = int(os.environ.get("PS_DATAPLANE_STEPS", "30"))
    out_path = Path(os.environ.get("PS_DATAPLANE_OUT",
                                   ROOT / "BENCH_ps_dataplane.json"))
    MAN = ("name: bench-ps-dataplane\nlearners: 1\ngpus: 1\n"
           f"steps: {steps}\n"
           "checkpoint_every: 1000000\nlr: 0.1\noptimizer: sgd\nseed: 0\n"
           "batch_docs: 4\n"
           "data:\n  n_docs: 128\n  seq_len: 16\n"
           "framework:\n  name: repro-lm\n  arch: stablelm-1.6b-smoke\n"
           "  distribution: software-ps\n  compression: %s\n")
    out = {}
    for comp in ("none", "int8"):
        core = DLaaSCore(tempfile.mkdtemp(prefix=f"bench_dp_{comp}_"),
                         tick_interval=0.005)
        try:
            mid = core.deploy_model(MAN % comp)["model_id"]
            t0 = time.time()
            tid = core.create_training(mid)["training_id"]
            status = core.wait_for(tid, timeout=300)
            wall = time.time() - t0
            loss = core.metrics.series(tid, "loss")
            dp = core.training_status(tid).get("data_plane") or {}
            n = len(loss.values)
            # per-step loss swings ~±5% with batch noise; the quality
            # comparison uses a tail-window mean so it measures the
            # trajectory, not one noisy sample
            tail = loss.values[-min(10, max(1, n // 3)):]
            row = {"status": status, "steps": n,
                   "wall_s": round(wall, 3),
                   "steps_per_s": round(n / wall, 2),
                   "final_loss": (round(sum(tail) / len(tail), 4)
                                  if tail else None),
                   "last_step_loss": (round(loss.values[-1], 4)
                                      if loss.values else None),
                   "bytes_pushed_wire": dp.get("bytes_pushed_wire"),
                   "bytes_pushed_dense": dp.get("bytes_pushed_dense"),
                   "compression_ratio": dp.get("compression_ratio"),
                   "agg_ms_per_round": dp.get("agg_ms_per_round")}
            out[comp] = row
            emit(f"ps_dataplane_{comp}", wall / max(n, 1) * 1e6,
                 f"steps_per_s={row['steps_per_s']};"
                 f"wire_ratio={row['compression_ratio']};"
                 f"agg_ms={row['agg_ms_per_round']};"
                 f"final_loss={row['final_loss']}")
        finally:
            core.close()
    summary = {"manifest": f"repro-lm/stablelm-1.6b smoke, {steps} steps",
               "pr2_baseline_steps_per_s": 3.49,
               "modes": out}
    ln, li = out["none"]["final_loss"], out["int8"]["final_loss"]
    if ln and li:
        summary["final_loss_rel_delta"] = round(abs(li - ln) / abs(ln), 4)
    wn = out["int8"]
    if wn["bytes_pushed_wire"]:
        summary["wire_bytes_reduction"] = round(
            wn["bytes_pushed_dense"] / wn["bytes_pushed_wire"], 3)
    out_path.write_text(json.dumps(summary, indent=1) + "\n")


def bench_serving():
    """Serving trajectory: one smoke-arch inference endpoint under
    closed-loop client load at increasing offered concurrency. Emits
    BENCH_serving.json with req/s, p50/p99 request latency and mean
    batch occupancy per load (occupancy measured from the engine's
    occupied-slot-steps delta, so each load reports its own window).
    ``SERVING_LOADS`` / ``SERVING_REQUESTS`` / ``SERVING_OUT`` shrink +
    redirect it for CI smoke runs."""
    import os
    import tempfile

    from repro.service.core import DLaaSCore
    loads = [int(x) for x in
             os.environ.get("SERVING_LOADS", "1,3,6").split(",")]
    n_req = int(os.environ.get("SERVING_REQUESTS", "18"))
    out_path = Path(os.environ.get("SERVING_OUT",
                                   ROOT / "BENCH_serving.json"))
    prompt_len, max_new, capacity = 12, 8, 3
    core = DLaaSCore(tempfile.mkdtemp(prefix="bench_serving_"),
                     tick_interval=0.005)
    rows = {}
    try:
        eid = core.deploy_endpoint(
            arch="stablelm-1.6b-smoke", capacity=capacity,
            max_queue=max(64, n_req), max_new=max_new)["endpoint_id"]
        t0 = time.time()
        while core.endpoint_status(eid)["state"] != "READY":
            if time.time() - t0 > 300:
                raise RuntimeError("endpoint never became READY")
            time.sleep(0.05)
        # warm the prefill jit for the bench prompt length so the first
        # load isn't dominated by one compile
        core.predict(eid, np.arange(prompt_len) + 1, max_new=1)
        for load in loads:
            before = core.endpoint_status(eid)["stats"]
            lats, lock = [], threading.Lock()
            rng = np.random.RandomState(load)
            prompts = [rng.randint(0, 100, size=prompt_len)
                       for _ in range(n_req)]

            def client(idx, load=load, prompts=prompts, lats=lats,
                       lock=lock):
                for i in range(idx, n_req, load):
                    t1 = time.time()
                    core.predict(eid, prompts[i], max_new=max_new)
                    with lock:
                        lats.append(time.time() - t1)

            t1 = time.time()
            ts = [threading.Thread(target=client, args=(k,))
                  for k in range(load)]
            [t.start() for t in ts]
            [t.join() for t in ts]
            wall = time.time() - t1
            after = core.endpoint_status(eid)["stats"]
            d_steps = after["decode_steps"] - before["decode_steps"]
            d_occ = (after["occupied_slot_steps"]
                     - before["occupied_slot_steps"])
            lats.sort()
            row = {
                "offered_clients": load, "requests": n_req,
                "wall_s": round(wall, 3),
                "req_per_s": round(n_req / wall, 2),
                "p50_latency_s": round(lats[len(lats) // 2], 4),
                "p99_latency_s": round(
                    lats[max(0, int(np.ceil(0.99 * len(lats))) - 1)], 4),
                "mean_batch_occupancy": round(
                    d_occ / (d_steps * capacity), 4) if d_steps else None,
                "rejected": after["rejected_total"]
                - before["rejected_total"],
            }
            rows[str(load)] = row
            emit(f"serving_load{load}", wall / n_req * 1e6,
                 f"req_per_s={row['req_per_s']};"
                 f"p50_s={row['p50_latency_s']};"
                 f"p99_s={row['p99_latency_s']};"
                 f"occupancy={row['mean_batch_occupancy']}")
        core.stop_endpoint(eid)
        t0 = time.time()
        while core.endpoint_status(eid)["state"] != "STOPPED" \
                and time.time() - t0 < 60:
            time.sleep(0.05)
    finally:
        core.close()
    out_path.write_text(json.dumps({
        "arch": "stablelm-1.6b smoke",
        "capacity": capacity, "prompt_len": prompt_len,
        "max_new": max_new,
        "note": ("closed-loop clients on one host; compare loads within "
                 "a file, not across commits — container speed varies "
                 "and the jax compile cache warm-starts repeats"),
        "loads": rows}, indent=1) + "\n")


def bench_roofline_table():
    """Summarise §Roofline over existing dry-run artifacts (if present)."""
    from repro.analysis.roofline import (KERNEL_SCOPES, analyze_file,
                                         model_flops, roofline_row)
    from repro.configs.base import SHAPES_BY_NAME
    from repro.configs.registry import get_arch
    d = ROOT / "results" / "dryrun"
    hlos = sorted(d.glob("*__single.hlo.gz")) if d.exists() else []
    if not hlos:
        emit("roofline_table", 0.0, "no_artifacts(run launch/dryrun first)")
        return
    t0 = time.perf_counter()
    worst = (None, 1.0)
    for h in hlos:
        parts = h.name.replace(".hlo.gz", "").split("__")
        if len(parts) != 3 or parts[2] != "single":
            continue
        arch, shape = parts[0], parts[1]
        try:
            a = analyze_file(str(h), KERNEL_SCOPES)
            row = roofline_row({}, a, get_arch(arch),
                               SHAPES_BY_NAME[shape], 256)
            emit(f"roofline[{arch}|{shape}]",
                 max(a["compute_s"], a["memory_s"],
                     a["collective_s"]) * 1e6,
                 f"dom={row['dominant']};frac={row['roofline_frac']};"
                 f"useful={row['useful_ratio']}")
            if row["roofline_frac"] < worst[1]:
                worst = (f"{arch}|{shape}", row["roofline_frac"])
        except Exception as e:
            emit(f"roofline[{arch}|{shape}]", 0.0,
                 f"ERROR:{type(e).__name__}")
    emit("roofline_table", (time.perf_counter() - t0) * 1e6,
         f"cells={len(hlos)};worst={worst[0]}:{worst[1]}")


# ---------------------------------------------------------------------------
# perf regression gate — compare fresh runs of the trajectory benches
# against the committed BENCH_*.json baselines.

GATE_FILES = {
    "backends": "BENCH_backends.json",
    "ps_dataplane": "BENCH_ps_dataplane.json",
    "serving": "BENCH_serving.json",
}
GATE_OUT_ENV = {
    "backends": "BACKENDS_OUT",
    "ps_dataplane": "PS_DATAPLANE_OUT",
    "serving": "SERVING_OUT",
}


def gate_metrics(doc):
    """Flatten one BENCH_*.json into its higher-is-better rate metrics:
    ``backends.*.steps_per_s``, ``modes.*.{steps_per_s,
    compression_ratio}``, ``loads.*.req_per_s``."""
    out = {}
    for b, row in (doc.get("backends") or {}).items():
        out[f"backends.{b}.steps_per_s"] = row.get("steps_per_s")
    for m, row in (doc.get("modes") or {}).items():
        out[f"modes.{m}.steps_per_s"] = row.get("steps_per_s")
        out[f"modes.{m}.compression_ratio"] = row.get("compression_ratio")
    for ld, row in (doc.get("loads") or {}).items():
        out[f"loads.{ld}.req_per_s"] = row.get("req_per_s")
    return {k: v for k, v in out.items() if v}


def compare(baseline, fresh, tolerance):
    """Pure gate verdict for one bench file. Every rate metric present
    in ``baseline`` must be matched by ``fresh`` at
    ``fresh >= tolerance * baseline`` (all metrics are higher-is-
    better). The tolerance band is deliberately wide by default: the
    baselines' own notes warn that container speed varies several-fold
    between runs, so the gate catches collapses (a kernel accidentally
    falling off its tuned path), not single-digit-percent noise.

    Returns ``{"verdict": "PASS"|"REGRESS"|"MISSING_BASELINE",
    "tolerance": ..., "checks": [{metric, baseline, fresh, ratio,
    ok}, ...]}``."""
    if not baseline:
        return {"verdict": "MISSING_BASELINE", "tolerance": tolerance,
                "checks": []}
    base_m, fresh_m = gate_metrics(baseline), gate_metrics(fresh or {})
    checks, regressed = [], False
    for k, bv in sorted(base_m.items()):
        fv = fresh_m.get(k)
        if fv is None:
            checks.append({"metric": k, "baseline": bv, "fresh": None,
                           "ok": False})
            regressed = True
            continue
        ok = fv >= tolerance * bv
        checks.append({"metric": k, "baseline": bv, "fresh": fv,
                       "ratio": round(fv / bv, 3), "ok": ok})
        regressed = regressed or not ok
    return {"verdict": "REGRESS" if regressed else "PASS",
            "tolerance": tolerance, "checks": checks}


def run_gate(kinds=None) -> int:
    """``python benchmarks/run.py gate [kinds...]``: re-run the
    trajectory benches into a temp dir and compare each against its
    committed baseline. ``GATE_TOLERANCE`` (default 0.5: fresh must
    reach half the baseline rate) widens/narrows the band;
    ``GATE_BENCHES`` subsets the files. Prints per-check lines plus a
    final machine-readable ``GATE {...}`` JSON line; exit 1 iff any
    file regresses (a missing baseline is advisory, not fatal)."""
    import os
    import tempfile
    tol = float(os.environ.get("GATE_TOLERANCE", "0.5"))
    kinds = [k.replace("-", "_") for k in
             (kinds or os.environ.get(
                 "GATE_BENCHES", "backends,ps_dataplane,serving"
             ).split(","))]
    bad = [k for k in kinds if k not in GATE_FILES]
    if bad:
        print(f"gate: unknown bench kind(s) {bad}; "
              f"choose from {sorted(GATE_FILES)}", file=sys.stderr)
        return 2
    benches = {"backends": bench_backends,
               "ps_dataplane": bench_ps_dataplane,
               "serving": bench_serving}
    tmp = Path(tempfile.mkdtemp(prefix="dlaas_gate_"))
    report = {"tolerance": tol, "files": {}}
    verdict = "PASS"
    print("name,us_per_call,derived")
    for kind in kinds:
        base_path = ROOT / GATE_FILES[kind]
        baseline = (json.loads(base_path.read_text())
                    if base_path.exists() else None)
        fresh_path = tmp / GATE_FILES[kind]
        prev = os.environ.get(GATE_OUT_ENV[kind])
        os.environ[GATE_OUT_ENV[kind]] = str(fresh_path)
        try:
            benches[kind]()
        except Exception as e:          # fresh run died -> all checks fail
            print(f"gate[{kind}] bench error: {type(e).__name__}: {e}",
                  file=sys.stderr)
        finally:
            if prev is None:
                os.environ.pop(GATE_OUT_ENV[kind], None)
            else:
                os.environ[GATE_OUT_ENV[kind]] = prev
        fresh = (json.loads(fresh_path.read_text())
                 if fresh_path.exists() else None)
        res = compare(baseline, fresh, tol)
        report["files"][kind] = res
        if res["verdict"] == "REGRESS":
            verdict = "REGRESS"
        elif res["verdict"] == "MISSING_BASELINE" and verdict == "PASS":
            verdict = "MISSING_BASELINE"
        for c in res["checks"]:
            mark = "ok" if c["ok"] else "REGRESS"
            print(f"gate[{kind}] {c['metric']}: "
                  f"{c['fresh']} vs {c['baseline']} "
                  f"(ratio={c.get('ratio')}, need>={tol}) {mark}",
                  flush=True)
        if res["verdict"] == "MISSING_BASELINE":
            print(f"gate[{kind}] MISSING_BASELINE: "
                  f"commit {GATE_FILES[kind]} first", flush=True)
    report["verdict"] = verdict
    print("GATE " + json.dumps(report), flush=True)
    return 1 if verdict == "REGRESS" else 0


def main(only=None) -> None:
    benches = [
        bench_software_ps, bench_solvers, bench_cursor,
        bench_checkpoint, bench_quantize, bench_kernels,
        bench_rest_api, bench_backends, bench_ps_dataplane,
        bench_serving,
        bench_scheduler, bench_ps_vs_broadcast, bench_roofline_table,
    ]
    if only:
        only = [s.replace("-", "_") for s in only]
        benches = [b for b in benches
                   if any(s in b.__name__ for s in only)]
    print("name,us_per_call,derived")
    for b in benches:
        try:
            b()
        except Exception as e:  # keep the harness running
            emit(b.__name__, 0.0, f"ERROR:{type(e).__name__}:{e}")


if __name__ == "__main__":
    if sys.argv[1:2] == ["gate"]:
        sys.exit(run_gate(sys.argv[2:] or None))
    main(sys.argv[1:])
