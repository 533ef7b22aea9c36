"""Open-loop serving through the platform's API entry.

Set-up: a ``DLaaSCore``, one endpoint deployed from the configuration's
registry id at its serving sizes, the benchmark's own weights put in place
of the fresh ones, and every program the traffic can reach compiled and
run once (each prompt length on the grid at each prefill batch from 1 to
the capacity, the splice, the decode step). The window then sends the
seed's schedule through ``DLaaSCore.predict``, one blocking call per
request on a thread of its own, each timed from its due time. Afterwards
the endpoint is stopped and a sample of what it served is compared with
the plain reference.
"""
from __future__ import annotations

import concurrent.futures as cf
import shutil
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from bench import compare, harness, trace as tracing, traffic as gen
from bench.reference import common

# requests compared per run: the longest and others drawn from the seed,
# until this many served tokens
COMPARE_TOKENS = 512
# how long past the window a request may take before it counts as never
# answered
GRACE_S = 60.0
# length of the traced part of the window (``--trace 1``), centred in it
TRACE_S = 10.0


@dataclass
class Outcome:
    e2e: Dict[str, float]
    counters: Dict[str, float]
    window_compiles: int
    attempted: int
    failed: int
    done: List[dict]
    lateness_p95_s: float
    alerts: List[str]
    trace: Optional[tracing.Trace] = None
    notes: List[str] = field(default_factory=list)


class Session:
    """One endpoint of the cell, deployed, loaded and warmed."""

    def __init__(self, cell: harness.Cell, clog: harness.CompileLog,
                 cache_all: bool = False):
        from repro.service.core import DLaaSCore
        self.cell, self.clog = cell, clog
        self.config = cfg = cell.config
        self.srv = cfg["serving"]
        self.ref = harness.reference(cfg["family"])
        self.arch = harness.register_config(cfg)
        harness.check_program_config(self.arch, cfg, self.ref)
        self.workdir = tempfile.mkdtemp(prefix="bench-")
        self.core = DLaaSCore(self.workdir)
        if cache_all:
            # every program goes to the persistent cache, the small ones
            # the engine runs around prefill too, so that a run after the
            # cell's first loads all of them and compiles none
            import jax
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        # the SLO engine keeps evaluating and its alerts are reported, but
        # its remediation (shed load, then recycle the server task at a
        # larger capacity) would recompile and reload the endpoint inside
        # the window
        self.core.health.remediate = False
        self.eid = self.core.deploy_endpoint(
            arch=self.arch, capacity=self.srv["capacity"],
            max_queue=self.srv["max_queue"], max_new=16,
            max_seq=self.srv["max_seq"])["endpoint_id"]
        self._wait_state(("READY",), 900)
        ep = self.core.endpoints[self.eid]
        self.engine, self.control = ep.engine, ep.plan.control
        self.params = None
        self.comparator = compare.Comparator(
            self.ref, cfg, self.srv["max_seq"], self.ref.REF_BATCH)

    # ---- set-up ------------------------------------------------------------
    def _wait_state(self, want, timeout):
        t0 = time.time()
        while time.time() - t0 < timeout:
            st = self.core.endpoint_status(self.eid)["state"]
            if st in want:
                return
            if st in ("FAILED", "STOPPED"):
                raise RuntimeError(f"endpoint {self.eid} is {st}")
            time.sleep(0.02)
        raise RuntimeError(f"endpoint {self.eid} not {want} in {timeout}s")

    def load_weights(self, seed: int):
        """Put the benchmark's weights for ``seed`` in place of the
        engine's; the tree must have the reference's layout."""
        import jax
        specs = self.ref.param_specs(self.config)
        want = common.shapes(specs, self.config["dtype"])
        have = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                            self.engine.params)
        if jax.tree.structure(want) != jax.tree.structure(have) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype)
                for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have))):
            raise RuntimeError("the program's weight tree differs from the "
                               f"{self.config['family']} reference's layout")
        self.engine.params = self.params = None      # free before making new
        self.params = common.make_params(specs, seed, self.config["dtype"])
        jax.block_until_ready(self.params)
        self.engine.params = self.params

    def warm(self):
        """Run every prefill shape once through the engine, with the
        decode step and the splice; each compiles, or loads from the
        persistent cache, here and not in the window."""
        lengths = gen.prompt_lengths(self.cell.traffic)
        cap = self.srv["capacity"]
        groups = [(L, b) for b in range(cap, 0, -1) for L in lengths]
        m0 = time.monotonic()
        rng = np.random.default_rng(0)
        vocab = self.config["vocab_size"]
        with cf.ThreadPoolExecutor(cap) as pool:
            for rnd in _rounds(groups, cap):
                reqs = [rng.integers(0, vocab, L, dtype=np.int32)
                        for L, b in rnd for _ in range(b)]
                # paused, the server loop admits nothing; on resume it
                # prefills each group of equal lengths as one batch
                self.control.pause()
                time.sleep(0.05)
                futs = [pool.submit(self.core.predict, self.eid, p,
                                    max_new=2, timeout=600) for p in reqs]
                while (self.engine.stats()["queue_depth"] < len(reqs)
                       and not any(f.done() for f in futs)):
                    time.sleep(0.001)
                self.control.resume()
                for f in futs:
                    f.result()
        t0 = time.time()
        while time.time() - t0 < 600:       # the decode step's roofline
            perf = self.core.endpoint_status(self.eid)["perf"]
            if perf.get("state") in ("ready", "error", "disabled"):
                break
            time.sleep(0.05)
        kinds = [k for _, k, _ in self.clog.between(m0, time.monotonic())]
        self.warm_note = (
            f"warm-up: {time.monotonic() - m0:.3f} s, {len(groups)} prefill shapes; "
            f"{kinds.count('compile')} programs compiled or loaded, "
            f"{kinds.count('cache_hit')} of them from the persistent cache")

    # ---- the window --------------------------------------------------------
    def window(self, seed: int, seconds: float, rate: float,
               trace_dir: Optional[str] = None) -> Outcome:
        from repro.platform.cluster import UserError
        from repro.serving.engine import (DeadlineExceeded, EndpointClosed,
                                          QueueFull)
        sched = gen.schedule(self.cell.traffic, rate, seconds, seed,
                             self.config["vocab_size"])
        limit_s = seconds + GRACE_S
        results: Dict[int, tuple] = {}

        def call(req: gen.Request):
            try:
                out = self.core.predict(self.eid, req.prompt,
                                        max_new=req.max_new, timeout=limit_s)
            except QueueFull:
                return "refused", time.monotonic(), None
            except (DeadlineExceeded, EndpointClosed, UserError,
                    RuntimeError) as e:
                return f"failed: {type(e).__name__}", time.monotonic(), None
            return "done", time.monotonic(), out["tokens"]

        stats0 = self.engine.stats()
        wall0 = time.time()
        t0 = time.monotonic()
        tracer = None
        if trace_dir is not None:
            tracer = _Tracer(trace_dir, t0 + max(0.0, (seconds - TRACE_S) / 2),
                             min(TRACE_S, seconds))
        late = []
        pool = cf.ThreadPoolExecutor(self.srv["max_queue"]
                                     + self.srv["capacity"] + 16)
        futs = {}
        for req in sched:
            due = t0 + req.due_s
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            futs[req.index] = pool.submit(call, req)
            late.append(time.monotonic() - due)
        t_end = t0 + seconds
        if t_end > time.monotonic():
            time.sleep(t_end - time.monotonic())
        stats1 = self.engine.stats()
        compiles = len(self.clog.between(t0, t_end))
        if tracer is not None:
            tracer.join()
        cf.wait(futs.values(), timeout=max(0.0, t0 + limit_s - time.monotonic()))
        t_stop = time.monotonic()
        for i, f in futs.items():
            results[i] = f.result() if f.done() else ("never answered", t_stop, None)
        pool.shutdown(wait=False, cancel_futures=True)

        lat, norm, done, toks_in_window, failed = [], [], [], 0, 0
        for req in sched:
            status, t_done, tokens = results[req.index]
            due = t0 + req.due_s
            if status == "done":
                lat.append(t_done - due)
                norm.append((t_done - due) / len(tokens))
                done.append({"prompt": req.prompt, "tokens": tokens})
                if t_done <= t_end:
                    toks_in_window += len(tokens)
            else:
                failed += 1
                lat.append(t_stop - due)          # sorts last: the whole run
                norm.append(t_stop - due)
        e2e = {
            "serve_latency_p95_s": float(np.percentile(lat, 95)),
            "serve_norm_latency_p95_s": float(np.percentile(norm, 95)),
            "serve_tokens_per_s": toks_in_window / seconds,
        }
        counters = {k: stats1[k] - stats0[k] for k in
                    ("decode_steps", "occupied_slot_steps", "completed_total",
                     "tokens_out_total", "rejected_total", "expired_total",
                     "failed_total")}
        counters["capacity"] = self.srv["capacity"]
        return Outcome(
            e2e=e2e, counters=counters, window_compiles=compiles,
            attempted=len(sched), failed=failed, done=done,
            lateness_p95_s=float(np.percentile(late, 95)),
            alerts=self._alerts_since(wall0),
            trace=tracer.result() if tracer is not None else None)

    def _alerts_since(self, wall0: float) -> List[str]:
        rep = self.core.alerts()
        out = [f"{a['name']}({a['scope']}) since {a['since'] - wall0:+.1f}s"
               for a in rep["active"] + rep["history"]
               if (a["resolved_at"] or time.time()) >= wall0]
        out += [f"remediation {r['action']} for {r['alert']} at "
                f"{r['ts'] - wall0:+.1f}s" for r in rep["remediations"]
                if r["ts"] >= wall0]
        return out

    # ---- after the window --------------------------------------------------
    def stop(self):
        """Stop the endpoint and free its cache and jits; the benchmark's
        weights stay for the reference."""
        self.core.stop_endpoint(self.eid)
        self._wait_state(("STOPPED",), 300)

    def compare(self, done: List[dict], seed: int, control: bool = False):
        items = [(r["prompt"], r["tokens"])
                 for r in compare.sample(done, seed, COMPARE_TOKENS)]
        if not items:
            return None, 0
        if control:
            return self.comparator.control_gap(self.params, items)
        return self.comparator.widest_gap(self.params, items)

    def close(self):
        try:
            self.core.close()
        finally:
            shutil.rmtree(self.workdir, ignore_errors=True)


def _rounds(groups, capacity):
    """Pack (length, batch) groups into rounds that fill at most the
    capacity, with no two neighbours of one length (they would merge)."""
    rounds: List[list] = []
    for g in groups:
        for r in rounds:
            if sum(b for _, b in r) + g[1] <= capacity and r[-1][0] != g[0]:
                r.append(g)
                break
        else:
            rounds.append([g])
    return rounds


class _Tracer(threading.Thread):
    """Profiles ``length`` seconds of the window from ``start``."""

    def __init__(self, log_dir: str, start: float, length: float):
        super().__init__(daemon=True)
        self.log_dir, self.start_at, self.length = log_dir, start, length
        self.error: Optional[BaseException] = None
        self.start()

    def run(self):
        import jax
        try:
            time.sleep(max(0.0, self.start_at - time.monotonic()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(tracing.WINDOW_ANNOTATION):
                time.sleep(self.length)
            jax.profiler.stop_trace()
        except BaseException as e:      # reported by result()
            self.error = e

    def result(self) -> tracing.Trace:
        from pathlib import Path
        if self.error is not None:
            raise self.error
        found = sorted(Path(self.log_dir).rglob("*.xplane.pb"))
        if not found:
            raise RuntimeError(f"no trace written under {self.log_dir}")
        return tracing.load(found[-1])


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, clog: harness.CompileLog, check_chip: bool = True):
    """One run of a serve cell: returns (Outcome, checks, correct,
    memory_peak_bytes, setup_s)."""
    rate = float(cell.params["rate_rps"])
    sess = Session(cell, clog, cache_all=check_chip)
    try:
        sess.load_weights(seed)
        sess.warm()
        setup_s = time.time() - t_start
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        try:
            out = sess.window(seed, seconds, rate, trace_dir)
        finally:
            if trace_dir:
                shutil.rmtree(trace_dir, ignore_errors=True)
        sess_warm_note = sess.warm_note
        mem = harness.memory_peak_bytes(cell.chips) if check_chip else None
        sess.stop()
        gap, n = sess.compare(out.done, seed)
    finally:
        sess.close()
    out.e2e["setup_s"] = setup_s
    limit = float(cell.params["widest_logit_gap_limit"])
    checks = {"widest_logit_gap": {"value": gap, "limit": limit,
                                   "tokens": n}}
    correct = gap is not None and n > 0 and gap <= limit
    out.notes += [
        sess_warm_note,
        f"generator lateness p95 {out.lateness_p95_s * 1e3:.3f} ms",
        f"window: {out.attempted} requests, {out.failed} failed, "
        f"{out.window_compiles} programs compiled or loaded; counters {out.counters}",
        "alerts in the window: " + ("; ".join(out.alerts) or "none"),
    ]
    return out, checks, correct, mem, setup_s
