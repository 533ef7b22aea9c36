"""Drive the platform's main path once on one TPU chip and check it.

    python chip_smoke.py

Phases, in one process (the chip belongs to one process at a time):

  (a) device   read ``jax.devices()``; anything but a TPU exits non-zero
               before anything else runs.
  (b) serve    deploy a stablelm-1.6b endpoint at its published widths
               (24 layers, d_model 2048, vocabulary 100352, bf16, fresh
               weights from a seed) through ``DLaaSCore``, send predicts
               at two prompt lengths and check the tokens: in range, the
               same prompt gives the same tokens, and the first token is
               the argmax of a direct forward pass of the same weights.
  (c) pjit     train stablelm-1.6b at its published widths on the pjit
               backend for a few steps; optimizer and batch x sequence
               are chosen from ``compiled.memory_analysis()`` so the step
               fits the chip's memory. Losses must be finite.
  (d) ps       train the smoke model on the software parameter server
               with two learners, adam and int8 pushes, and check that
               the Pallas aggregation and quantization kernels ran.

Every phase prints its wall time and its compile time (labelled cold or
warm by the state of the persistent compilation cache). Any failure exits
non-zero; on success the last line is one JSON object naming the device.
"""
from __future__ import annotations

import gc
import json
import math
import os
import shutil
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

SERVE_ARCH = "stablelm-1.6b"
TRAIN_ARCH = "stablelm-1.6b"
PS_ARCH = "stablelm-1.6b-smoke"
SERVE_CAPACITY = 4
SERVE_MAX_SEQ = 1024
SERVE_MAX_NEW = 8
PROMPT_LENS = (96, 700)
TRAIN_STEPS = 4
PS_STEPS = 6
# (optimizer, batch, seq) tried in order; the first whose compiled step
# fits the chip's memory with headroom is trained. adamw keeps two f32
# moments per weight, sgd none.
TRAIN_CANDIDATES = (("adamw", 2, 512), ("sgd", 4, 512), ("sgd", 2, 512),
                    ("sgd", 1, 512))
MEMORY_HEADROOM = 0.85      # of the device's bytes_limit


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


class CompileLog:
    """Backend-compile time and persistent-cache hits, read from JAX's
    monitoring events, so each phase can report its own compile cost."""

    def __init__(self):
        import jax
        self.lock = threading.Lock()
        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0

        def on_duration(event, secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                with self.lock:
                    self.compile_s += secs
                    self.compiles += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                with self.lock:
                    self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self):
        with self.lock:
            return self.compile_s, self.compiles, self.hits

    def since(self, mark):
        s, n, h = self.mark()
        return s - mark[0], n - mark[1], h - mark[2]


def cache_state() -> str:
    """Where the persistent compile cache lives and whether it held
    anything when this process started (cold) or not (warm)."""
    d = Path(os.environ.get("JAX_COMPILATION_CACHE_DIR")
             or ROOT / ".jax_cache")
    n = sum(1 for _ in d.iterdir()) if d.is_dir() else 0
    return f"{'warm' if n else 'cold'} ({n} entries in {d})"


def report(phase, t0, clog, mark, temp):
    secs, n, hits = clog.since(mark)
    print(f"[{phase}] wall {time.time() - t0:.1f}s; compile {secs:.1f}s "
          f"over {n} programs, {hits} persistent-cache hits "
          f"(cache {temp} at start)", flush=True)


def wait_state(get, want, bad, timeout, what):
    t0 = time.time()
    while time.time() - t0 < timeout:
        st = get()
        if st in want:
            return st
        check(st not in bad, f"{what} is {st}")
        time.sleep(0.05)
    raise SmokeFailure(f"{what} still {get()} after {timeout:.0f}s")


def wait_perf(get_perf, what, timeout=600.0):
    """status.perf must finish its analysis without an error."""
    t0 = time.time()
    while time.time() - t0 < timeout:
        perf = get_perf()
        if perf.get("state") in ("ready", "error", "disabled"):
            break
        time.sleep(0.2)
    check(perf.get("state") == "ready",
          f"{what} status.perf: {perf.get('state')} {perf.get('error', '')}")
    print(f"[{what}] status.perf: peaks {perf.get('peaks')} "
          f"({perf.get('device_kind')}): {perf.get('summary')}", flush=True)
    return perf


def phase_serve(core, arch, clog, *, capacity=SERVE_CAPACITY,
                max_seq=SERVE_MAX_SEQ, max_new=SERVE_MAX_NEW,
                prompt_lens=PROMPT_LENS, seed=0):
    import jax
    import numpy as np
    from repro.configs.registry import resolve_arch

    cfg = resolve_arch(arch)
    print(f"[serve] {arch}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab_size}, {cfg.dtype}, "
          f"{cfg.n_params() / 1e9:.3f}B params; capacity {capacity}, "
          f"max_seq {max_seq}", flush=True)
    temp, mark, t0 = cache_state(), clog.mark(), time.time()
    eid = core.deploy_endpoint(arch=arch, capacity=capacity,
                               max_seq=max_seq, max_new=max_new,
                               seed=seed)["endpoint_id"]
    wait_state(lambda: core.endpoint_status(eid)["state"], ("READY",),
               ("FAILED", "STOPPED"), 900, f"endpoint {eid}")
    print(f"[serve] endpoint READY after {time.time() - t0:.1f}s",
          flush=True)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in prompt_lens]
    out = {}
    for i, p in enumerate(prompts):
        for rep in range(2):
            tp = time.time()
            r = core.predict(eid, p.tolist(), max_new=max_new, timeout=600)
            toks = r["tokens"]
            print(f"[serve] prompt {len(p)} run {rep}: {len(toks)} tokens "
                  f"in {time.time() - tp:.2f}s", flush=True)
            check(len(toks) == max_new, f"got {len(toks)} tokens")
            check(all(0 <= t < cfg.vocab_size for t in toks),
                  f"token out of range: {toks}")
            if rep:
                check(toks == out[i], f"prompt {len(p)}: same prompt gave "
                      f"{toks} then {out[i]}")
            out[i] = toks
    # both prompts at once: continuous batching must not change a token
    got = {}
    ts = [threading.Thread(target=lambda i=i: got.__setitem__(
        i, core.predict(eid, prompts[i].tolist(), max_new=max_new,
                        timeout=600)["tokens"]))
          for i in range(len(prompts))]
    [t.start() for t in ts]
    [t.join(timeout=900) for t in ts]
    for i in range(len(prompts)):
        check(got.get(i) == out[i], f"prompt {len(prompts[i])} batched "
              f"with another gave {got.get(i)}, alone {out[i]}")
    # reference: a plain jit forward of the same weights
    engine = core.endpoints[eid].engine
    fwd = jax.jit(engine.model.prefill)
    for i, p in enumerate(prompts):
        logits, _ = fwd(engine.params, {"tokens": p[None]})
        ref = int(np.asarray(jax.numpy.argmax(logits[0, -1])))
        check(ref == out[i][0], f"prompt {len(p)}: first token "
              f"{out[i][0]} != forward-pass argmax {ref}")
    print("[serve] checked: tokens in range, repeatable, unchanged by "
          "batching, first token == forward-pass argmax", flush=True)
    del fwd, logits
    wait_perf(lambda: core.endpoint_status(eid)["perf"], "serve")
    core.stop_endpoint(eid)
    wait_state(lambda: core.endpoint_status(eid)["state"], ("STOPPED",),
               ("FAILED",), 300, f"endpoint {eid}")
    stats = core.endpoint_status(eid)["stats"]
    check(stats["failed_total"] == 0 and stats["expired_total"] == 0,
          f"endpoint errors: {stats}")
    print(f"[serve] endpoint STOPPED; {stats['completed_total']} requests, "
          f"{stats['tokens_out_total']} tokens", flush=True)
    report("serve", t0, clog, mark, temp)
    return out


def choose_train_size(arch, candidates):
    """First (optimizer, batch, seq) whose compiled pjit step leaves
    headroom in the device's memory, from ``memory_analysis()``."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import ShapeSpec
    from repro.configs.registry import resolve_arch
    from repro.distributed.sharding import Dist
    from repro.distributed.steps import jit_train_step
    from repro.models import make_model
    from repro.optim.optimizers import OptConfig, init_opt_state

    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    model = make_model(resolve_arch(arch), Dist(), {"remat": "none"})
    params = model.abstract_params()
    for opt, batch, seq in candidates:
        oc = OptConfig(name=opt, lr=0.01)
        state = jax.eval_shape(lambda p: init_opt_state(oc, p), params)
        tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        step = jit_train_step(model, oc, ShapeSpec("t", seq, batch, "train"))
        try:
            mem = step.lower(params, state, {"tokens": tok, "labels": tok}
                             ).compile().memory_analysis()
        except Exception as e:          # the compiler refused: too big
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            print(f"[pjit] {opt} {batch}x{seq}: does not fit "
                  f"({str(e).splitlines()[0][:160]})", flush=True)
            continue
        need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        fits = limit is None or need <= MEMORY_HEADROOM * limit
        print(f"[pjit] {opt} {batch}x{seq}: {need / 1e9:.2f} GB of "
              f"{(limit or 0) / 1e9:.2f} GB -> "
              f"{'fits' if fits else 'too big'}", flush=True)
        if fits:
            return opt, batch, seq
    raise SmokeFailure(f"no training size fits: {candidates}")


def train_manifest(name, arch, *, distribution, steps, optimizer, batch,
                   seq, learners=1, compression="none", lr=0.01):
    return (f"name: {name}\nlearners: {learners}\ngpus: 1\n"
            f"steps: {steps}\ncheckpoint_every: 100000\nlr: {lr}\n"
            f"optimizer: {optimizer}\nseed: 0\nbatch_docs: {batch}\n"
            f"data:\n  n_docs: 64\n  seq_len: {seq}\n"
            f"framework:\n  name: repro-lm\n  arch: {arch}\n"
            f"  distribution: {distribution}\n"
            f"  compression: {compression}\n")


def run_training(core, manifest, steps, what, timeout=900.0):
    import numpy as np
    mid = core.deploy_model(manifest)["model_id"]
    tid = core.create_training(mid)["training_id"]
    state = core.wait_for(tid, timeout=timeout)
    logs = core.training_logs(tid)
    check(state == "COMPLETED", f"{what} training {tid} is {state}: "
          f"{logs[-5:] if isinstance(logs, list) else logs}")
    losses = core.metrics.series(tid, "loss").values
    check(len(losses) >= steps and all(map(math.isfinite, losses)),
          f"{what} losses: {losses}")
    # each step sees a new batch: the same loss twice in a row means the
    # model's output no longer depends on its input
    check(all(a != b for a, b in zip(losses, losses[1:])),
          f"{what} loss stopped moving: {losses}")
    print(f"[{what}] {tid} COMPLETED; losses "
          f"{[round(float(x), 4) for x in losses]}", flush=True)
    wait_perf(lambda: core.training_status(tid).get("perf", {}), what)
    return tid, np.asarray(losses)


def phase_pjit(core, arch, clog, candidates=TRAIN_CANDIDATES,
               steps=TRAIN_STEPS):
    from repro.configs.registry import resolve_arch
    cfg = resolve_arch(arch)
    temp, mark, t0 = cache_state(), clog.mark(), time.time()
    opt, batch, seq = choose_train_size(arch, candidates)
    print(f"[pjit] {arch}: {opt}, batch {batch} x seq {seq}, depth "
          f"{cfg.n_layers} layers (no cut), d_model {cfg.d_model}",
          flush=True)
    run_training(core, train_manifest(
        "chip-smoke-pjit", arch, distribution="pjit", steps=steps,
        optimizer=opt, batch=batch, seq=seq), steps, "pjit")
    report("pjit", t0, clog, mark, temp)


def phase_ps(core, arch, clog, *, agg_path, quantize_path,
             steps=PS_STEPS):
    temp, mark, t0 = cache_state(), clog.mark(), time.time()
    tid, _ = run_training(core, train_manifest(
        "chip-smoke-ps", arch, distribution="software-ps", steps=steps,
        optimizer="adam", batch=4, seq=32, learners=2,
        compression="int8", lr=0.001), steps, "ps")
    dp = core.training_status(tid)["data_plane"]
    print(f"[ps] data plane: aggregation {dp['agg_path']}, quantization "
          f"{dp['quantize_path']}, {dp['agg_rounds']} rounds, "
          f"{dp['push_count']} pushes, compression ratio "
          f"{dp['compression_ratio']}", flush=True)
    check(dp["agg_path"] == agg_path and dp["quantize_path"] ==
          quantize_path, f"expected {agg_path}/{quantize_path}: {dp}")
    check(dp["agg_rounds"] >= steps and dp["push_timeouts"] == 0,
          f"data plane: {dp}")
    report("ps", t0, clog, mark, temp)


def main() -> int:
    try:
        import jax
        devs = jax.devices()
    except RuntimeError as e:
        print(f"FAIL: JAX found no usable device: {e}", file=sys.stderr)
        return 2
    d0 = devs[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    if d0.platform != "tpu":
        print("FAIL: no TPU; this check runs on the chip only",
              file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"FAIL: the repository's src/ is not beside {__file__}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.service.core import DLaaSCore

    clog = CompileLog()
    workdir = ROOT / ".chip_smoke"
    shutil.rmtree(workdir, ignore_errors=True)
    t0 = time.time()
    core = DLaaSCore(str(workdir))
    try:
        phase_serve(core, SERVE_ARCH, clog)
        gc.collect()
        phase_pjit(core, TRAIN_ARCH, clog)
        gc.collect()
        phase_ps(core, PS_ARCH, clog, agg_path="pallas",
                 quantize_path="pallas")
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    finally:
        core.close()
        shutil.rmtree(workdir, ignore_errors=True)
    print(f"all phases passed in {time.time() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
