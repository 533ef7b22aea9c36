"""Learner runtime — the ``train.sh`` analogue executed inside a simulated
container under watchdog supervision.

Pluggable "frameworks" (paper §Extensibility): each plugin provides the
three-script contract — ``load`` (fetch training data via the Storage
Manager), ``train`` (one local step given a batch), ``store`` (upload the
trained model). Registered plugins play the role of framework Docker
images; adding a family requires only a new plugin.
"""
from __future__ import annotations

import io
import json
import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from repro.checkpoint.checkpoint import CheckpointManager
from repro.core.cursor import GlobalCursor
from repro.core.software_ps import SoftwareParameterServer
from repro.data.pipeline import DatasetSpec, SyntheticCorpus
from repro.observability.trace import TRACE_STEP_SAMPLE, maybe_span
from repro.platform.cluster import UserError
from repro.platform.metrics import MetricsService
from repro.platform.storage import StorageManager
from repro.platform.watchdog import CHECKPOINTING, TRAINING, Watchdog

log = logging.getLogger("repro.learner")


# ---------------------------------------------------------------------------
# Framework plugins
# ---------------------------------------------------------------------------

PLUGINS: Dict[str, Callable] = {}


def _flat_io(abstract_tree):
    """(ravel, unravel) for a fixed pytree layout, built from abstract
    shapes (``jax.eval_shape``) so nothing materializes eagerly. Both
    directions are plain jnp ops, so they fuse into whatever jit they
    are called from; the flat vector is f32 (the PS wire dtype)."""
    leaves, treedef = jax.tree.flatten(abstract_tree)
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    sizes = [int(np.prod(s, dtype=np.int64)) for s in shapes]
    offs = np.concatenate([[0], np.cumsum(sizes)])

    def ravel(tree):
        return jnp.concatenate(
            [jnp.ravel(l).astype(jnp.float32)
             for l in jax.tree.leaves(tree)])

    def unravel(flat):
        return jax.tree.unflatten(treedef, [
            flat[o: o + n].reshape(s).astype(d)
            for o, n, s, d in zip(offs, sizes, shapes, dtypes)])

    return ravel, unravel


def register_plugin(name: str):
    def deco(cls):
        PLUGINS[name] = cls
        return cls
    return deco


@register_plugin("repro-lm")
class LMPlugin:
    """Decoder LM from the model zoo (``framework.arch``: any arch id,
    see configs/registry.py)."""

    def __init__(self, framework_cfg: Dict):
        from repro.configs.registry import DEFAULT_ARCH, resolve_arch
        from repro.distributed.sharding import Dist
        from repro.models import make_model
        cfg = resolve_arch(framework_cfg.get("arch", DEFAULT_ARCH))
        self.cfg = cfg
        self.model = make_model(cfg, Dist(), {"remat": "none",
                                              "xent_chunk": 64,
                                              "q_chunk": 64, "k_chunk": 64})
        self.vocab = cfg.vocab_size
        self._loss_grad = jax.jit(jax.value_and_grad(
            lambda p, b: self.model.loss(p, b)))
        self._flat_lg = None
        self._warming = None

    def init_params(self, seed: int):
        return self.model.init(jax.random.PRNGKey(seed))

    def loss_and_grad(self, params, batch):
        b = {"tokens": jnp.asarray(batch["tokens"]),
             "labels": jnp.asarray(batch["labels"])}
        return self._loss_grad(params, b)

    def _build_flat(self):
        """Build the flat-state jits from abstract shapes only (no
        eager init): ``_init_flat`` (init → flat f32) and ``_flat_lg``
        (flat → loss, flat grads)."""
        if self._flat_lg is not None:
            return
        shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        ravel, unravel = _flat_io(shapes)
        self.flat_size = int(sum(
            np.prod(l.shape, dtype=np.int64)
            for l in jax.tree.leaves(shapes)))
        self._init_flat = jax.jit(lambda k: ravel(self.model.init(k)))

        def lg(f, b):
            loss, g = jax.value_and_grad(self.model.loss)(unravel(f), b)
            return loss, ravel(g)
        self._flat_lg = jax.jit(lg)

    def warm_async(self, batch_docs: int, data_cfg: Dict):
        """Compile the fused train step on a background thread (XLA
        releases the GIL) so it overlaps the plan-time init compile and
        deployment instead of stalling the learner's first step."""
        self._build_flat()
        spec = self.dataset_spec(data_cfg)
        ev = threading.Event()
        self._warming = ev
        zeros = np.zeros((batch_docs, spec.seq_len), np.int32)

        def run():
            try:
                self._flat_lg(np.zeros(self.flat_size, np.float32),
                              {"tokens": jnp.asarray(zeros),
                               "labels": jnp.asarray(zeros)})
            except Exception as e:          # advisory: log, never crash
                log.warning("warmup compile failed: %s: %s",
                            type(e).__name__, e)
            finally:
                ev.set()
        threading.Thread(target=run, daemon=True,
                         name="plugin-warm").start()

    def lowered_hlo(self, batch_docs: int, data_cfg: Dict) -> str:
        """Compiled HLO text of the fused flat train step — feeds the
        status.perf roofline estimate. Called after ``warm_async`` so
        the second lowering rides the persistent compilation cache."""
        self._build_flat()
        spec = self.dataset_spec(data_cfg)
        tok = jax.ShapeDtypeStruct((batch_docs, spec.seq_len), jnp.int32)
        flat = jax.ShapeDtypeStruct((self.flat_size,), jnp.float32)
        return self._flat_lg.lower(
            flat, {"tokens": tok, "labels": tok}).compile().as_text()

    def flat_state(self, seed: int) -> np.ndarray:
        """Initial weights as one flat f32 vector — the learner's
        canonical state on the PS push/pull path. Init, unflatten,
        loss, grad and re-flatten all live inside two jits (built from
        abstract shapes, so nothing runs op-by-op): no per-step eager
        pytree traffic remains (it used to dominate the step). Cached
        per seed: every learner of a job asks for the same vector."""
        cached = getattr(self, "_flat_cache", None)
        if cached is not None and cached[0] == seed:
            return cached[1].copy()
        self._build_flat()
        flat = np.asarray(self._init_flat(jax.random.PRNGKey(seed)))
        self._flat_cache = (seed, flat)
        return flat.copy()

    def flat_loss_grad(self, flat, batch):
        warming = self._warming     # snapshot: learner threads race here
        if warming is not None:
            # first step: ride the background compile instead of racing
            # a second identical compile against it
            warming.wait(timeout=300)
            self._warming = None
        b = {"tokens": jnp.asarray(batch["tokens"]),
             "labels": jnp.asarray(batch["labels"])}
        return self._flat_lg(flat, b)

    def dataset_spec(self, data_cfg: Dict) -> DatasetSpec:
        return DatasetSpec(n_docs=data_cfg.get("n_docs", 512),
                           seq_len=data_cfg.get("seq_len", 32),
                           vocab_size=self.vocab,
                           seed=data_cfg.get("seed", 0))


@register_plugin("repro-mlp")
class MLPPlugin:
    """Minimal classifier used by the colloquium-style hyperparameter
    sweep (CIFAR-like synthetic task)."""

    def __init__(self, framework_cfg: Dict):
        self.d_in = framework_cfg.get("d_in", 32)
        self.d_hidden = framework_cfg.get("d_hidden", 64)
        self.n_classes = framework_cfg.get("n_classes", 10)
        self.vocab = self.n_classes

        def loss_fn(p, batch):
            h = jnp.tanh(batch["x"] @ p["w1"] + p["b1"])
            logits = h @ p["w2"] + p["b2"]
            nll = -jax.nn.log_softmax(logits)[
                jnp.arange(batch["y"].shape[0]), batch["y"]]
            acc = jnp.mean(jnp.argmax(logits, -1) == batch["y"])
            return jnp.mean(nll), acc
        self._loss_fn = loss_fn
        self._lg = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))
        self._flat_lg = None

    def init_params(self, seed: int):
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        s1 = 1.0 / np.sqrt(self.d_in)
        s2 = 1.0 / np.sqrt(self.d_hidden)
        return {"w1": jax.random.normal(k1, (self.d_in, self.d_hidden)) * s1,
                "b1": jnp.zeros(self.d_hidden),
                "w2": jax.random.normal(k2, (self.d_hidden,
                                             self.n_classes)) * s2,
                "b2": jnp.zeros(self.n_classes)}

    def loss_and_grad(self, params, batch):
        x = _synthetic_features(batch["tokens"], self.d_in,
                                self.n_classes)
        (loss, acc), g = self._lg(params, x)
        self.last_acc = float(acc)
        return loss, g

    def flat_state(self, seed: int) -> np.ndarray:
        cached = getattr(self, "_flat_cache", None)
        if cached is not None and cached[0] == seed:
            return cached[1].copy()
        params = self.init_params(seed)
        flat, unravel = ravel_pytree(params)
        if self._flat_lg is None:
            def lg(f, b):
                (loss, acc), g = jax.value_and_grad(
                    self._loss_fn, has_aux=True)(unravel(f), b)
                return loss, acc, ravel_pytree(g)[0]
            self._flat_lg = jax.jit(lg)
        flat = np.asarray(flat)
        self._flat_cache = (seed, flat)
        return flat.copy()

    def flat_loss_grad(self, flat, batch):
        x = _synthetic_features(batch["tokens"], self.d_in,
                                self.n_classes)
        loss, acc, g = self._flat_lg(flat, x)
        self.last_acc = float(acc)
        return loss, g

    def lowered_hlo(self, batch_docs: int, data_cfg: Dict) -> str:
        """Compiled HLO text of the flat step for status.perf."""
        if self._flat_lg is None:
            self.flat_state(0)
        b = _synthetic_features(np.zeros((batch_docs, 2), np.int64),
                                self.d_in, self.n_classes)
        flat = jax.ShapeDtypeStruct((self._flat_cache[1].size,),
                                    jnp.float32)
        return self._flat_lg.lower(flat, b).compile().as_text()

    def dataset_spec(self, data_cfg: Dict) -> DatasetSpec:
        return DatasetSpec(n_docs=data_cfg.get("n_docs", 2048),
                           seq_len=2, vocab_size=1024,
                           seed=data_cfg.get("seed", 0))


def _synthetic_features(tokens: np.ndarray, d_in: int, n_classes: int):
    """Deterministic vision-like task: class = doc token hash; features =
    class prototype + noise (learnable, accuracy can approach 1.0)."""
    rng = np.random.Generator(np.random.Philox(key=1234))
    protos = rng.normal(size=(n_classes, d_in)).astype(np.float32)
    seed_tokens = np.asarray(tokens)[:, 0]
    y = (seed_tokens % n_classes).astype(np.int32)
    noise_rng = np.random.Generator(np.random.Philox(key=99))
    noise = noise_rng.normal(size=(len(y), d_in)).astype(np.float32)
    x = protos[y] + 0.5 * noise
    return {"x": jnp.asarray(x), "y": jnp.asarray(y)}


# ---------------------------------------------------------------------------
# Learner body
# ---------------------------------------------------------------------------


@dataclass
class LearnerJobConfig:
    job_id: str
    framework: str = "repro-lm"
    framework_cfg: Dict = field(default_factory=dict)
    data_cfg: Dict = field(default_factory=dict)
    n_learners: int = 1
    batch_docs: int = 8
    steps: int = 50
    comm_every: int = 1
    lr: float = 0.1
    optimizer: str = "sgd"          # PS-side solver
    solver: str = "psgd"            # psgd | modelavg | easgd | downpour
    compression: str = "none"       # PS push wire format: none | int8
    seed: int = 0
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 20
    # (StorageManager, store_id, prefix): mirror every published
    # checkpoint into the object store (backoff-wrapped uploads)
    ckpt_mirror: Optional[tuple] = None
    # test hooks
    fail_at_step: Dict[int, int] = field(default_factory=dict)
    user_error_at: Optional[int] = None


def make_learner_body(cfg: LearnerJobConfig, ps: SoftwareParameterServer,
                      cursor: GlobalCursor, storage: StorageManager,
                      metrics: MetricsService,
                      results: Optional[Dict] = None,
                      control=None, plugin=None, tracer=None):
    """Returns fn(watchdog, learner_idx) run under the watchdog.

    ``control`` (platform.lcm.JobControl, optional) adds the backend
    lifecycle hooks: pause/resume and on-demand checkpoint, observed at
    step boundaries alongside preemption. ``plugin`` lets the caller
    reuse an already-built framework plugin (the backend builds one at
    plan time to size the PS; rebuilding it here would re-jit the
    model for several seconds)."""
    plugin = plugin or PLUGINS[cfg.framework](cfg.framework_cfg)
    corpus = SyntheticCorpus(plugin.dataset_spec(cfg.data_cfg))

    def body(wd: Watchdog, idx: int):
        ps.join(idx)
        try:
            _train(wd, idx)
        finally:
            ps.leave(idx)

    def _train(wd: Watchdog, idx: int):
        # the learner's canonical state is the flat f32 weight vector —
        # the same representation the PS shards, the checkpoint and the
        # wire use, so nothing re-flattens a pytree on the hot path
        flat = plugin.flat_state(cfg.seed)
        ckpt = None
        start_step = 0
        if cfg.checkpoint_dir and idx == 0:
            ckpt = CheckpointManager(cfg.checkpoint_dir, keep=3,
                                     mirror=cfg.ckpt_mirror)
        # resume from checkpoint if one exists (any learner may restore
        # the global params by pulling after learner-0 pushed them)
        if cfg.checkpoint_dir:
            probe = CheckpointManager(cfg.checkpoint_dir, keep=3)
            last = probe.latest_valid()
            if last is not None:
                tmpl = {"flat": np.zeros_like(flat)}
                tree, extra = probe.restore(last, tmpl)
                start_step = int(extra.get("step", last))
                # learner 0 republishes restored weights to the PS shards
                if idx == 0:
                    ps.load_flat(np.asarray(tree["flat"]))
                    cur_epoch = int(extra.get("epoch", 0))
                    cur_off = int(extra.get("offset", 0))
                    cursor.restore(cur_epoch, cur_off)
                wd.log(f"resumed from checkpoint step={start_step}")

        client = ps.make_client(idx)
        flat = client.pull()

        def save_ckpt(step, flat):
            wd.set_status(CHECKPOINTING)
            with maybe_span(tracer, cfg.job_id, "checkpoint_publish",
                            step=step):
                epoch, offset = cursor.position()
                # copy: the save is async and `flat` may alias the
                # reused pull buffer
                ckpt.save(step, {"flat": np.array(flat)},
                          extra={"step": step, "epoch": epoch,
                                 "offset": offset})
            metrics.event(cfg.job_id, "checkpoint", step)
            wd.set_status(TRAINING)

        t_round = time.time()
        for step in range(start_step, cfg.steps):
            # step boundary: yield to the scheduler if preempted (the
            # last checkpoint is on disk; the requeued task resumes
            # there), honor pause, serve on-demand checkpoint requests
            wd.maybe_preempt()
            if control is not None:
                control.wait_while_paused(should_abort=wd.maybe_preempt)
                # only the checkpointing member (idx 0) consumes the
                # request; others must leave the event set for it
                if ckpt is not None and control.take_checkpoint_request():
                    save_ckpt(step, flat)
            if cfg.fail_at_step.get(idx) == step:
                cfg.fail_at_step.pop(idx)     # transient: fires once
                wd.log(f"injected crash at step {step}")
                wd.crash()
                raise RuntimeError("simulated container crash")
            if cfg.user_error_at is not None and step == cfg.user_error_at:
                raise UserError("bad hyperparameter in user model")
            chunks = cursor.next_chunk(cfg.batch_docs)
            batch = corpus.batch_for(chunks)
            # sampled step spans from the lead learner only: one span
            # every TRACE_STEP_SAMPLE steps keeps the trace ring useful
            step_sp = (tracer.start(cfg.job_id, "step", step=step,
                                    learner=idx)
                       if tracer is not None and idx == 0
                       and step % TRACE_STEP_SAMPLE == 0 else None)
            loss, gflat = plugin.flat_loss_grad(flat, batch)
            if cfg.solver == "psgd":
                t0 = time.time()
                client.push(np.asarray(gflat))
                flat = client.pull()
                sync_s = time.time() - t0
            else:
                # local step; periodic weight sync (modelavg)
                flat = flat - cfg.lr * np.asarray(gflat)
                sync_s = 0.0
                if (step + 1) % cfg.comm_every == 0:
                    t0 = time.time()
                    client.push(flat)
                    flat = client.pull()
                    sync_s = time.time() - t0
            if step_sp is not None:
                tracer.end(step_sp, loss=float(loss))
            wd.heartbeat(step, loss=float(loss))
            wd.log(f"step={step} loss={float(loss):.4f}"
                   + (f" acc={plugin.last_acc:.4f}"
                      if hasattr(plugin, "last_acc") else ""))
            metrics.record(cfg.job_id, "loss", step, float(loss))
            if hasattr(plugin, "last_acc"):
                metrics.record(cfg.job_id, "accuracy", step,
                               plugin.last_acc)
            metrics.record(cfg.job_id, "lr", step, cfg.lr)
            metrics.record(cfg.job_id, "sync_time_s", step, sync_s)
            metrics.record(cfg.job_id, "round_time_s", step,
                           time.time() - t_round)
            t_round = time.time()
            if ckpt is not None and (step + 1) % cfg.checkpoint_every == 0:
                save_ckpt(step + 1, flat)
        # store.sh: upload the trained model
        if idx == 0:
            buf = io.BytesIO()
            np.save(buf, np.asarray(flat))
            storage.upload("results", cfg.job_id, "trained_model.npy",
                           buf.getvalue())
            if results is not None:
                results["final_loss"] = float(loss)
                results["params"] = np.array(flat)
        if ckpt is not None:
            ckpt.wait()

    return body
