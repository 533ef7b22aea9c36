"""Pluggable execution backends — the seam between the service's
submit→place→run→checkpoint→complete pipeline and *how* a training
actually executes.

The paper's orchestration layer exists so one service can run jobs
across heterogeneous frameworks and distribution modes (the FfDL
lineage: the platform, not the job, owns the execution strategy). An ``ExecutionBackend`` turns a resource envelope
(``JobSpec``) plus a user manifest into an ``ExecutionPlan`` — the task
sets the Lifecycle Manager deploys — and exposes launch plus
checkpoint/pause/resume hooks:

  * ``software-ps`` — the paper-faithful path: learner threads around a
    sharded ``SoftwareParameterServer`` (runtime/learner.py), with a PS
    app deployed first for multi-learner jobs (§Parameter Server,
    §Global Cursor, §Extensibility plugins).
  * ``pjit`` — the TPU-native adaptation: one SPMD gang driving
    ``Trainer``/``jit_train_step`` with distributed/sharding.py
    policies (runtime/trainer.py). Elastic by construction: every
    (re)incarnation rebuilds the step for the current ``Dist`` and
    restores the latest checkpoint with resharding, so
    preemption-resume and ``resume(new_dist)`` share one path.
  * ``serving`` (registered from serving/endpoint.py) — inference, not
    training: one ``server`` task runs a continuous-batching
    ``InferenceEngine`` until drained; endpoints queue, meter, preempt
    and pause through the identical plan/launch/control machinery.

Queue, fair-share, preemption and PREEMPTED-resume semantics are
backend-independent: both plans flow through the same FairShareQueue /
Scheduler / LCM machinery, and both bodies observe preemption and the
JobControl pause/checkpoint events at step boundaries.
"""
from __future__ import annotations

import io
import threading
import time
from dataclasses import dataclass
from typing import Dict, Optional

import numpy as np

from repro.platform.cluster import Resources, UserError
from repro.platform.lcm import (ExecutionPlan, JobControl, JobSpec,
                                LifecycleManager, PS_RESOURCES, TaskGroup)
from repro.platform.metrics import MetricsService
from repro.platform.storage import StorageManager
from repro.platform.zookeeper import ZooKeeper


def flat_npy(tree) -> bytearray:
    """A parameter tree as ``.npy`` bytes of one flat f32 vector (the
    results-store layout endpoints load, leaves in ``jax.tree`` order).
    Leaves are copied to the host and widened one at a time straight into
    the output buffer, so the host holds one copy of the model."""
    import jax
    leaves = jax.tree.leaves(tree)
    n = sum(int(np.prod(l.shape, dtype=np.int64)) for l in leaves)
    head = io.BytesIO()
    np.lib.format.write_array_header_1_0(
        head, {"descr": np.lib.format.dtype_to_descr(np.dtype(np.float32)),
               "fortran_order": False, "shape": (n,)})
    out = bytearray(head.getbuffer().nbytes + 4 * n)
    out[: head.getbuffer().nbytes] = head.getbuffer()
    flat = np.frombuffer(out, np.float32, offset=head.getbuffer().nbytes)
    off = 0
    for leaf in leaves:
        k = int(np.prod(leaf.shape, dtype=np.int64))
        flat[off: off + k] = np.asarray(leaf).reshape(-1)
        off += k
    return out


@dataclass
class BackendContext:
    """Platform services a backend may wire into its task bodies."""
    zk: ZooKeeper
    storage: StorageManager
    metrics: MetricsService
    workdir: str
    tracer: Optional[object] = None     # observability.trace.Tracer
    loghub: Optional[object] = None     # observability.log.JobLogHub


@dataclass
class JobHandle:
    """A launched job as seen by the service layer: enough to query
    state and drive the backend's lifecycle hooks."""
    job_id: str
    backend: str
    plan: ExecutionPlan
    lcm: LifecycleManager

    def state(self) -> str:
        return self.lcm.job_state(self.job_id)


class ExecutionBackend:
    """Protocol + default hook implementations. Subclasses must set
    ``name`` and implement ``plan``; the control-flow hooks work for any
    plan that carries a JobControl."""

    name: str = "?"

    def plan(self, spec: JobSpec, manifest: Dict,
             ctx: BackendContext) -> ExecutionPlan:
        raise NotImplementedError

    def launch(self, plan: ExecutionPlan,
               lcm: LifecycleManager) -> JobHandle:
        """Hand the plan to the LCM (queue → place → run) and return a
        handle for status/lifecycle operations."""
        lcm.submit_plan(plan)
        return JobHandle(plan.job_id, self.name, plan, lcm)

    # ---- lifecycle hooks (observed at step boundaries) -------------------
    def checkpoint(self, handle: JobHandle):
        """Request an immediate checkpoint from the running job."""
        handle.plan.control.request_checkpoint()

    def pause(self, handle: JobHandle):
        handle.plan.control.pause()

    def resume(self, handle: JobHandle, **kw):
        handle.plan.control.resume()


BACKENDS: Dict[str, ExecutionBackend] = {}


def register_backend(cls):
    BACKENDS[cls.name] = cls()
    return cls


def get_backend(name: str) -> ExecutionBackend:
    try:
        return BACKENDS[name]
    except KeyError:
        raise UserError(
            f"unknown execution backend {name!r}; "
            f"available: {sorted(BACKENDS)}") from None


# ---------------------------------------------------------------------------
# software-ps: learner threads + sharded software parameter server
# ---------------------------------------------------------------------------


@register_backend
class SoftwarePSBackend(ExecutionBackend):
    """Paper-faithful execution: N learner tasks coordinate through a
    sharded in-memory parameter server; multi-learner jobs additionally
    deploy a PS app (deployed first, as in the paper)."""

    name = "software-ps"

    def plan(self, spec: JobSpec, manifest: Dict,
             ctx: BackendContext) -> ExecutionPlan:
        from repro.core.cursor import GlobalCursor
        from repro.core.software_ps import SoftwareParameterServer
        from repro.runtime.learner import (LearnerJobConfig, PLUGINS,
                                           make_learner_body)
        from repro.service.manifest import (resolve_framework,
                                            resolve_ps_options)
        fw_name, fw_cfg = resolve_framework(manifest)
        if fw_name not in PLUGINS:
            raise UserError(f"unsupported framework {fw_name!r}; "
                            f"supported: {sorted(PLUGINS)}")
        compression, ps_shards = resolve_ps_options(manifest)
        jcfg = LearnerJobConfig(
            job_id=spec.job_id,
            framework=fw_name,
            framework_cfg=fw_cfg,
            data_cfg=manifest.get("data", {}) or {},
            n_learners=spec.learners,
            batch_docs=int(manifest.get("batch_docs", 8)),
            steps=int(manifest.get("steps", 40)),
            comm_every=int(manifest.get("comm_every", 1)),
            lr=float(manifest.get("lr", 0.1)),
            optimizer=str(manifest.get("optimizer", "sgd")),
            solver=str(manifest.get("solver", "psgd")),
            compression=compression,
            seed=int(manifest.get("seed", 0)),
            checkpoint_dir=f"{ctx.workdir}/ckpt/{spec.job_id}",
            checkpoint_every=int(manifest.get("checkpoint_every", 20)),
            ckpt_mirror=(ctx.storage, "objectstore",
                         f"ckpt/{spec.job_id}"),
            user_error_at=manifest.get("user_error_at"),
            fail_at_step={int(k): int(v) for k, v in
                          (manifest.get("fail_at_step") or {}).items()},
        )
        plugin = PLUGINS[jcfg.framework](jcfg.framework_cfg)
        # warm the fused train-step compile in the background so it
        # overlaps the init compile below and the deployment; the
        # learner's first step then finds it ready (or waits on it)
        if hasattr(plugin, "warm_async"):
            plugin.warm_async(jcfg.batch_docs, jcfg.data_cfg)
            warming = getattr(plugin, "_warming", None)
            if ctx.tracer is not None and warming is not None:
                wsp = ctx.tracer.start(spec.job_id, "warm_compile",
                                       framework=fw_name)
                threading.Thread(
                    target=lambda: (warming.wait(120.0),
                                    ctx.tracer.end(wsp)),
                    daemon=True).start()
        # flat_state caches the (seed -> flat weights) result, and the
        # plugin is handed to the learner body below — the model is
        # initialized and jitted once per job, not once per layer
        flat0 = plugin.flat_state(jcfg.seed)
        # roofline estimate of the fused step (status.perf): analyzed on
        # a background thread after the warm compile settles
        from repro.analysis.perf import JobPerf
        perf = JobPerf(spec.job_id, ctx.metrics)
        if hasattr(plugin, "lowered_hlo"):
            perf.start_async(
                lambda: plugin.lowered_hlo(jcfg.batch_docs,
                                           jcfg.data_cfg),
                wait_event=getattr(plugin, "_warming", None))
        ps = SoftwareParameterServer(
            flat0, n_shards=ps_shards,
            n_learners=spec.learners,
            optimizer=(jcfg.optimizer if jcfg.solver in
                       ("psgd", "downpour") else "average"),
            lr=jcfg.lr,
            trigger="on_arrival" if jcfg.solver == "downpour" else "bsp",
            compression=compression,
            metrics=ctx.metrics, job_id=spec.job_id)
        cursor = GlobalCursor(
            ctx.zk, f"/dlaas/jobs/{spec.job_id}/cursor",
            dataset_size=int((manifest.get("data") or {}).get(
                "n_docs", 512)))
        results: Dict = {}
        control = JobControl()
        body = make_learner_body(jcfg, ps, cursor, ctx.storage,
                                 ctx.metrics, results, control=control,
                                 plugin=plugin, tracer=ctx.tracer)
        groups = []
        if spec.learners > 1:
            groups.append(TaskGroup(
                "ps", 1,
                Resources(PS_RESOURCES.cpus, PS_RESOURCES.gpus,
                          PS_RESOURCES.memory_mb)))
        groups.append(TaskGroup(
            "learner", spec.learners,
            Resources(spec.cpus_per_learner, spec.gpus_per_learner,
                      spec.memory_mb),
            body=body))
        return ExecutionPlan(
            job_id=spec.job_id, backend=self.name, groups=groups,
            min_alive_fraction=spec.min_alive_fraction,
            tenant=spec.tenant, priority=spec.priority,
            results=results, control=control,
            meta={"ps": ps, "framework": fw_name, "steps": jcfg.steps,
                  "compression": compression, "ps_shards": ps_shards,
                  "perf": perf})


# ---------------------------------------------------------------------------
# pjit: SPMD gang around Trainer / jit_train_step
# ---------------------------------------------------------------------------


@register_backend
class PjitBackend(ExecutionBackend):
    """The fast path: a gang of workers executing one SPMD program
    (``jit_train_step`` with the sharding policies of
    distributed/sharding.py). In the simulated datacenter, worker 0
    drives the program (SPMD: all workers execute the same step) and
    the rest of the gang mirrors liveness; the gang is placed, queued,
    preempted and resumed as a unit. Every incarnation rebuilds the
    step for the current ``Dist`` and restores from the latest valid
    checkpoint — elastic resume and preemption-resume are one path."""

    name = "pjit"

    def plan(self, spec: JobSpec, manifest: Dict,
             ctx: BackendContext) -> ExecutionPlan:
        from repro.configs.registry import DEFAULT_ARCH, resolve_arch
        from repro.core.cursor import GlobalCursor
        from repro.data.pipeline import DatasetSpec

        from repro.service.manifest import resolve_framework
        fw_name, fw_cfg = resolve_framework(manifest)
        if fw_name != "repro-lm":
            raise UserError(
                f"distribution 'pjit' requires a model-zoo framework "
                f"('repro-lm'); got {fw_name!r} — use "
                f"'software-ps' for plugin frameworks")
        arch = fw_cfg.get("arch", DEFAULT_ARCH)
        cfg = resolve_arch(arch)
        data_cfg = manifest.get("data", {}) or {}
        dspec = DatasetSpec(n_docs=int(data_cfg.get("n_docs", 512)),
                            seq_len=int(data_cfg.get("seq_len", 32)),
                            vocab_size=cfg.vocab_size,
                            seed=int(data_cfg.get("seed", 0)))
        cursor = GlobalCursor(ctx.zk,
                              f"/dlaas/jobs/{spec.job_id}/cursor",
                              dataset_size=dspec.n_docs)
        results: Dict = {}
        control = JobControl()
        from repro.analysis.perf import JobPerf
        meta = {"arch": arch, "policy": fw_cfg.get("policy", "fsdp_tp"),
                "steps": int(manifest.get("steps", 40)), "elastic": True,
                # the SPMD step is built by the leader at run time, so
                # the roofline estimate starts there (first incarnation)
                "perf": JobPerf(spec.job_id, ctx.metrics)}
        state = {"done": threading.Event()}
        body = _make_pjit_body(
            job_id=spec.job_id, cfg=cfg, dspec=dspec, cursor=cursor,
            ctx=ctx, control=control, results=results, state=state,
            meta=meta,
            steps=int(manifest.get("steps", 40)),
            batch_docs=int(manifest.get("batch_docs", 8)),
            lr=float(manifest.get("lr", 0.1)),
            optimizer=str(manifest.get("optimizer", "sgd")),
            seed=int(manifest.get("seed", 0)),
            ckpt_every=int(manifest.get("checkpoint_every", 20)),
            user_error_at=manifest.get("user_error_at"),
            fail_at_step={int(k): int(v) for k, v in
                          (manifest.get("fail_at_step") or {}).items()},
        )
        groups = [TaskGroup(
            "worker", spec.learners,
            Resources(spec.cpus_per_learner, spec.gpus_per_learner,
                      spec.memory_mb),
            body=body)]
        return ExecutionPlan(
            job_id=spec.job_id, backend=self.name, groups=groups,
            # an SPMD gang cannot limp along with missing members
            min_alive_fraction=1.0,
            tenant=spec.tenant, priority=spec.priority,
            results=results, control=control, meta=meta)

    def resume(self, handle: JobHandle, new_dist=None, **kw):
        """Elastic resume: an optional new ``Dist`` takes effect on the
        next (re)incarnation — the step is rebuilt and the checkpoint
        restored with the new shardings (Trainer.resume path)."""
        if new_dist is not None:
            handle.plan.meta["next_dist"] = new_dist
        handle.plan.control.resume()


def _make_pjit_body(*, job_id, cfg, dspec, cursor, ctx, control, results,
                    state, meta, steps, batch_docs, lr, optimizer, seed,
                    ckpt_every, user_error_at, fail_at_step):
    """Body fn(watchdog, idx) for one gang member. Worker 0 runs the
    SPMD program; the others mirror liveness until the leader finishes
    (or the gang is preempted/killed)."""

    def leader(wd):
        import jax
        import jax.numpy as jnp
        from repro.data.pipeline import SyntheticCorpus
        from repro.distributed.sharding import Dist
        from repro.optim.optimizers import OptConfig
        from repro.platform.watchdog import CHECKPOINTING, TRAINING
        from repro.runtime.trainer import Trainer, TrainerConfig

        corpus = SyntheticCorpus(dspec)
        # distribution context: an elastic resume's new Dist wins once,
        # then sticks (meta["dist"]) so later preemptions reincarnate at
        # the rescaled distribution; otherwise the manifest's sharding
        # policy applies (mesh-less at smoke scale — policies take
        # effect when a mesh is attached via resume(new_dist))
        dist = (meta.pop("next_dist", None) or meta.get("dist")
                or Dist(policy=meta.get("policy", "fsdp_tp")))
        meta["dist"] = dist
        tc = TrainerConfig(batch=batch_docs, seq=dspec.seq_len,
                           ckpt_every=ckpt_every,
                           ckpt_dir=f"{ctx.workdir}/ckpt/{job_id}",
                           job_id=job_id,
                           ckpt_mirror=(ctx.storage, "objectstore",
                                        f"ckpt/{job_id}"))
        tr = Trainer(cfg, dist, OptConfig(name=optimizer, lr=lr), tc,
                     metrics=ctx.metrics).init(seed)
        perf = meta.get("perf")
        if perf is not None:
            # shapes, not arrays: the step donates params and opt state
            tok = jax.ShapeDtypeStruct((batch_docs, dspec.seq_len),
                                       jnp.int32)
            args = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (tr.params, tr.opt_state))
            # idempotent across incarnations (start_async runs once)
            perf.start_async(lambda: tr._step_fn.lower(
                *args, {"tokens": tok, "labels": tok}).compile().as_text())
        last = tr.ckpt.latest_valid()
        if last is not None:
            extra = tr.restore(last)
            cursor.restore(int(extra.get("epoch", 0)),
                           int(extra.get("offset", 0)))
            wd.log(f"resumed from checkpoint step={tr.step}")

        from repro.observability.trace import (TRACE_STEP_SAMPLE,
                                               maybe_span)
        tracer = ctx.tracer

        def save_ckpt():
            wd.set_status(CHECKPOINTING)
            with maybe_span(tracer, job_id, "checkpoint_publish",
                            step=tr.step):
                epoch, offset = cursor.position()
                tr.save(extra={"epoch": epoch, "offset": offset})
            ctx.metrics.event(job_id, "checkpoint", tr.step)
            wd.set_status(TRAINING)

        loss = None
        t_round = time.time()
        while tr.step < steps:
            # step boundary: preemption, pause and on-demand checkpoint
            wd.maybe_preempt()
            control.wait_while_paused(should_abort=wd.maybe_preempt)
            if control.take_checkpoint_request():
                save_ckpt()
            step = tr.step
            if fail_at_step.get(0) == step:
                fail_at_step.pop(0)          # transient: fires once
                wd.log(f"injected crash at step {step}")
                wd.crash()
                raise RuntimeError("simulated container crash")
            if user_error_at is not None and step == user_error_at:
                raise UserError("bad hyperparameter in user model")
            batch = corpus.batch_for(cursor.next_chunk(batch_docs))
            step_sp = (tracer.start(job_id, "step", step=step)
                       if tracer is not None
                       and step % TRACE_STEP_SAMPLE == 0 else None)
            loss = tr.step_once({"tokens": jnp.asarray(batch["tokens"]),
                                 "labels": jnp.asarray(batch["labels"])})
            if step_sp is not None:
                tracer.end(step_sp, loss=float(loss))
            wd.heartbeat(step, loss=loss)
            wd.log(f"step={step} loss={loss:.4f}")
            ctx.metrics.record(job_id, "lr", step, lr)
            ctx.metrics.record(job_id, "round_time_s", step,
                               time.time() - t_round)
            t_round = time.time()
            if tr.step % ckpt_every == 0:
                save_ckpt()
        # store.sh analogue: upload the trained model
        ctx.storage.upload("results", job_id, "trained_model.npy",
                           flat_npy(tr.params))
        if loss is not None:
            results["final_loss"] = float(loss)
        tr.ckpt.wait()
        state["done"].set()

    def body(wd, idx):
        if idx == 0:
            leader(wd)
        else:
            # gang member: the SPMD program runs everywhere at scale;
            # here it mirrors liveness and yields with the gang
            while not state["done"].is_set():
                wd.maybe_preempt()
                control.wait_while_paused(should_abort=wd.maybe_preempt)
                time.sleep(0.01)

    return body
