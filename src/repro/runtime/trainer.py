"""Production trainer — the pjit/FSDP execution path (the TPU-native
adaptation; the paper-architecture software-PS path is runtime/learner.py).

Features required at 1000-node scale, exercised here at host scale:
  * sharded params/optimizer per distributed/sharding.py policies,
  * periodic async checkpointing + restore-from-latest-valid,
  * step-retry on executor failure, restoring the latest checkpoint
    first (with no checkpoint to restore, the error surfaces at once),
  * ELASTIC restart: ``Trainer.resume(new_dist)`` rebuilds the step on a
    different mesh/learner count and restores the same checkpoint with the
    new shardings (resharding via device_put),
  * metrics emission compatible with the platform MetricsService.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpoint import CheckpointManager
from repro.configs.base import ArchConfig, ShapeSpec
from repro.distributed.sharding import Dist, tree_shardings
from repro.distributed.steps import jit_train_step
from repro.models.model import Model, make_model
from repro.optim.optimizers import (OptConfig, init_opt_state,
                                    opt_state_specs)
from repro.platform.metrics import MetricsService


@dataclass
class TrainerConfig:
    batch: int = 8
    seq: int = 128
    ckpt_every: int = 25
    ckpt_dir: str = "/tmp/repro_ckpt"
    max_step_retries: int = 2
    log_every: int = 10
    job_id: str = "train"
    # (StorageManager, store_id, prefix): object-store checkpoint mirror
    ckpt_mirror: Optional[tuple] = None


class Trainer:
    def __init__(self, cfg: ArchConfig, dist: Dist, opt: OptConfig,
                 tc: TrainerConfig, metrics: Optional[MetricsService] = None,
                 opts: Optional[Dict[str, Any]] = None):
        self.cfg = cfg
        self.opt = opt
        self.tc = tc
        self.metrics = metrics or MetricsService()
        self.opts = opts or {"remat": "none"}
        self.ckpt = CheckpointManager(tc.ckpt_dir, keep=3,
                                      mirror=tc.ckpt_mirror)
        self.step = 0
        self._build(dist)

    # ---- build / rebuild (elastic) ----------------------------------------
    def _build(self, dist: Dist):
        self.dist = dist.resolve_batch(self.tc.batch)
        self.model = make_model(self.cfg, self.dist, self.opts)
        shape = ShapeSpec("trainer", self.tc.seq, self.tc.batch, "train")
        self.shape = shape
        self._step_fn = jit_train_step(self.model, self.opt, shape)

    def init(self, seed: int = 0):
        params = self.model.init(jax.random.PRNGKey(seed))
        opt_state = init_opt_state(self.opt, params)
        if self.dist.has_mesh:
            ps = tree_shardings(self.dist, self.model.param_defs())
            params = jax.device_put(params, ps)
        self.params = params
        self.opt_state = opt_state
        return self

    def _shardings(self):
        if not self.dist.has_mesh:
            return None, None
        from jax.sharding import NamedSharding
        import jax.tree_util as jtu
        pspec = tree_shardings(self.dist, self.model.param_defs())
        ospec = opt_state_specs(self.opt, self.model.param_defs(),
                                self.dist)
        osh = jax.tree.map(
            lambda s: NamedSharding(self.dist.mesh, s), ospec,
            is_leaf=lambda x: hasattr(x, "_normalized_spec")
            or type(x).__name__ == "PartitionSpec")
        return pspec, osh

    # ---- data ---------------------------------------------------------------
    def _batch(self, step: int):
        rng = np.random.Generator(np.random.Philox(key=step))
        toks = rng.integers(0, self.cfg.vocab_size,
                            size=(self.tc.batch, self.tc.seq + 1),
                            dtype=np.int64)
        toks[:, 1::2] = toks[:, 0::2][:, : toks[:, 1::2].shape[1]]
        toks = toks.astype(np.int32)
        b = {"tokens": jnp.asarray(toks[:, :-1]),
             "labels": jnp.asarray(toks[:, 1:])}
        if self.cfg.mrope:
            pos = np.broadcast_to(np.arange(self.tc.seq, dtype=np.int32),
                                  (3, self.tc.batch, self.tc.seq))
            b["positions"] = jnp.asarray(pos)
        if self.cfg.frontend != "none" or self.cfg.family == "encdec":
            raise NotImplementedError(
                "Trainer synthesizes token batches; stub-frontend archs "
                "train via the dry-run path")
        return b

    # ---- loop -----------------------------------------------------------------
    def step_once(self, batch):
        """One supervised step (a failed step is retried from the latest
        checkpoint); records metrics and advances ``self.step``. This is
        the seam the pjit execution backend drives with its own data
        pipeline and watchdog hooks. A step that fails with no
        checkpoint to restore (a compile or out-of-memory error at step
        0, say) raises at once: retrying it would only fail again."""
        tries = 0
        while True:
            try:
                self.params, self.opt_state, loss = self._step_fn(
                    self.params, self.opt_state, batch)
                break
            except Exception:
                tries += 1
                if tries > self.tc.max_step_retries or \
                        self.ckpt.latest_valid() is None:
                    raise
                self._restore_latest()
        loss = float(loss)
        self.metrics.record(self.tc.job_id, "loss", self.step, loss)
        self.step += 1
        return loss

    def train(self, steps: int):
        losses = []
        while self.step < steps:
            losses.append(self.step_once(self._batch(self.step)))
            if self.step % self.tc.ckpt_every == 0:
                self.save()
        self.ckpt.wait()
        return losses

    # ---- checkpoint / restore ----------------------------------------------
    def save(self, extra: Optional[Dict[str, Any]] = None):
        self.ckpt.save(self.step,
                       {"params": self.params, "opt": self.opt_state},
                       extra={"step": self.step, **(extra or {})})

    def _restore_latest(self):
        last = self.ckpt.latest_valid()
        if last is None:
            return
        self.restore(last)

    def restore(self, step: int) -> Dict[str, Any]:
        """Restore params/opt-state; returns the checkpoint's ``extra``
        metadata (step, plus whatever the caller saved — e.g. the data
        cursor position)."""
        tmpl = {"params": self.model.abstract_params(),
                "opt": jax.eval_shape(
                    lambda p: init_opt_state(self.opt, p),
                    self.model.abstract_params())}
        sh = None
        if self.dist.has_mesh:
            psh, osh = self._shardings()
            sh = {"params": psh, "opt": osh}
        tree, extra = self.ckpt.restore(step, tmpl, sh)
        self.params = tree["params"]
        self.opt_state = tree["opt"]
        self.step = int(extra.get("step", step))
        return extra

    # ---- elastic scaling ---------------------------------------------------
    def resume(self, new_dist: Dist) -> "Trainer":
        """Continue the SAME run on a different mesh (elastic scaling):
        checkpoint now, rebuild step/shardings, restore with resharding."""
        self.save()
        self.ckpt.wait()
        step = self.step
        self._build(new_dist)
        self.restore(self.ckpt.latest_valid())
        assert self.step == step, (self.step, step)
        return self
