import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
# ^ before jax import: this example demonstrates multi-device elasticity
#   on 8 simulated host devices.

"""Elastic scaling + failover with the production trainer.

Phase 1: train on a 4x2 (data x model) mesh.
Phase 2: two "nodes" leave the pool -> resume on 2x2 (checkpointed state
         is resharded onto the new mesh via device_put).
Phase 3: simulated coordinator crash -> a brand-new Trainer restores from
         the latest valid checkpoint and finishes the run.

  PYTHONPATH=src python examples/elastic_failover.py
"""
import shutil
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.configs.registry import resolve_arch  # noqa: E402
from repro.distributed.sharding import Dist  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.optim.optimizers import OptConfig  # noqa: E402
from repro.runtime.trainer import Trainer, TrainerConfig  # noqa: E402


def main():
    cfg = resolve_arch("stablelm-1.6b-smoke")
    ckpt = tempfile.mkdtemp(prefix="elastic_")
    tc = TrainerConfig(batch=8, seq=32, ckpt_every=10, ckpt_dir=ckpt)
    opt = OptConfig(name="adamw", lr=3e-3)

    print("phase 1: mesh 4x2 (8 chips)")
    tr = Trainer(cfg, Dist(mesh=make_mesh(data=4, model=2)), opt, tc).init(0)
    l1 = tr.train(20)
    print(f"  loss {l1[0]:.3f} -> {l1[-1]:.3f} at step {tr.step}")

    print("phase 2: 4 chips leave -> resume on 2x2 (elastic reshard)")
    tr.resume(Dist(mesh=make_mesh(data=2, model=2)))
    l2 = tr.train(40)
    print(f"  loss {l2[0]:.3f} -> {l2[-1]:.3f} at step {tr.step}")
    assert l2[0] < l1[0] + 0.2, "training continued, not restarted"

    print("phase 3: coordinator crash -> cold restore from checkpoint")
    tr2 = Trainer(cfg, Dist(mesh=make_mesh(data=2, model=2)), opt,
                  tc).init(seed=99)     # fresh (different) init...
    tr2._restore_latest()               # ...replaced by checkpoint state
    print(f"  restored at step {tr2.step}")
    assert tr2.step == 40
    l3 = tr2.train(60)
    print(f"  loss {l3[0]:.3f} -> {l3[-1]:.3f} at step {tr2.step}")
    shutil.rmtree(ckpt, ignore_errors=True)
    print("OK")


if __name__ == "__main__":
    main()
