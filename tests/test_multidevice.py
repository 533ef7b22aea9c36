"""Multi-device behaviour (subprocess with 8 host devices — XLA locks the
device count at first import, so these cannot run in the pytest process).

Covers: sharded-vs-local MoE equivalence, mesh solver collective patterns
(the paper's O(L) vs O(L^2) bytes), elastic trainer resharding, and a
miniature dry-run (lower+compile with shardings on a 4x2 mesh)."""
import pytest

from util_subproc import run_with_devices

pytestmark = pytest.mark.slow


def test_moe_sharded_matches_local():
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import reduce_for_smoke
from repro.configs.registry import get_arch
from repro.distributed.sharding import Dist
from repro.launch.mesh import make_mesh
from repro.models.moe import moe_block, moe_param_defs, _moe_single, replication_factor
from repro.models.layers import init_params

cfg = reduce_for_smoke(get_arch("kimi-k2-1t-a32b"))  # 4 experts top-2
mesh = make_mesh(data=2, model=4)
dist = Dist(mesh=mesh).resolve_batch(4)
defs = moe_param_defs(cfg, dist)
params = init_params(defs, jax.random.PRNGKey(0), jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model)) * 0.5

with jax.set_mesh(mesh):
    y_sh = jax.jit(lambda x, p: moe_block(x, p, cfg, dist))(x, params)
r = replication_factor(cfg.moe, dist)
y_loc = _moe_single(x, params, cfg.moe, r)
d = float(jnp.max(jnp.abs(np.asarray(y_sh) - np.asarray(y_loc))))
print("moe diff:", d)
assert d < 5e-2, d

# decode path (seq=1)
x1 = x[:, :1]
with jax.set_mesh(mesh):
    y1 = jax.jit(lambda x, p: moe_block(x, p, cfg, dist))(x1, params)
y1l = _moe_single(x1, params, cfg.moe, r)
d1 = float(jnp.max(jnp.abs(np.asarray(y1) - np.asarray(y1l))))
print("moe decode diff:", d1)
assert d1 < 5e-2, d1
print("OK")
""", n=8)
    assert "OK" in out


def test_mesh_solvers_converge_and_byte_pattern():
    out = run_with_devices("""
import re, jax, jax.numpy as jnp
from repro.core.solvers import SolverConfig, make_solver
from repro.optim.optimizers import OptConfig
from repro.launch.mesh import make_mesh

mesh = make_mesh(data=8, model=1)
D, NL, B = 512, 8, 16
W = jax.random.normal(jax.random.PRNGKey(0), (D,)) * 0.1
loss = lambda p, b: jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)
p0 = {"w": jnp.zeros((D,))}
def batches(rng, h):
    xs = jax.random.normal(rng, (h, NL, B, D))
    return {"x": xs, "y": xs @ W}

def run(scfg):
    s = make_solver(loss, p0, OptConfig(name="sgd", lr=0.01), scfg, NL, mesh=mesh)
    st = s.init_state(p0)
    rng = jax.random.PRNGKey(1)
    for _ in range(120):
        rng, k = jax.random.split(rng)
        st, m = s.round(st, batches(k, scfg.rounds_h))
    err = float(jnp.linalg.norm(s.params_of(st)["w"] - W))
    txt = jax.jit(s._round).lower(st, batches(rng, scfg.rounds_h)).compile().as_text()
    ag = sum(1 for _ in re.finditer(r'all-gather', txt))
    return err, txt

err_ps, txt_ps = run(SolverConfig(name="psgd", push_mode="ps"))
err_bc, txt_bc = run(SolverConfig(name="psgd", push_mode="broadcast"))
assert err_ps < 0.3 and err_bc < 0.3, (err_ps, err_bc)
def ag_bytes(txt):
    tot = 0
    for m in re.finditer(r'f32\\[([\\d,]+)\\][^\\n]*all-gather', txt):
        n = 1
        for d in m.group(1).split(','): n *= int(d)
        tot += 4*n
    return tot
bps, bbc = ag_bytes(txt_ps), ag_bytes(txt_bc)
print("ps bytes:", bps, "broadcast bytes:", bbc)
assert bbc > 3 * bps, "broadcast must move O(L) more bytes than PS"
print("OK")
""", n=8)
    assert "OK" in out


def test_elastic_trainer_reshard():
    out = run_with_devices("""
import shutil
import jax
from repro.configs.base import reduce_for_smoke
from repro.configs.registry import get_arch
from repro.distributed.sharding import Dist
from repro.launch.mesh import make_mesh
from repro.optim.optimizers import OptConfig
from repro.runtime.trainer import Trainer, TrainerConfig

shutil.rmtree("/tmp/el_ckpt_t", ignore_errors=True)
cfg = reduce_for_smoke(get_arch("stablelm-1.6b"))
tc = TrainerConfig(batch=8, seq=32, ckpt_every=10, ckpt_dir="/tmp/el_ckpt_t")
tr = Trainer(cfg, Dist(mesh=make_mesh(data=4, model=2)),
             OptConfig(name="adamw", lr=3e-3), tc).init(0)
l1 = tr.train(20)
tr.resume(Dist(mesh=make_mesh(data=2, model=2)))
l2 = tr.train(40)
assert l2[0] < l1[0] + 0.1 and l2[-1] < l2[0], (l1[0], l2[0], l2[-1])
tr2 = Trainer(cfg, Dist(mesh=make_mesh(data=2, model=2)),
              OptConfig(name="adamw", lr=3e-3), tc).init(1)
tr2._restore_latest()
assert tr2.step == 40
print("OK")
""", n=8)
    assert "OK" in out


def test_tiny_dryrun_all_step_kinds():
    """lower+compile with shardings for train/prefill/decode on a 4x2
    mesh — the in-repo miniature of the 512-device production dry-run."""
    out = run_with_devices("""
import jax
from repro.configs.base import ShapeSpec, reduce_for_smoke
from repro.configs.registry import get_arch
from repro.distributed.sharding import Dist
from repro.launch.mesh import make_mesh
from repro.distributed.steps import (abstract_inputs, jit_train_step,
                                     jit_prefill_step, jit_decode_step)
from repro.models.model import make_model
from repro.optim.optimizers import OptConfig

mesh = make_mesh(data=4, model=2)
for arch in ("stablelm-1.6b", "kimi-k2-1t-a32b", "mamba2-1.3b",
             "jamba-1.5-large-398b", "whisper-large-v3", "qwen2-vl-2b"):
    cfg = reduce_for_smoke(get_arch(arch))
    for kind, B, S in (("train", 8, 64), ("prefill", 8, 64),
                       ("decode", 8, 64)):
        shape = ShapeSpec("t", S, B, kind)
        dist = Dist(mesh=mesh).resolve_batch(B)
        model = make_model(cfg, dist, {"remat": "full", "xent_chunk": 32,
                                       "q_chunk": 32, "k_chunk": 32})
        opt = OptConfig(name="adamw")
        step = {"train": lambda: jit_train_step(model, opt, shape),
                "prefill": lambda: jit_prefill_step(model, shape),
                "decode": lambda: jit_decode_step(model, shape)}[kind]()
        args = abstract_inputs(model, shape, opt)
        c = step.lower(*args).compile()
        assert c.memory_analysis() is not None
        print(arch, kind, "ok")
print("OK")
""", n=8, timeout=900)
    assert "OK" in out


def test_sp_attention_matches_reference():
    """zero3_sp sequence-parallel attention == unsharded reference
    (values AND grads), including the causal per-shard offset."""
    out = run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.sharding import Dist
from repro.launch.mesh import make_mesh
from repro.models.attention import (flash_attention_ref, repeat_kv,
                                    sp_flash_attention)

mesh = make_mesh(data=2, model=4)
dist = Dist(mesh=mesh, policy="zero3_sp").resolve_batch(4)
B, S, H, KV, hd = 4, 128, 8, 2, 32
q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, hd))
k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, hd))
v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, hd))
w = jax.random.normal(jax.random.PRNGKey(3), (B, S, H, hd))
for causal in (True, False):
    f_sp = jax.jit(lambda q, k, v: jnp.sum(sp_flash_attention(
        q, k, v, dist, causal=causal, q_chunk=32, k_chunk=32) * w))
    f_ref = lambda q, k, v: jnp.sum(flash_attention_ref(
        q, repeat_kv(k, H), repeat_kv(v, H), causal=causal,
        q_chunk=32, k_chunk=32) * w)
    o1, g1 = jax.value_and_grad(f_sp, argnums=(0, 1, 2))(q, k, v)
    o2, g2 = jax.value_and_grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    assert abs(float(o1 - o2)) < 1e-2, (causal, o1, o2)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-3)
    print("causal", causal, "ok")
print("OK")
""", n=8)
    assert "OK" in out
