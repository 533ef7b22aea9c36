"""Sharding spec engine + optimizer state specs + batch resolution.
(Pure spec logic — no devices needed; Dist with mesh=None plus fakes.)"""
from dataclasses import replace

import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import reduce_for_smoke
from repro.configs.registry import get_arch
from repro.distributed.sharding import Dist, dim_shardable, spec_for
from repro.models.layers import ParamDef
from repro.optim.optimizers import OptConfig, opt_state_specs


class FakeMesh:
    """Duck-typed mesh: only axis_names + shape are consulted by the
    spec engine."""

    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


def dist(policy="fsdp_tp", pod=False):
    shape = {"pod": 2, "data": 16, "model": 16} if pod else \
        {"data": 16, "model": 16}
    return Dist(mesh=FakeMesh(shape), policy=policy)


def test_tp_dims_take_model_axis():
    d = dist()
    assert spec_for(d, ("embed", "ff"), (1024, 4096)) == \
        P(("data",), "model")
    assert spec_for(d, ("vocab", "embed"), (163840, 7168)) == \
        P("model", ("data",))


def test_indivisible_dims_fall_back_to_replicated():
    d = dist()
    # whisper: 20 heads, vocab 51866 — neither divides 16
    assert spec_for(d, ("embed", "heads", "hd"), (1280, 20, 64)) == \
        P(("data",), None, None)
    assert spec_for(d, ("vocab", "embed"), (51866, 1280)) == \
        P(None, ("data",))
    assert not dim_shardable(d, 51866, "vocab")
    assert dim_shardable(d, 49152, "vocab")


def test_policies():
    # dp_only: no TP, no FSDP
    d = dist("dp_only")
    assert spec_for(d, ("embed", "ff"), (1024, 4096)) == P(None, None)
    # tp_dp: TP only
    d = dist("tp_dp")
    assert spec_for(d, ("embed", "ff"), (1024, 4096)) == P(None, "model")
    # fsdp over pod axis too
    d = dist("fsdp_tp", pod=True)
    assert spec_for(d, ("embed", "ff"), (1024, 4096)) == \
        P(("pod", "data"), "model")


def test_axis_used_once_per_spec():
    d = dist()
    # two fsdp dims: only the first takes the axis
    s = spec_for(d, ("embed", "eff"), (1024, 2048))
    assert s == P(("data",), None)


def test_batch_resolution():
    d = dist(pod=True)
    assert d.resolve_batch(256).batch_axes == ("pod", "data")
    assert d.resolve_batch(16).batch_axes == ("data",)
    assert d.resolve_batch(1).batch_axes is None


def test_adafactor_state_specs_follow_factoring():
    d = dist()
    defs = {"w": ParamDef((1024, 4096), ("embed", "ff")),
            "b": ParamDef((4096,), ("ff",))}
    specs = opt_state_specs(OptConfig(name="adafactor"), defs, d)
    assert specs["vr"]["w"] == P(("data",))        # row stats: (1024,)
    assert specs["vc"]["w"] == P("model")          # col stats: (4096,)
    assert specs["vc"]["b"] == P()                 # non-factored marker
    specs = opt_state_specs(OptConfig(name="adamw"), defs, d)
    assert specs["m"]["w"] == P(("data",), "model")


def test_model_param_specs_cover_tree():
    d = dist()
    cfg = get_arch("kimi-k2-1t-a32b")
    from repro.models.model import make_model
    m = make_model(cfg, d)
    specs = m.param_specs()
    import jax
    leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert all(isinstance(s, P) for s in leaves)
    # expert weights: expert dim on model axis
    assert specs["blocks"]["moe"]["wg"][1] == "model"
