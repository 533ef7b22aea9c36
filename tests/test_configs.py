"""Arch registry: assignment dims are exact; param counts are plausible."""
import pytest

from repro.configs.base import (ALL_SHAPES, reduce_for_smoke, shapes_for,
                                skip_reason)
from repro.configs.registry import (ARCH_IDS, DEFAULT_ARCH, REGISTRY,
                                    get_arch, resolve_arch)

ASSIGNED = {
    "kimi-k2-1t-a32b": dict(n_layers=61, d_model=7168, n_heads=64,
                            n_kv_heads=8, d_ff=2048, vocab_size=163840),
    "grok-1-314b": dict(n_layers=64, d_model=6144, n_heads=48,
                        n_kv_heads=8, d_ff=32768, vocab_size=131072),
    "stablelm-1.6b": dict(n_layers=24, d_model=2048, n_heads=32,
                          n_kv_heads=32, d_ff=5632, vocab_size=100352),
    "minitron-8b": dict(n_layers=32, d_model=4096, n_heads=32,
                        n_kv_heads=8, d_ff=16384, vocab_size=256000),
    "qwen1.5-110b": dict(n_layers=80, d_model=8192, n_heads=64,
                         n_kv_heads=8, d_ff=49152, vocab_size=152064),
    "granite-20b": dict(n_layers=52, d_model=6144, n_heads=48,
                        n_kv_heads=1, d_ff=24576, vocab_size=49152),
    "mamba2-1.3b": dict(n_layers=48, d_model=2048, d_ff=0,
                        vocab_size=50280),
    "whisper-large-v3": dict(n_layers=32, d_model=1280, n_heads=20,
                             n_kv_heads=20, d_ff=5120, vocab_size=51866),
    "jamba-1.5-large-398b": dict(n_layers=72, d_model=8192, n_heads=64,
                                 n_kv_heads=8, d_ff=24576,
                                 vocab_size=65536),
    "qwen2-vl-2b": dict(n_layers=28, d_model=1536, n_heads=12,
                        n_kv_heads=2, d_ff=8960, vocab_size=151936),
}


def test_all_archs_present():
    assert set(ARCH_IDS) == set(ASSIGNED)


@pytest.mark.parametrize("arch_id", sorted(ASSIGNED))
def test_exact_dims(arch_id):
    cfg = get_arch(arch_id)
    for k, v in ASSIGNED[arch_id].items():
        assert getattr(cfg, k) == v, (arch_id, k, getattr(cfg, k), v)


def test_moe_specs():
    k = get_arch("kimi-k2-1t-a32b")
    assert k.moe.n_experts == 384 and k.moe.top_k == 8
    g = get_arch("grok-1-314b")
    assert g.moe.n_experts == 8 and g.moe.top_k == 2
    j = get_arch("jamba-1.5-large-398b")
    assert j.moe.n_experts == 16 and j.moe.top_k == 2
    assert j.attn_period == 8
    assert get_arch("mamba2-1.3b").ssm.d_state == 128


def test_param_counts_plausible():
    # within the right order of magnitude of the advertised sizes
    assert 0.8e12 < get_arch("kimi-k2-1t-a32b").n_params() < 1.3e12
    assert 2.4e11 < get_arch("grok-1-314b").n_params() < 3.8e11
    assert 1.2e9 < get_arch("stablelm-1.6b").n_params() < 2.2e9
    assert 6e9 < get_arch("minitron-8b").n_params() < 11e9
    assert 0.9e11 < get_arch("qwen1.5-110b").n_params() < 1.4e11
    # granite-20b lands ~28B here: the zoo uses gated (3-matrix) MLPs
    # uniformly, vs granite's 2-matrix GELU MLP
    assert 1.4e10 < get_arch("granite-20b").n_params() < 3.0e10
    assert 0.9e9 < get_arch("mamba2-1.3b").n_params() < 2.0e9
    assert 3.0e11 < get_arch("jamba-1.5-large-398b").n_params() < 5.0e11
    # MoE active << total
    k = get_arch("kimi-k2-1t-a32b")
    assert k.n_active_params() < 0.08 * k.n_params()


def test_shape_skips():
    for aid in ARCH_IDS:
        cfg = get_arch(aid)
        names = {s.name for s in shapes_for(cfg)}
        if cfg.subquadratic:
            assert "long_500k" in names
        else:
            assert "long_500k" not in names
            assert skip_reason(cfg, ALL_SHAPES[3]) is not None


def test_smoke_reduction_small():
    for aid in ARCH_IDS:
        sc = reduce_for_smoke(get_arch(aid))
        assert sc.n_params() < 3e6, (aid, sc.n_params())
        assert sc.family == get_arch(aid).family


def test_resolve_arch_published_and_smoke_ids():
    for aid in ARCH_IDS:
        assert resolve_arch(aid) == get_arch(aid)
        smoke = resolve_arch(aid + "-smoke")
        assert smoke == reduce_for_smoke(get_arch(aid))
        assert smoke.arch_id == aid + "-smoke"
    assert resolve_arch(DEFAULT_ARCH).d_model == 64
    with pytest.raises(KeyError):
        resolve_arch("no-such-arch-smoke")
