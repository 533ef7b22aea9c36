"""The main path's Pallas kernels and serving decode step compiled at
real widths for a described TPU v5e (no chip needed: the TPU compiler
runs here against a topology description). The kernel tests assert the
Mosaic kernel is in the compiled program; the decode step's test, that
it updates its cache in place. The topology is described inside a
fixture, never at import, so every test worker collects the same
tests."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import resolve_arch
from repro.core.software_ps import ShardLayout
from repro.kernels import autotune
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.ps_aggregate import ps_aggregate
from repro.kernels.quantize import dequantize, quantize_ef
from repro.kernels.ssd_scan import ssd_scan_fwd
from repro.models import make_model
from repro.service.manifest import DEFAULT_PS_SHARDS
from repro.serving.engine import InferenceEngine, decode_in_place

N_LEARNERS = 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """The persistent cache off: a program compiled for a described chip
    is written to the cache but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_tpu(one_chip, no_persistent_cache):
    """``compile_tpu(fn, *shapes)`` -> compiled text."""
    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()
    return run


def _flat_size(arch):
    shapes = make_model(resolve_arch(arch)).abstract_params()
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))


# flat model sizes the software PS shards: the published stablelm-1.6b,
# its smoke reduction and the default repro-mlp classifier (32 -> 64 -> 10)
SIZES = {"stablelm-1.6b": lambda: _flat_size("stablelm-1.6b"),
         "stablelm-1.6b-smoke": lambda: _flat_size("stablelm-1.6b-smoke"),
         "mlp": lambda: 32 * 64 + 64 + 64 * 10 + 10}


@pytest.mark.parametrize("model", sorted(SIZES))
@pytest.mark.parametrize("solver", ["sgd", "momentum", "adam"])
def test_ps_aggregate_compiles(compile_tpu, solver, model):
    f = ShardLayout.build(SIZES[model](), DEFAULT_PS_SHARDS).shard_len
    block = autotune.tuned_ps_block(N_LEARNERS, f)
    txt = compile_tpu(
        lambda g, p, m, v, s: ps_aggregate(g, p, m, v, s, solver=solver,
                                           block=block),
        ((N_LEARNERS, f), jnp.float32), ((f,), jnp.float32),
        ((f,), jnp.float32), ((f,), jnp.float32), ((), jnp.float32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("model", sorted(SIZES))
def test_quantize_and_dequantize_compile(compile_tpu, model):
    # one shard's worth: a whole-model int8 push of stablelm-1.6b (x, err
    # and new err in f32) does not fit one chip's HBM
    f = ShardLayout.build(SIZES[model](), DEFAULT_PS_SHARDS).shard_len
    block = autotune.tuned_quantize_block(f)
    txt = compile_tpu(lambda x, e: quantize_ef(x, e, block=block),
                      ((f,), jnp.float32), ((f,), jnp.float32))
    assert "tpu_custom_call" in txt
    txt = compile_tpu(lambda q, s: dequantize(q, s, block=block),
                      ((f,), jnp.int8), ((f // 256,), jnp.float32))
    assert "tpu_custom_call" in txt


def test_flash_attention_compiles_at_stablelm_heads(compile_tpu):
    cfg = resolve_arch("stablelm-1.6b")
    bh, s = cfg.n_heads, 2048
    bq, bk = autotune.tuned_flash_blocks(bh, s, s, cfg.hd, jnp.bfloat16)
    shape = ((bh, s, cfg.hd), jnp.bfloat16)
    txt = compile_tpu(
        lambda q, k, v: flash_attention_fwd(q, k, v, causal=True,
                                            block_q=bq, block_k=bk),
        shape, shape, shape)
    assert "tpu_custom_call" in txt


def test_ssd_scan_compiles_at_mamba2_widths(compile_tpu):
    ssm = resolve_arch("mamba2-1.3b").ssm
    d_in = ssm.expand * resolve_arch("mamba2-1.3b").d_model
    bh, s = d_in // ssm.head_dim, 2048
    txt = compile_tpu(
        lambda x, l, b, c: ssd_scan_fwd(x, l, b, c, chunk=ssm.chunk_size),
        ((bh, s, ssm.head_dim), jnp.float32), ((bh, s, 1), jnp.float32),
        ((bh, s, ssm.d_state), jnp.float32),
        ((bh, s, ssm.d_state), jnp.float32))
    assert "tpu_custom_call" in txt


def _materialized(hlo_text):
    """(opcode, dtype, dims without unit dims) of every op outside fused
    computations: the buffers the program writes."""
    fused = set(re.findall(r"calls=%([\w.-]+)", hlo_text))
    ops, comp = [], None
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.-]+) \(.*\{$", line)
        if head:
            comp = head.group(1)
            continue
        op = re.match(r"\s+(?:ROOT )?%[\w.-]+ = (\w+)\[([\d,]*)\]\S* "
                      r"([\w-]+)\(", line)
        if op and comp not in fused:
            dims = tuple(int(d) for d in op.group(2).split(",")
                         if d and int(d) != 1)
            ops.append((op.group(3), op.group(1), dims))
    return ops


def test_serve_decode_updates_the_kv_cache_in_place(one_chip,
                                                    no_persistent_cache):
    """The engine's own decode program at stablelm-1.6b's published widths
    (8 slots of 1280 positions): a few MB of temporaries, every cache
    leaf aliased to its donated input, and nothing written in the shape
    of a whole cache leaf or of one layer's slab of it but the in-place
    row writes (dynamic-update-slice)."""
    eng = InferenceEngine(resolve_arch("stablelm-1.6b"), capacity=8,
                          max_seq=1280)
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    cache = on_chip(eng.cache_shapes())
    compiled = eng.decode_program().lower(
        on_chip(eng.model.abstract_params()), cache,
        jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)).compile()
    temp, aliased = decode_in_place(compiled, cache)
    assert temp < 64 * 2 ** 20, temp
    assert aliased
    leaf = tuple(d for d in cache["k"].shape if d != 1)
    shapes = {leaf, leaf[1:]}
    cache_sized = [op for op, dt, dims in _materialized(compiled.as_text())
                   if dt == "bf16" and dims in shapes
                   and op not in ("parameter", "get-tuple-element", "tuple",
                                  "bitcast", "while")]
    assert cache_sized and set(cache_sized) == {"dynamic-update-slice"}, \
        cache_sized
