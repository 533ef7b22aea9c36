"""The plain float32 references against the program's own prefill and
decode at smoke size, and the fp8 control against the reference."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import compare  # noqa: E402
from bench.reference import common, dense, ssm  # noqa: E402
from repro.configs.registry import resolve_arch  # noqa: E402
from repro.models import make_model  # noqa: E402

DENSE = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, intermediate_size=128, vocab_size=256,
             rope_theta=10000.0, norm_eps=1e-5, dtype="float32")
SSM = dict(hidden_size=64, num_hidden_layers=2, vocab_size=256, padded_vocab_size=256,
           state_size=16,
           head_dim=16, expand=2, chunk_size=32, n_groups=1, conv_kernel=4,
           norm_eps=1e-5, dtype="float32")
CASES = [("stablelm-1.6b-smoke", dense, DENSE), ("mamba2-1.3b-smoke", ssm, SSM)]


def _pad_seq(cache, length):
    """The engine's padding of a prefill cache to its max_seq."""
    out = dict(cache)
    for k in ("k", "v"):
        if k in out:
            pads = [(0, 0)] * out[k].ndim
            pads[2] = (0, length - out[k].shape[2])
            out[k] = jnp.pad(out[k], pads)
    return out


@pytest.mark.parametrize("arch,ref,cfg", CASES, ids=["dense", "ssm"])
def test_reference_matches_prefill_then_decode(arch, ref, cfg):
    a = resolve_arch(arch)
    for key, field in ref.PROGRAM_FIELDS.items():
        obj = a
        for part in field.split("."):
            obj = getattr(obj, part)
        assert cfg[key] == obj, key
    model = make_model(a, None, {"remat": "none"})
    specs = ref.param_specs(cfg)
    assert jax.tree.structure(common.shapes(specs, cfg["dtype"])) == \
        jax.tree.structure(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    params = common.make_params(specs, 2**35 + 3, cfg["dtype"])
    T, P = 45, 37            # prompt past the SSD chunk of 32
    toks = np.random.default_rng(0).integers(0, 256, (2, T)).astype(np.int32)
    want = np.asarray(ref.logits(params, jnp.asarray(toks), cfg))
    with jax.default_matmul_precision("highest"):
        last, cache = model.prefill(params, {"tokens": jnp.asarray(toks[:, :P])})
        got = [np.asarray(last[:, -1])]
        cache = _pad_seq(cache, T)
        for t in range(P, T - 1):
            lg, cache = model.decode(params, cache, {"tokens": jnp.asarray(toks[:, t:t + 1])})
            got.append(np.asarray(lg[:, -1]))
    np.testing.assert_allclose(np.stack(got, 1), want[:, P - 1:T - 1],
                               atol=2e-4, rtol=0)


@pytest.mark.parametrize("ref,cfg", [(dense, DENSE), (ssm, SSM)], ids=["dense", "ssm"])
def test_comparator_reads_zero_for_the_reference_and_more_for_fp8(ref, cfg):
    params = common.make_params(ref.param_specs(cfg), 7, "float32")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (9, 20, 31)]
    fwd = jax.jit(lambda t: ref.logits(params, t, cfg))
    items = []
    for p in prompts:                      # greedy continuation by the reference
        seq = list(p)
        for _ in range(6):
            row = np.zeros((1, 48), np.int32)
            row[0, :len(seq)] = seq
            seq.append(int(jnp.argmax(fwd(jnp.asarray(row))[0, len(seq) - 1])))
        items.append((p, seq[len(p):]))
    cmp = compare.Comparator(ref, cfg, 48, 2)
    gap, n = cmp.widest_gap(params, items)
    assert n == 18 and gap < 1e-4
    ctrl, n = cmp.control_gap(params, items)
    assert n == 18 and ctrl > 100 * max(gap, 1e-4)
    # one served token moved off the argmax shows as its gap
    bad = [(items[0][0], [items[0][1][0] + 1] + items[0][1][1:])] + items[1:]
    assert cmp.widest_gap(params, bad)[0] > 1e-2


def test_pack_positions():
    toks, tgt, mask = compare.pack([(np.array([5, 6, 7], np.int32), [8, 9])], 6)
    assert toks.tolist() == [[5, 6, 7, 8, 0, 0]]
    assert mask.tolist() == [[False, False, True, True, False, False]]
    assert tgt[0, 2:4].tolist() == [8, 9]


def test_seed_key_takes_seeds_past_32_bits():
    a = common.seed_key(5)
    b = common.seed_key(5 + 2**32)
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_sample_holds_the_longest():
    done = [{"prompt": np.zeros(n, np.int32), "tokens": [0] * m}
            for n, m in ((10, 5), (50, 30), (20, 4), (5, 2))]
    s = compare.sample(done, 3, 10)
    assert len(s[0]["prompt"]) == 50
    assert sum(len(r["tokens"]) for r in s) >= 10
    assert compare.sample([], 3, 10) == []
