"""Per-arch smoke: reduced config, one train step + prefill + decode on
CPU, asserting shapes and no NaNs; decode/prefill consistency."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import reduce_for_smoke
from repro.configs.registry import ARCH_IDS, get_arch
from repro.distributed.sharding import Dist
from repro.models import make_model

OPTS = {"remat": "none", "xent_chunk": 32, "q_chunk": 32, "k_chunk": 32}


def _batch(sc, B=2, S=64, with_labels=True):
    b = {}
    if sc.family == "encdec":
        b = {"enc_embeds": jnp.ones((B, S // 2, sc.d_model)) * 0.01,
             "tokens": jnp.zeros((B, S // 2), jnp.int32)}
        if with_labels:
            b["labels"] = jnp.zeros((B, S // 2), jnp.int32)
        return b
    if sc.frontend != "none":
        b["embeds"] = jnp.ones((B, S, sc.d_model)) * 0.01
    else:
        b["tokens"] = jnp.zeros((B, S), jnp.int32)
    if sc.mrope:
        b["positions"] = jnp.zeros((3, B, S), jnp.int32)
    if with_labels:
        b["labels"] = jnp.zeros((B, S), jnp.int32)
    return b


@pytest.mark.parametrize("arch_id", sorted(ARCH_IDS))
def test_smoke_train_prefill_decode(arch_id):
    sc = reduce_for_smoke(get_arch(arch_id))
    m = make_model(sc, Dist(), OPTS)
    params = m.init(jax.random.PRNGKey(0))
    B, S = 2, 64
    loss = jax.jit(m.loss)(params, _batch(sc))
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), (arch_id, loss)

    logits, cache = jax.jit(m.prefill)(params, _batch(sc, with_labels=False))
    assert logits.shape[0] == B and logits.shape[1] == 1
    assert logits.shape[2] == sc.vocab_size
    assert bool(jnp.all(jnp.isfinite(logits)))

    logits2, cache2 = jax.jit(m.decode)(
        params, cache, {"tokens": jnp.zeros((B, 1), jnp.int32)})
    assert logits2.shape == (B, 1, sc.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits2)))
    assert int(cache2["pos"]) == int(cache["pos"]) + 1


@pytest.mark.parametrize("arch_id", ["stablelm-1.6b", "granite-20b",
                                     "kimi-k2-1t-a32b", "mamba2-1.3b"])
def test_decode_matches_prefill(arch_id):
    """Prefill over t+1 tokens must give the same last-position logits as
    prefill over t tokens followed by one decode step of token t."""
    sc = reduce_for_smoke(get_arch(arch_id))
    m = make_model(sc, Dist(), OPTS)
    params = m.init(jax.random.PRNGKey(1))
    B, S = 2, 32
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S + 1), 0,
                              sc.vocab_size)
    full, _ = m.prefill(params, {"tokens": toks})
    logits_a, cache = m.prefill(params, {"tokens": toks[:, :S]})
    # decode caches must have capacity S+1: pad the prefill cache
    def pad(c):
        out = dict(c)
        for k in ("k", "v"):
            if k in out:
                pads = [(0, 0)] * out[k].ndim
                pads[2] = (0, 1)
                out[k] = jnp.pad(out[k], pads)
        return out
    logits_b, _ = m.decode(params, pad(cache),
                           {"tokens": toks[:, S:S + 1]})
    err = float(jnp.max(jnp.abs(full - logits_b)))
    assert err < 2e-2, (arch_id, err)


def test_init_scales_weights_by_their_fan_in():
    """Each weight's init std is 1/sqrt(the elements each output sums
    over): d_model for q/k/v, heads x head_dim for the attention output,
    one row for the embedding table (a lookup, not a sum)."""
    sc = reduce_for_smoke(get_arch("stablelm-1.6b"))
    p = make_model(sc, Dist(), OPTS).init(jax.random.PRNGKey(0))
    attn = p["blocks"]["attn"]
    want = {"wq": sc.d_model, "wk": sc.d_model, "wv": sc.d_model,
            "wo": sc.n_heads * sc.hd}
    for name, fan_in in want.items():
        std = float(jnp.std(attn[name]))
        assert abs(std * fan_in ** 0.5 - 1) < 0.1, (name, std)
    assert abs(float(jnp.std(p["embed"])) - 1) < 0.1


def test_train_reduces_loss():
    """A few SGD steps on the structured synthetic corpus reduce loss."""
    sc = reduce_for_smoke(get_arch("stablelm-1.6b"))
    m = make_model(sc, Dist(), OPTS)
    params = m.init(jax.random.PRNGKey(0))
    import numpy as np
    rng = np.random.Generator(np.random.Philox(key=7))

    def batch(i):
        t = rng.integers(0, sc.vocab_size, size=(8, 33), dtype=np.int64)
        t[:, 1::2] = t[:, 0::2][:, : t[:, 1::2].shape[1]]
        t = t.astype(np.int32)
        return {"tokens": jnp.asarray(t[:, :-1]),
                "labels": jnp.asarray(t[:, 1:])}

    @jax.jit
    def step(p, b):
        l, g = jax.value_and_grad(m.loss)(p, b)
        return jax.tree.map(lambda x, y: x - 0.5 * y, p, g), l

    first = last = None
    for i in range(30):
        params, l = step(params, batch(i))
        first = first if first is not None else float(l)
        last = float(l)
    assert last < first - 0.2, (first, last)
