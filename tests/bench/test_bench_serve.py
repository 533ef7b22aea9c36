"""A serve cell driven end to end on the CPU at smoke size, past the chip
check: a sound run is correct, the control and each fault the cell can
have are not."""
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench.drivers import open_loop_serve as drv  # noqa: E402

LIMIT = 0.01            # float32 smoke program against the float32 reference
TRAFFIC = {"kind": "open_loop_serve", "arrival": {"cv": 2.0}, "arrangement_seed": 1,
           "prompt": {"median": 16, "sigma": 0.8, "min": 8, "max": 48, "grid": 8},
           "output": {"median": 6, "sigma": 0.8, "min": 4, "max": 12, "grid": 1}}
CONFIGS = {
    "dense": dict(name="stablelm-1.6b-smoke", arch="stablelm-1.6b-smoke", family="dense",
                  hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, intermediate_size=128, vocab_size=256,
                  rope_theta=10000.0),
    "ssm": dict(name="mamba2-1.3b-smoke", arch="mamba2-1.3b-smoke", family="ssm",
                hidden_size=64, num_hidden_layers=2, vocab_size=256, padded_vocab_size=256,
                state_size=16,
                head_dim=16, expand=2, chunk_size=32, n_groups=1, conv_kernel=4),
}


def _cell(family):
    cfg = dict(CONFIGS[family], dtype="float32", norm_eps=1e-5,
               serving={"capacity": 2, "max_seq": 64, "max_queue": 64})
    return harness.Cell(f"{cfg['name']}.chat", 1, cfg, TRAFFIC,
                        {"rate_rps": 4.0, "widest_logit_gap_limit": LIMIT}, [], [])


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_sound_run_is_correct(family):
    out, checks, correct, _, setup_s = drv.run(
        _cell(family), seed=2**33 + 1, seconds=2.0, trace=False,
        t_start=time.time(), clog=harness.CompileLog(), check_chip=False)
    assert correct, checks
    assert checks["widest_logit_gap"]["tokens"] > 0
    assert out.failed == 0 and out.attempted == 8
    assert out.window_compiles == 0
    assert set(out.e2e) == {"setup_s", "serve_latency_p95_s",
                            "serve_norm_latency_p95_s", "serve_tokens_per_s"}
    assert all(v > 0 for v in out.e2e.values())
    assert out.counters["decode_steps"] > 0 and setup_s > 0


@pytest.fixture(scope="module")
def session():
    sess = drv.Session(_cell("dense"), harness.CompileLog())
    try:
        sess.load_weights(11)
        sess.warm()
        yield sess
    finally:
        sess.close()


def _token_altered(decode):
    def f(p, c, t):
        logits, c = decode(p, c, t)
        return jnp.roll(logits, 1, axis=-1), c
    return f


def _state_unchanged(decode):
    def f(p, c, t):
        old = jax.tree.map(jnp.copy, c)
        logits, _ = decode(p, c, t)
        return logits, old
    return f


def _half_the_batch(decode):
    def f(p, c, t):
        logits, c = decode(p, c, t)
        half = logits.shape[0] // 2
        return logits.at[half:].set(logits[:logits.shape[0] - half]), c
    return f


def test_control_fails_where_the_program_passes(session):
    out = session.window(5, 2.0, 4.0)
    gap, n = session.compare(out.done, 5)
    ctrl, m = session.compare(out.done, 5, control=True)
    assert n == m > 0
    assert gap <= LIMIT < ctrl


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged, _half_the_batch],
                         ids=["token_altered", "state_unchanged", "half_the_batch"])
def test_fault_makes_the_run_incorrect(session, fault):
    eng = session.engine
    orig = eng._decode
    eng._decode = fault(orig)
    try:
        out = session.window(7, 2.0, 4.0)
    finally:
        eng._decode = orig
    gap, n = session.compare(out.done, 7)
    assert n > 0 and gap > LIMIT
