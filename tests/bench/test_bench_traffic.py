"""The open-loop generator: deterministic by seed, and its lengths and
inter-arrival spread follow the traffic file."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import traffic as gen  # noqa: E402

CHAT = json.loads((ROOT / "bench" / "traffic" / "serve-chat.json").read_text())


def _sched(seed, rate=8.0, seconds=40.0):
    return gen.schedule(CHAT, rate, seconds, seed, vocab_size=100352)


def test_same_seed_same_schedule():
    a, b = _sched(2**33 + 1), _sched(2**33 + 1)
    assert [(r.due_s, r.max_new) for r in a] == [(r.due_s, r.max_new) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_share_the_work_in_another_order():
    """Seeds offer the same sizes and arrivals, in the order the file's
    arrangement seed draws; a seed changes the prompts' tokens. Another
    arrangement seed puts the same work in another order."""
    a, b = _sched(1), _sched(2)
    assert [(r.due_s, len(r.prompt), r.max_new) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new) for r in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    other = gen.schedule(dict(CHAT, arrangement_seed=CHAT["arrangement_seed"] + 1),
                         8.0, 40.0, 1, vocab_size=100352)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in other)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in other)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in other]
    ga, go = np.diff([r.due_s for r in a]), np.diff([r.due_s for r in other])
    assert not np.allclose(ga, go)


def test_count_and_span():
    s = _sched(3, rate=8.0, seconds=40.0)
    assert len(s) == 320
    due = [r.due_s for r in s]
    assert due[0] == 0.0 and all(0 <= d < 40.0 for d in due)
    assert due == sorted(due)


def test_lengths_follow_the_file():
    s = _sched(4, rate=50.0, seconds=40.0)
    plens = np.array([len(r.prompt) for r in s])
    outs = np.array([r.max_new for r in s])
    p, o = CHAT["prompt"], CHAT["output"]
    assert set(plens) <= set(gen.prompt_lengths(CHAT))
    assert plens.min() == p["min"] and plens.max() == p["max"]
    assert np.all(plens % p["grid"] == 0)
    assert o["min"] <= outs.min() and outs.max() <= o["max"]
    # medians within one grid step (prompts) / 2 tokens (outputs)
    assert abs(np.median(plens) - p["median"]) <= p["grid"]
    assert abs(np.median(outs) - o["median"]) <= 2
    # lognormal sigma from the unclipped middle of the distribution
    q25, q75 = np.percentile(outs, [25, 75])
    assert np.log(q75 / q25) / 1.349 == pytest.approx(o["sigma"], rel=0.1)


def test_arrival_cv_follows_the_file():
    s = _sched(5, rate=50.0, seconds=40.0)
    gaps = np.diff([r.due_s for r in s])
    assert gaps.std() / gaps.mean() == pytest.approx(CHAT["arrival"]["cv"], rel=0.1)


def test_token_ids_in_vocabulary():
    s = gen.schedule(CHAT, 4.0, 10.0, 6, vocab_size=300)
    assert all(r.prompt.dtype == np.int32 for r in s)
    assert max(int(r.prompt.max()) for r in s) < 300
    assert min(int(r.prompt.min()) for r in s) >= 0


def test_prompt_lengths_is_the_grid():
    assert gen.prompt_lengths(CHAT) == [128, 256, 384, 512, 640, 768, 896, 1024]
