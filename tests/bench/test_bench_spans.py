"""The serving engine's loop phases on the profiler's clock: the reduction
of device idle time by phase (``bench/spans.py``) and its three readers on
a hand-made trace, and a real engine run traced on the CPU."""
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, spans  # noqa: E402
from bench import trace as tracing  # noqa: E402
from bench.trace import Event, Trace  # noqa: E402

TPU = "/device:TPU:0"
PHASES = ("serve.wait", "serve.schedule", "serve.admit", "serve.dispatch",
          "serve.read", "serve.bookkeep")


def _trace(with_spans=True, with_device=True):
    # device idle in (2, 4) and (5, 9.5) of the window (0, 10): 6.5 s
    ops = [Event(0.0, 2.0, "fusion", TPU), Event(4.0, 5.0, "fusion", TPU),
           Event(9.5, 11.0, "copy", TPU)]
    engine = [Event(0.0, 2.5, "serve.admit", "python"),       # idle 0.5
              Event(2.5, 3.0, "serve.read", "python"),        # idle 0.5
              Event(3.0, 3.2, "serve.bookkeep", "python"),    # idle 0.2
              # 3.2-3.5 in no span: unattributed 0.3
              Event(3.5, 4.5, "serve.dispatch", "python"),    # idle 0.5
              Event(5.0, 9.0, "serve.wait", "python"),        # idle 4.0
              Event(9.0, 9.2, "serve.schedule", "python"),    # idle 0.2
              Event(9.2, 9.7, "serve.admit", "python"),       # idle 0.3
              Event(9.7, 10.5, "serve.wait", "python")]       # past the window
    other = [Event(2.0, 4.0, "np.asarray", "python"),
             Event(0.0, 10.0, tracing.WINDOW_ANNOTATION, "python")]
    return Trace(window=(0.0, 10.0), ops={TPU: ops} if with_device else {},
                 host=other + (engine if with_spans else []))


def _read(name, tr):
    return harness.metric_reader(name).read(None, tr)


def test_idle_by_phase_hand_computed():
    idle = spans.idle_by_phase(_trace())
    want = {"serve.admit": 0.8, "serve.read": 0.5, "serve.bookkeep": 0.2,
            "serve.dispatch": 0.5, "serve.wait": 4.0, "serve.schedule": 0.2,
            spans.UNATTRIBUTED: 0.3}
    assert set(idle) == set(want)
    for k, v in want.items():
        assert idle[k] == pytest.approx(v), k


def test_readers_hand_computed():
    tr = _trace()
    assert _read("idle_no_work_share.serve", tr) == pytest.approx(40.0)
    assert _read("idle_host_share.serve", tr) == pytest.approx(22.0)
    # admits wholly inside the window: 2.5 s and 0.5 s
    assert _read("admit_ms.serve", tr) == pytest.approx(1500.0)
    assert spans.idle_shares(tr)["serve.read"] == pytest.approx(5.0)


def test_parts_add_up_to_the_device_idle_share():
    tr = _trace()
    shares = spans.idle_shares(tr)
    total = _read("device_idle_share.serve", tr)
    assert total == pytest.approx(65.0)
    assert (shares["no_work"] + shares["host"] + shares[spans.UNATTRIBUTED]
            == pytest.approx(total))


def test_no_engine_span_reads_none():
    tr = _trace(with_spans=False)
    assert _read("device_idle_share.serve", tr) == pytest.approx(65.0)
    for name in ("idle_host_share.serve", "idle_no_work_share.serve",
                 "admit_ms.serve"):
        assert _read(name, tr) is None, name
    assert spans.idle_by_phase(tr) is None
    # spans but no device: no idle time to split, the admits still read
    tr = _trace(with_device=False)
    assert _read("idle_host_share.serve", tr) is None
    assert _read("idle_no_work_share.serve", tr) is None
    assert _read("admit_ms.serve", tr) == pytest.approx(1500.0)


def test_idle_averages_over_chips():
    tr = _trace()
    tr.ops["/device:TPU:1"] = [Event(0.0, 10.0, "fusion", "/device:TPU:1")]
    shares = spans.idle_shares(tr)
    assert shares["no_work"] == pytest.approx(20.0)
    assert shares[spans.UNATTRIBUTED] == pytest.approx(1.5)


# ---------------------------------------------------------------------------
# a real engine, traced on the CPU
# ---------------------------------------------------------------------------

MAX_SEQ = 32


def _serve(cfg, prompts, max_news, trace_dir=None):
    """Serve the prompts on a fresh two-slot engine; with ``trace_dir``,
    under the profiler. Returns (tokens, decode steps, trace file)."""
    import jax
    from repro.serving.engine import InferenceEngine
    eng = InferenceEngine(cfg, capacity=2, max_seq=MAX_SEQ, max_queue=16,
                          endpoint_id="ep-spans")
    eng.start(None)
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tracing.WINDOW_ANNOTATION):
            t = threading.Thread(target=eng.run, daemon=True)
            t.start()
            threading.Event().wait(0.1)         # the loop waits for work
            reqs = [eng.submit(p, max_new=m) for p, m in zip(prompts, max_news)]
            for r in reqs:
                assert r.wait(180), r.status
            eng.drain()
            t.join(30)
        assert not t.is_alive()
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    path = next(Path(trace_dir).rglob("*.xplane.pb")) if trace_dir else None
    return [r.tokens for r in reqs], eng.stats()["decode_steps"], path


def _thread_spans(path):
    """The ``serve.*`` spans of the host thread that ran the most decode
    steps. ``load`` merges threads of one name, and engines that earlier
    tests left in this process write ``serve.wait`` spans of their own."""
    from jax.profiler import ProfileData
    lines = [[Event(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                    e.name, line.name)
              for e in line.events if e.name.startswith(spans.PREFIX)]
             for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines]
    best = max(lines, key=lambda evs: sum(e.name == "serve.read" for e in evs))
    return sorted(best, key=lambda e: e.start)


def test_engine_phases_tile_the_loop(tmp_path):
    from repro.configs.registry import resolve_arch
    cfg = resolve_arch("stablelm-1.6b-smoke")
    rng = np.random.RandomState(0)
    # two equal lengths first (one prefill batch), then joins mid-flight
    lengths, max_news = [8, 8, 5, 11, 8], [3, 6, 4, 5, 7]
    prompts = [rng.randint(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in lengths]
    plain, _, _ = _serve(cfg, prompts, max_news)
    traced, steps, path = _serve(cfg, prompts, max_news, tmp_path)
    assert traced == plain

    tr = tracing.load(path)
    assert {e.name for e in spans.engine_spans(tr)} == set(PHASES)
    assert spans.mean_span_ms(tr, spans.ADMIT) > 0
    ours = _thread_spans(path)
    names = [e.name for e in ours]
    assert set(names) == set(PHASES)
    assert names.count("serve.read") == steps > 0
    for a, b in zip(ours, ours[1:]):
        assert b.start >= a.end, (a, b)
    covered = sum(e.end - e.start for e in ours)
    assert covered >= 0.9 * (ours[-1].end - ours[0].start)
