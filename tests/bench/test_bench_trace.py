"""Trace reduction: busy union, idle share, time by program, idle gaps
labelled by host events; and reading a real (CPU) profiler trace."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace as tracing  # noqa: E402
from bench.trace import Event, Trace  # noqa: E402

TPU = "/device:TPU:0"


def _trace():
    ops = [Event(0.0, 1.0, "fusion.1", TPU), Event(0.5, 2.0, "fusion.2", TPU),
           Event(4.0, 5.0, "fusion.1", TPU), Event(9.5, 11.0, "copy", TPU)]
    mods = [Event(0.0, 2.0, "jit_prefill(1)", TPU), Event(4.0, 5.0, "jit_decode_one(2)", TPU),
            Event(9.5, 11.0, "jit_decode_one(2)", TPU)]
    host = [Event(2.5, 3.4, "PjitFunction(prefill)", "python3"),
            Event(2.0, 2.2, "short", "python3"),
            Event(0.0, 10.0, "too long to say anything", "python3"),
            Event(0.0, 10.0, tracing.WINDOW_ANNOTATION, "python3")]
    return Trace(window=(0.0, 10.0), ops={TPU: ops}, modules={TPU: mods}, host=host)


def test_union_and_busy():
    assert tracing.union([(0, 1), (0.5, 2), (3, 4)]) == [(0, 2), (3, 4)]
    tr = _trace()
    # ops clipped to the window: [0,2] + [4,5] + [9.5,10]
    assert tracing.mean_busy_s(tr) == pytest.approx(3.5)


def test_gaps_and_labels():
    tr = _trace()
    gaps = tracing.gaps(tr.ops[TPU], tr.window)
    assert gaps == [(2.0, 4.0), (5.0, 9.5)]
    assert tracing.label_gap(gaps[0], tr.host) == "PjitFunction(prefill)"
    assert tracing.label_gap(gaps[1], tr.host) == "unattributed"
    assert tracing.longest_gaps(tr) == [["unattributed", pytest.approx(4.5)],
                                        ["PjitFunction(prefill)", pytest.approx(2.0)]]


def test_time_by_program_and_top_ops():
    tr = _trace()
    secs, n = tracing.module_time(tr, "decode")
    assert (secs, n) == (pytest.approx(1.5), 2)
    assert tracing.module_time(tr, "prefill") == (pytest.approx(2.0), 1)
    top = tracing.top_ops(tr)
    assert top[0] == ["fusion.1", pytest.approx(2.0)]
    assert [n for n, _ in top] == ["fusion.1", "fusion.2", "copy"]


def test_metric_readers_on_a_trace():
    from bench import harness
    tr = _trace()
    idle = harness.metric_reader("device_idle_share.serve").read(None, tr)
    assert idle == pytest.approx(65.0)
    assert harness.metric_reader("decode_step_ms.serve").read(None, tr) == pytest.approx(750.0)
    assert harness.metric_reader("prefill_share.serve").read(None, tr) == pytest.approx(100 * 2.0 / 3.5)
    empty = Trace(window=(0.0, 1.0))
    assert harness.metric_reader("decode_step_ms.serve").read(None, empty) is None
    assert harness.metric_reader("device_idle_share.serve").read(None, empty) is None


def test_load_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.WINDOW_ANNOTATION):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    tr = tracing.load(path)
    assert tr.window_s > 0
    names = {e.name for e in tr.host}
    assert "bench.step" in names
    step = next(e for e in tr.host if e.name == "bench.step")
    assert tr.window[0] <= step.start <= step.end <= tr.window[1]
