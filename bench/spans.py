"""The serving engine's loop phases against the device's idle time.

The engine writes one profiler span per phase of its serve loop on its own
thread (``serve.wait``, ``serve.schedule``, ``serve.admit``,
``serve.dispatch``, ``serve.read``, ``serve.bookkeep``). The phases tile
the loop, so each moment the device is idle lies in the phase the engine
was in then: in ``serve.wait`` the chip idles for want of work, in any
other phase it waits on the host. Idle time in no phase is
``unattributed``. A trace with no ``serve.*`` span, from a program that
writes none, reads ``None`` rather than 0.
"""
from __future__ import annotations

import bisect
import collections
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace as tracing
from bench.trace import Event, Trace

PREFIX = "serve."
WAIT = "serve.wait"
ADMIT = "serve.admit"
UNATTRIBUTED = "unattributed"


def engine_spans(tr: Trace) -> List[Event]:
    """The engine's phase spans that overlap the window, clipped to it,
    in order of start."""
    return sorted(tracing.clip((e for e in tr.host if e.name.startswith(PREFIX)),
                               tr.window), key=lambda e: e.start)


def _idle_in(gaps: Sequence[Tuple[float, float]], spans: Sequence[Event]
             ) -> Dict[str, float]:
    """Seconds of the (sorted, disjoint) idle ``gaps`` inside each span,
    by span name."""
    ends = [b for _, b in gaps]
    out: Dict[str, float] = collections.Counter()
    for e in spans:
        i = bisect.bisect_right(ends, e.start)
        while i < len(gaps) and gaps[i][0] < e.end:
            out[e.name] += min(e.end, gaps[i][1]) - max(e.start, gaps[i][0])
            i += 1
    return out


def idle_by_phase(tr: Trace) -> Optional[Dict[str, float]]:
    """Device idle seconds in each ``serve.*`` phase and in none
    (``unattributed``), mean over the chips; None where the trace holds no
    engine span or no device op."""
    spans = engine_spans(tr)
    per_chip = tracing.device_events(tr)
    if not spans or not per_chip:
        return None
    out: Dict[str, float] = collections.Counter()
    for evs in per_chip.values():
        for name, secs in _idle_in(tracing.gaps(evs, tr.window), spans).items():
            out[name] += secs / len(per_chip)
        # idle and in no span: the gaps left by device ops and spans alike
        out[UNATTRIBUTED] += sum(b - a for a, b in tracing.gaps(
            list(evs) + spans, tr.window)) / len(per_chip)
    return dict(out)


def idle_shares(tr: Trace) -> Optional[Dict[str, float]]:
    """Shares (%) of the window in which the device is idle: for want of
    work (``no_work``: inside ``serve.wait``), on the host (``host``: in
    any other phase), and ``unattributed``; beside them each phase's own
    share under its span name."""
    idle = idle_by_phase(tr)
    if idle is None or tr.window_s <= 0:
        return None
    pct = {k: 100.0 * v / tr.window_s for k, v in idle.items()}
    pct["no_work"] = pct.get(WAIT, 0.0)
    pct["host"] = sum(v for k, v in pct.items()
                      if k.startswith(PREFIX) and k != WAIT)
    return pct


def mean_span_ms(tr: Trace, name: str) -> Optional[float]:
    """Mean wall time (ms) of the spans called ``name`` that lie wholly
    inside the window; None where there are none."""
    lo, hi = tr.window
    secs = [e.end - e.start for e in tr.host
            if e.name == name and lo <= e.start and e.end <= hi]
    return 1e3 * sum(secs) / len(secs) if secs else None
