"""Profiler trace to per-layer numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
events on one clock; the functions below reduce them. Device busy time is
the union of the intervals in which an operation ran on a chip, averaged
over the chips; idle gaps are the rest of the traced window, each labelled
by the host event that overlaps it most (JAX's own dispatch events and the
benchmark's annotations), or ``unattributed`` where none does.
"""
from __future__ import annotations

import collections
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_ANNOTATION = "bench.traced_window"
# host events at least this long span whole phases (a blocking call, the
# window itself) and say nothing about one gap
LONG_HOST_EVENT_S = 1.0


@dataclass(frozen=True)
class Event:
    start: float            # seconds on the trace clock
    end: float
    name: str
    where: str = ""         # device plane, or host thread


@dataclass
class Trace:
    window: Tuple[float, float]
    ops: Dict[str, List[Event]] = field(default_factory=dict)      # chip -> ops
    modules: Dict[str, List[Event]] = field(default_factory=dict)  # chip -> programs
    host: List[Event] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def load(path: Path) -> Trace:
    """Read one ``.xplane.pb``: TPU planes' ``XLA Ops`` and ``XLA Modules``
    lines, and every host thread. The window is the span of the
    ``bench.traced_window`` annotation, else of all events."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    tr = Trace(window=(0.0, 0.0))
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    tr.ops[plane.name] = _events(line, plane.name)
                elif line.name == "XLA Modules":
                    tr.modules[plane.name] = _events(line, plane.name)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host.extend(_events(line, line.name))
    marks = [e for e in tr.host if e.name == WINDOW_ANNOTATION]
    if marks:
        tr.window = (marks[0].start, marks[0].end)
    else:
        every = [e for evs in tr.ops.values() for e in evs] + tr.host
        if every:
            tr.window = (min(e.start for e in every),
                         max(e.end for e in every))
    return tr


def _events(line, where: str) -> List[Event]:
    return [Event(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                  e.name, where) for e in line.events]


def clip(events: Iterable[Event], window: Tuple[float, float]) -> List[Event]:
    lo, hi = window
    return [Event(max(e.start, lo), min(e.end, hi), e.name, e.where)
            for e in events if e.end > lo and e.start < hi]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(events: Sequence[Event], window: Tuple[float, float]) -> float:
    return sum(b - a for a, b in union(
        (e.start, e.end) for e in clip(events, window)))


def gaps(events: Sequence[Event], window: Tuple[float, float]
         ) -> List[Tuple[float, float]]:
    lo, hi = window
    out, t = [], lo
    for a, b in union((e.start, e.end) for e in clip(events, window)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def label_gap(gap: Tuple[float, float], host: Sequence[Event]) -> str:
    """Name of the host event that overlaps the gap most."""
    a, b = gap
    best, best_overlap = "unattributed", 0.0
    for e in host:
        if e.name == WINDOW_ANNOTATION or e.end - e.start >= LONG_HOST_EVENT_S:
            continue
        overlap = min(b, e.end) - max(a, e.start)
        if overlap > best_overlap:
            best, best_overlap = e.name, overlap
    return best


def device_events(tr: Trace) -> Dict[str, List[Event]]:
    """Per chip, the finest device events the trace has."""
    chips = sorted(set(tr.ops) | set(tr.modules))
    return {c: tr.ops.get(c) or tr.modules.get(c, []) for c in chips}


def mean_busy_s(tr: Trace) -> Optional[float]:
    per_chip = device_events(tr)
    if not per_chip:
        return None
    return sum(busy_s(evs, tr.window) for evs in per_chip.values()) / len(per_chip)


def module_time(tr: Trace, match: str) -> Tuple[float, float]:
    """Device seconds and number of runs of the programs whose name
    contains ``match`` within the window, per chip (mean over chips)."""
    total, n = 0.0, 0
    for evs in tr.modules.values():
        for e in clip(evs, tr.window):
            if match in e.name:
                total += e.end - e.start
                n += 1
    chips = max(1, len(tr.modules))
    return total / chips, n / chips


def top_ops(tr: Trace, k: int = 10) -> List[List]:
    by_name: Dict[str, float] = collections.Counter()
    for evs in device_events(tr).values():
        for e in clip(evs, tr.window):
            by_name[e.name] += e.end - e.start
    return [[n, s] for n, s in sorted(by_name.items(),
                                      key=lambda kv: -kv[1])[:k]]


def top_modules(tr: Trace, k: int = 10) -> List[List]:
    """Programs by device time in the window: [name, seconds, runs]."""
    by_name: Dict[str, List[float]] = collections.defaultdict(lambda: [0.0, 0])
    for evs in tr.modules.values():
        for e in clip(evs, tr.window):
            by_name[e.name][0] += e.end - e.start
            by_name[e.name][1] += 1
    return [[n, s, c] for n, (s, c) in sorted(by_name.items(),
                                              key=lambda kv: -kv[1][0])[:k]]


def longest_gaps(tr: Trace, k: int = 10) -> List[List]:
    """The ``k`` longest idle gaps, each labelled by its host event. Only
    those are labelled: a traced window holds thousands of gaps and
    hundreds of thousands of host events."""
    found = [g for evs in device_events(tr).values()
             for g in gaps(evs, tr.window)]
    found = sorted(found, key=lambda g: g[0] - g[1])[:k]
    host = [e for e in tr.host if e.name != WINDOW_ANNOTATION
            and e.end - e.start < LONG_HOST_EVENT_S]
    return [[label_gap(g, host), g[1] - g[0]] for g in found]
