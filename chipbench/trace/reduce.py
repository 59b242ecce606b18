"""Reductions from the plain trace form (``xplane.read``) to numbers.

Device work is read from each device plane's ``OPS_LINE`` (one event per
operation executed on the chip) and programs from its ``MODULES_LINE``
(one event per compiled program executed).  Host spans of the program
(``repro.obs.trace``: ``batch``, ``admission``, ``request``, ...) are on
``time.perf_counter``; ``Clock`` maps them onto the trace's clock through
the window marker, which both clocks saw open.
"""

from __future__ import annotations

import bisect
from typing import Dict, Iterable, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Clock:
    """perf_counter seconds -> trace nanoseconds, anchored at the marker."""

    def __init__(self, marker_start_ns: float, marker_perf_s: float):
        self.ns0 = float(marker_start_ns)
        self.s0 = float(marker_perf_s)

    def ns(self, t_perf_s: float) -> float:
        return self.ns0 + (float(t_perf_s) - self.s0) * 1e9


def window(plain: dict) -> Tuple[float, float]:
    start, dur = plain["marker"]
    return start, start + dur


def device_lines(plain: dict, line: str) -> Dict[str, List[list]]:
    """{plane: events} of the named line on every device plane."""
    out = {}
    for key, evs in plain["lines"].items():
        plane, _, name = key.partition("|")
        if name == line:
            out[plane] = evs
    return out


def merged(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Union of [start, end) intervals, sorted and non-overlapping."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events: Sequence[list], t0: float, t1: float) -> float:
    """Nanoseconds of [t0, t1) in which some event ran."""
    iv = ((max(s, t0), min(s + d, t1)) for _, s, d in events)
    return sum(e - s for s, e in merged((s, e) for s, e in iv if e > s))


def busy_s(plain: dict) -> float:
    """Device-busy seconds in the window, averaged over the device planes."""
    t0, t1 = window(plain)
    planes = device_lines(plain, OPS_LINE)
    if not planes:
        return 0.0
    return sum(busy_ns(evs, t0, t1) for evs in planes.values()) / (
        len(planes) * 1e9)


def idle_percent(plain, busy: float, window_s: float):
    """100 * (1 - busy / window), or None where the trace holds no device
    operations to read."""
    if plain is None or window_s <= 0 or not device_lines(plain, OPS_LINE):
        return None
    return 100.0 * (1.0 - busy / window_s)


def idle_gaps(events: Sequence[list], t0: float, t1: float
              ) -> List[Tuple[float, float]]:
    """The [start, end) stretches of the window in which no event ran."""
    gaps, cur = [], t0
    for s, e in merged((max(s, t0), min(s + d, t1)) for _, s, d in events):
        if e <= s:
            continue
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    return gaps


def label_gaps(gaps: Sequence[Tuple[float, float]],
               host_spans: Sequence[Tuple[str, float, float]],
               top: int = 10) -> List[list]:
    """The longest idle gaps, each named by the host span open at its
    midpoint (the latest-opened one, i.e. the innermost; ``"no span"``
    when none is).  Gaps with the same label are summed."""
    spans = sorted(host_spans, key=lambda sp: sp[1])
    starts = [sp[1] for sp in spans]
    totals: Dict[str, float] = {}
    for s, e in gaps:
        mid = (s + e) / 2
        best = "no span"
        # the covering span that opened last: scan back from the last
        # span opened before the midpoint
        for i in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            if spans[i][2] > mid:
                best = spans[i][0]
                break
        totals[best] = totals.get(best, 0.0) + (e - s)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]


def top_ops(events: Sequence[list], top: int = 10) -> List[list]:
    """Device operations with the most total time, in seconds."""
    totals: Dict[str, float] = {}
    for name, _, d in events:
        totals[name] = totals.get(name, 0.0) + d
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]


def count_in(events: Sequence[list], start: float, end: float) -> int:
    """Events that start inside [start, end)."""
    return sum(1 for _, s, _ in events if start <= s < end)
