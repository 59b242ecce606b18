"""Reductions from the plain trace form (``xplane.read``) to numbers.

Device work is read from each device plane's ``OPS_LINE`` (one event per
operation executed on the chip) and programs from its ``MODULES_LINE``
(one event per compiled program executed).  Host spans of the program
(``repro.obs.trace``: ``batch``, ``admission``, ``request``, ...) are on
``time.perf_counter``; ``Clock`` maps them onto the trace's clock through
the window marker, which both clocks saw open.
"""

from __future__ import annotations

import heapq
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

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


def _union(intervals) -> Tuple[np.ndarray, np.ndarray]:
    """(starts, ends) of the union of [start, end) intervals (each start
    <= end; pairs or an (n, 2) array), sorted: intervals that overlap or
    touch join.  Vectorised, for traces of millions of events."""
    iv = np.asarray(intervals if isinstance(intervals, np.ndarray)
                    else list(intervals), np.float64).reshape(-1, 2)
    if not len(iv):
        return iv[:, 0], iv[:, 1]
    iv = iv[np.lexsort((iv[:, 1], iv[:, 0]))]
    starts, reach = iv[:, 0], np.maximum.accumulate(iv[:, 1])
    first = np.flatnonzero(np.r_[True, starts[1:] > reach[:-1]])
    last = np.r_[first[1:] - 1, len(starts) - 1]
    return starts[first], reach[last]


def merged(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Union of [start, end) intervals, sorted and non-overlapping."""
    return np.stack(_union(intervals), axis=1).tolist()


def clipped(events: Sequence[list], t0: float, t1: float) -> np.ndarray:
    """(n, 2) [start, end) of each event cut to [t0, t1), the events that
    leave nothing there dropped."""
    n = len(events)
    start = np.fromiter((ev[1] for ev in events), np.float64, n)
    end = np.minimum(start + np.fromiter((ev[2] for ev in events),
                                         np.float64, n), t1)
    start = np.maximum(start, t0)
    keep = end > start
    return np.stack([start[keep], end[keep]], axis=1)


def busy_ns(events: Sequence[list], t0: float, t1: float) -> float:
    """Nanoseconds of [t0, t1) in which some event ran."""
    starts, ends = _union(clipped(events, t0, t1))
    return sum((ends - starts).tolist())


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
    starts, ends = _union(clipped(events, t0, t1))
    lo, hi = np.r_[t0, ends], np.r_[starts, t1]
    keep = hi > lo
    return list(zip(lo[keep].tolist(), hi[keep].tolist()))


def label_gaps(gaps: Sequence[Tuple[float, float]],
               host_spans: Sequence[Tuple[str, float, float]],
               top: int = 10) -> List[list]:
    """The longest idle gaps, each named by the host span open at its
    midpoint (the latest-opened one, i.e. the innermost; ``"no span"``
    when none is).  Gaps with the same label are summed."""
    spans = sorted(host_spans, key=lambda sp: sp[1])
    mids = [(s + e) / 2 for s, e in gaps]
    label = ["no span"] * len(mids)
    # midpoints in time order; ``opened`` holds every span opened at or
    # before the midpoint, the latest-opened on top, and a span on top
    # that has closed is dropped for good (later midpoints lie later)
    opened: List[Tuple[int, float]] = []
    nxt = 0
    for j in sorted(range(len(mids)), key=mids.__getitem__):
        mid = mids[j]
        while nxt < len(spans) and spans[nxt][1] <= mid:
            heapq.heappush(opened, (-nxt, spans[nxt][2]))
            nxt += 1
        while opened and opened[0][1] <= mid:
            heapq.heappop(opened)
        if opened:
            label[j] = spans[-opened[0][0]][0]
    totals: Dict[str, float] = {}
    for j, (s, e) in enumerate(gaps):
        totals[label[j]] = totals.get(label[j], 0.0) + (e - s)
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
