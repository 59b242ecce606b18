"""The program's served-path spans (``repro.obs.trace``) grouped by the
``batch`` span they ran in, and interval arithmetic on the trace's clock,
for the per-layer metrics that read them.

A span belongs to a batch when its chain of parents reaches that batch's
span: the spans of one batch share its trace and nest under it on the
batcher's thread (a writer's spans, on another thread, never do)."""

from __future__ import annotations

from typing import Dict, Iterable, List, Sequence, Tuple


def per_batch_ms(spans: Sequence[dict], name: str) -> List[float]:
    """Milliseconds of the spans named ``name`` inside each ``batch``
    span, one total per batch that holds at least one."""
    by_id = {s["span_id"]: s for s in spans}
    totals: Dict[int, float] = {}
    for s in spans:
        if s["name"] != name:
            continue
        p = by_id.get(s["parent_id"])
        while p is not None and p["name"] != "batch":
            p = by_id.get(p["parent_id"])
        if p is not None:
            totals[p["span_id"]] = (totals.get(p["span_id"], 0.0)
                                    + (s["t1"] - s["t0"]) * 1e3)
    return list(totals.values())


def overlap(a: Iterable[Tuple[float, float]],
            b: Iterable[Tuple[float, float]]) -> float:
    """Length of the intersection of two sets of [start, end) intervals,
    each sorted and non-overlapping (``reduce.merged`` gives that)."""
    a, b = list(a), list(b)
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total
