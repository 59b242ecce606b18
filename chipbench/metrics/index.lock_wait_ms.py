"""index.lock_wait_ms: median ``index.lock_wait`` span of the window's
inserts and deletes (attr ``op`` in {insert, delete};
``SegmentedIndex.insert``/``delete`` in ``serve/segments.py``): how long
a write waits for the index lock that a query's fan-out holds."""

import statistics


def read(ctx):
    waits = [s["t1"] - s["t0"] for s in ctx.spans
             if s["name"] == "index.lock_wait"
             and s["attrs"].get("op") in ("insert", "delete")]
    if not waits:
        return None
    return statistics.median(waits) * 1e3
