"""batcher.queue_wait_ms: median ``admission`` span of the window
(``serve/batcher.py``): how long a query waited in the micro-batcher's
queue before its batch was dispatched."""

import statistics


def read(ctx):
    waits = [s["t1"] - s["t0"] for s in ctx.spans if s["name"] == "admission"]
    if not waits:
        return None
    return statistics.median(waits) * 1e3
