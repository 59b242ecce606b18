"""fanout.survivor_gather_ms: median, over the window's ``batch`` spans,
of the ``survivor.gather`` span inside each (``_query_quantized`` in
``serve/segments.py``): the survivor gids' copy to the host and the
exact rows' gather from the host pools, which ROADMAP S5 would keep on
the device."""

import statistics

from chipbench import spans


def read(ctx):
    per_batch = spans.per_batch_ms(ctx.spans, "survivor.gather")
    if not per_batch:
        return None
    return statistics.median(per_batch)
