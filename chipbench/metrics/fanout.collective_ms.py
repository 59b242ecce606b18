"""fanout.collective_ms: median, over the window's ``batch`` spans, of the
``query.collective`` span inside each (``SegmentedIndex._fan_out`` in
``serve/segments.py`` on a tenant sharded over a serve mesh): the host
time of the call of the sharded program
(``core/distributed.py::segment_query_sharded``: each chip's static unroll
over the sealed segments it holds, the replicated delta, the
``all_gather`` fan-in and the unique merge)."""

import statistics

from chipbench import spans


def read(ctx):
    per_batch = spans.per_batch_ms(ctx.spans, "query.collective")
    if not per_batch:
        return None
    return statistics.median(per_batch)
