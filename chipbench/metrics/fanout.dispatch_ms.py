"""fanout.dispatch_ms: median, over the window's ``batch`` spans, of the
``query.segments`` span inside each (``SegmentedIndex._fan_out`` in
``serve/segments.py``): the host time from the first per-segment program
call through the concatenate and merge dispatch, the work ROADMAP S3
would fold into one program."""

import statistics

from chipbench import spans


def read(ctx):
    per_batch = spans.per_batch_ms(ctx.spans, "query.segments")
    if not per_batch:
        return None
    return statistics.median(per_batch)
