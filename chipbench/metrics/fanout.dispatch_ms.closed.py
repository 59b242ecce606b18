"""fanout.dispatch_ms.closed: ``fanout.dispatch_ms`` for closed-loop
cells, where the fan-out's dispatch time moves the rows answered per
second: the median ``query.segments`` span inside a ``batch`` span."""

import statistics

from chipbench import spans


def read(ctx):
    per_batch = spans.per_batch_ms(ctx.spans, "query.segments")
    if not per_batch:
        return None
    return statistics.median(per_batch)
