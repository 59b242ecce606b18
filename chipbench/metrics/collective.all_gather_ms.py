"""collective.all_gather_ms: device time of the sharded fan-in's
all-gather per query batch (``core/distributed.py::segment_query_sharded``:
one ``all_gather`` of each chip's (nq, k) gids and one of its distances).
Per ``batch`` span, the durations of the ``XLA Ops`` events of each device
plane that are all-gather ops and start inside the span are summed, the
sums are averaged over the chips, and the median over batches is taken.

An event is an all-gather op where its name, the HLO text of the
instruction, names an ``all-gather`` instruction.  On a four-chip TPU v5e
host the program runs them synchronously, one pair per chunk shape, e.g.
``%all-gather = s32[32,10]{1,0:T(8,128)S(1)} all-gather(...)`` and
``%all-gather.1 = f32[32,10]...`` for 8-row batches,
``%all-gather.13 = s32[4,32,10]...`` and ``%all-gather.12 =
f32[4,32,10]...`` for 32-row ones; an asynchronous pair
(``%all-gather-start`` / ``%all-gather-done``) would match as well."""

import re
import statistics

from chipbench.trace import reduce

ALL_GATHER = re.compile(r"^%all-gather[-.\w]* = ")


def read(ctx):
    if ctx.plain is None:
        return None
    planes = reduce.device_lines(ctx.plain, reduce.OPS_LINE)
    batches = ctx.batch_spans_ns()
    if not planes or not batches:
        return None
    gathers = [[(s, d) for name, s, d in evs if ALL_GATHER.match(name)]
               for evs in planes.values()]
    per_batch = []
    for b0, b1, _ in batches:
        chips = [sum(d for s, d in evs if b0 <= s < b1) for evs in gathers]
        if any(chips):
            per_batch.append(sum(chips) / len(chips) / 1e6)
    if not per_batch:
        return None
    return statistics.median(per_batch)
