"""fanout.programs_per_batch: mean number of device programs that start
inside a ``batch`` span (``serve/batcher.py`` around
``SegmentedIndex.query`` in ``serve/segments.py``): the segment fan-out's
dispatches per query batch, read from the ``XLA Modules`` line of the
device trace.  Programs that other threads start meanwhile (an insert's)
would count too, and the trace names the segment program as it names the
insert's, so the metric is listed only for cells without writes."""

from chipbench.trace import reduce


def read(ctx):
    if ctx.plain is None:
        return None
    planes = reduce.device_lines(ctx.plain, reduce.MODULES_LINE)
    batches = ctx.batch_spans_ns()
    if not planes or not batches:
        return None
    counts = [sum(reduce.count_in(evs, b0, b1) for evs in planes.values())
              for b0, b1, _ in batches]
    return sum(counts) / len(counts)
