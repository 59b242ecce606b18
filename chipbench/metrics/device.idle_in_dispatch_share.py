"""device.idle_in_dispatch_share: percent of the window's device-idle
time that falls inside a ``query.segments`` span (the unsharded
fan-out's dispatch, ``serve/segments.py``), the spans mapped onto the
trace's clock with ``ctx.clock``; idle is where no ``XLA Ops`` event of a
device plane runs (``chipbench.trace.reduce.idle_gaps``), summed over the
chips."""

from chipbench import spans
from chipbench.trace import reduce


def read(ctx):
    if ctx.plain is None or ctx.clock is None:
        return None
    planes = reduce.device_lines(ctx.plain, reduce.OPS_LINE)
    dispatch = reduce.merged(
        (ctx.clock.ns(s["t0"]), ctx.clock.ns(s["t1"]))
        for s in ctx.spans if s["name"] == "query.segments")
    if not planes or not dispatch:
        return None
    t0, t1 = reduce.window(ctx.plain)
    idle = inside = 0.0
    for evs in planes.values():
        gaps = reduce.idle_gaps(evs, t0, t1)
        idle += sum(e - s for s, e in gaps)
        inside += spans.overlap(gaps, dispatch)
    if idle <= 0:
        return None
    return 100.0 * inside / idle
