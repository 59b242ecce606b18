"""device.idle_share: percent of the traced window in which no operation
ran on the device: 100 * (1 - union of the device-operation intervals /
window), from the trace's ``XLA Ops`` line of each device plane
(``chipbench.trace.reduce.idle_percent``), averaged over the chips."""

from chipbench.trace import reduce


def read(ctx):
    return reduce.idle_percent(ctx.plain, ctx.busy_s, ctx.window_s)
