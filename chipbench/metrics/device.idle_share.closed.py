"""device.idle_share.closed: ``device.idle_share`` for closed-loop cells,
where the device's idle share moves the rows answered per second."""

from chipbench.trace import reduce


def read(ctx):
    return reduce.idle_percent(ctx.plain, ctx.busy_s, ctx.window_s)
