"""fused_query_roofline: percent of its roofline the fused query kernel
reaches (``kernels/fused_query.py``, also run on int8/bf16 codes through
``kernels/quantize.py``), over every call of the window: the least time
its shapes allow over its device time
(``chipbench.work.fused_query.roofline_share``, which names the trace
events it reads)."""

from chipbench.trace import reduce
from chipbench.work import fused_query


def read(ctx):
    if ctx.plain is None:
        return None
    return fused_query.roofline_share(
        reduce.device_lines(ctx.plain, reduce.OPS_LINE), ctx.peaks)
