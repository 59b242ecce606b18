"""quantized_query_roofline: percent of its roofline the query kernel
reaches on int8 or bf16 codes (``kernels/quantize.py`` running
``kernels/fused_query.py`` on the codes; the trace's
``%_quantized_query_impl`` events), as ``fused_query_roofline`` counts it;
the float32 delta segment's calls are left out.  For closed-loop cells,
where it moves the rows answered per second."""

from chipbench.trace import reduce
from chipbench.work import fused_query


def read(ctx):
    if ctx.plain is None:
        return None
    return fused_query.roofline_share(
        reduce.device_lines(ctx.plain, reduce.OPS_LINE), ctx.peaks,
        wrappers=("quantized",))
