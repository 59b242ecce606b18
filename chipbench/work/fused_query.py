"""The logical work of one fused query-kernel call, from its shapes.

``kernels/fused_query.py`` scores ``nq`` queries against ``C`` candidate
rows each (``C = n_tables * n_probes * bucket_capacity``) of width ``N``
and keeps a top-``k``; the quantized tier runs the same kernel on int8 or
bf16 codes.  The work is counted as query scoring needs it, whatever
implements it:

* bytes: every candidate row once at the stored dtype's width
  (``nq * C * N * itemsize``), the float32 queries (``nq * N * 4``) and the
  ``(nq, k)`` float32 distances and int32 ids written back;
* operations: a subtract, an absolute value or square, and an add per
  coordinate of each candidate (``3 * N`` per candidate).

The kernel moves a whole native tile (32 bytes of rows) per candidate, so
what it moves exceeds these bytes; the roofline share says how far the
kernel is from the least the work allows.

On a TPU each call is one ``XLA Ops`` event of the trace whose name is the
HLO text of the custom call, named after the jitted wrapper that holds it
(``%_fused_query_impl...`` for float32 rows, ``%_quantized_query_impl...``
for codes).  ``calls`` takes each call's shapes from that text: the
``(nq, 1, k)`` outputs, the ``(nq * C,)`` candidate ids, the ``(nq, 1, N)``
queries and the stored rows' dtype; ``roofline_share`` is the summed least
time over the summed device time, in percent.
"""

from __future__ import annotations

import re
from typing import Optional, Tuple

KERNEL = re.compile(
    r"^%_(fused|quantized)_query_impl[.\d]* = "
    r"\(f32\[(\d+),1,(\d+)\].*?custom-call\("
    r"s32\[(\d+)\]\{[^}]*\} %[^,]+, "
    r"f32\[\d+,1,(\d+)\]\{[^}]*\} %[^,]+, "
    r"(f32|bf16|s8)\[\d+,\d+\]")
ITEMSIZE = {"f32": 4, "bf16": 2, "s8": 1}


def work(nq: int, c: int, n: int, k: int, itemsize: int
         ) -> Tuple[float, float]:
    """(operations, bytes) of one call."""
    ops = 3.0 * n * nq * c
    nbytes = float(nq * c * n * itemsize + nq * n * 4 + nq * k * 8)
    return ops, nbytes


def least_seconds(nq: int, c: int, n: int, k: int, itemsize: int,
                  peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    the peak rate for the stored dtype and bytes over HBM bandwidth."""
    ops, nbytes = work(nq, c, n, k, itemsize)
    rate = peaks["int8_ops_per_s"] if itemsize == 1 else \
        peaks["bf16_flops_per_s"]
    return max(ops / rate, nbytes / peaks["hbm_bytes_per_s"])


def calls(events, wrappers=("fused", "quantized")):
    """(nq, C, N, k, itemsize, seconds) of each kernel event, of the
    jitted wrappers named (``fused``, ``quantized``)."""
    out = []
    for name, _, dur in events:
        m = KERNEL.match(name)
        if m is None or m.group(1) not in wrappers:
            continue
        _, nq, k, ids, n, dt = m.groups()
        nq, k, ids, n = int(nq), int(k), int(ids), int(n)
        out.append((nq, ids // nq, n, k, ITEMSIZE[dt], dur / 1e9))
    return out


def roofline_share(lines: dict, peaks: dict,
                   wrappers=("fused", "quantized")) -> Optional[float]:
    """Percent of the least time over the device time of the named
    wrappers' calls, over every device's ``XLA Ops`` events (``lines``);
    None where there is none."""
    least = busy = 0.0
    for evs in lines.values():
        for nq, c, n, k, itemsize, sec in calls(evs, wrappers):
            least += least_seconds(nq, c, n, k, itemsize, peaks)
            busy += sec
    if busy <= 0:
        return None
    return 100.0 * least / busy
