"""frontend.wire_ms: median, over the window's answered queries, of the
client's latency minus the server's ``request`` span (``serve/frontend.py``)
-- the time a query spends in the wire protocol, the socket and the
client's own sending.  Reads the client's records and the program's
``request`` spans with ``op=query``; each record takes the earliest-opened
unmatched span that lies inside its send-to-receive interval (all times
are ``time.perf_counter``, one clock across processes)."""

import bisect
import statistics


def read(ctx):
    recs = sorted((r for r in ctx.records
                   if r["kind"] == "query" and r["ok"]),
                  key=lambda r: r["send"])
    spans = sorted((s for s in ctx.spans if s["name"] == "request"
                    and s["attrs"].get("op") == "query"),
                   key=lambda s: s["t0"])
    starts = [s["t0"] for s in spans]
    used = set()
    gaps = []
    for r in recs:
        i = bisect.bisect_left(starts, r["send"])
        while i < len(spans) and spans[i]["t0"] <= r["recv"]:
            s = spans[i]
            if i not in used and s["t1"] <= r["recv"]:
                used.add(i)
                gaps.append((r["recv"] - r["send"]) - (s["t1"] - s["t0"]))
                break
            i += 1
    if not gaps:
        return None
    return statistics.median(gaps) * 1e3
