"""The load generator's process: sends a plan over the wire and records
every request.

    python3 -m chipbench.loadgen.child PLAN_DIR

``PLAN_DIR`` holds ``plan.json`` (the plan of ``schedule.build`` plus
``host``, ``port``, ``tenant``, ``k``, ``n_probes`` and ``cpu``, the core
to run on, or null) and the payload pools ``queries.npy`` and
``inserts.npy``.  The child opens its connections, prints ``ready``, and
starts the window when it reads ``go`` on its standard input; the garbage
collector stays off in the window.  It prints ``closed`` when the window
ends, waits for every request still outstanding (at most ``DRAIN_S``
more), writes ``results.json`` (the records, the lateness, and how often
the child was preempted, the CPU time it used and its page faults in the
window) and prints ``done``.

Every time is ``time.perf_counter()``, which on Linux reads the same
monotonic clock in every process, so the server's spans and these records
share one time line.  This process never imports JAX.
"""

from __future__ import annotations

import gc
import json
import os
import queue
import resource
import sys
import threading
import time

import numpy as np

from .. import wire
from . import schedule

DRAIN_S = 60.0       # how long past the window's close answers are awaited


def _send(conn: wire.Connection, rec: dict, plan: dict, pools: dict) -> None:
    """Send one request and fill in its record."""
    kind = rec["kind"]
    rec["send"] = time.perf_counter()
    try:
        if kind == "query":
            a, b = rec["rows"]
            resp = conn.query(plan["tenant"], pools["queries"][a:b],
                              plan["k"], plan["n_probes"])
        elif kind == "insert":
            a, b = rec["rows"]
            resp = conn.insert(plan["tenant"], pools["inserts"][a:b])
        else:
            resp = conn.delete(plan["tenant"], rec["gids"])
    except (OSError, ValueError) as e:
        rec["recv"] = None
        rec["ok"] = False
        rec["code"] = f"lost: {type(e).__name__}: {e}"
        return
    rec["recv"] = time.perf_counter()
    rec["ok"] = bool(resp.get("ok"))
    if not rec["ok"]:
        rec["code"] = resp.get("code")
        return
    if kind == "query":
        rec["gids"] = resp["gids"]
        rec["dists"] = resp["dists"]
    elif kind == "insert":
        rec["ack_gids"] = resp["gids"]
    else:
        rec["n_deleted"] = resp["n_deleted"]


def run_open(conns, plan, pools, t0: float) -> list:
    """Send each request when it is due, on the first idle connection."""
    todo: queue.Queue = queue.Queue()
    records = []
    for r in plan["requests"]:
        rec = dict(r)
        rec["due"] = t0 + r["due"]
        records.append(rec)

    def worker(conn):
        while True:
            rec = todo.get()
            if rec is None:
                return
            _send(conn, rec, plan, pools)

    threads = [threading.Thread(target=worker, args=(c,), daemon=True)
               for c in conns]
    for t in threads:
        t.start()
    for rec in records:
        wait = rec["due"] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        rec["put"] = time.perf_counter()     # handed to the workers
        todo.put(rec)
    return records, threads, todo


def run_closed(conns, plan, pools, t0: float) -> list:
    """Each client keeps one query outstanding until the window closes."""
    t_end = t0 + plan["seconds"]
    rows = plan["rows"]
    per_client = [[] for _ in conns]

    def client(ci, conn):
        a, b = plan["clients"][ci]
        n = (b - a) // rows
        i = 0
        while time.perf_counter() < t_end:
            s = a + (i % n) * rows
            rec = {"kind": "query", "rows": [s, s + rows], "due": None,
                   "client": ci}
            per_client[ci].append(rec)
            _send(conn, rec, plan, pools)
            i += 1

    threads = [threading.Thread(target=client, args=(ci, c), daemon=True)
               for ci, c in enumerate(conns)]
    for t in threads:
        t.start()
    return per_client, threads, None


def main(argv=None) -> int:
    plan_dir = (argv or sys.argv[1:])[0]
    with open(os.path.join(plan_dir, "plan.json"), encoding="utf-8") as f:
        plan = json.load(f)
    if plan.get("cpu") is not None:
        os.sched_setaffinity(0, {int(plan["cpu"])})
    pools = {name: np.load(os.path.join(plan_dir, f"{name}.npy"))
             for name in ("queries", "inserts")}
    n_conn = (len(plan["clients"]) if plan["mode"] == "closed"
              else plan["connections"])
    conns = [wire.Connection(plan["host"], plan["port"])
             for _ in range(n_conn)]
    print("ready", flush=True)
    if sys.stdin.readline().strip() != "go":
        return 2
    gc.freeze()
    gc.disable()
    use0 = resource.getrusage(resource.RUSAGE_SELF)
    t0 = time.perf_counter()
    run = run_open if plan["mode"] == "open" else run_closed
    records, threads, todo = run(conns, plan, pools, t0)
    left = t0 + plan["seconds"] - time.perf_counter()
    if left > 0:
        time.sleep(left)
    use1 = resource.getrusage(resource.RUSAGE_SELF)
    preempted = use1.ru_nivcsw - use0.ru_nivcsw
    cpu_ms = (use1.ru_utime + use1.ru_stime
              - use0.ru_utime - use0.ru_stime) * 1e3
    faults = (use1.ru_majflt - use0.ru_majflt,
              use1.ru_minflt - use0.ru_minflt)
    gc.enable()
    print("closed", flush=True)
    if todo is not None:
        for _ in threads:
            todo.put(None)
    deadline = t0 + plan["seconds"] + DRAIN_S
    for t in threads:
        t.join(max(0.0, deadline - time.perf_counter()))
    if plan["mode"] == "closed":
        records = [r for recs in records for r in recs]
    # a request still unanswered here never came back: it counts as lost
    for r in records:
        if "ok" not in r:
            r.update(ok=False, recv=None, code="lost: no answer")
    late = schedule.lateness(records)
    # the schedule thread's own lateness: where it is small and the send
    # was late, the request waited for a free connection, not for the core
    late["put_max_ms"] = max(((r["put"] - r["due"]) * 1e3 for r in records
                              if "put" in r), default=0.0)
    print(f"[chipbench] load generator lateness: sent={late['n']} "
          f"p50_ms={late['p50_ms']!r} p99_ms={late['p99_ms']!r} "
          f"max_ms={late['max_ms']!r} put_max_ms={late['put_max_ms']!r} "
          f"preempted={preempted}", file=sys.stderr, flush=True)
    with open(os.path.join(plan_dir, "results.json"), "w",
              encoding="utf-8") as f:
        json.dump({"t0": t0, "records": records, "lateness": late,
                   "preempted": preempted, "cpu_ms": cpu_ms,
                   "faults": faults}, f)
    for c in conns:
        c.close()
    print("done", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
