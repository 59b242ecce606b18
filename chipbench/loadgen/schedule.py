"""The request plan of one run, drawn from the seed and the traffic file.

Every seed gets the same amount of work: an open-loop stream of rate ``r``
over ``s`` seconds has exactly ``round(r * s)`` arrivals, whose gaps are
one fixed draw of a Poisson process conditioned on its count, put in an
order drawn from the seed; so seeds differ in arrival order and payloads
but never in how many requests, rows, inserts or deletes a run carries, or
in the set of gaps between them.

Traffic file keys (``chipbench/traffic/<name>.json``):

* ``mode``: ``"open"`` (arrivals on a schedule, each request timed from
  when it was due) or ``"closed"`` (``clients`` connections, each keeping
  one request outstanding);
* ``query``: ``rows`` per request and, open loop, ``rate_per_s``;
* ``writes`` (open loop, optional): ``rate_per_s``, ``rows`` and ``ops``,
  the cycle of write kinds (``"insert"``, ``"delete"``) the stream follows;
* ``connections`` (open loop): connections the sender may use at once;
* ``pool_per_client`` (closed loop): distinct query requests per client
  before its rows repeat;
* ``warm_chunks``: the batcher chunk shapes the traffic reaches, warmed
  during set-up;
* ``probe_rows`` / ``probe_request_rows``: the held-out recall probe asked
  after the window;
* ``check_rows`` / ``check_deletes``: acknowledged inserted rows and
  deleted gids read back after it, drawn from the seed;
* ``check_loaded`` (optional): loaded items still live, read back after
  it in requests of ``probe_request_rows``, drawn from the seed.
"""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy stream per purpose, from any whole seed."""
    seed = int(seed) % (1 << 64)
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def arrivals(rate_per_s: float, seconds: float,
             gen: np.random.Generator) -> np.ndarray:
    """Sorted due times (s from the window's start) of a Poisson stream
    conditioned on its count, ``round(rate * seconds)``.  The gaps between
    arrivals are one fixed set for a given count and window; the seed only
    orders them, so every seed offers the same gaps and bursts in another
    order."""
    n = int(round(rate_per_s * seconds))
    fixed = np.random.default_rng(np.random.SeedSequence([n, 0x5EED]))
    gaps = np.diff(np.sort(fixed.uniform(0.0, seconds, size=n)),
                   prepend=0.0)
    return np.cumsum(gen.permutation(gaps))


def pool_rows(traffic: dict, seconds: float) -> Dict[str, int]:
    """Rows of each payload pool a run of this traffic needs."""
    q_rows = int(traffic["query"]["rows"])
    if traffic["mode"] == "closed":
        queries = int(traffic["clients"]) * int(traffic["pool_per_client"])
        return {"queries": queries * q_rows, "inserts": 0}
    n_q = int(round(traffic["query"]["rate_per_s"] * seconds))
    w = traffic.get("writes")
    inserts = 0
    if w:
        n_w = int(round(w["rate_per_s"] * seconds))
        ops = w["ops"]
        inserts = sum(ops[i % len(ops)] == "insert" for i in range(n_w)) \
            * int(w["rows"])
    return {"queries": n_q * q_rows, "inserts": inserts}


def build(traffic: dict, seconds: float, seed: int,
          victims: np.ndarray) -> dict:
    """The plan the child sends.  Payloads are referred to by row ranges
    into the query and insert pools; deletes carry their gids, taken in
    order from ``victims`` (a seeded permutation of live gids)."""
    q_rows = int(traffic["query"]["rows"])
    plan: dict = {"mode": traffic["mode"], "seconds": float(seconds)}
    if traffic["mode"] == "closed":
        per = int(traffic["pool_per_client"]) * q_rows
        plan["clients"] = [[c * per, (c + 1) * per]
                           for c in range(int(traffic["clients"]))]
        plan["rows"] = q_rows
        return plan
    if traffic["mode"] != "open":
        raise ValueError(f"unknown traffic mode {traffic['mode']!r}")
    plan["connections"] = int(traffic["connections"])
    reqs: List[dict] = []
    for i, due in enumerate(arrivals(traffic["query"]["rate_per_s"],
                                     seconds, rng(seed, 10))):
        reqs.append({"kind": "query", "due": float(due),
                     "rows": [i * q_rows, (i + 1) * q_rows]})
    w = traffic.get("writes")
    if w:
        w_rows = int(w["rows"])
        n_ins = n_del = 0
        for i, due in enumerate(arrivals(w["rate_per_s"], seconds,
                                         rng(seed, 11))):
            kind = w["ops"][i % len(w["ops"])]
            if kind == "insert":
                reqs.append({"kind": "insert", "due": float(due),
                             "rows": [n_ins * w_rows, (n_ins + 1) * w_rows]})
                n_ins += 1
            elif kind == "delete":
                gids = victims[n_del * w_rows:(n_del + 1) * w_rows]
                if len(gids) < w_rows:
                    raise ValueError("not enough live gids to delete")
                reqs.append({"kind": "delete", "due": float(due),
                             "gids": [int(g) for g in gids]})
                n_del += 1
            else:
                raise ValueError(f"unknown write op {kind!r}")
    reqs.sort(key=lambda r: r["due"])
    plan["requests"] = reqs
    return plan


def lateness(records: List[dict]) -> Dict[str, float]:
    """How late the sender ran: send time minus due time, in ms, over the
    requests that were sent (open loop only; closed loops have no due
    time)."""
    late = sorted((r["send"] - r["due"]) * 1e3 for r in records
                  if r.get("due") is not None and r.get("send") is not None)
    if not late:
        return {"n": 0, "p50_ms": 0.0, "p99_ms": 0.0, "max_ms": 0.0}
    return {"n": len(late),
            "p50_ms": late[len(late) // 2],
            "p99_ms": late[min(len(late) - 1,
                               math.ceil(0.99 * len(late)) - 1)],
            "max_ms": late[-1]}
