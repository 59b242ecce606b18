"""Whether what the timed path served is correct, and the numbers that say so.

Every answer the served entry produced -- each query of the window, the
recall probe, and the read-backs after it -- is held against the plain
reference (``chipbench.reference``):

* ``lost``: requests that never got an answer (limit 0);
* ``dist_err``: the largest gap between a served distance and the exact
  float64 distance of the gid it names, over the median exact distance of
  the answers checked (limit per configuration, ``limits.dist_err``);
* ``order_errors``: answers not ascending, with duplicate gids, or with
  empty slots (-1 / inf) anywhere but at the end (limit 0);
* ``bad_gids``: served gids never acknowledged, or deleted by an
  acknowledgement that came before the query was sent (limit 0);
* ``readback_miss``: sampled acknowledged inserts (the window's, and the
  bulk load's where the traffic sets ``check_loaded``) not served as their
  own nearest neighbour at distance 0 (limit per configuration,
  ``limits.readback_miss``: the index drops an item from every bucket that
  is already at capacity, so a sound run misses the few whose buckets are
  all full; the limit lies below what a loss of one insert in eight reads);
* ``deleted_served``: sampled acknowledged deletes served back when their
  own rows are asked (limit 0);
* ``compiled_in_window``: programs compiled, or fetched from the compile
  cache, inside the measured window (limit 0: a run that compiles there
  measures set-up, not serving);
* ``compared``: answer rows compared, at least 1.

``control_answers`` gives the controls (``CONTROLS``): the same answers
with their distances recomputed by the plain reference in a lower precision,
which ``check`` has to find not correct.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from . import reference


class Ledger:
    """What the benchmark knows of the data: the row of every acknowledged
    gid and when (if ever) an acknowledged delete removed it."""

    def __init__(self):
        self._rows: List[np.ndarray] = []
        self._gids: List[np.ndarray] = []
        self.deleted_at: Dict[int, float] = {}
        self._map: Optional[np.ndarray] = None
        self._all: Optional[np.ndarray] = None

    def acknowledge(self, gids, rows) -> None:
        self._gids.append(np.asarray(gids, np.int64))
        self._rows.append(np.asarray(rows, np.float32))
        self._map = None

    def delete(self, gids, t_ack: float) -> None:
        for g in gids:
            self.deleted_at.setdefault(int(g), t_ack)

    def _build(self) -> None:
        gids = np.concatenate(self._gids)
        self._all = np.concatenate(self._rows)
        self._map = np.full(int(gids.max()) + 1, -1, np.int64)
        self._map[gids] = np.arange(gids.shape[0])

    def rows_of(self, gids: np.ndarray) -> np.ndarray:
        """Rows of known gids; ``known(gids)`` says which are known."""
        if self._map is None:
            self._build()
        idx = self._map[np.clip(gids, 0, self._map.shape[0] - 1)]
        return self._all[np.maximum(idx, 0)]

    def known(self, gids: np.ndarray) -> np.ndarray:
        if self._map is None:
            self._build()
        ok = (gids >= 0) & (gids < self._map.shape[0])
        return ok & (self._map[np.clip(gids, 0, self._map.shape[0] - 1)]
                     >= 0)

    def live(self) -> tuple:
        """(rows, gids) of every acknowledged gid not deleted."""
        if self._map is None:
            self._build()
        gids = np.concatenate(self._gids)
        keep = np.array([int(g) not in self.deleted_at for g in gids])
        return self._all[keep], gids[keep]


class Answers:
    """Served answer rows to check: the query rows asked, the gids and
    distances served, and when each request was sent."""

    def __init__(self):
        self.queries: List[np.ndarray] = []
        self.gids: List[np.ndarray] = []
        self.dists: List[np.ndarray] = []
        self.sent: List[np.ndarray] = []

    def add(self, queries, gids, dists, sent: float) -> None:
        g = np.asarray(gids, np.int64).reshape(len(queries), -1)
        self.queries.append(np.asarray(queries, np.float32))
        self.gids.append(g)
        self.dists.append(np.asarray(dists, np.float64).reshape(g.shape))
        self.sent.append(np.full(len(queries), sent))

    def arrays(self):
        return (np.concatenate(self.queries), np.concatenate(self.gids),
                np.concatenate(self.dists), np.concatenate(self.sent))


def order_errors(gids: np.ndarray, dists: np.ndarray) -> int:
    """Answer rows that are not a sorted, duplicate-free top-k with empty
    slots only at the end."""
    bad = 0
    for g, d in zip(gids, dists):
        valid = g >= 0
        n = int(valid.sum())
        fin = np.isfinite(d)
        if (not valid[:n].all() or valid[n:].any() or (fin != valid).any()
                or (np.diff(d[:n]) < 0).any()
                or np.unique(g[:n]).size != n):
            bad += 1
    return bad


def distance_gap(queries, gids, dists, ledger: Ledger, p: float) -> float:
    """Largest |served - exact| over the median exact distance."""
    valid = gids >= 0
    exact = reference.lp_distances(queries, ledger.rows_of(gids), p)
    scale = np.median(exact[valid & (exact > 0)]) if (
        valid & (exact > 0)).any() else 1.0
    if not valid.any():
        return 0.0
    return float(np.max(np.abs(dists[valid] - exact[valid])) / scale)


def bad_gids(gids: np.ndarray, sent: np.ndarray, ledger: Ledger) -> int:
    known = ledger.known(gids)
    bad = int(((gids >= 0) & ~known).sum())
    for gi, s in zip(gids, sent):
        for g in gi[gi >= 0]:
            t = ledger.deleted_at.get(int(g))
            if t is not None and t < s:
                bad += 1
    return bad


def check(answers: Answers, ledger: Ledger, *, p: float, lost: int,
          readback_miss: int, deleted_served: int, compiled_in_window: int,
          limits: dict) -> Dict[str, dict]:
    """The compared numbers, each with its limit and whether it holds."""
    q, g, d, sent = answers.arrays()
    out = {
        "lost": (lost, 0),
        "dist_err": (distance_gap(q, g, d, ledger, p),
                     float(limits["dist_err"])),
        "order_errors": (order_errors(g, d), 0),
        "bad_gids": (bad_gids(g, sent, ledger), 0),
        "readback_miss": (readback_miss, int(limits["readback_miss"])),
        "deleted_served": (deleted_served, 0),
        "compiled_in_window": (compiled_in_window, 0),
    }
    checks = {name: {"value": v, "limit": lim, "ok": bool(v <= lim)}
              for name, (v, lim) in out.items()}
    n = int(q.shape[0])
    checks["compared"] = {"value": n, "limit": 1, "ok": n >= 1}
    return checks


# the controls of each stated precision: the plain reference in the
# program's place one step below it (bf16 below fp32, int4 below int8) and,
# below int8, also the int8 code-space distances served without the exact
# survivor rerank, the step a faster int8 path would be tempted to skip
CONTROLS = {"fp32": ("bf16",), "int8": ("int4", "int8")}


def control_answers(answers: Answers, ledger: Ledger, *, p: float,
                    kind: str) -> Answers:
    """The served answers with each distance recomputed by the reference
    from the query and the named gid's row held in precision ``kind``
    (``reference.lower_precision``, one scale over the live rows)."""
    q, g, _, sent = answers.arrays()
    rows, _ = ledger.live()
    scale = float(np.max(np.abs(rows))) if rows.size else 1.0
    d = reference.lp_distances(
        reference.lower_precision(q, kind, scale),
        reference.lower_precision(ledger.rows_of(g), kind, scale), p)
    out = Answers()
    out.add(q, g, np.where(g >= 0, d, np.inf), 0.0)
    out.sent = [sent]
    return out
