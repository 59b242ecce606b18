"""The plain reference: exact l^p nearest neighbours of stored rows.

Independent of the program: it sees only the rows the benchmark generated
and the gids the server acknowledged, never the program's index, hash
family or tables.  Distances of served answers are recomputed in float64;
the recall probe's exact top-k is a brute-force scan (the arithmetic of
``repro.serve.stats.recall_proxy``, recomputed here).

``lower_precision`` gives the controls: the same rows and queries held in a
lower precision (bf16 below fp32; int4 below int8, and int8 codes scored
without the exact rerank), which must fail the distance check.
"""

from __future__ import annotations

import numpy as np

try:
    from ml_dtypes import bfloat16 as _BF16
except ImportError:          # ml_dtypes ships with every jaxlib
    _BF16 = None


def lp_distances(queries: np.ndarray, rows: np.ndarray, p: float
                 ) -> np.ndarray:
    """(n, N) queries against (n, k, N) rows -> (n, k) float64 l^p."""
    diff = (np.asarray(rows, np.float64)
            - np.asarray(queries, np.float64)[:, None, :])
    if p == 2.0:
        return np.sqrt(np.sum(diff * diff, axis=-1))
    if p == 1.0:
        return np.sum(np.abs(diff), axis=-1)
    return np.sum(np.abs(diff) ** p, axis=-1) ** (1.0 / p)


def _pow(a: np.ndarray, p: float) -> np.ndarray:
    return a if p == 1.0 else a ** p


def brute_force_topk(queries: np.ndarray, items: np.ndarray,
                     gids: np.ndarray, k: int, p: float,
                     block: int = 16384, rows: int = 8) -> np.ndarray:
    """Exact top-k gids of each query over ``items``, in (distance, gid)
    order as every merge in the server uses.  l^2 in float64; other p in
    float32 (exact enough to rank), ``rows`` queries at a time."""
    q = np.asarray(queries, np.float64)
    cand_d, cand_g = [], []
    for s in range(0, items.shape[0], block):
        x = items[s:s + block]
        if p == 2.0:
            x = np.asarray(x, np.float64)
            d = np.sqrt(np.maximum(
                (q * q).sum(1)[:, None] + (x * x).sum(1)[None, :]
                - 2.0 * q @ x.T, 0.0))
        else:
            x = np.asarray(x, np.float32)
            qf = q.astype(np.float32)
            d = np.concatenate([
                _pow(np.abs(x[None, :, :] - qf[i:i + rows, None, :]),
                     p).sum(-1) for i in range(0, qf.shape[0], rows)])
        keep = min(k, d.shape[1])
        idx = np.argpartition(d, keep - 1, axis=1)[:, :keep]
        cand_d.append(np.take_along_axis(d, idx, axis=1))
        cand_g.append(np.asarray(gids[s:s + block], np.int64)[idx])
    cat_d = np.concatenate(cand_d, axis=1)
    cat_g = np.concatenate(cand_g, axis=1)
    order = np.lexsort((cat_g, cat_d), axis=1)[:, :k]
    return np.take_along_axis(cat_g, order, axis=1)


# levels of each symmetric integer code a control may hold rows in
CODE_LEVELS = {"int8": 127, "int4": 7}


def lower_precision(x: np.ndarray, kind: str, scale: float) -> np.ndarray:
    """``x`` held in the lower precision ``kind``, as float64: ``bf16`` a
    cast; ``int8`` / ``int4`` symmetric integer codes of step ``scale /
    127`` / ``scale / 7``, queries and rows alike, as a code-space scan
    maps them (``scale`` is the largest |x| of the rows, so no row leaves
    the code range)."""
    x = np.asarray(x, np.float32)
    if kind == "bf16":
        if _BF16 is None:
            raise RuntimeError("bfloat16 control needs ml_dtypes")
        return x.astype(_BF16).astype(np.float64)
    if kind in CODE_LEVELS:
        step = scale / CODE_LEVELS[kind]
        return np.round(x / step).astype(np.float64) * step
    raise ValueError(f"no control precision {kind!r}")
