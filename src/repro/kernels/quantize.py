"""Symmetric per-segment storage quantizer + dequant-free candidate scoring.

The storage-precision tier (docs/architecture.md § "The precision tier"):
sealed segments may hold their embedding rows at reduced precision --
``bf16`` (a cast) or ``int8`` (symmetric, one scale per segment:
``scale = max|x| / 127``, ``code = round(x / scale)``) -- while the mutable
delta always stays fp32, so the insert path and the ``precision="fp32"``
tier are structurally untouched.

Candidate scoring against a quantized segment is **dequant-free**: instead
of materialising ``codes * scale`` rows, the query is mapped once into code
space (``q_c = round(q / scale)``) and L^p distances are computed between
integer codes (cast to f32 in-register, never in HBM); one final multiply
by ``scale`` makes the distances comparable across segments, because
``|| s*a - s*b ||_p = s * || a - b ||_p``.  Per-coordinate round-off is at
most ``scale/2`` on both the stored row and the query
(tests/test_quantize.py property-checks the bound), so code-space ordering
is the exact ordering up to O(scale) distance ties -- which is why the
serve layer treats the quantized top-m only as a *survivor set* and
rescores it exactly from fp32 rows (:func:`rerank_survivors`).

The Pallas path is the fused_query.py kernels themselves, run on the
codes: a segment's codes are taken into VMEM, widened to f32 once a call
and a block of candidates' rows gathered per grid step (a database past
VMEM gathers one native tile per candidate instead), so the (nq, C, N)
candidate tensor never exists in HBM.  Past the one widening pass the
work per candidate matches the fp32 path; the tier's win is capacity.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import fused_query, merge

Array = jax.Array

PRECISIONS = ("fp32", "bf16", "int8")

_DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}
_WIDTHS = {"fp32": 4, "bf16": 2, "int8": 1}


def storage_dtype(precision: str):
    """The jnp dtype a sealed segment's ``db`` leaf holds at this tier."""
    if precision not in _DTYPES:
        raise ValueError(
            f"unknown precision {precision!r}; want one of {PRECISIONS}")
    return _DTYPES[precision]


def bytes_per_item(precision: str, n_dims: int) -> int:
    """Sealed-storage bytes per item row (the capacity-planning number)."""
    return _WIDTHS[precision] * n_dims


# -- encode / decode ---------------------------------------------------------


@jax.jit
def _encode_int8(db: Array) -> tuple[Array, Array]:
    amax = jnp.max(jnp.abs(db.astype(jnp.float32)))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    codes = jnp.clip(jnp.round(db / scale), -127, 127).astype(jnp.int8)
    return codes, scale


def encode(db: Array, precision: str) -> tuple[Array, Array]:
    """fp32 rows -> (codes, scale) at ``precision``.

    int8: symmetric per-segment scale ``max|x|/127`` (an all-zero segment
    gets scale 1 so decode stays well-defined).  bf16: a cast; scale is a
    constant 1 so every tier carries the same (codes, scale) pair through
    placement/snapshot plumbing.  fp32 never encodes -- callers gate on the
    tier precisely so the fp32 path stays bit-identical by construction.
    NaN/Inf rows must be rejected upstream (insert validation does); codes
    produced from non-finite input are undefined.
    """
    if precision == "int8":
        return _encode_int8(db)
    if precision == "bf16":
        return db.astype(jnp.bfloat16), jnp.float32(1.0)
    raise ValueError(f"no encoder for precision {precision!r}")


def decode(codes: Array, scale: Array) -> Array:
    """(codes, scale) -> fp32 rows, within scale/2 per coordinate of the
    original for int8 and within 1 ulp-of-bf16 for bf16.  Exactness for
    survivors comes from the fp32 side pool, not from this."""
    if codes.dtype == jnp.int8:
        return codes.astype(jnp.float32) * scale
    return codes.astype(jnp.float32)


# -- dequant-free candidate scoring -----------------------------------------


def _code_query(q: Array, codes_dtype, scale: Array) -> tuple[Array, Array]:
    """Map queries into code space; returns (q_c, post_scale)."""
    if codes_dtype == jnp.int8:
        return jnp.round(q / scale), scale
    return q, jnp.float32(1.0)


def quantized_topk_ref(q: Array, codes: Array, scale: Array, ids: Array,
                       k: int, p: float = 2.0,
                       valid_items: int | None = None
                       ) -> tuple[Array, Array]:
    """jnp oracle: gather quantized candidate rows, score in code space,
    scale once, top-k.  Mirrors ``ref.fused_query_topk_ref`` op-for-op so
    the masking/tie semantics of the two query tails match."""
    m = codes.shape[0]
    qf = q.astype(jnp.float32)
    qc, post = _code_query(qf, codes.dtype, scale)
    rows = codes[jnp.clip(ids, 0, m - 1)].astype(jnp.float32)   # (nq, C, N)
    diff = rows - qc[:, None, :]
    if p == 2.0:
        d = jnp.sqrt(jnp.sum(diff * diff, axis=-1))
    elif p == 1.0:
        d = jnp.sum(jnp.abs(diff), axis=-1)
    else:
        d = jnp.sum(jnp.abs(diff) ** p, axis=-1) ** (1.0 / p)
    d = d * post
    d = jnp.where(ids < 0, jnp.inf, d)
    if valid_items is not None:
        d = jnp.where(ids >= valid_items, jnp.inf, d)
    neg, idx = jax.lax.top_k(-d, k)
    out_ids = jnp.take_along_axis(ids, idx, axis=-1)
    dist = -neg
    return dist, jnp.where(jnp.isinf(dist), -1, out_ids)


def quantized_query_topk(q: Array, codes: Array, scale: Array, ids: Array,
                         k: int, p: float = 2.0,
                         valid_items: int | None = None,
                         interpret: bool = True, *,
                         live: Array | None = None, dedup: bool = False
                         ) -> tuple[Array, Array]:
    """The fused_query kernels over a quantized db: gather of int8/bf16
    codes, code-space L^p, top-k.  Distances are scaled to the
    fp32 metric after the kernel.  Shapes/contract (``live``, ``dedup``
    too) as ``ops.fused_query_topk``.
    """
    qc, post = _code_query(q.astype(jnp.float32), codes.dtype, scale)
    dists, out_ids = fused_query.fused_query_topk(
        qc, codes, ids, k, p=p, valid_items=valid_items, interpret=interpret,
        live=live, dedup=dedup)
    return dists * post, out_ids


# -- exact survivor rescoring ------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k", "p"))
def rerank_survivors(q: Array, rows: Array, gids: Array, k: int,
                     p: float = 2.0) -> tuple[Array, Array]:
    """Exactly rescore the survivor set from fp32 rows and take top-k.

    q: (nq, N) f32; rows: (nq, m, N) fp32 rows of the m merged survivors
    (garbage where gid < 0); gids: (nq, m) int32, -1 = empty.  Returns
    (gids (nq, k), dists (nq, k)) under the same lexicographic
    (distance, gid) order every merge in the stack uses, so sharded and
    unsharded quantized queries agree whenever their survivor sets do.
    """
    diff = rows.astype(jnp.float32) - q.astype(jnp.float32)[:, None, :]
    if p == 2.0:
        d = jnp.sqrt(jnp.sum(diff * diff, axis=-1))
    elif p == 1.0:
        d = jnp.sum(jnp.abs(diff), axis=-1)
    else:
        d = jnp.sum(jnp.abs(diff) ** p, axis=-1) ** (1.0 / p)
    d = jnp.where(gids < 0, jnp.inf, d)
    sd, si = merge.sort_pairs(d, gids.astype(jnp.int32))
    sd, si = sd[..., :k], si[..., :k]
    return jnp.where(jnp.isinf(sd), -1, si), sd


def survivor_width(k: int, survivor_k: int, cap: int) -> int:
    """Resolve the survivor-pool width m: explicit ``survivor_k`` when set,
    else 4k (the ~4k candidates the rerank stage re-reads at fp32), clipped
    to [k, cap] and to the fused kernel's top-k scratch."""
    m = survivor_k if survivor_k and survivor_k > 0 else 4 * k
    return max(k, min(int(m), int(cap), fused_query._KP))


def np_bytes_per_live_item(precision: str, n_dims: int) -> float:
    """Float alias of :func:`bytes_per_item` for metric publishing."""
    return float(bytes_per_item(precision, n_dims))


__all__ = [
    "PRECISIONS", "storage_dtype", "bytes_per_item", "encode", "decode",
    "quantized_topk_ref", "quantized_query_topk", "rerank_survivors",
    "survivor_width", "np_bytes_per_live_item",
]
