"""Fused gather + masked L^p re-rank + partial top-k query kernel.

The classical LSH query tail -- gather candidate embeddings, compute exact
distances, select top-k -- is memory-bound: the naive jnp path materializes
a ``(nq, C, N)`` candidate tensor in HBM (C = tables x probes x capacity,
routinely 10^3), then a same-shape difference tensor, then sorts.  This
kernel never builds either:

* the grid is ``(nq, C)`` -- one candidate per step;
* candidate **ids** ride in scalar-prefetch memory (SMEM), and the aligned
  row tile holding the candidate for step ``(i, c)`` is DMA'd HBM->VMEM by
  the BlockSpec index map ``ids[i, c] // TR`` itself (the block-sparse
  scalar-prefetch idiom), so Pallas double-buffers the gather against the
  distance math of the previous step;
* the masked L^p distance and a running top-k (replace-worst-if-better,
  provably exact for "k smallest seen so far") live in VMEM scratch;
* the epilogue selection-sorts the k best and writes ``(nq, k)`` ids +
  distances -- the only HBM traffic besides the row gathers themselves.

TPU block rule: a block's last two dims must be multiples of the native
tile or equal the array's.  So the gather moves a ``(TR, N)`` tile -- TR
rows of one native tile height for the db dtype (8 for f32, 16 for bf16,
32 for int8) -- and the candidate's row is selected inside the kernel;
queries and outputs travel as ``(nq, 1, N)`` / ``(nq, 1, k)`` so their
row blocks equal the array in the last two dims.

Invalid candidates (id < 0, or id >= valid_items for partially-filled
databases) are forced to +inf / id -1, matching ``ref.fused_query_topk_ref``
bit-for-bit on ids when distances are distinct.

The db rows may be f32, bf16 or int8 (the quantized tier scores in code
space through this same kernel; see ``quantize.quantized_query_topk``):
the only dequant in the hot loop is an in-register widening cast.

SMEM holds the (flattened) id table of one call; the wrapper splits the
queries into calls whose table fits ``_SMEM_ID_BYTES`` (a v5e core has
1 MiB of SMEM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_KP = 128  # top-k scratch width: lane-aligned; k <= _KP enforced by wrapper
_SMEM_ID_BYTES = 512 * 1024  # id-table budget per call (half of v5e SMEM)


def _lp_rows(diff: Array, p: float) -> Array:
    """Row-wise L^p norm of a (TR, N) tile -> (TR, 1)."""
    if p == 2.0:
        return jnp.sqrt(jnp.sum(diff * diff, axis=1, keepdims=True))
    if p == 1.0:
        return jnp.sum(jnp.abs(diff), axis=1, keepdims=True)
    return jnp.sum(jnp.abs(diff) ** p, axis=1, keepdims=True) ** (1.0 / p)


def _fused_query_kernel(ids_ref, q_ref, tile_ref, od_ref, oi_ref, dacc, iacc,
                        *, k: int, p: float, valid: int, c_total: int,
                        tr: int):
    i, c = pl.program_id(0), pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        dacc[...] = jnp.full_like(dacc, jnp.inf)
        iacc[...] = jnp.full_like(iacc, -1)

    cid = ids_ref[i * c_total + c]
    dr = _lp_rows(tile_ref[...].astype(jnp.float32) - q_ref[0], p)  # (TR, 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, dr.shape, 0)
    d = jnp.min(jnp.where(sub == jnp.maximum(cid, 0) % tr, dr, jnp.inf))
    ok = (cid >= 0) & (cid < valid)
    d = jnp.where(ok, d, jnp.inf)

    # Streaming top-k: replace the current worst slot iff the new distance
    # beats it.  Invariant: scratch always holds the KP smallest seen.
    cur = dacc[...]                                     # (1, KP)
    lane = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 1)
    hit = (lane == jnp.argmax(cur)) & (d < jnp.max(cur))
    dacc[...] = jnp.where(hit, d, cur)
    iacc[...] = jnp.where(hit, cid, iacc[...])

    @pl.when(c == pl.num_programs(1) - 1)
    def _epilogue():
        # Selection-sort the k best ascending (k static => unrolled).
        dv, iv = dacc[...], iacc[...]
        il = jax.lax.broadcasted_iota(jnp.int32, dv.shape, 1)
        out_d, out_i = [], []
        for _ in range(k):
            m = jnp.argmin(dv)
            one = il == m
            dm = jnp.min(dv)
            im = jnp.sum(jnp.where(one, iv, 0))
            out_d.append(dm)
            out_i.append(jnp.where(jnp.isinf(dm), -1, im))
            dv = jnp.where(one, jnp.inf, dv)
        od_ref[...] = jnp.stack(out_d).reshape(1, 1, k)
        oi_ref[...] = jnp.stack(out_i).reshape(1, 1, k).astype(jnp.int32)


def _call(q3: Array, db: Array, ids: Array, k: int, p: float, valid: int,
          tr: int, interpret: bool) -> tuple[Array, Array]:
    nq, _, n = q3.shape
    c = ids.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nq, c),
        in_specs=[
            pl.BlockSpec((1, 1, n), lambda i, c_, ids: (i, 0, 0)),
            # The gather: the scalar-prefetched id picks the row tile.
            pl.BlockSpec((tr, n), lambda i, c_, ids: (
                jnp.maximum(ids[i * c + c_], 0) // tr, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, k), lambda i, c_, ids: (i, 0, 0)),
            pl.BlockSpec((1, 1, k), lambda i, c_, ids: (i, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, _KP), jnp.float32),
            pltpu.VMEM((1, _KP), jnp.int32),
        ],
    )
    dists, out_ids = pl.pallas_call(
        functools.partial(_fused_query_kernel, k=k, p=p, valid=valid,
                          c_total=c, tr=tr),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((nq, 1, k), jnp.float32),
                   jax.ShapeDtypeStruct((nq, 1, k), jnp.int32)),
        interpret=interpret,
    )(ids.reshape(-1), q3, db)
    return dists.reshape(nq, k), out_ids.reshape(nq, k)


def fused_query_topk(q: Array, db: Array, ids: Array, k: int, p: float = 2.0,
                     valid_items: int | None = None, interpret: bool = True
                     ) -> tuple[Array, Array]:
    """Top-k nearest candidates without materializing (nq, C, N).

    q: (nq, N) queries; db: (M, N) stored rows (f32, bf16 or int8); ids:
    (nq, C) int32 candidate ids, -1 = empty/deduped slot.  Returns (dists
    (nq, k) f32, ids (nq, k) int32) sorted ascending, id -1 / dist +inf
    where fewer than k valid candidates exist.
    """
    nq, n = q.shape
    m, n2 = db.shape
    c = ids.shape[1]
    assert n == n2 and ids.shape == (nq, c)
    assert k <= c, f"k={k} exceeds candidate count C={c}"
    assert k <= _KP, f"k={k} exceeds kernel top-k width {_KP}"
    valid = m if valid_items is None else int(valid_items)

    tr = 32 // db.dtype.itemsize       # one native tile of rows
    if m % tr:                         # whole tiles only; pad rows are
        db = jnp.pad(db, ((0, -m % tr), (0, 0)))   # never selected
    q3 = q.astype(jnp.float32).reshape(nq, 1, n)
    ids = ids.astype(jnp.int32)
    step = max(1, _SMEM_ID_BYTES // (4 * c))
    parts = [_call(q3[s:s + step], db, ids[s:s + step], k, p, valid, tr,
                   interpret) for s in range(0, nq, step)]
    if len(parts) == 1:
        return parts[0]
    return (jnp.concatenate([d for d, _ in parts]),
            jnp.concatenate([i for _, i in parts]))
