"""Fused gather + masked L^p re-rank + top-k query kernel.

The classical LSH query tail -- gather candidate embeddings, compute exact
distances, select top-k -- is memory-bound: the naive jnp path materializes
a ``(nq, C, N)`` candidate tensor in HBM (C = tables x probes x capacity,
routinely 10^3), then a same-shape difference tensor, then sorts.  This
module never builds either.  It has two kernels, chosen by the database's
size (``_resident``), and both answer ``ref.fused_query_topk_ref``'s ids
when distances are distinct.

**Block kernel** (the served path: a segment's 1,024 rows; any database
whose rows, widened to f32, fit ``_RESIDENT_BYTES`` of VMEM):

* the grid is ``(nq, ceil(C / B))`` -- a block of ``B`` candidates per
  step (``_block``: 128 at the served C = 1,024; the wrapper pads the ids
  with -1 to whole blocks, which at C = 1,024 pads nothing);
* the rows come in as one VMEM block, widened to f32 once a call beside a
  row of +inf;
* candidate **ids** ride in scalar-prefetch memory (SMEM).  A scalar loop
  reads each of the block's ids and copies that candidate's row, or the
  +inf row where ``id < 0`` or ``id >= valid_items``, into a ``(B, N)``
  block; the block's L^p distances are then computed at once;
* with ``dedup`` the same walk drops repeated and dead candidates: an
  SMEM table ``seen`` of the M rows, filled at the call's first step
  from the live mask (a fourth operand, after the rows: -1 live, nq
  dead), holds the last query that took each row, and query i takes a
  row iff ``seen[id] < i``.  The grid runs (i, j) in order and a block's
  candidates in order, so each id is kept at its first position -- the
  survivors of ``ref.dedup_candidates``' first-seen table, which the
  tile kernel takes its ids through instead;
* the distances land, in candidate order, in an ``(nq, ceil(C / B), B)``
  scratch; there is no per-candidate top-k update.  At a query's last
  step the epilogue takes its k smallest by k rounds of min, the lowest
  candidate position first among equal distances -- ``lax.top_k``'s
  order, so the ids match the reference even on ties.  The kernel answers
  candidate positions; the wrapper maps them to ids.

**Tile kernel** (a larger database: a whole index queried at once,
``query_index`` / ``query_distributed``).  Its cost does not grow with the
database: the grid is ``(nq, C)``, one candidate per step, and the aligned
native row tile holding it (8 f32 rows, 16 bf16, 32 int8) is DMA'd
HBM->VMEM by the BlockSpec index map ``ids[i, c] // TR`` (the block-sparse
scalar-prefetch idiom), so Pallas double-buffers the gather against the
previous step's math.  A running top-k (replace the worst slot when
better) lives in VMEM scratch and the epilogue selection-sorts it; among
equal distances the order is the scratch's, not ``lax.top_k``'s.

Why the block kernel does not gather by one DMA per candidate from HBM:
Mosaic refuses a DMA of any slice of a row array whose lane width (N = 64
on the served path) pads to 128, and XLA already stages a segment's rows
in VMEM for the call, so the one copy in moves the bytes the per-row
gathers would.  Why a larger database does not stream through VMEM a
chunk at a time: every call would then read the whole database and score
every candidate once per chunk, a cost that grows with the database where
the tile kernel's does not.  Why there is no dense scan of the segment:
scoring every row and masking by candidate is a different algorithm, not
a faster gather, and ``chipbench/work/fused_query.py`` counts every
candidate row once per query.

The rows may be f32, bf16 or int8 (the quantized tier scores in code space
through these same kernels; see ``quantize.quantized_query_topk``); the
only dequant is the widening cast.  Queries and outputs travel as
``(nq, 1, N)`` / ``(nq, 1, k)`` so their blocks equal the array in the
last two dims.

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

from . import ref

Array = jax.Array

_KP = 128  # widest top-k either kernel selects; k <= _KP enforced by wrapper
_SMEM_ID_BYTES = 512 * 1024  # id-table budget per call (half of v5e SMEM)
_BLOCK = 128  # candidates per grid step at most: one lane row of the scratch
_UNROLL = 8  # candidates per iteration of the gather loop
_RESIDENT_BYTES = 2 << 20  # widened rows the block kernel holds: 4,096 at N=64


def _resident(m: int, n: int) -> bool:
    """Whether M rows of width N, widened to f32 and lane-padded, fit the
    block kernel's VMEM budget; past it the tile kernel runs."""
    return m * (-(-n // 128) * 128) * 4 <= _RESIDENT_BYTES


def _block(c: int) -> int:
    """Candidates per grid step for C candidates: ``_BLOCK``, or C rounded
    up to a whole sublane group when fewer."""
    return min(_BLOCK, -(-c // _UNROLL) * _UNROLL)


def _lp_rows(diff: Array, p: float) -> Array:
    """Row-wise L^p norm of a (R, N) block -> (R, 1)."""
    if p == 2.0:
        return jnp.sqrt(jnp.sum(diff * diff, axis=1, keepdims=True))
    if p == 1.0:
        return jnp.sum(jnp.abs(diff), axis=1, keepdims=True)
    return jnp.sum(jnp.abs(diff) ** p, axis=1, keepdims=True) ** (1.0 / p)


def _fused_query_kernel(ids_ref, q_ref, db_ref, *refs, k: int, p: float,
                        valid: int, bsz: int, dedup: bool):
    if dedup:
        live_ref, od_ref, oi_ref, wide, buf, dacc, seen = refs
    else:
        od_ref, oi_ref, wide, buf, dacc = refs
    i, j = pl.program_id(0), pl.program_id(1)
    n_blk = pl.num_programs(1)
    m = db_ref.shape[0]

    @pl.when((i == 0) & (j == 0))
    def _widen():
        # the rows as f32, then a row of +inf that invalid candidates
        # gather instead
        wide[pl.ds(0, m), :] = db_ref[...].astype(jnp.float32)
        wide[pl.ds(m, 8), :] = jnp.full((8, wide.shape[1]), jnp.inf,
                                        jnp.float32)
        if dedup:
            # the first-seen table: the last query that took each row, -1
            # for none yet; a dead row reads nq, taken by every query
            dead = pl.num_programs(0)

            def fill(u, carry):
                for v in range(_UNROLL):
                    r = u * _UNROLL + v
                    seen[r] = jnp.where(live_ref[r] != 0, -1, dead)
                return carry

            jax.lax.fori_loop(0, m // _UNROLL, fill, 0)
            for r in range(m - m % _UNROLL, m):
                seen[r] = jnp.where(live_ref[r] != 0, -1, dead)

    base = (i * n_blk + j) * bsz

    def gather(u, carry):
        for v in range(_UNROLL):
            t = u * _UNROLL + v
            cid = ids_ref[base + t]
            ok = (cid >= 0) & (cid < valid)
            if dedup:
                # the grid walks (i, j) in order and t rises in a block, so
                # a query meets its candidates in position order: keep a
                # row the first time this query meets it, if live
                at = jnp.where(ok, cid, 0)
                s = seen[at]
                ok = ok & (s < i)
                seen[at] = jnp.where(ok, i, s)
            buf[pl.ds(t, 1), :] = wide[pl.ds(jnp.where(ok, cid, m), 1), :]
        return carry

    jax.lax.fori_loop(0, bsz // _UNROLL, gather, 0)

    dr = _lp_rows(buf[...] - q_ref[0], p)                    # (B, 1)
    # (B, 1) -> (1, B): each lane takes its own row, every other term 0
    sub = jax.lax.broadcasted_iota(jnp.int32, (bsz, bsz), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (bsz, bsz), 1)
    dacc[i, pl.ds(j, 1), :] = jnp.sum(jnp.where(sub == lane, dr, 0.0),
                                      axis=0, keepdims=True)

    @pl.when(j == n_blk - 1)
    def _epilogue():
        # k rounds of min over every candidate; among equal distances the
        # lowest candidate position first (k static => unrolled)
        dv = dacc[i]                                         # (n_blk, B)
        pos = (jax.lax.broadcasted_iota(jnp.int32, dv.shape, 0) * bsz
               + jax.lax.broadcasted_iota(jnp.int32, dv.shape, 1))
        big = jnp.int32(dv.size)
        out_d, out_p = [], []
        for _ in range(k):
            dm = jnp.min(dv)
            pm = jnp.min(jnp.where(dv == dm, pos, big))
            out_d.append(dm)
            out_p.append(jnp.where(jnp.isinf(dm), -1, pm))
            dv = jnp.where(pos == pm, jnp.inf, dv)
        od_ref[...] = jnp.stack(out_d).reshape(1, 1, k)
        oi_ref[...] = jnp.stack(out_p).reshape(1, 1, k).astype(jnp.int32)


def _block_call(q3: Array, db: Array, ids: Array, k: int, p: float,
                valid: int, interpret: bool, *, bsz: int,
                live: Array | None) -> tuple[Array, Array]:
    """One block-kernel call; returns (dists, ids) -- the kernel itself
    answers candidate positions, mapped back to ids here.  With ``live``
    ((M,) int32, 0 = dead) the kernel drops repeated and dead candidates
    in its walk."""
    nq, _, n = q3.shape
    m = db.shape[0]
    n_blk = ids.shape[1] // bsz
    in_specs = [
        pl.BlockSpec((1, 1, n), lambda i, j, ids: (i, 0, 0)),
        pl.BlockSpec((m, n), lambda i, j, ids: (0, 0)),
    ]
    scratch = [
        pltpu.VMEM((m + 8, n), jnp.float32),          # widened rows + inf
        pltpu.VMEM((bsz, n), jnp.float32),            # a block's rows
        pltpu.VMEM((nq, n_blk, bsz), jnp.float32),    # every distance
    ]
    args = (ids.reshape(-1), q3, db)
    if live is not None:       # after the rows: a trace reads the first three
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        scratch.append(pltpu.SMEM((m,), jnp.int32))   # first-seen table
        args += (live,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nq, n_blk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, k), lambda i, j, ids: (i, 0, 0)),
            pl.BlockSpec((1, 1, k), lambda i, j, ids: (i, 0, 0)),
        ],
        scratch_shapes=scratch,
    )
    dists, pos = pl.pallas_call(
        functools.partial(_fused_query_kernel, k=k, p=p, valid=valid,
                          bsz=bsz, dedup=live is not None),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((nq, 1, k), jnp.float32),
                   jax.ShapeDtypeStruct((nq, 1, k), jnp.int32)),
        interpret=interpret,
    )(*args)
    pos = pos.reshape(nq, k)
    out = jnp.take_along_axis(ids, jnp.maximum(pos, 0), axis=1)
    return dists.reshape(nq, k), jnp.where(pos >= 0, out, -1)


def _tile_kernel(ids_ref, q_ref, tile_ref, od_ref, oi_ref, dacc, iacc,
                 *, k: int, p: float, valid: int, c_total: int, tr: int):
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


def _tile_call(q3: Array, db: Array, ids: Array, k: int, p: float,
               valid: int, interpret: bool, *, tr: int
               ) -> tuple[Array, Array]:
    """One tile-kernel call; returns (dists, ids)."""
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
        functools.partial(_tile_kernel, k=k, p=p, valid=valid, c_total=c,
                          tr=tr),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((nq, 1, k), jnp.float32),
                   jax.ShapeDtypeStruct((nq, 1, k), jnp.int32)),
        interpret=interpret,
    )(ids.reshape(-1), q3, db)
    return dists.reshape(nq, k), out_ids.reshape(nq, k)


def fused_query_topk(q: Array, db: Array, ids: Array, k: int, p: float = 2.0,
                     valid_items: int | None = None, interpret: bool = True,
                     *, live: Array | None = None, dedup: bool = False
                     ) -> tuple[Array, Array]:
    """Top-k nearest candidates without materializing (nq, C, N).

    q: (nq, N) queries; db: (M, N) stored rows (f32, bf16 or int8); ids:
    (nq, C) int32 candidate ids, -1 = empty slot.  Returns (dists (nq, k)
    f32, ids (nq, k) int32) sorted ascending, id -1 / dist +inf where
    fewer than k valid candidates exist.  Among equal distances the lower
    candidate position comes first when the rows fit the block kernel
    (``_resident``).

    ``dedup`` scores each id once per query, at its first position, and
    with ``live`` ((M,) bool) none of the dead rows: the block kernel does
    it in its candidate walk, the tile kernel takes ids already passed
    through ``ref.dedup_candidates``.  Either way the survivors, and so
    the answers, are the same.
    """
    nq, n = q.shape
    m, n2 = db.shape
    c = ids.shape[1]
    assert n == n2 and ids.shape == (nq, c)
    assert k <= c, f"k={k} exceeds candidate count C={c}"
    assert k <= _KP, f"k={k} exceeds kernel top-k width {_KP}"
    assert dedup or live is None, "a live mask is applied by the dedup"
    valid = m if valid_items is None else min(int(valid_items), m)

    ids = ids.astype(jnp.int32)
    if _resident(m, n):
        bsz = _block(c)
        if c % bsz:                    # whole blocks; pads score +inf
            ids = jnp.pad(ids, ((0, 0), (0, -c % bsz)), constant_values=-1)
        if dedup:                      # in the kernel's walk
            live = (jnp.ones((m,), jnp.int32) if live is None
                    else live.astype(jnp.int32))
        call = functools.partial(_block_call, bsz=bsz, live=live)
    else:
        if dedup:                      # a first-seen table ahead of it
            ids = ref.dedup_candidates(ids, m, live)
        tr = 32 // db.dtype.itemsize   # one native tile of rows
        if m % tr:                     # whole tiles only; pad rows are
            db = jnp.pad(db, ((0, -m % tr), (0, 0)))   # never selected
        call = functools.partial(_tile_call, tr=tr)
    q3 = q.astype(jnp.float32).reshape(nq, 1, n)
    step = max(1, _SMEM_ID_BYTES // (4 * ids.shape[1]))
    parts = [call(q3[s:s + step], db, ids[s:s + step], k, p, valid,
                  interpret) for s in range(0, nq, step)]
    if len(parts) == 1:
        return parts[0]
    return (jnp.concatenate([d for d, _ in parts]),
            jnp.concatenate([i for _, i in parts]))
