"""Multi-table, multi-probe LSH index with static shapes (jit/TPU friendly).

Design (TPU adaptation of the classical pointer-based LSH table):

* L tables x K hashes/table from one ``PStableHash`` family (K*L hashes total,
  evaluated as ONE matmul -- see kernels/hash_mm).
* A bucket is a fixed-capacity slot array: ``table[l, b, s] -> item id`` with -1
  sentinel; insertion ranks items within their bucket via sort + segmented
  cumsum (no data-dependent shapes, no pointer chasing).
* Multi-probe (Lv et al., 2007): probes are the base bucket plus the
  single-coordinate +-1 perturbations ranked by boundary distance, computed
  from the pre-floor projections -- vectorized, no per-probe control flow.
* Query = gather candidate ids from probed buckets -> dedup -> exact re-rank
  against the stored embeddings -> top-k.

Kernel dispatch: hashing goes through kernels/ops.pstable_hash{,_proj}
(hash_mm on TPU) and the re-rank/top-k tail goes through
ops.fused_query_topk (kernels/fused_query on TPU: a segment's rows sit in
VMEM and a block of candidates' rows is gathered there per grid step; a
database past VMEM has each candidate's row tile DMA'd per grid step; the
(nq, C, N) candidate tensor never exists in HBM).  On CPU both default to
the jnp reference; pass ``backend="interpret"`` (or set
REPRO_QUERY_BACKEND) to run the fused kernel under the Pallas interpreter
for validation.

Hashing is deliberately NOT switchable per call: build- and query-time
bucket ids must match bitwise, so both sides use the process-constant
``dispatch.hash_backend()`` implementation.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import dispatch, ops, ref
from .hashes import PStableHash

Array = jax.Array

GOLDEN = np.uint32(0x9E3779B1)

@dataclasses.dataclass(frozen=True)
class IndexConfig:
    n_dims: int                 # embedding dimension N
    n_tables: int = 8           # L
    n_hashes: int = 4           # K per table
    log2_buckets: int = 12      # B = 2**log2_buckets
    bucket_capacity: int = 32   # S
    r: float = 1.0
    p: float = 2.0

    @property
    def n_buckets(self) -> int:
        return 1 << self.log2_buckets


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LSHIndexState:
    """Pytree: hash family params + bucket arrays + stored embeddings."""

    alpha: Array        # (N, L*K) p-stable projections
    b: Array            # (L*K,)
    mix: Array          # (L, K) uint32 odd multipliers (bucket mixing)
    table: Array        # (L, B, S) int32 item ids, -1 = empty
    counts: Array       # (L, B) int32 items per bucket (pre-clip)
    db: Array           # (n_items, N) stored embeddings (re-rank source)

    def tree_flatten(self):
        return ((self.alpha, self.b, self.mix, self.table, self.counts, self.db), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _bucket_ids(hashes: Array, mix: Array, log2_buckets: int) -> Array:
    """Combine per-table K int32 hashes into bucket ids.

    hashes: (..., L, K) int32; mix: (L, K) uint32.  Universal-style mixing:
    b = ((sum_k h_k * m_k) * GOLDEN) >> (32 - log2B).
    """
    h = hashes.astype(jnp.uint32)
    acc = (h * mix).sum(axis=-1, dtype=jnp.uint32)
    acc = acc * GOLDEN
    return (acc >> np.uint32(32 - log2_buckets)).astype(jnp.int32)


def make_family(key: jax.Array, cfg: IndexConfig
                ) -> Tuple[Array, Array, Array]:
    """Draw a hash family (alpha, b, mix) without allocating index storage --
    for callers that share one family across several indexes/segments."""
    ka, kb, km = jax.random.split(key, 3)
    fam = PStableHash.create(ka, cfg.n_dims, cfg.n_tables * cfg.n_hashes,
                             r=cfg.r, p=cfg.p)
    mix = jax.random.randint(km, (cfg.n_tables, cfg.n_hashes), 0,
                             np.iinfo(np.int32).max,
                             dtype=jnp.int32).astype(jnp.uint32) | np.uint32(1)
    return fam.alpha, fam.b, mix


def create_index(key: jax.Array, cfg: IndexConfig, n_items_cap: int,
                 family: Optional[Tuple[Array, Array, Array]] = None
                 ) -> LSHIndexState:
    """Fresh empty index.  ``family`` = (alpha, b, mix) reuses an existing
    hash family so several indexes (e.g. the segments of a streaming index)
    produce bitwise-identical bucket ids for the same item."""
    alpha, b, mix = make_family(key, cfg) if family is None else family
    table = jnp.full((cfg.n_tables, cfg.n_buckets, cfg.bucket_capacity), -1, jnp.int32)
    counts = jnp.zeros((cfg.n_tables, cfg.n_buckets), jnp.int32)
    db = jnp.zeros((n_items_cap, cfg.n_dims), jnp.float32)
    return LSHIndexState(alpha=alpha, b=b, mix=mix, table=table,
                         counts=counts, db=db)


def hash_family(state: LSHIndexState) -> Tuple[Array, Array, Array]:
    """The (alpha, b, mix) triple that determines bucket ids -- share it via
    ``create_index(..., family=...)`` to make indexes bucket-compatible."""
    return state.alpha, state.b, state.mix


def hash_stage(alpha: Array, b: Array, cfg: IndexConfig, x: Array
               ) -> Tuple[Array, Array]:
    """Stage 1 of the query pipeline: (..., L, K) int32 hashes and
    pre-floor projections (kernel-dispatched).  Takes the family arrays
    directly so a fan-out can run it once per query batch -- every segment
    shares one family (:func:`probe_queries`) -- while the build and
    insert paths call it through :func:`_hashes_and_proj`."""
    h, proj = ops.pstable_hash_proj(x, alpha, b, cfg.r,
                                    backend=dispatch.hash_backend())
    shape = x.shape[:-1] + (cfg.n_tables, cfg.n_hashes)
    return h.reshape(shape), proj.reshape(shape)


def _hashes_and_proj(state: LSHIndexState, cfg: IndexConfig, x: Array
                     ) -> Tuple[Array, Array]:
    """(..., L, K) int32 hashes and pre-floor projections (kernel-dispatched)."""
    return hash_stage(state.alpha, state.b, cfg, x)


def build_index(state: LSHIndexState, cfg: IndexConfig, embeddings: Array
                ) -> LSHIndexState:
    """One-shot build: insert ``embeddings`` as items 0..n-1.

    Args:
        state: fresh state from :func:`create_index` (capacity >= n).
        cfg: the index config the state was created with.
        embeddings: (n, N) f32 items; row index becomes the item id.

    Returns:
        New state with every table/counts/db leaf filled.  Pure & jittable.

    Per table: sort items by bucket, within-bucket rank = position - segment
    start, drop items ranked beyond capacity (classical LSH behaviour under
    fixed-size buckets; counts records true occupancy for diagnostics).
    """
    n = embeddings.shape[0]
    hashes, _ = _hashes_and_proj(state, cfg, embeddings.astype(jnp.float32))
    buckets = _bucket_ids(hashes, state.mix, cfg.log2_buckets)      # (n, L)

    def insert_one_table(b_col: Array, table_l: Array, counts_l: Array):
        order = jnp.argsort(b_col)                                   # (n,)
        sb = b_col[order]
        is_start = jnp.concatenate([jnp.ones((1,), jnp.bool_), sb[1:] != sb[:-1]])
        seg_start = jax.lax.associative_scan(jnp.maximum,
                                             jnp.where(is_start, jnp.arange(n), 0))
        rank = jnp.arange(n) - seg_start
        flat = table_l.reshape(-1)
        # overflowed items get an out-of-range position -> dropped by the scatter
        pos = jnp.where(rank < cfg.bucket_capacity,
                        sb * cfg.bucket_capacity + rank, flat.shape[0])
        flat = flat.at[pos].set(order.astype(jnp.int32), mode="drop")
        counts_l = counts_l.at[b_col].add(1)
        return flat.reshape(table_l.shape), counts_l

    table, counts = jax.vmap(insert_one_table, in_axes=(1, 0, 0))(
        buckets, state.table, state.counts)
    db = state.db.at[:n].set(embeddings.astype(state.db.dtype))
    return dataclasses.replace(state, table=table, counts=counts, db=db)


def insert_items(state: LSHIndexState, cfg: IndexConfig, embeddings: Array,
                 start: Array, n_valid: Array) -> LSHIndexState:
    """Incrementally append ``embeddings[:n_valid]`` as items
    ``start .. start+n_valid-1``.  Pure & jittable with *fixed* shapes: the
    (m, N) embedding block is a static-size chunk, ``start``/``n_valid`` are
    traced scalars, and rows >= n_valid are padding (never written anywhere),
    so a streaming caller reuses one compiled program for every insert.

    Within-chunk placement uses the same sort + segmented-rank machinery as
    ``build_index``; each item's slot is offset by the bucket's existing
    occupancy (``counts``), so interleaved insert batches fill buckets exactly
    like a one-shot build would (overflow beyond capacity is dropped, counts
    still record true occupancy).
    """
    m = embeddings.shape[0]
    hashes, _ = _hashes_and_proj(state, cfg, embeddings.astype(jnp.float32))
    buckets = _bucket_ids(hashes, state.mix, cfg.log2_buckets)        # (m, L)
    valid = jnp.arange(m) < n_valid
    ids = (start + jnp.arange(m)).astype(jnp.int32)

    def insert_one_table(b_col: Array, table_l: Array, counts_l: Array):
        # padding rows get sentinel bucket B: sorts last, scatters are dropped
        b_eff = jnp.where(valid, b_col, cfg.n_buckets)
        order = jnp.argsort(b_eff)
        sb = b_eff[order]
        is_start = jnp.concatenate([jnp.ones((1,), jnp.bool_), sb[1:] != sb[:-1]])
        seg_start = jax.lax.associative_scan(jnp.maximum,
                                             jnp.where(is_start, jnp.arange(m), 0))
        rank = jnp.arange(m) - seg_start
        slot = counts_l[jnp.clip(sb, 0, cfg.n_buckets - 1)] + rank
        flat = table_l.reshape(-1)
        pos = jnp.where((slot < cfg.bucket_capacity) & (sb < cfg.n_buckets),
                        sb * cfg.bucket_capacity + slot, flat.shape[0])
        flat = flat.at[pos].set(ids[order], mode="drop")
        counts_l = counts_l.at[b_eff].add(1, mode="drop")
        return flat.reshape(table_l.shape), counts_l

    table, counts = jax.vmap(insert_one_table, in_axes=(1, 0, 0))(
        buckets, state.table, state.counts)
    rows = jnp.where(valid, ids, state.db.shape[0])
    db = state.db.at[rows].set(embeddings.astype(state.db.dtype), mode="drop")
    return dataclasses.replace(state, table=table, counts=counts, db=db)


@jax.jit
def bucket_overflow(state: LSHIndexState, n_items) -> Tuple[Array, Array]:
    """Bucket health of an index holding items ``0 .. n_items-1``:
    (placements dropped because their bucket was full, items that no table
    holds).

    ``counts`` records true occupancy while a bucket keeps at most
    ``bucket_capacity`` ids, so the first is sum(max(counts - capacity,
    0)) over every table; an item dropped from all of its buckets is found
    by no query.  Computed on the device from ``counts`` and ``table``."""
    dropped = jnp.maximum(state.counts - state.table.shape[-1], 0).sum()
    n_cap = state.db.shape[0]
    ids = state.table.reshape(-1)
    held = jnp.zeros((n_cap,), jnp.bool_).at[
        jnp.where(ids >= 0, ids, n_cap)].set(True, mode="drop")
    unreachable = jnp.sum((jnp.arange(n_cap) < n_items) & ~held)
    return dropped, unreachable


def probe_stage(mix: Array, cfg: IndexConfig, hashes: Array,
                proj: Array, n_probes: int) -> Array:
    """Stage 2: (..., L, T) bucket ids: base bucket + best (T-1)
    single-coordinate perturbations ranked by distance-to-boundary
    (Lv et al. step-wise probing).  Family-array form, like
    :func:`hash_stage`; :func:`probe_queries` runs the two."""
    frac = proj - jnp.floor(proj)                                    # (..., L, K)
    # score for delta=+1 is (1 - frac), for delta=-1 is frac; smaller = better.
    scores = jnp.concatenate([1.0 - frac, frac], axis=-1)            # (..., L, 2K)
    base = _bucket_ids(hashes, mix, cfg.log2_buckets)[..., None]
    if n_probes <= 1:
        return base
    t = min(n_probes - 1, 2 * cfg.n_hashes)
    _, pick = jax.lax.top_k(-scores, t)                              # (..., L, t)
    k_idx = pick % cfg.n_hashes
    delta = jnp.where(pick < cfg.n_hashes, 1, -1).astype(jnp.int32)
    pert = hashes[..., None, :] + delta[..., :, None] * (
        jax.nn.one_hot(k_idx, cfg.n_hashes, dtype=jnp.int32))        # (..., L, t, K)
    pb = _bucket_ids(pert, mix[:, None, :], cfg.log2_buckets)        # (..., L, t)
    return jnp.concatenate([base, pb], axis=-1)


def _gather_candidates(table: Array, buckets: Array, cfg: IndexConfig,
                       slot: Optional[Array] = None) -> Array:
    """The probed buckets' slots as (nq, L*T*S) candidate ids, -1 =
    empty, repeats and dead items included.

    With ``slot``, ``table`` is a stack of segments (n_slots, L, B, S) and
    the one at ``slot`` is read in place: the slot is one more coordinate
    of the gather, so no segment's tables are sliced out (a slice would be
    a copy)."""
    nq = buckets.shape[0]
    at = (jnp.arange(cfg.n_tables)[:, None, None], buckets.transpose(1, 0, 2))
    if slot is not None:
        at = (jnp.full(at[1].shape, slot, jnp.int32),) + at
    cands = table[at]                                                # (L, nq, T, S)
    return cands.transpose(1, 0, 2, 3).reshape(nq, -1)               # (nq, L*T*S)


def gather_stage(table: Array, buckets: Array, cfg: IndexConfig,
                 n_cap: int, live_mask: Optional[Array] = None,
                 slot: Optional[Array] = None) -> Array:
    """Stage 3: gather bucket slots + dedup (+ optional tombstone filter):
    (nq, L*T*S) candidate ids, -1 = empty/dup/dead, from the first-seen
    table of ``kernels.ref.dedup_candidates`` (the tombstones ride in it).

    With ``slot``, ``table`` (n_slots, L, B, S) and ``live_mask``
    (n_slots, n_cap) are stacks of segments and the one at ``slot`` is
    read in place (only its (n_cap,) live row is sliced out).  The serve
    layer's :func:`segment_topk` skips this table: the query kernel drops
    repeats and dead items in its own candidate walk."""
    cands = _gather_candidates(table, buckets, cfg, slot)
    if live_mask is not None and slot is not None:
        live_mask = live_mask[slot]                                  # (n_cap,)
    return ref.dedup_candidates(cands, n_cap, live_mask)


def probe_queries(family: Tuple[Array, Array, Array], cfg: IndexConfig,
                  q: Array, n_probes: int) -> Array:
    """Stages 1-2 for one query batch under a hash family
    ``(alpha, b, mix)``: (nq, L, T) probed bucket ids.  Every segment of a
    serve index shares one family, so a fan-out runs this once per batch
    and hands the buckets to each segment's :func:`segment_topk`."""
    alpha, b, mix = family
    hashes, proj = hash_stage(alpha, b, cfg, q)
    return probe_stage(mix, cfg, hashes, proj, n_probes)


def segment_topk(table: Array, db: Array, gids: Array, live: Array,
                 cfg: IndexConfig, q: Array, buckets: Array, k: int, *,
                 slot: Optional[Array] = None, scale: Optional[Array] = None,
                 backend: Optional[str] = None) -> Tuple[Array, Array]:
    """The serve layer's per-segment body, from probed ``buckets`` to the
    segment's top-k: the table gather, the query kernel over the
    candidates' rows ``db`` with the dedup and tombstone filter
    (``dedup=True``: inside the block kernel's candidate walk on a
    segment's rows, see ``ops.dedup_site``), then slot -> global id.

    Args:
        table, gids, live: one segment's (L, B, S) tables and (n_cap,)
            global ids and live mask; or, with ``slot``, stacks of segments
            -- (n_slots, L, B, S) tables, (n_slots, n_cap) gids and live --
            read in place at ``slot``.
        db: the segment's own (n_cap, N) rows, whichever form the rest
            takes: the kernel takes a segment's rows whole into VMEM and
            gathers each candidate's row there, so it gets one segment's
            rows, not a stack of them.
        q, buckets: (nq, N) queries and their :func:`probe_queries`.
        k: results per query (static).
        scale: the segment's dequant scale when ``db`` holds int8/bf16
            codes (scored dequant-free by ``ops.quantized_query_topk``);
            None scores fp32 rows exactly (``ops.fused_query_topk``).
        backend: the query kernel's mode (``dispatch.query_backend``).
    Returns:
        (gids (nq, k) int32, dists (nq, k) f32), -1/inf padded.

    Every fan-out runs this one body -- the per-segment programs and the
    stacked program of ``serve/segments.py``, and the SPMD collective of
    ``core/distributed.py`` -- so their answers agree by construction.
    """
    n_cap = gids.shape[-1]
    cands = _gather_candidates(table, buckets, cfg, slot)
    if live is not None and slot is not None:
        live = live[slot]                                            # (n_cap,)
    kw = dict(p=cfg.p, backend=backend, live=live, dedup=True)
    if scale is None:
        dist, ids = ops.fused_query_topk(q, db, cands, k, **kw)
    else:
        dist, ids = ops.quantized_query_topk(q, db, scale, cands, k, **kw)
    at = jnp.clip(ids, 0, n_cap - 1)
    g = (gids[at] if slot is None
         else gids[jnp.full(at.shape, slot, jnp.int32), at])
    return jnp.where(ids >= 0, g, -1), dist


def _candidate_ids(state: LSHIndexState, cfg: IndexConfig, q: Array,
                   n_probes: int, live_mask: Optional[Array] = None
                   ) -> Array:
    """hash -> probe -> gather bucket slots -> dedup (-> tombstone
    filter): (nq, L*T*S) ids."""
    buckets = probe_queries(hash_family(state), cfg, q, n_probes)   # (nq, L, T)
    return gather_stage(state.table, buckets, cfg, state.db.shape[0],
                        live_mask)


def query_index(state: LSHIndexState, cfg: IndexConfig, queries: Array,
                k: int, n_probes: int = 1, valid_items: Optional[int] = None,
                backend: Optional[str] = None,
                live_mask: Optional[Array] = None) -> Tuple[Array, Array]:
    """k-NN query: hash -> probe -> gather -> dedup -> re-rank -> top-k.

    Args:
        state, cfg: a built (or incrementally filled) index.
        queries: (nq, N) f32.
        k: results per query (static).
        n_probes: buckets probed per table (1 = base bucket only; more adds
            the best single-coordinate perturbations, Lv et al. 2007).
        valid_items: optionally mask item ids >= this (partially-filled
            capacity).
        backend: selects the re-rank tail only (fused / reference /
            compiled / interpret; default per dispatch.query_backend) --
            hashing always uses the process-constant implementation so
            probed buckets match the build exactly.
        live_mask: bool (n_items_cap,); False rows are dropped from the
            candidate set before re-rank -- the streaming serve layer's
            tombstone delete path.

    Returns:
        (ids (nq, k) int32, dists (nq, k) f32), ascending by distance;
        ids are -1 (dist +inf) where fewer than k candidates were found.
    """
    q = queries.astype(jnp.float32)
    cands = _candidate_ids(state, cfg, q, n_probes, live_mask)
    dist, ids = ops.fused_query_topk(q, state.db, cands, k, p=cfg.p,
                                     valid_items=valid_items, backend=backend)
    return ids, dist


def query_index_gids(state: LSHIndexState, cfg: IndexConfig, queries: Array,
                     k: int, gids: Array, n_probes: int = 1,
                     backend: Optional[str] = None,
                     live_mask: Optional[Array] = None,
                     scale: Optional[Array] = None
                     ) -> Tuple[Array, Array]:
    """One segment's k-NN in global ids: :func:`probe_queries` then
    :func:`segment_topk` on the segment's own arrays.

    Args:
        gids: (n_items_cap,) int32 global id per slot (-1 = empty).
        scale: the dequant scale of a quantized segment (int8/bf16
            ``state.db``): candidates are scored in code space and the
            distances are approximate within O(scale), so serve callers
            rescore survivors exactly (``kernels.quantize.rerank_survivors``).
            None (fp32 rows) scores exactly.
        Everything else as in :func:`query_index`.
    Returns:
        (gids (nq, k) int32, dists (nq, k) f32), -1/inf padded.

    The per-segment program of the serve layer's fan-out (serve/segments.py)
    and of each instance in the SPMD collective (core/distributed.py).
    """
    q = queries.astype(jnp.float32)
    buckets = probe_queries(hash_family(state), cfg, q, n_probes)
    return segment_topk(state.table, state.db, gids, live_mask, cfg, q,
                        buckets, k, scale=scale, backend=backend)


@functools.lru_cache(maxsize=32)
def _batched_query_fn(cfg: IndexConfig, k: int, n_probes: int,
                      valid_items: Optional[int], backend: Optional[str],
                      donate: bool, masked: bool):
    fn = functools.partial(query_index, cfg=cfg, k=k, n_probes=n_probes,
                           valid_items=valid_items, backend=backend)
    if masked:
        wrapped = lambda state, queries, live_mask: fn(
            state, queries=queries, live_mask=live_mask)
    else:
        wrapped = lambda state, queries: fn(state, queries=queries)
    # Donating the query chunk lets XLA reuse its HBM for the outputs on
    # accelerators; CPU would only warn, so skip it there.
    return jax.jit(wrapped, donate_argnums=(1,) if donate else ())


def query_index_batched(state: LSHIndexState, cfg: IndexConfig,
                        queries: Array, k: int, n_probes: int = 1,
                        valid_items: Optional[int] = None,
                        batch_size: int = 1024,
                        backend: Optional[str] = None,
                        live_mask: Optional[Array] = None
                        ) -> Tuple[Array, Array]:
    """Streaming k-NN for large query sets: tiles ``queries`` into fixed
    ``batch_size`` chunks (one compiled program total -- the last chunk is
    zero-padded, not retraced) and concatenates results.

    Bounds peak memory at O(batch_size * C) for the candidate tables and
    keeps the fused kernel's scalar-prefetch id table within SMEM limits.
    """
    nq = queries.shape[0]
    if nq <= batch_size:
        return query_index(state, cfg, queries, k, n_probes, valid_items,
                           backend, live_mask=live_mask)
    # Resolve the backend BEFORE the lru_cache key is formed: caching on a
    # raw None would bake the first call's env/platform default into the
    # trace and silently ignore later REPRO_QUERY_BACKEND changes.
    mode = dispatch.query_backend(backend)
    fn = _batched_query_fn(cfg, k, n_probes, valid_items, mode,
                           donate=jax.default_backend() != "cpu",
                           masked=live_mask is not None)
    ids_out, dist_out = [], []
    for start in range(0, nq, batch_size):
        chunk = queries[start:start + batch_size]
        pad = batch_size - chunk.shape[0]
        if pad:
            chunk = jnp.pad(chunk, ((0, pad), (0, 0)))
        args = (state, chunk) if live_mask is None else (state, chunk, live_mask)
        ids, dist = fn(*args)
        ids_out.append(ids if not pad else ids[:-pad])
        dist_out.append(dist if not pad else dist[:-pad])
    return jnp.concatenate(ids_out), jnp.concatenate(dist_out)


def brute_force_topk(db: Array, queries: Array, k: int, p: float = 2.0,
                     valid_items: Optional[int] = None) -> Tuple[Array, Array]:
    """Exact k-NN oracle for recall measurement.

    Args:
        db: (n_items, N) f32; queries: (nq, N) f32; p: L^p exponent.
    Returns:
        (ids (nq, k) int32, dists (nq, k) f32) -- exact, O(n_items * nq * N).
    """
    q = queries.astype(jnp.float32)
    if p == 2.0:
        d = jnp.linalg.norm(db[None, :, :] - q[:, None, :], axis=-1)
    else:
        d = jnp.sum(jnp.abs(db[None, :, :] - q[:, None, :]) ** p, axis=-1) ** (1.0 / p)
    if valid_items is not None:
        mask = jnp.arange(db.shape[0]) >= valid_items
        d = jnp.where(mask[None, :], jnp.inf, d)
    neg, ids = jax.lax.top_k(-d, k)
    return ids, -neg


def recall_at_k(lsh_ids: Array, exact_ids: Array) -> Array:
    """Fraction of the exact top-k retrieved by the LSH query.

    Args:
        lsh_ids / exact_ids: (nq, k) int32 id lists (-1 = empty slot).
    Returns:
        Scalar f32: per-query hit fraction, averaged over queries.
    """
    hit = (lsh_ids[:, :, None] == exact_ids[:, None, :]) & (exact_ids[:, None, :] >= 0)
    per_q = hit.any(axis=1).sum(axis=-1) / jnp.maximum((exact_ids >= 0).sum(axis=-1), 1)
    return per_q.mean()
