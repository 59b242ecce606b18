"""Segmented mutable LSH index: the streaming lifecycle over core/index.

core/index is deliberately build-once (static shapes, jit-friendly).  This
module turns it into a *living* index the way LSM storage engines do:

* one mutable **delta** segment absorbs inserts via the incremental
  ``insert_items`` path (fixed-size padded chunks -> one compiled program for
  every insert, ever);
* when the delta reaches ``segment_capacity`` it is **sealed** -- sealing is
  free because incremental inserts maintain a valid LSH table at all times;
* **deletes** are tombstones: a per-segment live mask consulted at query time
  (``query_index(..., live_mask=...)``), never a structural mutation;
* **compact** folds every live item into fresh segments (dropping
  tombstones and re-packing buckets), using the same incremental-insert
  program -- no new compilation.  It runs in three phases so a background
  worker can do the heavy rebuild **off the query path**: a locked
  *freeze* (log COMPACT, force-seal the delta, open a delete ledger), a
  lock-free *shadow build* (queries keep serving the old segments), and a
  locked atomic *swap* (adopt the shadow, splice in segments inserted
  meanwhile, re-apply ledgered deletes);
* the mutation surface is split into a **data plane** (insert / delete /
  query, on the index) and a **maintenance plane**: ``index.maintenance``
  (:class:`repro.serve.maintenance.IndexMaintenance`) owns ``seal()``,
  ``compact()`` and ``set_replication()`` and serialises them against each
  other.  The old direct methods survive as ``DeprecationWarning`` shims;
* **query()** fans out to all segments and merges per-segment top-k via
  ``kernels.ops.merge_topk`` -- on one device in ONE program per batch:
  the sealed segments are stacked (``sharding.placement``, capacity-
  doubling headroom, per-slot diffs) and a loop over the occupied slots
  scores each, then the delta, then the merge;
* **shard(mesh)** moves the fan-out onto a device mesh: sealed segments
  round-robin over the mesh's serve axis, delta + hash family replicated,
  collective top-k fan-in (``core.distributed.query_segments_sharded`` via
  ``sharding.placement``) -- results stay bit-identical to the
  single-device path (the sharding invariant, docs/architecture.md §
  "Invariants");
* **set_replication(...)** materializes hot sealed segments on several
  devices (``sharding/placement.py`` instance assignment); a per-placement
  ``QueryRouter`` then activates one replica per segment per micro-batch so
  per-device load equalizes, with results still bit-identical to the
  unreplicated path (replicas are copies; the collective fan-in dedups by
  gid as a second line of defense);
* an optional **on_fanout hook** attributes every merged top-k slot back to
  the segment (and device, when sharded) that contributed it -- the serve
  layer wires it to ``ServingStats.record_fanout`` so placement skew is
  observable per tenant, and the ``auto`` replication policy turns that
  skew back into placement (``router.auto_factors`` at compact time).

Every segment shares ONE hash family (``create_index(family=...)``), so an
item's bucket ids are independent of which segment holds it.  Consequence
(verified by tests/test_serve.py): as long as no bucket overflows its
capacity, a cross-segment query returns ids *bit-identical* to a single
``build_index`` over the union of live items -- segmentation is invisible to
callers.

All segments share the same (capacity, cfg) shapes, so the stacked query
program is compiled once per stack width and reused for every seal into
headroom and every insert-order history (the padded-chunk shape palette --
docs/architecture.md has the full table).  Host-side bookkeeping (gid maps,
live masks) is numpy; device state is the ``LSHIndexState`` pytree plus a
(capacity,) gid vector and live mask.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import warnings
import zlib
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..core import distributed, index as lidx
from ..core.index import IndexConfig, LSHIndexState
from ..kernels import dispatch, ops, quantize
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..sharding import placement as seg_placement
from . import faults, wal as walmod
from .router import QueryRouter

Array = jax.Array


@dataclasses.dataclass
class Segment:
    """One shard of the segmented index (sealed or delta)."""

    state: LSHIndexState          # device pytree (table/counts/db + family)
    gids: Array                   # (capacity,) int32 global id per slot
    live: Array                   # (capacity,) bool, False = tombstoned
    n_items: int = 0              # slots used (including tombstoned)
    n_live: int = 0               # live items
    sealed: bool = False
    # Precision tier (sealed segments under bf16/int8 only; always None on
    # fp32 tenants and on the mutable delta, which stays fp32 until sealed):
    scale: Optional[Array] = None     # () f32 symmetric dequant scale
    pool: Optional[np.ndarray] = None  # (capacity, N) f32 survivor side pool
    # (bucket placements dropped, items no table holds): dispatched on the
    # device at seal (``core.index.bucket_overflow``), read back to ints
    # once by ``read_bucket_health``; never serialized
    bucket_health: Optional[tuple] = None
    # Incremental re-placement fingerprints (``sharding.placement`` diffs):
    # computed lazily, cached only for sealed segments, live half
    # invalidated on tombstone flips.  Never serialized.
    _content_key: Optional[tuple] = None
    _live_key: Optional[int] = None
    # Host copy of ``live``, written with it at every mutation (see
    # ``live_np``).  Never serialized.
    _live_host: Optional[np.ndarray] = None

    @property
    def capacity(self) -> int:
        return self.gids.shape[0]

    def live_np(self) -> np.ndarray:
        """``live`` on the host, kept in step by every mutation, so a
        delete reads liveness without waiting for the device (whose queue
        may hold a whole batch's fan-out program).  Read back once for a
        segment built elsewhere (a restored snapshot)."""
        if self._live_host is None:
            self._live_host = np.array(self.live)
        return self._live_host

    def placement_key(self) -> tuple:
        """``(content, live)`` fingerprint for placement diffing.

        A sealed segment's rows are fully determined by its ordered gid
        vector (invariant 3: every segment shares ONE hash family and an
        item's embedding never changes), so ``(n_items, crc32(gids))``
        fingerprints the content; the live mask gets its own crc so
        sealed-segment deletes diff as a mask-row rewrite instead of a
        full row.  Unsealed segments get an identity-keyed fingerprint
        that changes with every mutation -- they are never cached and
        never spuriously match across builds.
        """
        if not self.sealed:
            k = ("unsealed", id(self), int(self.n_items), int(self.n_live))
            return (k, k)
        if self._content_key is None:
            self._content_key = (int(self.n_items),
                                 zlib.crc32(np.asarray(self.gids).tobytes()))
        if self._live_key is None:
            self._live_key = zlib.crc32(self.live_np().tobytes())
        return (self._content_key, self._live_key)

    def read_bucket_health(self) -> Tuple[int, int]:
        """``bucket_health`` as host ints (computed here for a segment
        sealed without it, e.g. one restored from a snapshot)."""
        if self.bucket_health is None:
            self.bucket_health = lidx.bucket_overflow(
                self.state, jnp.int32(self.n_items))
        self.bucket_health = tuple(int(x) for x in self.bucket_health)
        return self.bucket_health

    def occupancy(self) -> dict:
        cap = self.capacity
        return {
            "n_items": self.n_items,
            "n_live": self.n_live,
            "capacity": cap,
            "fill": self.n_items / cap,
            "tombstone_frac": ((self.n_items - self.n_live) / self.n_items
                               if self.n_items else 0.0),
            "sealed": self.sealed,
        }


@functools.lru_cache(maxsize=64)
def _segment_query_fn(cfg: IndexConfig, k: int, n_probes: int,
                      backend: Optional[str]):
    """One compiled program per (cfg, k, n_probes, backend): query a segment
    and translate local slot ids to global ids.  Shared by ALL segments of
    all indexes with the same config, so segment count never multiplies
    compilations."""

    # the function's name is the program's name in a profiler trace
    def segment_query(state: LSHIndexState, q: Array, live: Array,
                      gids: Array):
        return lidx.query_index_gids(state, cfg, q, k, gids,
                                     n_probes=n_probes, backend=backend,
                                     live_mask=live)

    return jax.jit(segment_query)


@functools.lru_cache(maxsize=64)
def _quantized_segment_query_fn(cfg: IndexConfig, k: int, n_probes: int,
                                backend: Optional[str]):
    """Quantized-tier sibling of :func:`_segment_query_fn`: candidates are
    scored in code space against the segment's int8/bf16 ``db`` with one
    per-segment dequant ``scale`` -- no fp32 decode of the stored rows."""

    def segment_query_codes(state: LSHIndexState, q: Array, live: Array,
                            gids: Array, scale: Array):
        return lidx.query_index_gids(state, cfg, q, k, gids,
                                     n_probes=n_probes, backend=backend,
                                     live_mask=live, scale=scale)

    return jax.jit(segment_query_codes)


@functools.lru_cache(maxsize=64)
def _stacked_query_fn(cfg: IndexConfig, k: int, n_probes: int,
                      backend: Optional[str], quantized: bool):
    """One compiled program per (cfg, k, n_probes, backend, tier) for a
    batch's whole unsharded fan-out: every sealed segment of the one-device
    stack (``sharding.placement``; slot i holds sealed segment i), then the
    delta, then the merge -- one dispatch instead of one per segment.

    Hash and probe run once (every segment shares the family).  A
    ``lax.fori_loop`` then runs the per-segment body
    (``core.index.segment_topk``) for slots ``0 .. n_sealed-1``; the trip
    count is traced, so headroom slots cost nothing and a seal into
    headroom reuses the program.  A slot's tables, gids and live mask are
    gathered from the stacks in place; its rows are sliced out, so the
    query kernel gets the operands it gets per segment: one segment's rows,
    which it takes whole into VMEM and gathers its candidates from.  Sealed
    slots score in code space when ``quantized`` (int8/bf16 codes, one
    scale each); the delta always scores exactly.

    Returns the merged ``(gids, dists)`` and the valid candidates each
    segment offered the merge, ``(n_slots + 1,)``: the sealed segments'
    in slot order, then the delta's at ``n_sealed`` (the telemetry's
    per-segment candidate counts in one copy).  The merge
    is a total (distance, gid) order over every slot's top-k, so the answer
    is bit-identical to the per-segment programs' merge."""

    # the function's name is the program's name in a profiler trace
    def segment_query_stacked(stack: LSHIndexState, gids: Array, live: Array,
                              scales: Array, n_sealed: Array,
                              delta: LSHIndexState, delta_gids: Array,
                              delta_live: Array, q: Array):
        buckets = lidx.probe_queries(lidx.hash_family(delta), cfg, q,
                                     n_probes)
        n_slots = gids.shape[0]
        nq = q.shape[0]

        def one_slot(slot, acc):
            out_g, out_d, counts = acc
            rows = jax.lax.dynamic_index_in_dim(stack.db, slot,
                                                keepdims=False)
            g, d = lidx.segment_topk(
                stack.table, rows, gids, live, cfg, q, buckets, k,
                slot=slot, scale=scales[slot] if quantized else None,
                backend=backend)
            return (out_g.at[slot].set(g), out_d.at[slot].set(d),
                    counts.at[slot].set(jnp.sum(g >= 0, dtype=jnp.int32)))

        out_g, out_d, counts = jax.lax.fori_loop(
            0, n_sealed, one_slot,
            (jnp.full((n_slots, nq, k), -1, jnp.int32),
             jnp.full((n_slots, nq, k), jnp.inf, jnp.float32),
             jnp.zeros((n_slots + 1,), jnp.int32)))
        g, d = lidx.segment_topk(delta.table, delta.db, delta_gids,
                                 delta_live, cfg, q, buckets, k,
                                 backend=backend)
        g_all = jnp.concatenate(
            [out_g.transpose(1, 0, 2).reshape(nq, n_slots * k), g], axis=1)
        d_all = jnp.concatenate(
            [out_d.transpose(1, 0, 2).reshape(nq, n_slots * k), d], axis=1)
        d_m, g_m = ops.merge_topk(d_all, g_all, k)
        counts = counts.at[n_sealed].set(jnp.sum(g >= 0, dtype=jnp.int32))
        return g_m, d_m, counts

    return jax.jit(segment_query_stacked)


@functools.lru_cache(maxsize=1)
def _local_mesh() -> Mesh:
    """The one-device mesh the unsharded stack is placed on."""
    return Mesh(np.array(jax.devices()[:1]), ("stack",))


def _stackable(sealed: Sequence[Segment]) -> bool:
    """True iff the sealed segments share one storage dtype and tier, so
    their leaves stack (fp32 segments sealed before a tenant's precision
    changed cannot stack with its int8 ones)."""
    return len({(s.state.db.dtype, s.scale is None) for s in sealed}) <= 1


@dataclasses.dataclass
class _Fanout:
    """One batch's fan-out on the device: the merged ``gids``/``dists``
    and, for the telemetry, the valid candidates ``counts[j]`` that
    segment ``seg_ids[j]`` offered the merge (unsharded only), and the
    router's ``plan`` (routed sharded batches only)."""

    gids: Array
    dists: Array
    counts: Optional[Array] = None
    seg_ids: Optional[List[int]] = None
    plan: object = None


@functools.lru_cache(maxsize=64)
def _segment_insert_fn(cfg: IndexConfig, chunk: int):
    """One compiled incremental-insert program per (cfg, chunk shape)."""

    def segment_insert(state: LSHIndexState, emb: Array, start, n_valid):
        return lidx.insert_items(state, cfg, emb, start, n_valid)

    return jax.jit(segment_insert)


class SegmentedIndex:
    """Mutable, queryable, compactable index built from fixed-shape segments.

    Thread-safety: mutators and query take an internal lock; a query holds
    it only to dispatch the stacked fan-out program (one asynchronous jax
    call) and waits for the device after releasing it, so readers and
    writers contend for that one dispatch (the micro-batcher serialises
    heavy traffic anyway).
    """

    def __init__(self, cfg: IndexConfig, *, segment_capacity: int = 1024,
                 insert_chunk: int = 256, key: Optional[jax.Array] = None,
                 backend: Optional[str] = None, seed: int = 0,
                 on_fanout=None, tenant: str = "default",
                 precision: str = "fp32", survivor_k: int = 0,
                 family=None):
        if insert_chunk > segment_capacity:
            insert_chunk = segment_capacity
        self.cfg = cfg
        self.tenant = tenant              # label on spans/metrics only
        # Storage precision tier: taken VERBATIM (validated, never re-
        # resolved against $REPRO_STORE_DTYPE) so recovery serves the tier
        # the WAL/snapshot recorded -- dispatch.store_dtype is the caller's
        # job (the registry runs it once at registration).  survivor_k = 0
        # means the default 4*k survivor pool (quantize.survivor_width).
        if precision not in dispatch.STORE_DTYPES:
            raise ValueError(f"unknown precision {precision!r}; want one "
                             f"of {dispatch.STORE_DTYPES}")
        self.precision = precision
        self.survivor_k = int(survivor_k)
        # load/imbalance telemetry hook: called after every cross-segment
        # merge with (seg_wins, dev_wins, seg_candidates) -- see
        # ServingStats.record_fanout, whose signature this matches.  None
        # (the default) costs nothing: no host sync, no attribution loop.
        self._on_fanout = on_fanout
        self.segment_capacity = int(segment_capacity)
        self.insert_chunk = int(insert_chunk)
        # Resolve once: a raw None would bake the first call's platform
        # default into lru_cache keys (see core.index.query_index_batched).
        self.backend = dispatch.query_backend(backend)
        key = jax.random.PRNGKey(seed) if key is None else key
        # family= lets compaction build its shadow index against the SAME
        # hash family (invariant 3 makes the shadow's answers identical)
        self.family = (lidx.make_family(key, cfg) if family is None
                       else family)
        self.segments: List[Segment] = []
        self._locator: dict = {}          # gid -> (segment index, slot)
        self._next_gid = 0
        self._lock = threading.RLock()
        # SPMD serve path: shard(mesh) sets these.  Two mutation counters
        # drive lazy placement refresh: _version bumps on EVERY mutation
        # (delta re-replication, O(delta bytes)); _sealed_version bumps only
        # when the sealed set changes (seal/compact/sealed-segment delete),
        # which is what forces the full restack + device transfer.
        self._mesh = None
        self._shard_axis: Optional[str] = None
        self._placement = None
        self._version = 0
        self._sealed_version = 0
        self._delta_synced = -1        # _version the placement's delta is at
        # replication policy: None (off) | int (every sealed segment) |
        # positional per-sealed-segment factors.  Normalized against the
        # live sealed count/mesh at placement-build time, so it can be set
        # before shard() or while the segment set is still churning.
        self._replication = None
        self._router: Optional[QueryRouter] = None
        # distinct query batch shapes seen -- the serve bench asserts this
        # stays bounded by the batcher's chunk palette (no per-request traces)
        self.query_shapes: set = set()
        # durability: when a WAL is attached every mutation is framed and
        # appended BEFORE it is applied; _wal_mute suppresses logging for
        # mutations that are consequences of an already-logged record
        # (compaction's internal re-inserts, replay itself)
        self._wal: Optional[walmod.WriteAheadLog] = None
        self._wal_mute = False
        self.n_rejected = 0            # rows refused by insert validation
        # maintenance plane: handle built lazily (avoids an import cycle);
        # _compact_deletes is the delete ledger a background compaction
        # opens at freeze and re-applies at swap
        self._maintenance = None
        self._compact_deletes: Optional[set] = None
        self._open_segment()

    # -- lifecycle ----------------------------------------------------------

    def _open_segment(self) -> Segment:
        state = lidx.create_index(jax.random.PRNGKey(0), self.cfg,
                                  self.segment_capacity, family=self.family)
        seg = Segment(state=state,
                      gids=jnp.full((self.segment_capacity,), -1, jnp.int32),
                      live=jnp.zeros((self.segment_capacity,), jnp.bool_),
                      _live_host=np.zeros((self.segment_capacity,), bool))
        self.segments.append(seg)
        return seg

    @property
    def delta(self) -> Segment:
        return self.segments[-1]

    @property
    def n_live(self) -> int:
        return sum(s.n_live for s in self.segments)

    @property
    def n_items(self) -> int:
        return sum(s.n_items for s in self.segments)

    @property
    def maintenance(self):
        """The maintenance-plane handle (:class:`IndexMaintenance`): owns
        ``seal()`` / ``compact()`` / ``set_replication()`` and serialises
        them against each other.  The data plane (insert/delete/query)
        stays on the index itself."""
        if self._maintenance is None:
            from .maintenance import IndexMaintenance
            self._maintenance = IndexMaintenance(self)
        return self._maintenance

    def seal(self) -> None:
        """Deprecated: use ``index.maintenance.seal()``."""
        warnings.warn(
            "SegmentedIndex.seal() is deprecated; seal through the "
            "maintenance plane (index.maintenance.seal())",
            DeprecationWarning, stacklevel=2)
        self._maint_seal()

    def _maint_seal(self) -> None:
        """Seal the current delta (no-op if empty) and open a fresh one.

        Logged to the WAL as an explicit SEAL record; the implicit seal
        that ``insert`` performs when the delta fills is *not* logged --
        replaying the INSERT record reproduces it.  A replayed SEAL on an
        emptier-than-original delta only changes segment *structure*, and
        invariant 3 makes structure invisible to query results.
        """
        with self._lock:
            if self.delta.n_items == 0:
                return
            tr = obs_trace.tracer()
            with tr.span("seal", tenant=self.tenant,
                         rows=self.delta.n_items) as sp:
                self._log(walmod.encode_seal())
                # mid-seal crash point: the SEAL record is durable-framed
                # but the segment mutation below has not happened yet
                faults.fire("seal")
                sealed = self.delta
                self._seal()
                if tr.sampled():
                    # only a recorded span pays for the readback
                    dropped, unreachable = sealed.read_bucket_health()
                    sp.set(overflow_slots=dropped,
                           unreachable_items=unreachable)

    def _seal(self) -> None:
        """Apply a seal (callers hold the lock; never logs).

        Under a quantized precision tier this is the encode point: the
        delta's fp32 rows become int8/bf16 codes + one dequant scale, and
        the exact fp32 rows move to a host-side survivor pool (rerank,
        ``live_items``, compaction all read through it).  Encoding happens
        BEFORE the sealed flag flips, so a failed encode leaves the delta
        mutable and untouched.  fp32 tenants never enter this branch --
        their sealed state is byte-for-byte what it was before the tier
        existed (invariant 10).
        """
        if self.delta.n_items == 0:
            return
        if self.precision != "fp32":
            self._quantize_segment(self.delta)
        # dispatched, not read back: a readback here would stall every
        # seal of a bulk load until the device drains; the counts are read
        # at report time (``bucket_overflow``)
        self.delta.bucket_health = lidx.bucket_overflow(
            self.delta.state, jnp.int32(self.delta.n_items))
        self.delta.sealed = True
        self._open_segment()
        self._version += 1
        self._sealed_version += 1
        self._publish_store_metrics()

    def _quantize_segment(self, seg: Segment) -> None:
        """Encode one about-to-seal segment into the storage tier."""
        pool = np.asarray(seg.state.db)
        if not np.isfinite(pool).all():
            # insert() already rejects NaN/Inf batches; this is the seal-
            # time defense the quantizer contract requires (a non-finite
            # row would corrupt the shared scale for the whole segment)
            raise ValueError(
                f"segment holds non-finite embeddings; refusing to "
                f"quantize to {self.precision} at seal")
        codes, scale = quantize.encode(seg.state.db, self.precision)
        seg.state = dataclasses.replace(seg.state, db=codes)
        seg.scale = scale
        seg.pool = pool

    def _publish_store_metrics(self) -> None:
        """Sealed-store bytes per live item (the tier's capacity win)."""
        sealed = [s for s in self.segments[:-1] if s.n_items > 0]
        items = sum(s.n_live for s in sealed)
        if not items:
            return
        nbytes = sum(int(s.state.db.nbytes) for s in sealed)
        obs_metrics.registry().set("store_bytes_per_item", nbytes / items,
                                   tenant=self.tenant)

    def bucket_overflow(self) -> dict:
        """Over the sealed segments: bucket placements dropped because the
        bucket was full (``overflow_slots``) and items no table holds, so
        no query finds (``unreachable_items``).  Reads each segment's
        counts back once and publishes them as the gauges
        ``index_bucket_overflow_slots`` / ``index_unreachable_items``;
        called at report time, never on the query path."""
        with self._lock:
            sealed = [s for s in self.segments[:-1] if s.n_items > 0]
        health = [s.read_bucket_health() for s in sealed]
        out = {"overflow_slots": sum(h[0] for h in health),
               "unreachable_items": sum(h[1] for h in health)}
        reg = obs_metrics.registry()
        reg.set("index_bucket_overflow_slots", out["overflow_slots"],
                tenant=self.tenant)
        reg.set("index_unreachable_items", out["unreachable_items"],
                tenant=self.tenant)
        return out

    # -- durability ---------------------------------------------------------

    def attach_wal(self, wal: Optional[walmod.WriteAheadLog]) -> None:
        """Log every subsequent mutation to ``wal`` (None detaches)."""
        with self._lock:
            self._wal = wal

    @property
    def wal(self) -> Optional[walmod.WriteAheadLog]:
        return self._wal

    def _log(self, payload: bytes) -> None:
        """Append one framed record (write-ahead: callers log, then apply).
        Callers hold the lock, so the WAL order is the apply order."""
        if self._wal is not None and not self._wal_mute:
            self._wal.append(payload)

    def replay(self, wal_path: str, start: int = 0) -> dict:
        """Apply the WAL records in ``wal_path`` from byte ``start``.

        The recovery half of the durability contract: duplicate-gid
        inserts (records already reflected in this index -- replay after a
        partial apply, or a full-log replay over a restored snapshot) are
        **dropped idempotently** and counted; deletes/seals/compactions
        are naturally idempotent.  Replay stops at the first bad frame
        (truncated tail, crc mismatch) and reports it -- everything before
        the damage is recovered, nothing after it is guessed at.

        Returns the ``read_wal`` report plus ``applied`` (records applied)
        and ``dropped_duplicates`` (gids skipped as already present).
        Never appends to the attached WAL (mutations here re-apply records
        the log already holds).
        """
        records, report = walmod.read_wal(wal_path, start=start)
        counts = self.apply_records(records)
        return dict(report, **counts)

    def apply_records(self, records) -> dict:
        """Apply already-decoded WAL records (the replay core).

        Factored out of :meth:`replay` so the warm standby
        (:class:`repro.serve.standby.WalStandby`) can tail a live
        primary's log incrementally -- same idempotence rules, no file
        re-reads.  Returns ``{"applied", "dropped_duplicates"}``.
        """
        out = {"applied": 0, "dropped_duplicates": 0}
        with self._lock:
            self._wal_mute = True
            try:
                for rec in records:
                    if rec.op == walmod.OP_INSERT:
                        gids = np.asarray(rec.gids, np.int32)
                        fresh = np.array(
                            [int(g) not in self._locator for g in
                             gids.tolist()], bool)
                        out["dropped_duplicates"] += int(
                            (~fresh).sum())
                        if fresh.any():
                            self.insert(
                                np.asarray(rec.embeddings,
                                           np.float32)[fresh],
                                gids=gids[fresh])
                    elif rec.op == walmod.OP_DELETE:
                        self.delete(rec.gids)
                    elif rec.op == walmod.OP_SEAL:
                        self._seal()
                    elif rec.op == walmod.OP_COMPACT:
                        self._maint_compact()
                    elif rec.op == walmod.OP_SET_REPLICATION:
                        self._maint_set_replication(rec.value)
                    elif rec.op in (walmod.OP_REGISTER,
                                    walmod.OP_LIFECYCLE):
                        pass               # registry-level; nothing to apply
                    out["applied"] += 1
            finally:
                self._wal_mute = False
        return out

    # -- SPMD placement -----------------------------------------------------

    def shard(self, mesh, axis: str = "serve") -> None:
        """Serve queries SPMD across ``mesh``: sealed segments round-robin
        over the ``axis`` mesh axis, delta + hash family replicated.

        Queries stay **bit-identical** to the single-device path over the
        same live items -- the same per-segment programs run, only placed
        differently, and the collective top-k merge preserves the total
        (distance, gid) order.  Mutations (insert/delete/seal/compact)
        remain host-coordinated; the device placement is re-snapshotted
        lazily on the first query after any mutation.

        A 1-device mesh is the supported degenerate case (same code path,
        no-op collectives), so one binary serves laptop and pod alike.
        """
        if axis not in mesh.axis_names:
            raise ValueError(
                f"mesh has axes {mesh.axis_names}, no {axis!r} axis")
        with self._lock:
            self._mesh = mesh
            self._shard_axis = axis
            self._placement = None

    def unshard(self) -> None:
        """Back to the single-device fan-out path (drops the placement)."""
        with self._lock:
            self._mesh = None
            self._shard_axis = None
            self._placement = None
            self._router = None

    def set_replication(self, replication) -> None:
        """Deprecated: use ``index.maintenance.set_replication(...)``."""
        warnings.warn(
            "SegmentedIndex.set_replication() is deprecated; set policy "
            "through the maintenance plane "
            "(index.maintenance.set_replication(...))",
            DeprecationWarning, stacklevel=2)
        self._maint_set_replication(replication)

    def _maint_set_replication(self, replication) -> None:
        """Set the sealed-segment replication policy.

        Args:
            replication: None (factor 1 everywhere -- replication off), an
                int (every sealed segment gets that factor, the
                ``static:k`` registry policy), or a positional sequence of
                per-sealed-segment factors (what the ``auto`` policy
                derives from ``ServingStats.shard_balance``).  Factors are
                clipped to the mesh size at placement-build time.

        Replicas are bit-identical, so this changes *where* queries run,
        never what they return (invariant 6); it takes effect on the next
        sharded query (placement rebuild + fresh router ledger) and is
        remembered across shard()/unshard().
        """
        with self._lock:
            if replication is not None and not isinstance(replication, int):
                replication = tuple(int(f) for f in replication)
            self._log(walmod.encode_set_replication(replication))
            self._replication = replication
            # force a full placement rebuild: the instance assignment (not
            # just the delta) changed shape
            self._sealed_version += 1
            self._version += 1

    def replication(self):
        """The current replication policy (as set, un-normalized)."""
        return self._replication

    def _current_placement(self):
        """The up-to-date SegmentPlacement, or None when unsharded sealed
        segments cannot stack (:func:`_stackable`).

        Sharded, the live sealed segments are placed over the mesh.
        Unsharded, every sealed segment is stacked on the one device, slot
        i = sealed segment i, for the stacked fan-out program; a segment
        whose items are all deleted keeps its slot (its candidates are all
        dead), so a delete never moves a slot.

        Sealed-set changes rebuild *through the previous placement*
        (``place_segments(..., prev=...)``): slots whose fingerprint is
        unchanged move zero bytes, so sealing one segment re-replicates
        O(that segment's bytes), not O(all sealed bytes) -- the actual vs
        full-restack transfer is published as the
        ``placement_replaced_bytes_total`` / ``placement_restack_bytes_total``
        counters.  The stack grows by capacity doubling, so a seal lands in
        headroom and reuses the compiled programs.  Sharded, delta-only
        mutations -- the streaming write hot path -- just re-replicate the
        one mutable segment; the unsharded program reads the delta itself.
        """
        if (self._placement is None
                or self._placement.version != self._sealed_version):
            if self._mesh is None:
                sealed = self.segments[:-1]
                if not _stackable(sealed):
                    self._placement = None
                    return None
                mesh, axis = _local_mesh(), "stack"
            else:
                sealed = [s for s in self.segments[:-1] if s.n_live > 0]
                mesh, axis = self._mesh, self._shard_axis
            self._placement = seg_placement.place_segments(
                sealed, self.delta, mesh, axis,
                self._sealed_version, replication=self._replication,
                prev=self._placement)
            self._delta_synced = self._version
            pl = self._placement
            reg = obs_metrics.registry()
            reg.inc("placement_replaced_bytes_total", pl.replaced_bytes,
                    tenant=self.tenant)
            reg.inc("placement_restack_bytes_total", pl.sealed_bytes,
                    tenant=self.tenant)
            reg.inc("placement_rebuilds_total",
                    tenant=self.tenant,
                    kind="diff" if pl.diffed else "full")
            # fresh ledger per placement: the instance assignment the
            # router balances over just changed.  layout() reports the
            # stripe width that actually serves (headroom included), so
            # the router's slot math matches the collective.
            self._router = (QueryRouter(pl.layout(), tenant=self.tenant)
                            if any(f > 1 for f in pl.replication)
                            else None)
        elif self._mesh is not None and self._delta_synced != self._version:
            self._placement = seg_placement.refresh_delta(self._placement,
                                                          self.delta)
            self._delta_synced = self._version
        return self._placement

    def refresh_placement(self) -> None:
        """Pre-pay the lazy placement rebuild off the query path.

        Maintenance workers call this after seal/compact so the device
        transfer (the diff) happens on the worker thread; the next query
        finds the placement already current.  Unsharded, it only updates a
        stack that a query has already built.
        """
        with self._lock:
            if self._mesh is not None or self._placement is not None:
                self._current_placement()

    def shard_layout(self) -> Optional[dict]:
        """JSON-able placement report (None when unsharded).

        Derived from host bookkeeping only -- calling this (reports,
        snapshots) never triggers the device-placement rebuild that a
        post-mutation query would.
        """
        with self._lock:
            if self._mesh is None:
                return None
            n_sealed = sum(1 for s in self.segments[:-1] if s.n_live > 0)
            return seg_placement.layout_dict(self._mesh, self._shard_axis,
                                             n_sealed,
                                             replication=self._replication)

    # -- mutation -----------------------------------------------------------

    def insert(self, embeddings, gids: Optional[Sequence[int]] = None
               ) -> np.ndarray:
        """Insert (m, N) embeddings; returns their global ids (int32).

        Splits across segment boundaries automatically; sealing happens when
        the delta fills.  Every device call is a fixed (insert_chunk, N)
        padded program.

        Validation is all-or-nothing: width-mismatched batches and batches
        containing NaN/Inf rows are rejected with a ``ValueError`` before
        any row lands (and before anything reaches the WAL) -- silently
        hashing garbage would poison the segment tables for every later
        query.  Rejected rows are counted in ``n_rejected`` (surfaced per
        tenant via ``ServingStats``).
        """
        emb = np.asarray(embeddings, np.float32)
        if emb.ndim != 2 or emb.shape[1] != self.cfg.n_dims:
            self.n_rejected += emb.shape[0] if emb.ndim == 2 else 1
            raise ValueError(
                f"expected embeddings of shape (m, {self.cfg.n_dims}), "
                f"got {emb.shape}")
        if not np.isfinite(emb).all():
            bad = int((~np.isfinite(emb).all(axis=1)).sum())
            self.n_rejected += emb.shape[0]
            raise ValueError(
                f"embeddings contain NaN/Inf in {bad} of {emb.shape[0]} "
                f"rows; rejecting the batch (nothing was inserted)")
        m = emb.shape[0]
        tr = obs_trace.tracer()
        with tr.locked(self._lock, "index.lock_wait", op="insert",
                       tenant=self.tenant), \
                tr.span("write.apply", tenant=self.tenant, op="insert",
                        rows=m):
            # gid allocation + uniqueness checks must sit inside the lock or
            # two concurrent inserts hand out the same id range
            if gids is None:
                out_gids = np.arange(self._next_gid, self._next_gid + m,
                                     dtype=np.int32)
            else:
                out_gids = np.asarray(list(gids), np.int32)
                if out_gids.shape != (m,):
                    raise ValueError("gids length must match embeddings")
                if m and out_gids.min() < 0:
                    raise ValueError("gids must be >= 0 (-1 is the "
                                     "empty-slot sentinel)")
                if np.unique(out_gids).size != m:
                    raise ValueError("duplicate gids within one insert")
                dup = [g for g in out_gids.tolist() if g in self._locator]
                if dup:
                    raise ValueError(f"gids already present: {dup[:5]}")
            self._next_gid = max(self._next_gid, int(out_gids.max()) + 1 if m else
                                 self._next_gid)
            # write-ahead: the record (with resolved gids) hits the log
            # before the first row hits a segment, so a crash mid-apply
            # replays to the same end state (duplicates drop by gid)
            if m:
                self._log(walmod.encode_insert(out_gids, emb))
            ins = _segment_insert_fn(self.cfg, self.insert_chunk)
            pos = 0
            while pos < m:
                seg = self.delta
                room = seg.capacity - seg.n_items
                if room == 0:
                    # implicit seal: not logged -- replaying the INSERT
                    # record reproduces it at the same fill point
                    self._seal()
                    continue
                take = min(m - pos, room, self.insert_chunk)
                chunk = np.zeros((self.insert_chunk, self.cfg.n_dims),
                                 np.float32)
                chunk[:take] = emb[pos:pos + take]
                seg.state = ins(seg.state, jnp.asarray(chunk),
                                jnp.int32(seg.n_items), jnp.int32(take))
                sl = jnp.arange(seg.n_items, seg.n_items + take)
                seg.gids = seg.gids.at[sl].set(
                    jnp.asarray(out_gids[pos:pos + take]))
                seg.live = seg.live.at[sl].set(True)
                seg.live_np()[seg.n_items:seg.n_items + take] = True
                si = len(self.segments) - 1
                for j in range(take):
                    self._locator[int(out_gids[pos + j])] = (si, seg.n_items + j)
                seg.n_items += take
                seg.n_live += take
                pos += take
            self._version += 1
        return out_gids

    def delete(self, gids: Sequence[int]) -> int:
        """Tombstone items by global id; returns how many were live."""
        req = np.asarray(gids).ravel().astype(np.int32)
        tr = obs_trace.tracer()
        with tr.locked(self._lock, "index.lock_wait", op="delete",
                       tenant=self.tenant), \
                tr.span("write.apply", tenant=self.tenant, op="delete",
                        rows=int(req.size)):
            if req.size:
                # logged as requested (not as applied): deletes are
                # idempotent, so replaying a delete of already-dead or
                # unknown gids is a no-op
                self._log(walmod.encode_delete(req))
                if self._compact_deletes is not None:
                    # a background compaction froze its input before this
                    # delete: ledger it so the swap re-applies it to the
                    # shadow copy (re-applying is idempotent)
                    self._compact_deletes.update(
                        int(g) for g in req.tolist())
            by_seg: dict = {}
            for g in req.tolist():
                loc = self._locator.get(int(g))
                if loc is None:
                    continue
                # a set per segment: duplicate gids in one call must not
                # double-decrement n_live for a single slot
                by_seg.setdefault(loc[0], set()).add(loc[1])
            n = 0
            sealed_hit = False
            delta_si = len(self.segments) - 1
            for si, slot_set in by_seg.items():
                slots = sorted(slot_set)
                seg = self.segments[si]
                live_np = seg.live_np()
                hits = int(live_np[slots].sum())
                if hits == 0:        # retried/idempotent delete: no change
                    continue
                seg.live = seg.live.at[jnp.asarray(slots, jnp.int32)].set(
                    False)
                live_np[slots] = False
                seg._live_key = None      # mask changed: re-fingerprint
                seg.n_live -= hits
                n += hits
                sealed_hit |= si != delta_si
            if n:
                self._version += 1
            if sealed_hit:
                self._tombstone_stack(req, delta_si)
            return n

    def _tombstone_stack(self, req: np.ndarray, delta_si: int) -> None:
        """Make a sealed-segment delete visible to the next query.

        A current unsharded stack takes one scatter of every requested
        gid's (slot, row) -- slot = segment index there -- with the gids
        that no sealed segment holds sent out of range, so the scatter has
        one shape per gid count whichever segments the gids fall in.
        Otherwise (sharded, or no current stack) the placement rebuilds
        on the next query."""
        pl = self._placement
        if (self._mesh is not None or pl is None
                or pl.version != self._sealed_version):
            self._sealed_version += 1
            return
        off = pl.n_dev * pl.per_dev            # out of range: dropped
        slots = np.full(req.shape, off, np.int32)
        rows = np.zeros(req.shape, np.int32)
        for j, g in enumerate(req.tolist()):
            loc = self._locator.get(int(g))
            if loc is not None and loc[0] < delta_si:
                slots[j], rows[j] = loc
        self._placement = seg_placement.clear_live(pl, slots, rows)

    def live_items(self) -> Tuple[np.ndarray, np.ndarray]:
        """Host copies of every live item: (embeddings (n_live, N),
        gids (n_live,)).  The one canonical live-set gather -- compaction
        and the stats recall proxy both read through it."""
        with self._lock:
            emb_parts, gid_parts = [], []
            for seg in self.segments:
                if seg.n_items == 0:
                    continue
                live = seg.live_np()[:seg.n_items]
                if not live.any():
                    continue
                # quantized sealed segments read their exact fp32 rows from
                # the survivor pool, so live_items (and through it compact
                # and the recall proxy) never sees quantization error
                db = (seg.pool if seg.pool is not None
                      else np.asarray(seg.state.db))
                emb_parts.append(db[:seg.n_items][live])
                gid_parts.append(np.asarray(seg.gids)[:seg.n_items][live])
        if not emb_parts:
            return (np.zeros((0, self.cfg.n_dims), np.float32),
                    np.zeros((0,), np.int32))
        return np.concatenate(emb_parts), np.concatenate(gid_parts)

    def compact(self) -> int:
        """Deprecated: use ``index.maintenance.compact()``."""
        warnings.warn(
            "SegmentedIndex.compact() is deprecated; compact through the "
            "maintenance plane (index.maintenance.compact())",
            DeprecationWarning, stacklevel=2)
        return self._maint_compact()

    def _maint_compact(self) -> int:
        """Rebuild live items into freshly-packed segments (tombstones and
        bucket-overflow shadows are dropped; gids are preserved).  Returns
        the number of segments after compaction.

        Three phases so a background worker can run the expensive rebuild
        off the query path:

        1. **freeze** (locked): log COMPACT, force-seal the delta so the
           input prefix is immutable, open the delete ledger;
        2. **build** (lock-free): gather the frozen prefix's live items
           from host copies and insert them into a *shadow* index sharing
           this one's hash family -- queries and inserts keep running
           against the old segments the whole time;
        3. **swap** (locked): adopt the shadow's segments, splice back any
           segments created after the freeze, rebuild the locator, and
           re-apply ledgered deletes idempotently.

        A sequential caller (or WAL replay) sees the classic inline
        behaviour: freeze-build-swap back to back under the reentrant
        lock.  A *live* compaction with concurrent inserts force-seals the
        shadow's partial delta at swap, so the segment *structure* can
        differ from what a sequential replay of the same WAL produces --
        invariant 3 makes that divergence invisible to query results (the
        same guarantee the replayed-SEAL note above leans on).
        """
        frozen_n, frozen = self._compact_freeze()
        try:
            shadow = self._compact_build(frozen)
        except BaseException:
            with self._lock:
                self._compact_deletes = None     # close the ledger
            raise
        return self._compact_swap(frozen_n, shadow)

    def _compact_freeze(self) -> Tuple[int, List[Segment]]:
        """Phase 1 (locked): make the compaction input immutable."""
        with self._lock:
            self._log(walmod.encode_compact())
            # crash point: COMPACT is durable-framed, nothing applied yet
            faults.fire("compact.freeze")
            self._seal()                 # no-op when the delta is empty
            frozen = list(self.segments[:-1])
            self._compact_deletes = set()
            return len(frozen), frozen

    def _compact_build(self, frozen: List[Segment]) -> "SegmentedIndex":
        """Phase 2 (lock-free): build the shadow index from the frozen
        prefix.  Frozen segments are sealed, so concurrent mutations can
        only flip live masks -- every such delete is in the ledger and
        re-applied at swap, so a torn read here cannot lose it."""
        emb_parts, gid_parts = [], []
        for seg in frozen:
            if seg.n_items == 0:
                continue
            live = np.asarray(seg.live)[:seg.n_items]   # an immutable
            if not live.any():                           # snapshot: no lock
                continue
            db = (seg.pool if seg.pool is not None
                  else np.asarray(seg.state.db))
            emb_parts.append(np.asarray(db)[:seg.n_items][live])
            gid_parts.append(np.asarray(seg.gids)[:seg.n_items][live])
        shadow = SegmentedIndex(
            self.cfg, segment_capacity=self.segment_capacity,
            insert_chunk=self.insert_chunk, backend=self.backend,
            tenant=self.tenant, precision=self.precision,
            survivor_k=self.survivor_k, family=self.family)
        if emb_parts:
            emb = np.concatenate(emb_parts)
            gid = np.concatenate(gid_parts)
            order = np.argsort(gid, kind="stable")   # insertion order
            shadow.insert(emb[order], gids=gid[order])
        return shadow

    def _compact_swap(self, frozen_n: int, shadow: "SegmentedIndex") -> int:
        """Phase 3 (locked): atomically publish the shadow."""
        with self._lock, obs_trace.tracer().span(
                "compact", tenant=self.tenant, n_live=self.n_live,
                segments_before=len(self.segments)):
            # crash point: shadow fully built, swap not yet applied
            faults.fire("compact.swap")
            after = self.segments[frozen_n:]
            if len(after) == 1 and after[0].n_items == 0:
                # quiet window (also the only shape sequential replay ever
                # sees): adopt the shadow wholesale, open delta included
                self.segments = shadow.segments
                self._locator = shadow._locator
            else:
                # inserts landed during the build: seal the shadow's
                # partial delta and splice the post-freeze segments (which
                # end with the current delta) behind it
                shadow._seal()
                self.segments = ([s for s in shadow.segments[:-1]
                                  if s.n_items > 0] + after)
                self._locator = {}
                for si, seg in enumerate(self.segments):
                    gid_arr = np.asarray(seg.gids)[:seg.n_items]
                    for slot, g in enumerate(gid_arr.tolist()):
                        if g >= 0:
                            self._locator[int(g)] = (si, slot)
            pending, self._compact_deletes = self._compact_deletes, None
            for g in pending or ():
                loc = self._locator.get(int(g))
                if loc is None:
                    continue
                seg = self.segments[loc[0]]
                if seg.live_np()[loc[1]]:
                    seg.live = seg.live.at[loc[1]].set(False)
                    seg.live_np()[loc[1]] = False
                    seg.n_live -= 1
                    seg._live_key = None
            self._version += 1
            self._sealed_version += 1
            self._publish_store_metrics()
            return len(self.segments)

    # -- query --------------------------------------------------------------

    def query(self, queries, k: int, n_probes: int = 1
              ) -> Tuple[Array, Array]:
        """Cross-segment k-NN: (nq, N) -> (gids (nq, k), dists (nq, k)).

        Unsharded, one program (:func:`_stacked_query_fn`) queries the
        stacked sealed segments and the delta and merges their top-k with
        ``ops.merge_topk``; sealed segments that cannot stack fall back to
        one program per live segment and a separate merge.  After
        ``shard(mesh)`` the fan-out runs SPMD instead (one collective
        program over the mesh).  All three answer bit-identically.

        Tracing (sampled traces only): ``index.lock_wait``, then
        ``query.segments`` (the fan-out's dispatch) or ``query.collective``
        (the sharded program), then ``fanout.wait`` (the first copy of the
        result to the host) and ``fanout.telemetry``.
        """
        q = jnp.asarray(queries, jnp.float32)
        if self.precision != "fp32":
            # quantized tiers run the survivor-rerank engine
            return self._query_quantized(q, k, n_probes)
        fan = self._fan_out(q, k, n_probes, (int(q.shape[0]), k, n_probes))
        if fan is None:
            return (jnp.full((q.shape[0], k), -1, jnp.int32),
                    jnp.full((q.shape[0], k), jnp.inf, jnp.float32))
        if self._on_fanout is not None:
            self._fanout_telemetry(fan.gids, fan)
        return fan.gids, fan.dists

    def _fan_out(self, q: Array, width: int, n_probes: int, shape: tuple
                 ) -> Optional[_Fanout]:
        """Every live segment's top-``width`` for ``q``, merged (None when
        no segment holds a live item).

        The index lock is held for the dispatch only.  Unsharded, that is
        one call of the stacked program; sealed segments that cannot stack
        instead dispatch one program per live segment under the lock, and
        their two concatenates and merge after it.  Sealed quantized
        segments score in code space (``width`` is then the survivor
        width); fp32 segments score exactly.  Every batch counts in
        ``serve_fanout_dedup_total{where}``, unsharded ones in
        ``serve_fanout_batches_total{path}`` too.
        """
        tr = obs_trace.tracer()
        quantized = self.precision != "fp32"
        # the fallback's dispatch span opens under the lock and closes
        # after the merge, which runs once the lock is released
        with contextlib.ExitStack() as dispatch_span:
            with tr.locked(self._lock, "index.lock_wait", op="query",
                           tenant=self.tenant):
                self.query_shapes.add(shape)
                if self._mesh is not None:
                    pl = self._current_placement()
                    # replica selection per micro-batch: the router
                    # activates one instance per sealed segment so
                    # replicated devices alternate; without a router every
                    # instance answers and the collective fan-in dedups by
                    # gid -- both bit-identical
                    plan = self._router.route() if self._router else None
                    with tr.span("query.collective", tenant=self.tenant,
                                 devices=pl.n_dev, per_dev=pl.per_dev):
                        g, d = distributed.query_segments_sharded(
                            pl, self.cfg, q, width, n_probes=n_probes,
                            backend=self.backend,
                            active=None if plan is None else plan.active,
                            quantized=quantized)
                    self._count_fanout(None)
                    return _Fanout(g, d, plan=plan)
                if self.n_live == 0:
                    return None
                pl = self._current_placement()
                if pl is not None:
                    fn = _stacked_query_fn(
                        self.cfg, width, n_probes, self.backend,
                        pl.n_sealed > 0 and self.segments[0].scale is not None)
                    with tr.span("query.segments", tenant=self.tenant,
                                 segments=pl.n_sealed + 1, programs=1):
                        g, d, counts = fn(
                            pl.sealed_state, pl.sealed_gids, pl.sealed_live,
                            pl.sealed_scales, np.int32(pl.n_sealed),
                            self.delta.state, self.delta.gids,
                            self.delta.live, q)
                    self._count_fanout("stacked")
                    seg_ids = list(range(pl.n_sealed))
                    return _Fanout(g, d, counts,
                                   seg_ids + [len(self.segments) - 1])
                seg_ids = [i for i, s in enumerate(self.segments)
                           if s.n_live > 0]
                exact = _segment_query_fn(self.cfg, width, n_probes,
                                          self.backend)
                codes = (_quantized_segment_query_fn(
                    self.cfg, width, n_probes, self.backend)
                    if quantized else None)
                # programs: one per segment, then two concatenates and the
                # merge (the merge alone for a single segment)
                dispatch_span.enter_context(tr.span(
                    "query.segments", tenant=self.tenant,
                    segments=len(seg_ids),
                    programs=len(seg_ids) + (3 if len(seg_ids) > 1 else 1)))
                shards = []
                for i in seg_ids:
                    seg = self.segments[i]
                    if codes is not None and seg.scale is not None:
                        shards.append(codes(seg.state, q, seg.live, seg.gids,
                                            seg.scale))
                    else:   # fp32 tiers, and the delta of a quantized one
                        shards.append(exact(seg.state, q, seg.live,
                                            seg.gids))
            self._count_fanout("per_segment")
            # a single segment is already top-k; it is merged only to
            # normalise tie order so results don't depend on the count
            g_all = jnp.concatenate([sg for sg, _ in shards], axis=1)
            d_all = jnp.concatenate([sd for _, sd in shards], axis=1)
            g, d = _merged(d_all, g_all, width)
        counts = None
        if self._on_fanout is not None:
            counts = (g_all >= 0).reshape(
                q.shape[0], len(seg_ids), width).sum(axis=(0, 2))
        return _Fanout(g, d, counts, seg_ids)

    def _count_fanout(self, path: Optional[str]) -> None:
        """Count a batch by where its segments dedup their candidates
        and, unsharded, by its fan-out ``path``."""
        reg = obs_metrics.registry()
        reg.inc("serve_fanout_dedup_total", tenant=self.tenant,
                where=ops.dedup_site(self.segment_capacity, self.cfg.n_dims,
                                     self.backend))
        if path is not None:
            reg.inc("serve_fanout_batches_total", tenant=self.tenant,
                    path=path)

    def _query_quantized(self, q: Array, k: int, n_probes: int
                         ) -> Tuple[Array, Array]:
        """Two-stage quantized query: cheap code-space candidate scoring to
        a survivor pool of ``m >= k``, then an exact fp32 rescore of just
        those survivors.

        Stage 1 runs the same fan-out as :meth:`query` but asks each
        segment for the top ``m = survivor_width(k, survivor_k, C)``
        candidates scored against the int8/bf16 codes (the delta, still
        fp32, is scored exactly).  Stage 2 gathers the survivors' exact
        rows from the host-side pools and reranks under the same total
        (distance, gid) order, so any survivor set containing the true
        top-k yields exactly the fp32 answer.  Sharded and unsharded paths
        agree because the rerank is a pure function of the survivor set.
        The host waits for stage 1 in ``fanout.wait``; stage 2 runs under
        the ``survivor.gather`` and ``survivor.rerank`` spans.
        """
        kq = quantize.survivor_width(
            k, self.survivor_k,
            self.cfg.n_tables * n_probes * self.cfg.bucket_capacity)
        fan = self._fan_out(q, kq, n_probes, (int(q.shape[0]), k, n_probes))
        if fan is None:
            return (jnp.full((q.shape[0], k), -1, jnp.int32),
                    jnp.full((q.shape[0], k), jnp.inf, jnp.float32))
        # survivor rescore: host-gather the exact rows, rerank on device
        tr = obs_trace.tracer()
        with tr.span("fanout.wait", tenant=self.tenant):
            g_np = np.asarray(fan.gids).copy()
        with tr.span("survivor.gather", tenant=self.tenant,
                     rows=int(q.shape[0]), width=kq):
            rows = self._survivor_rows(g_np)
        with tr.span("survivor.rerank", tenant=self.tenant, width=kq):
            g, d = quantize.rerank_survivors(q, jnp.asarray(rows),
                                             jnp.asarray(g_np), k,
                                             p=self.cfg.p)
        if self._on_fanout is not None:
            self._fanout_telemetry(g)
        if g_np.size:
            obs_metrics.registry().set("rerank_survivor_frac",
                                       float((g_np >= 0).mean()),
                                       tenant=self.tenant)
        return g, d

    def _survivor_rows(self, g_np: np.ndarray) -> np.ndarray:
        """Exact fp32 rows for a (nq, m) survivor-gid matrix.

        Sealed quantized segments serve from their host pools (zero device
        traffic); fp32 segments (the delta, or every segment on a tenant
        that mixed seals before a precision change) fetch their device db
        once per batch.  Gids the locator no longer knows (a concurrent
        compact between merge and gather) are masked to -1 in-place so the
        rerank drops them instead of scoring a zero row.
        """
        nq, m = g_np.shape
        rows = np.zeros((nq, m, self.cfg.n_dims), np.float32)
        with obs_trace.tracer().locked(self._lock, "index.lock_wait",
                                       op="gather", tenant=self.tenant):
            host_db: dict = {}
            for qi in range(nq):
                for j in range(m):
                    gid = int(g_np[qi, j])
                    if gid < 0:
                        continue
                    loc = self._locator.get(gid)
                    if loc is None:
                        g_np[qi, j] = -1
                        continue
                    si, slot = loc
                    seg = self.segments[si]
                    if seg.pool is not None:
                        rows[qi, j] = seg.pool[slot]
                    else:
                        db = host_db.get(si)
                        if db is None:
                            db = np.asarray(seg.state.db)
                            host_db[si] = db
                        rows[qi, j] = db[slot]
        return rows

    def _fanout_telemetry(self, g: Array,
                          fan: Optional[_Fanout] = None) -> None:
        """Attribute one merged top-k back to segments/devices and feed the
        ``on_fanout`` hook (ServingStats.record_fanout signature).

        Wins come from the merged gids via the locator (gids are globally
        unique, so the winning segment is unambiguous); candidate counts
        are the valid rows each unsharded segment offered the merge
        (``fan.counts``, one device array copied once); device wins map
        segments through the live placement's assignment (delta -> rank 0,
        matching the collective program).  When a router ``fan.plan``
        routed this batch, the win goes to the replica that actually
        answered and the hook additionally receives the plan's per-device
        instance load (4th argument -- only ever passed on routed batches,
        so factor-1 deployments keep the 3-argument hook contract).  Given
        ``fan``, the first copy of its result waits for the fan-out program
        in ``fanout.wait``; the rest runs under ``fanout.telemetry``.
        """
        tr = obs_trace.tracer()
        counts = None
        if fan is not None:
            with tr.span("fanout.wait", tenant=self.tenant):
                g_np = np.asarray(g)
                if fan.counts is not None:
                    counts = np.asarray(fan.counts)
        plan = fan.plan if fan is not None else None
        with tr.span("fanout.telemetry", tenant=self.tenant,
                     shards=len(fan.seg_ids) if counts is not None else 0):
            if fan is None:
                g_np = np.asarray(g)
            with tr.locked(self._lock, "index.lock_wait", op="telemetry",
                           tenant=self.tenant):
                n_segs = len(self.segments)
                wins = [0] * n_segs
                for gid in g_np.ravel().tolist():
                    if gid < 0:
                        continue
                    loc = self._locator.get(int(gid))
                    if loc is not None:
                        wins[loc[0]] += 1
                cands = None
                if counts is not None:
                    cands = [0] * n_segs
                    for si, c in zip(fan.seg_ids, counts.tolist()):
                        if si < n_segs:   # a concurrent compact may have
                            # shrunk the list
                            cands[si] = c
                dev_wins = None
                if self._mesh is not None and self._placement is not None:
                    pl = self._placement
                    sealed_pos = [i for i, s in
                                  enumerate(self.segments[:-1])
                                  if s.n_live > 0]
                    dev_of = {n_segs - 1: 0}   # delta contributes on rank 0
                    if plan is not None:
                        # routed batch: attribute to the chosen replica
                        for fi, dev in plan.dev_of.items():
                            if fi < len(sealed_pos):
                                dev_of[sealed_pos[fi]] = dev
                    else:
                        for dev, block in enumerate(pl.assignment):
                            for fi in block:
                                # placement may lag a concurrent mutation;
                                # replicas (instance duplicates) attribute
                                # to the first holder
                                if fi < len(sealed_pos):
                                    dev_of.setdefault(sealed_pos[fi], dev)
                    dev_wins = [0] * pl.n_dev
                    for si, w in enumerate(wins):
                        if w:
                            dev_wins[dev_of.get(si, 0)] += w
            if plan is not None:
                self._on_fanout(wins, dev_wins, cands,
                                plan.per_device_active)
            else:
                self._on_fanout(wins, dev_wins, cands)

    def occupancy(self) -> List[dict]:
        return [s.occupancy() for s in self.segments]


def _merged(dists: Array, gids: Array, k: int) -> Tuple[Array, Array]:
    d, g = ops.merge_topk(dists, gids, k)
    return g, d
