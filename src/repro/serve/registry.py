"""Multi-tenant servable registry: named endpoints over segmented indexes.

saxml-style separation of concerns: a **ServableSpec** is the declarative
unit of deployment (hash-family knobs p/r/L/K, the function->R^N embedder,
segment sizing, batching palette); a **Servable** is the live instance
(segmented index + micro-batcher + stats); the **ServableRegistry** maps
names to servables and owns snapshot/restore.

Per-tenant configs are the point: the paper's family covers p in {1, 2}
and all three embedding constructions (truncated orthonormal basis,
Sec. 3.1 / Eq. 3; (Q)MC node sampling, Sec. 3.2 / Eq. 6; clipped quantile
functions for Wasserstein distance over distributions, Sec. 2.2 /
Remark 1), and "Efficient ANN Search for Multiple Weighted l_p Distance
Functions" needs *several* metrics live at once -- so each tenant picks
its own and the admission front end stays shared.

Embedder resolution is registry-driven: ``ServableSpec.embedder`` names a
:mod:`repro.embedders` implementation and ``ServableSpec.embedder_params``
carries its JSON-able construction kwargs -- no embedder-specific branches
live here, and a new embedder registers without touching the serve layer.

Snapshots go through checkpoint/ (atomic rename, keep-last-k, manifest) --
arrays in the pytree payload, host bookkeeping (specs, fill counters, gid
maps are reconstructed from the gid arrays; the embedder-params dict) in
the manifest's ``extra`` dict.  Restore tolerates unknown spec keys, so a
snapshot written by a newer build loads on an older one.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import warnings
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import checkpoint as ckpt
from ..core.index import IndexConfig, LSHIndexState
from ..embedders import embedder_names, make_embedder
from ..kernels import dispatch, quantize
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from . import faults, wal as walmod
from .batcher import MicroBatcher
from .maintenance import ServableMaintenance
from .segments import Segment, SegmentedIndex
from .stats import ServingStats, occupancy_report

# NOTE: deliberately not snapshotted into a module constant -- specs are
# validated against the *live* embedder registry, so an embedder registered
# after this module imports (the @register_embedder extension point) is
# immediately deployable.


@dataclasses.dataclass(frozen=True)
class ServableSpec:
    """Declarative tenant config (everything needed to rebuild the endpoint)."""

    name: str
    n_dims: int = 64
    p: float = 2.0                 # l_p of the p-stable family (1 or 2)
    r: float = 1.0                 # quantisation width (Eq. 5)
    n_tables: int = 8
    n_hashes: int = 4
    log2_buckets: int = 10
    bucket_capacity: int = 32
    embedder: str = "basis"        # a repro.embedders name: "basis" (Eq. 3)
                                   # | "qmc" (Eq. 6) | "wasserstein" (Rem. 1)
    # embedder-specific construction kwargs (JSON-able; rides the snapshot
    # manifest's ``extra`` dict) -- see each embedder's ``params()``
    embedder_params: Optional[Dict[str, Any]] = None
    volume: float = 1.0            # domain volume for the MC embedding
    segment_capacity: int = 1024
    insert_chunk: int = 256
    chunk_sizes: Tuple[int, ...] = (8, 32, 128)
    max_delay_ms: float = 5.0
    seed: int = 0
    # SPMD placement: mesh axis to shard sealed segments over (None =
    # single-device).  Applied iff the registry was built with a mesh
    # carrying this axis -- the spec declares intent, the registry owns
    # the hardware.
    shard_axis: Optional[str] = None
    # hot-segment replication policy (sharded tenants only):
    #   "none"     -- factor 1 everywhere (the classic placement);
    #   "static:k" -- every sealed segment on k devices;
    #   "auto"     -- factors re-derived from ServingStats.shard_balance
    #                 merge-win skew at every compact() (the telemetry ->
    #                 placement loop; see serve/router.auto_factors).
    replication: str = "none"
    # Sealed-segment storage precision tier: "fp32" (bit-exact, the
    # default) | "bf16" | "int8" (bounded-loss, survivor-reranked --
    # invariant 10).  register() resolves it ONCE through
    # ``dispatch.store_dtype`` (where $REPRO_STORE_DTYPE wins), so the WAL
    # REGISTER record and every snapshot carry the tier that actually
    # served; recovery never re-reads the env.
    precision: str = "fp32"
    # survivor-rerank pool width m (0 = the default 4*k; see
    # ``kernels.quantize.survivor_width``) -- quantized tiers only
    survivor_k: int = 0

    def __post_init__(self):
        if self.embedder not in embedder_names():
            raise ValueError(
                f"embedder must be one of {embedder_names()}")
        if self.precision not in dispatch.STORE_DTYPES:
            raise ValueError(
                f"precision must be one of {dispatch.STORE_DTYPES}, "
                f"got {self.precision!r}")
        self.replication_policy()    # fail fast on a malformed policy

    def replication_policy(self):
        """The replication field parsed: None | int k | the string "auto"."""
        rep = self.replication
        if rep in ("none", None):
            return None
        if rep == "auto":
            return "auto"
        if isinstance(rep, str) and rep.startswith("static:"):
            try:
                k = int(rep.split(":", 1)[1])
            except ValueError:
                k = 0
            if k >= 1:
                return k
        raise ValueError(
            f"replication must be 'none', 'static:k' or 'auto', got {rep!r}")

    def index_config(self) -> IndexConfig:
        return IndexConfig(n_dims=self.n_dims, n_tables=self.n_tables,
                           n_hashes=self.n_hashes,
                           log2_buckets=self.log2_buckets,
                           bucket_capacity=self.bucket_capacity,
                           r=self.r, p=self.p)


def _spec_from_manifest(raw: Dict[str, Any]) -> ServableSpec:
    """Rebuild a ServableSpec from a snapshot manifest dict.

    Unknown keys are dropped (a snapshot written by a newer build with extra
    spec fields still restores here); JSON-decoded lists are re-tupled where
    the dataclass wants tuples.
    """
    known = {f.name for f in dataclasses.fields(ServableSpec)}
    kw = {k: v for k, v in raw.items() if k in known}
    if "chunk_sizes" in kw:
        kw["chunk_sizes"] = tuple(kw["chunk_sizes"])
    return ServableSpec(**kw)


class Servable:
    """A live endpoint: embedder + segmented index + batcher + stats.

    Args:
        spec: the declarative tenant config.
        backend: re-rank tail backend override (see
            ``kernels.dispatch.query_backend``).
        mesh: serve mesh; when it carries ``spec.shard_axis`` the tenant's
            index is sharded over it (``SegmentedIndex.shard``).
    """

    def __init__(self, spec: ServableSpec, *, backend: Optional[str] = None,
                 mesh=None):
        self.spec = spec
        self.embedder = make_embedder(spec.embedder, n_dims=spec.n_dims,
                                      p=spec.p, volume=spec.volume,
                                      params=spec.embedder_params)
        self.stats = ServingStats(tenant=spec.name)
        self.index = SegmentedIndex(spec.index_config(),
                                    segment_capacity=spec.segment_capacity,
                                    insert_chunk=spec.insert_chunk,
                                    key=jax.random.PRNGKey(spec.seed),
                                    backend=backend,
                                    on_fanout=self.stats.record_fanout,
                                    tenant=spec.name,
                                    precision=spec.precision,
                                    survivor_k=spec.survivor_k)
        # the tenant's maintenance-plane handle: seal/compact/replication
        # re-placement live here (the MaintenancePool is the production
        # caller); Servable.compact survives as a deprecated shim
        self.maintenance = ServableMaintenance(self)
        if spec.shard_axis is not None and mesh is not None \
                and spec.shard_axis in mesh.axis_names:
            self.index.shard(mesh, spec.shard_axis)
            policy = spec.replication_policy()
            if isinstance(policy, int):
                self.index.maintenance.set_replication(policy)
            # "auto" starts unreplicated and re-places at compact() time,
            # once shard_balance has seen real traffic
        self.batcher = MicroBatcher(self._raw_query,
                                    chunk_sizes=spec.chunk_sizes,
                                    max_delay_ms=spec.max_delay_ms,
                                    on_batch=self.stats.record_batch,
                                    tenant=spec.name)

    # -- data plane ---------------------------------------------------------

    def embed(self, fvals) -> jnp.ndarray:
        """Function data (B, in_width) -> (B, n_dims) embeddings under the
        tenant's construction.

        ``in_width`` is ``len(self.nodes())`` for node-sampled embedders and
        the raw draw count for distribution embedders.  Batched through the
        fixed ingest-chunk palette (``FunctionEmbedder.embed_batched``) with
        kernel-backend dispatch, so sustained ingest compiles one embed
        program per chunk, like queries do.
        """
        fvals = np.asarray(fvals)
        with obs_trace.tracer().span("embed", tenant=self.spec.name,
                                     rows=int(fvals.shape[0]),
                                     embedder=self.spec.embedder):
            return self.embedder.embed_batched(
                fvals, batch_size=max(self.spec.chunk_sizes))

    def nodes(self) -> np.ndarray:
        """Where to sample functions for ``embed`` (tenant's shared node
        set; quantile levels for distribution tenants)."""
        return self.embedder.nodes()

    def insert(self, embeddings, gids=None) -> np.ndarray:
        before = self.index.n_rejected
        try:
            out = self.index.insert(embeddings, gids=gids)
        except ValueError:
            # validation rejections (NaN/Inf rows, width mismatch) are an
            # operator signal: count them per tenant, then let the caller
            # see the error -- nothing was inserted
            self.stats.record_rejected(self.index.n_rejected - before)
            raise
        self.stats.record_insert(len(out))
        return out

    def delete(self, gids) -> int:
        n = self.index.delete(gids)
        self.stats.record_delete(n)
        return n

    def compact(self) -> int:
        """Deprecated: use ``servable.maintenance.compact()`` (which also
        owns the ``auto``-replication re-placement epoch)."""
        warnings.warn(
            "Servable.compact() is deprecated; compact through the "
            "maintenance plane (servable.maintenance.compact())",
            DeprecationWarning, stacklevel=2)
        return self.maintenance.compact()

    def _raw_query(self, queries, k: int, n_probes: int):
        g, d = self.index.query(queries, k, n_probes=n_probes)
        with obs_trace.tracer().span("result.sync", tenant=self.spec.name):
            return np.asarray(g), np.asarray(d)

    def submit_query(self, queries, k: int, n_probes: int = 1):
        """Admission-queue path: returns a Future of (gids, dists)."""
        return self.batcher.submit(queries, k, n_probes)

    def query(self, queries, k: int, n_probes: int = 1):
        """Synchronous path (still batched/padded through the admission
        queue, so it shares the same compiled shapes as async traffic)."""
        return self.batcher.query(queries, k, n_probes)

    def report(self) -> dict:
        return {"spec": dataclasses.asdict(self.spec),
                "embedder": self.embedder.describe(),
                "stats": self.stats.snapshot(),
                "batcher": {"unique_shapes": self.batcher.unique_shapes(),
                            "n_batches": self.batcher.n_batches,
                            "n_requests": self.batcher.n_requests},
                "occupancy": occupancy_report(self.index),
                # read back here, not at seal (publishes the bucket gauges
                # before the metrics summary below is taken)
                "buckets": self.index.bucket_overflow(),
                "shard_layout": self.index.shard_layout(),
                # which kernel/query/hash/embed paths this process resolves
                # to right now (env overrides included)
                "dispatch": dispatch.describe(),
                # the unified registry's view of this tenant (counters,
                # gauges, histogram summaries) -- same names the exporter
                # emits, so in-process reports and out-of-process scrapes
                # can be cross-checked
                "metrics": obs_metrics.registry().summary(
                    tenant=self.spec.name)}


class ServableRegistry:
    """Name -> Servable map with snapshot/restore through checkpoint/.

    Args:
        backend: re-rank tail backend for every tenant (see
            ``kernels.dispatch.query_backend``).
        mesh: optional serve mesh handed to every tenant whose spec asks
            for sharding (``ServableSpec.shard_axis``); tenants without a
            shard axis stay single-device on the same registry.
        wal_dir: when set, every tenant gets a write-ahead delta log at
            ``<wal_dir>/<name>.wal`` -- all mutations are framed and
            appended before being applied, and ``recover`` replays
            ``latest snapshot + WAL tail`` after a crash
            (docs/architecture.md, invariant 7).
        fsync_every: WAL group-commit interval (see
            ``wal.WriteAheadLog``); default from ``REPRO_WAL_FSYNC_EVERY``.
    """

    def __init__(self, *, backend: Optional[str] = None, mesh=None,
                 wal_dir: Optional[str] = None,
                 fsync_every: Optional[int] = None):
        self._servables: Dict[str, Servable] = {}
        self._backend = backend
        self._mesh = mesh
        self._wal_dir = wal_dir
        self._fsync_every = fsync_every
        self._lock = threading.Lock()

    def _wal_path(self, name: str) -> Optional[str]:
        return (os.path.join(self._wal_dir, f"{name}.wal")
                if self._wal_dir else None)

    def register(self, spec: ServableSpec) -> Servable:
        # resolve the precision tier exactly once, here: the env override
        # ($REPRO_STORE_DTYPE) is applied at registration and the RESOLVED
        # value is what rides the WAL REGISTER record and every snapshot,
        # so recovery rebuilds the tier that actually served
        resolved = dispatch.store_dtype(spec.precision)
        if resolved != spec.precision:
            spec = dataclasses.replace(spec, precision=resolved)
        with self._lock:
            sv = self._register(spec)
            wpath = self._wal_path(spec.name)
            if wpath is not None:
                # a fresh tenant's log starts with its spec, so WAL-only
                # recovery (no snapshot yet) can rebuild the endpoint
                wal = walmod.WriteAheadLog(wpath,
                                           fsync_every=self._fsync_every)
                wal.append(walmod.encode_register(
                    dataclasses.asdict(spec)))
                wal.sync()
                sv.index.attach_wal(wal)
            return sv

    def _register(self, spec: ServableSpec) -> Servable:
        """Build + record the servable (callers hold the lock; no WAL)."""
        if spec.name in self._servables:
            raise ValueError(f"servable {spec.name!r} already registered")
        sv = Servable(spec, backend=self._backend, mesh=self._mesh)
        self._servables[spec.name] = sv
        return sv

    def adopt(self, spec: ServableSpec) -> Servable:
        """Register a tenant from an already-resolved spec, verbatim.

        The warm-standby path (:class:`repro.serve.standby.WalStandby`):
        the spec came off another process's WAL REGISTER record, where the
        precision tier was already resolved and the record already logged
        -- so unlike :meth:`register` this neither re-resolves
        ``$REPRO_STORE_DTYPE`` nor writes to any WAL (the standby replays
        a foreign log; it must not append to it)."""
        with self._lock:
            return self._register(spec)

    def get(self, name: str) -> Servable:
        try:
            return self._servables[name]
        except KeyError:
            raise KeyError(f"no servable {name!r}; have {self.names()}")

    def log_lifecycle(self, name: str, state: str) -> None:
        """Append a LIFECYCLE audit record to the tenant's WAL and count
        the transition (``tenant_lifecycle_transitions_total``).

        No-op on the index at replay time; the one state recovery *acts*
        on is a trailing "unloaded", which marks the tenant as cleanly
        detached (``recover`` skips it instead of resurrecting it).
        Fsync'd immediately -- lifecycle transitions are rare and an
        unloaded tenant must not come back because its record was still
        in the group-commit window when the process died."""
        obs_metrics.registry().inc("tenant_lifecycle_transitions_total",
                                   tenant=name, state=state)
        sv = self._servables.get(name)
        wal = sv.index.wal if sv is not None else None
        if wal is not None:
            wal.append(walmod.encode_lifecycle(state))
            wal.sync()

    def unregister(self, name: str) -> None:
        with self._lock:
            sv = self._servables.pop(name, None)
            if sv is not None:
                sv.batcher.stop()

    def names(self) -> List[str]:
        return sorted(self._servables)

    def report(self) -> dict:
        return {name: sv.report() for name, sv in sorted(
            self._servables.items())}

    # -- persistence --------------------------------------------------------

    def snapshot(self, root: str, step: int = 0, keep: int = 3) -> str:
        """Atomic per-tenant checkpoints under ``root/<name>/step_*``.

        WAL-backed tenants additionally fsync their log and record the
        durable byte offset (``wal_offset``) in the manifest -- the point
        ``recover`` replays the tail from.  The offset is captured under
        the same index lock as the array payload, so snapshot + tail is
        exactly one consistent history.
        """
        for name, sv in self._servables.items():
            idx = sv.index
            # per-tenant crash point: a kill here leaves some tenants
            # snapshotted at `step` and others not -- recovery must replay
            # a longer WAL tail for the others, and does
            faults.fire("snapshot")
            # capture under the index lock so the array payload and the
            # host-side counters describe the same instant (a concurrent
            # insert must not land between them)
            with idx._lock:
                # quantized sealed segments additionally persist their
                # dequant scale and the fp32 survivor pool -- the pool IS
                # canonical exact state under a lossy tier, so a restored
                # tenant reranks/compacts byte-for-byte like the original
                tree = {"segments": [
                    dict({"state": seg.state, "gids": seg.gids,
                          "live": seg.live},
                         **({"scale": seg.scale, "pool": seg.pool}
                            if seg.scale is not None else {}))
                    for seg in idx.segments]}
                extra = {
                    "spec": dataclasses.asdict(sv.spec),
                    "next_gid": idx._next_gid,
                    "segments": [{"n_items": s.n_items, "n_live": s.n_live,
                                  "sealed": s.sealed,
                                  "quantized": s.scale is not None}
                                 for s in idx.segments],
                    # observability only: restore re-derives placement from
                    # spec.shard_axis + the restoring registry's mesh (which
                    # may be a different size -- elastic re-mesh)
                    "shard_layout": idx.shard_layout(),
                }
                if idx.wal is not None:
                    idx.wal.sync()
                    extra["wal_offset"] = idx.wal.offset
            ckpt.save(os.path.join(root, name), step, tree, keep=keep,
                      extra=extra)
        return root

    def restore(self, root: str, step: Optional[int] = None) -> List[str]:
        """Load every tenant checkpoint under ``root`` into this registry.
        Returns the restored names.  (Snapshot-only; ``recover`` is the
        crash path that also replays the WAL tail.)"""
        restored = []
        for name in sorted(os.listdir(root)):
            tdir = os.path.join(root, name)
            if not os.path.isdir(tdir):
                continue
            s = ckpt.latest_step(tdir) if step is None else step
            if s is None:
                continue
            self._restore_tenant(tdir, s)
            restored.append(name)
        return restored

    def _restore_tenant(self, tdir: str, s: int) -> Servable:
        """Rebuild one tenant from checkpoint step ``s`` (integrity-checked;
        raises CheckpointCorruptError on damage).  Returns the servable."""
        extra = ckpt.load_extra(tdir, s)
        spec = _spec_from_manifest(extra["spec"])
        with self._lock:
            sv = self._register(spec)
        idx = sv.index
        cfg = spec.index_config()
        cap = spec.segment_capacity
        lk = spec.n_tables * spec.n_hashes
        seg_meta = extra["segments"]

        def seg_struct(quantized: bool) -> dict:
            # sealed segments on a lossy tier store codes (int8/bf16) plus
            # a scale and the fp32 survivor pool; everything else is fp32
            db_dt = (quantize.storage_dtype(spec.precision) if quantized
                     else jnp.float32)
            struct = {
                "state": LSHIndexState(
                    alpha=jax.ShapeDtypeStruct((spec.n_dims, lk),
                                               jnp.float32),
                    b=jax.ShapeDtypeStruct((lk,), jnp.float32),
                    mix=jax.ShapeDtypeStruct((spec.n_tables, spec.n_hashes),
                                             jnp.uint32),
                    table=jax.ShapeDtypeStruct(
                        (spec.n_tables, cfg.n_buckets, spec.bucket_capacity),
                        jnp.int32),
                    counts=jax.ShapeDtypeStruct(
                        (spec.n_tables, cfg.n_buckets), jnp.int32),
                    db=jax.ShapeDtypeStruct((cap, spec.n_dims), db_dt)),
                "gids": jax.ShapeDtypeStruct((cap,), jnp.int32),
                "live": jax.ShapeDtypeStruct((cap,), jnp.bool_),
            }
            if quantized:
                struct["scale"] = jax.ShapeDtypeStruct((), jnp.float32)
                struct["pool"] = jax.ShapeDtypeStruct((cap, spec.n_dims),
                                                      jnp.float32)
            return struct

        target = {"segments": [seg_struct(m.get("quantized", False))
                               for m in seg_meta]}
        try:
            tree = ckpt.restore(tdir, s, target)
        except ckpt.CheckpointCorruptError:
            # the half-built tenant must not shadow a retry on an older step
            with self._lock:
                self._servables.pop(spec.name, None)
            sv.batcher.stop()
            raise
        idx.segments = []
        idx._locator = {}
        for si, (payload, meta) in enumerate(zip(tree["segments"],
                                                 seg_meta)):
            seg = Segment(state=payload["state"], gids=payload["gids"],
                          live=payload["live"], n_items=meta["n_items"],
                          n_live=meta["n_live"], sealed=meta["sealed"],
                          scale=payload.get("scale"),
                          pool=(np.asarray(payload["pool"])
                                if "pool" in payload else None))
            idx.segments.append(seg)
            g = np.asarray(seg.gids)[:seg.n_items]
            for slot, gid in enumerate(g.tolist()):
                idx._locator[int(gid)] = (si, slot)
        idx.family = (idx.segments[0].state.alpha,
                      idx.segments[0].state.b,
                      idx.segments[0].state.mix)
        idx._next_gid = extra["next_gid"]
        # segments were swapped in under the register()-time placement:
        # bump both versions so a sharded tenant fully re-snapshots its
        # device placement (possibly onto a different-size mesh) on the
        # next query
        idx._version += 1
        idx._sealed_version += 1
        return sv

    def recover(self, ckpt_root: Optional[str] = None,
                wal_dir: Optional[str] = None,
                replay_from: str = "offset") -> Dict[str, dict]:
        """Crash recovery: latest verifiable snapshot + WAL-tail replay.

        For every tenant found under ``ckpt_root`` (checkpoint subdirs)
        and/or ``wal_dir`` (``<name>.wal`` logs):

        1. restore the newest checkpoint step that passes its integrity
           checks -- a corrupt step (``CheckpointCorruptError``) is
           reported and the next older step is tried (``checkpoint._gc``
           guarantees at least one verifiable step survives GC);
        2. a tenant with a WAL but no usable snapshot is rebuilt from the
           log's leading REGISTER record and replayed from byte 0;
        3. replay the WAL from the snapshot's durable ``wal_offset``
           (``replay_from="offset"``) or from the beginning
           (``replay_from="start"`` -- correct either way: replayed
           inserts drop idempotently by gid, deletes/seals/compacts are
           naturally idempotent);
        4. reattach the WAL for appending, so the recovered process keeps
           logging to the same file.

        Returns per-tenant reports: the replay report (records applied,
        duplicates dropped, truncation diagnostics) plus
        ``restored_step`` / ``corrupt_steps``.  Recovered state answers
        queries **bit-identically** to an uninterrupted process that
        performed the same durable operations -- invariant 7, guarded by
        ``tests/test_crash_recovery.py``.
        """
        if replay_from not in ("offset", "start"):
            raise ValueError(f"replay_from must be 'offset' or 'start', "
                             f"got {replay_from!r}")
        wal_dir = wal_dir if wal_dir is not None else self._wal_dir
        names = set()
        if ckpt_root and os.path.isdir(ckpt_root):
            names.update(n for n in os.listdir(ckpt_root)
                         if os.path.isdir(os.path.join(ckpt_root, n)))
        if wal_dir and os.path.isdir(wal_dir):
            names.update(n[:-len(".wal")] for n in os.listdir(wal_dir)
                         if n.endswith(".wal"))
        reports: Dict[str, dict] = {}
        for name in sorted(names):
            report: dict = {"restored_step": None, "corrupt_steps": []}
            wpath0 = (os.path.join(wal_dir, f"{name}.wal")
                      if wal_dir else None)
            if wpath0 is not None and os.path.exists(wpath0) and \
                    walmod.read_last_lifecycle(wpath0) == "unloaded":
                # the log ends in a clean unload: the tenant was detached
                # on purpose, not lost in the crash -- keep the WAL as an
                # audit trail but do not resurrect the endpoint
                reports[name] = dict(report, skipped="unloaded")
                continue
            sv = None
            offset = 0
            tdir = (os.path.join(ckpt_root, name)
                    if ckpt_root and os.path.isdir(
                        os.path.join(ckpt_root, name)) else None)
            tr = obs_trace.tracer()
            reg = obs_metrics.registry()
            if tdir is not None:
                for s in reversed(ckpt.steps(tdir)):
                    try:
                        with tr.span("recover.restore", tenant=name, step=s):
                            sv = self._restore_tenant(tdir, s)
                        extra = ckpt.load_extra(tdir, s)
                        offset = int(extra.get("wal_offset", 0))
                        report["restored_step"] = s
                        reg.inc("recovery_restores_total", tenant=name)
                        break
                    except ckpt.CheckpointCorruptError as e:
                        report["corrupt_steps"].append([s, str(e)])
            wpath = (os.path.join(wal_dir, f"{name}.wal")
                     if wal_dir else None)
            has_wal = wpath is not None and os.path.exists(wpath)
            if sv is None:
                if not has_wal:
                    continue               # nothing restorable for it
                raw = walmod.read_spec(wpath)
                if raw is None:
                    report["error"] = "no snapshot and no REGISTER record"
                    reports[name] = report
                    continue
                with self._lock:
                    sv = self._register(_spec_from_manifest(raw))
                offset = 0
            if has_wal:
                start = 0 if replay_from == "start" else offset
                with tr.span("recover.replay", tenant=name, start=start):
                    rep = sv.index.replay(wpath, start=start)
                reg.inc("recovery_replayed_records_total",
                        int(rep.get("n_records", 0)), tenant=name)
                report.update(rep)
                if rep.get("truncated"):
                    # drop the torn/corrupt tail before reattaching:
                    # appends after a bad frame would be invisible to every
                    # future replay (which stops at the first bad frame)
                    with open(wpath, "rb+") as f:
                        f.truncate(rep["end_offset"])
                    report["truncated_to"] = rep["end_offset"]
                # keep logging where the crashed process stopped
                sv.index.attach_wal(walmod.WriteAheadLog(
                    wpath, fsync_every=self._fsync_every))
            reports[name] = report
        return reports
