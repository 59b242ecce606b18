"""Device-mesh placement for the segmented serve index.

The serve layer's :class:`~repro.serve.segments.SegmentedIndex` is a list of
fixed-shape segments (sealed immutables + one mutable delta).  To serve it
across a mesh we exploit exactly that regularity:

* **sealed segments** are assigned **round-robin** over the mesh's serve axis
  (segment ``i`` -> device ``i % n_dev``) and their state pytrees are stacked
  into one leading-axis array per leaf, sharded over that axis -- device ``d``
  holds a contiguous ``(per_dev, ...)`` block;
* devices with fewer real segments get **empty padding segments** (all-dead
  live mask), so every device runs the same static program -- a padding
  segment contributes only ``(-1, inf)`` rows which the top-k merge discards;
* the **delta segment** and the **hash family** are **replicated**: every
  device could absorb local inserts/serve the freshest writes, and bucket
  ids stay globally consistent because all segments share one family.

Placement is **embedder-agnostic by construction**: it sees only segment
pytrees (state/gids/live), never what the vectors embed, so a
distribution-valued Wasserstein tenant is placed identically to the basis/
QMC function tenants -- one placement rule for every workload the embedder
registry can express (verified by
``tests/test_sharded_serve.py::test_wasserstein_tenant_sharded_parity``).

A :class:`SegmentPlacement` is an immutable snapshot of the index at one
mutation ``version``; the serve layer rebuilds it lazily when the index
mutates (insert/delete/seal/compact all bump the version).  Queries against
a placement go through :func:`repro.core.distributed.query_segments_sharded`
and are **bit-identical** to the unsharded ``SegmentedIndex.query`` -- the
same per-segment programs run, only their placement changes, and the
two-level (local, then collective) ``merge_topk`` is order-equivalent to the
single-level merge because the (distance, gid) order is total.

**Incremental re-placement** (the in-place ingestion tentpole): a rebuild
that is handed the previous placement (``place_segments(..., prev=...)``)
applies a *diff* instead of restacking every sealed leaf.  Each stacked
slot carries a ``(content, live)`` fingerprint (``Segment.placement_key``);
a slot whose fingerprint is unchanged moves **zero** bytes, a slot whose
content is unchanged but whose live mask flipped (sealed-segment deletes)
rewrites only the mask row, and only genuinely new/changed slots pay a
full row write -- so sealing one segment re-replicates O(that segment's
bytes), not O(all sealed bytes).  ``replaced_bytes`` /
``sealed_bytes`` on the returned placement account the actual vs
full-restack transfer (the serve layer publishes them as obs metrics and
the bench gates their ratio).  To keep both full restacks *and*
``per_dev``-keyed jit recompiles O(log n) under a growing sealed set, the
stacked stripe width grows by capacity doubling and only shrinks once the
need falls below a quarter of it -- intermediate seals reuse headroom
slots.  ``SegmentPlacement.layout()`` reports the stripe width that
actually serves, so the router's slot math and the collective always
agree.

**Replication** (the read-QPS lever): each sealed segment additionally
carries a replication factor (default 1).  A factor-f segment is
materialized on f distinct devices -- the *instance-level* assignment
(:func:`replicated_assignment`) spreads replicas onto the least-loaded
devices while factor-1 placements reduce exactly to the round-robin rule
above.  Replicas are bit-identical copies, so query results cannot depend
on which replica answers: either every replica answers and the collective
fan-in dedups by gid (``ops.merge_topk_unique``), or a
:class:`repro.serve.router.QueryRouter` activates exactly one replica per
segment per micro-batch to spread load.  Both stay bit-identical to the
unreplicated path (invariant 6, docs/architecture.md).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SegmentPlacement:
    """Immutable device placement of a segmented index at one version.

    Attributes:
        mesh, axis: the serve mesh and the axis sealed segments shard over.
        n_dev: mesh size along ``axis``.
        per_dev: sealed segments per device (after round-robin + padding).
        n_sealed: real (non-padding) sealed segments placed.
        version: the ``SegmentedIndex`` mutation counter this snapshot is of.
        sealed_state: state pytree, leaves stacked ``(n_dev * per_dev, ...)``
            and sharded over ``axis`` on the leading dim.
        sealed_gids / sealed_live: ``(n_dev * per_dev, capacity)`` sharded
            alongside the state.
        delta_state / delta_gids / delta_live: the mutable delta segment,
            replicated on every device.
        assignment: ``assignment[d]`` = list of index-level segment positions
            placed on device ``d`` (for reports and snapshot manifests).
            Instance-level: a segment with replication factor f appears in f
            distinct devices' lists.
        replication: per-sealed-segment replication factors (all 1 = the
            classic unreplicated placement).
    """

    mesh: Mesh
    axis: str
    n_dev: int
    per_dev: int
    n_sealed: int
    version: int
    sealed_state: Any
    sealed_gids: Array
    sealed_live: Array
    delta_state: Any
    delta_gids: Array
    delta_live: Array
    assignment: tuple
    replication: tuple = ()
    # Per-instance symmetric dequant scales, (n_dev * per_dev,) f32 sharded
    # alongside the sealed stack.  1.0 for fp32/bf16/padding instances, so
    # the quantized collective can consume it unconditionally; the fp32
    # collective simply never reads it.
    sealed_scales: Any = None
    # Incremental re-placement bookkeeping: one (content, live) fingerprint
    # per stacked slot (None = padding/headroom), and the byte ledger of the
    # build that produced this snapshot -- ``replaced_bytes`` is what the
    # build actually transferred, ``sealed_bytes`` what a full restack
    # would have (for a full build the two are equal).
    slot_keys: tuple = ()
    replaced_bytes: int = 0
    sealed_bytes: int = 0
    diffed: bool = False

    def layout(self) -> dict:
        """JSON-able description of the placement (snapshot manifests,
        ``launch.serve`` reports, tests)."""
        lay = layout_dict(self.mesh, self.axis, self.n_sealed,
                          replication=self.replication or None)
        # The stacked stripe may be wider than the minimal layout (capacity-
        # doubling headroom); the router's slot math (d * per_dev + j) and
        # the collective's active-mask length must use the stripe that
        # actually serves, so the actual width overrides the computed one.
        lay["per_dev"] = self.per_dev
        return lay


def round_robin(n_items: int, n_dev: int) -> List[List[int]]:
    """``assignment[d]`` = item indices owned by device ``d`` (i % n_dev)."""
    return [[i for i in range(n_items) if i % n_dev == d]
            for d in range(n_dev)]


def normalize_replication(n_sealed: int, n_dev: int,
                          replication) -> Tuple[int, ...]:
    """Per-segment factors as a canonical tuple: length ``n_sealed``,
    clipped to ``[1, n_dev]`` (a replica set can't exceed the device count),
    missing positions defaulting to 1.  Accepts ``None`` (all 1), an int
    (every sealed segment gets that factor) or a positional sequence."""
    if replication is None:
        return (1,) * n_sealed
    if isinstance(replication, int):
        return (max(1, min(int(replication), n_dev)),) * n_sealed
    fac = [max(1, min(int(f), n_dev)) for f in replication][:n_sealed]
    fac += [1] * (n_sealed - len(fac))
    return tuple(fac)


def replicated_assignment(n_sealed: int, n_dev: int,
                          factors: Sequence[int]) -> List[List[int]]:
    """Instance-level device assignment under per-segment replication.

    Primary copies go round-robin (``i % n_dev``) -- so all-1 factors
    reproduce :func:`round_robin` exactly, keeping unreplicated layouts
    (and their parity guarantees) byte-for-byte stable.  Each extra
    replica then lands on the least-loaded device that doesn't already
    hold a copy of that segment (ties -> lowest device id), which is what
    equalizes instance counts when a few hot segments carry factor > 1.
    Deterministic: same inputs, same assignment.
    """
    assignment = round_robin(n_sealed, n_dev)
    holders = [{d for d in range(n_dev) if i in assignment[d]}
               for i in range(n_sealed)]
    for i in range(n_sealed):
        for _ in range(factors[i] - 1):
            free = [d for d in range(n_dev) if d not in holders[i]]
            if not free:
                break
            d = min(free, key=lambda d: (len(assignment[d]), d))
            assignment[d].append(i)
            holders[i].add(d)
    return assignment


def layout_dict(mesh: Mesh, axis: str, n_sealed: int,
                replication=None) -> dict:
    """The placement rule as data: where ``n_sealed`` sealed segments land
    on ``mesh``'s ``axis``.  The single source of truth for per-device
    counts and assignment -- :func:`place_segments` builds device arrays
    from it and ``SegmentedIndex.shard_layout`` reports it, so the report
    can never drift from what actually runs."""
    n_dev = int(mesh.shape[axis])
    factors = normalize_replication(n_sealed, n_dev, replication)
    assignment = replicated_assignment(n_sealed, n_dev, factors)
    return {
        "axis": axis,
        "mesh_axes": list(mesh.axis_names),
        "mesh_shape": [int(mesh.shape[a]) for a in mesh.axis_names],
        "n_dev": n_dev,
        "per_dev": max(1, max(len(a) for a in assignment)),
        "n_sealed": n_sealed,
        "n_instances": int(sum(factors)),
        "replication": list(factors),
        "assignment": assignment,
    }


@functools.lru_cache(maxsize=16)
def _slot_writer(mesh: Mesh, axis: str):
    """One jitted slot-row writer per (mesh, axis): write ``row`` into
    leading-dim position ``slot`` of a stacked sealed array, keeping the
    result sharded over ``axis``.

    ``slot`` is a *traced* scalar, so writing any slot reuses one compiled
    program per leaf shape/dtype -- no per-slot retraces.  Deliberately NOT
    donating the input: in-flight queries may still hold references to the
    previous placement's buffers (the atomic-swap contract: queries keep
    serving the old placement until the new one is published), and PJRT
    donation with outstanding references is undefined.  The device-local
    copy this costs is exactly that -- local; the host->device transfer
    stays O(row bytes), which is what the re-placement metric measures.
    """
    shard = NamedSharding(mesh, P(axis))

    @jax.jit
    def write(stacked, row, slot):
        out = jax.lax.dynamic_update_slice(
            stacked, row[None, ...], (slot,) + (0,) * row.ndim)
        return jax.lax.with_sharding_constraint(out, shard)

    return write


def _slot_key_table(segments: Sequence, assignment, per_dev: int,
                    version: int) -> tuple:
    """Desired per-slot fingerprints for one build: ``(content, live)``
    from ``Segment.placement_key`` per real slot, ``None`` for padding.
    Segments without a fingerprint get a build-unique opaque key (never
    ``None``: a padding match on a real segment would leave stale live
    rows serving), so the next build rewrites their slots."""
    keys = []
    for block in assignment:
        for j in range(per_dev):
            if j < len(block):
                seg = segments[block[j]]
                pk = getattr(seg, "placement_key", None)
                if callable(pk):
                    keys.append(pk())
                else:
                    k = ("opaque", version, len(keys))
                    keys.append((k, k))
            else:
                keys.append(None)
    return tuple(keys)


def _rows_compatible(segments: Sequence, prev: SegmentPlacement) -> bool:
    """True iff every segment's rows can be written into ``prev``'s stacked
    leaves (same tree arity, leaf dtypes and trailing shapes).  Catches the
    fp32->int8 template flip when a quantized tenant seals its first real
    segment over a delta-templated padding stack."""
    stacked = jax.tree.leaves(prev.sealed_state)
    for seg in segments:
        rows = jax.tree.leaves(seg.state)
        if len(rows) != len(stacked):
            return False
        for r, s in zip(rows, stacked):
            if r.dtype != s.dtype or tuple(r.shape) != tuple(s.shape[1:]):
                return False
        if (seg.gids.dtype != prev.sealed_gids.dtype
                or tuple(seg.gids.shape) != tuple(prev.sealed_gids.shape[1:])):
            return False
    return True


def _headroom_per_dev(need: int, prev: Optional[SegmentPlacement],
                      mesh: Mesh, axis: str, n_dev: int) -> int:
    """Stripe width under capacity doubling: grow to at least 2x the
    previous width when the need outgrows it, keep the previous width while
    the need fits (headroom -> diffable builds, stable jit keys), shrink to
    2x the need only once the need falls below a quarter of the width."""
    if prev is None or prev.mesh != mesh or prev.axis != axis \
            or prev.n_dev != n_dev:
        return need
    if need > prev.per_dev:
        return max(need, 2 * prev.per_dev)
    if need * 4 <= prev.per_dev and prev.per_dev > 1:
        return max(1, need * 2)
    return prev.per_dev


def _seg_row_bytes(seg) -> int:
    """Bytes one full slot write transfers for ``seg`` (state leaves +
    gids + live + the f32 scale row)."""
    return (sum(int(x.nbytes) for x in jax.tree.leaves(seg.state))
            + int(seg.gids.nbytes) + int(seg.live.nbytes) + 4)


def _stacked_bytes(state, gids, live, scales) -> int:
    return (sum(int(x.nbytes) for x in jax.tree.leaves(state))
            + int(gids.nbytes) + int(live.nbytes) + int(scales.nbytes))


def place_segments(segments: Sequence, delta, mesh: Mesh, axis: str,
                   version: int, replication=None,
                   prev: Optional[SegmentPlacement] = None
                   ) -> SegmentPlacement:
    """Build a :class:`SegmentPlacement` from serve-layer segments.

    Args:
        segments: sealed segments to shard (objects with ``.state`` /
            ``.gids`` / ``.live``; typically the live sealed segments of a
            ``SegmentedIndex``).  The positions in this sequence are what
            ``assignment`` refers to.
        delta: the mutable delta segment, replicated across the mesh.
        mesh: serve mesh; ``axis`` must be one of its axis names.
        version: mutation counter recorded on the placement.
        replication: per-segment replication factors (None / int / sequence,
            see :func:`normalize_replication`); factor-f segments are
            stacked into f devices' stripes.
        prev: the placement being replaced, if any.  When it is diff-
            compatible (same mesh/axis/stripe width, row templates match,
            every segment fingerprinted) only changed slots are written --
            O(changed bytes) instead of a full restack.

    Returns:
        A placement whose device arrays are already ``device_put`` with the
        proper :class:`NamedSharding` -- ready for
        ``core.distributed.query_segments_sharded``.
    """
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has axes {mesh.axis_names}, no {axis!r}")
    n_sealed = len(segments)
    lay = layout_dict(mesh, axis, n_sealed, replication=replication)
    n_dev, assignment = lay["n_dev"], lay["assignment"]
    per_dev = _headroom_per_dev(lay["per_dev"], prev, mesh, axis, n_dev)
    keys = _slot_key_table(segments, assignment, per_dev, version)

    diffable = (
        prev is not None and prev.mesh == mesh and prev.axis == axis
        and prev.n_dev == n_dev and prev.per_dev == per_dev
        and len(prev.slot_keys) == n_dev * per_dev
        and all(callable(getattr(s, "placement_key", None))
                for s in segments)
        and _rows_compatible(segments, prev))
    if diffable:
        return _place_diff(prev, segments, delta, mesh, axis, version,
                           lay, per_dev, keys)

    # Full (re)stack -- first build, mesh/stripe change, or template flip.
    # Block layout: device d's contiguous stripe is assignment[d] + padding.
    # Padding reuses a sealed segment's (zeroed) leaf shapes with an
    # all-dead live mask, so it is queryable but contributes nothing.  The
    # zero-template must come from a SEALED segment when any exist: under a
    # quantized precision tier the sealed ``db`` leaves are int8/bf16 while
    # the delta stays fp32, and jnp.stack refuses (rightly) to mix them.
    pad_src = segments[0].state if n_sealed else delta.state
    pad_state = jax.tree.map(jnp.zeros_like, pad_src)
    pad_gids = jnp.full_like(delta.gids, -1)
    pad_live = jnp.zeros_like(delta.live)
    states, gids, lives, scales = [], [], [], []
    for d in range(n_dev):
        block = assignment[d]
        for si in block:
            seg = segments[si]
            states.append(seg.state)
            gids.append(seg.gids)
            lives.append(seg.live)
            scale = getattr(seg, "scale", None)
            scales.append(jnp.float32(1.0) if scale is None
                          else jnp.asarray(scale, jnp.float32))
        for _ in range(per_dev - len(block)):
            states.append(pad_state)
            gids.append(pad_gids)
            lives.append(pad_live)
            scales.append(jnp.float32(1.0))

    shard = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
    sealed_state = jax.device_put(stacked, shard)
    sealed_gids = jax.device_put(jnp.stack(gids), shard)
    sealed_live = jax.device_put(jnp.stack(lives), shard)
    sealed_scales = jax.device_put(jnp.stack(scales), shard)
    total = _stacked_bytes(sealed_state, sealed_gids, sealed_live,
                           sealed_scales)
    return SegmentPlacement(
        mesh=mesh, axis=axis, n_dev=n_dev, per_dev=per_dev,
        n_sealed=n_sealed, version=version,
        sealed_state=sealed_state,
        sealed_gids=sealed_gids,
        sealed_live=sealed_live,
        sealed_scales=sealed_scales,
        delta_state=jax.device_put(delta.state, repl),
        delta_gids=jax.device_put(delta.gids, repl),
        delta_live=jax.device_put(delta.live, repl),
        assignment=tuple(tuple(a) for a in assignment),
        replication=tuple(lay["replication"]),
        slot_keys=keys, replaced_bytes=total, sealed_bytes=total,
        diffed=False,
    )


def _place_diff(prev: SegmentPlacement, segments: Sequence, delta,
                mesh: Mesh, axis: str, version: int, lay: dict,
                per_dev: int, keys: tuple) -> SegmentPlacement:
    """Apply a placement diff: rewrite only slots whose fingerprint changed.

    Three per-slot cases, cheapest first: fingerprint unchanged -> zero
    bytes; content unchanged but live mask flipped (sealed-segment deletes)
    -> only the (capacity,) mask row; anything else -> a full row write.
    Freed slots (a segment left the placement) get a dead ``gids = -1`` /
    all-false ``live`` row -- their stale db rows stay on device but are
    unreachable (every candidate from them is masked, contributing only
    ``(-1, inf)`` like padding), which is the same invisibility padding
    slots already rely on.
    """
    n_dev, assignment = lay["n_dev"], lay["assignment"]
    write = _slot_writer(mesh, axis)
    sealed_state = prev.sealed_state
    sealed_gids = prev.sealed_gids
    sealed_live = prev.sealed_live
    sealed_scales = prev.sealed_scales
    pad_gids = jnp.full_like(delta.gids, -1)
    pad_live = jnp.zeros_like(delta.live)
    seg_at = {}
    for d, block in enumerate(assignment):
        for j, si in enumerate(block):
            seg_at[d * per_dev + j] = segments[si]
    replaced = 0
    for slot, (key, old) in enumerate(zip(keys, prev.slot_keys)):
        if key == old:
            continue
        idx = jnp.int32(slot)
        if key is None:
            sealed_gids = write(sealed_gids, pad_gids, idx)
            sealed_live = write(sealed_live, pad_live, idx)
            replaced += int(pad_gids.nbytes) + int(pad_live.nbytes)
            continue
        seg = seg_at[slot]
        if old is not None and key[0] == old[0]:
            sealed_live = write(sealed_live, seg.live, idx)
            replaced += int(seg.live.nbytes)
            continue
        sealed_state = jax.tree.map(
            lambda st, row: write(st, row, idx), sealed_state, seg.state)
        sealed_gids = write(sealed_gids, seg.gids, idx)
        sealed_live = write(sealed_live, seg.live, idx)
        scale = getattr(seg, "scale", None)
        sealed_scales = write(
            sealed_scales,
            jnp.float32(1.0) if scale is None
            else jnp.asarray(scale, jnp.float32), idx)
        replaced += _seg_row_bytes(seg)
    repl = NamedSharding(mesh, P())
    return SegmentPlacement(
        mesh=mesh, axis=axis, n_dev=n_dev, per_dev=per_dev,
        n_sealed=len(segments), version=version,
        sealed_state=sealed_state,
        sealed_gids=sealed_gids,
        sealed_live=sealed_live,
        sealed_scales=sealed_scales,
        delta_state=jax.device_put(delta.state, repl),
        delta_gids=jax.device_put(delta.gids, repl),
        delta_live=jax.device_put(delta.live, repl),
        assignment=tuple(tuple(a) for a in assignment),
        replication=tuple(lay["replication"]),
        slot_keys=keys, replaced_bytes=replaced,
        sealed_bytes=_stacked_bytes(sealed_state, sealed_gids, sealed_live,
                                    sealed_scales),
        diffed=True,
    )


@functools.lru_cache(maxsize=16)
def _live_clearer(mesh: Mesh, axis: str):
    """One jitted tombstone scatter per (mesh, axis): clear the stacked
    live mask at ``(slot, row)`` pairs, dropping out-of-range pairs.  Like
    :func:`_slot_writer` it does not donate, for the same reason."""
    shard = NamedSharding(mesh, P(axis))

    @jax.jit
    def clear(live, slots, rows):
        out = live.at[slots, rows].set(False, mode="drop")
        return jax.lax.with_sharding_constraint(out, shard)

    return clear


def clear_live(pl: SegmentPlacement, slots, rows) -> SegmentPlacement:
    """Tombstone stacked slots in place, instead of a rebuild: one
    scatter of the ``(slot, row)`` pairs into ``sealed_live``.  A pair
    whose slot is out of range (``n_dev * per_dev``) is dropped, so a
    caller can pad to a fixed count: the scatter compiles once per count,
    whichever slots it touches.  ``slot_keys`` keep the old live
    fingerprints; the next rebuild's diff rewrites those mask rows with
    what they already hold."""
    live = _live_clearer(pl.mesh, pl.axis)(
        pl.sealed_live, jnp.asarray(slots, jnp.int32),
        jnp.asarray(rows, jnp.int32))
    return dataclasses.replace(pl, sealed_live=live)


def refresh_delta(pl: SegmentPlacement, delta) -> SegmentPlacement:
    """Re-replicate only the delta leaves of an existing placement.

    Delta-only mutations (every insert that doesn't seal, deletes that hit
    only the delta) dominate streaming write traffic; refreshing just the
    one mutable segment keeps them O(delta bytes) instead of restacking and
    re-transferring every sealed segment.
    """
    repl = NamedSharding(pl.mesh, P())
    return dataclasses.replace(
        pl,
        delta_state=jax.device_put(delta.state, repl),
        delta_gids=jax.device_put(delta.gids, repl),
        delta_live=jax.device_put(delta.live, repl),
    )
