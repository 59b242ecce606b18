"""Mesh-sharded distributed LSH index (the paper's technique at pod scale).

Sharding scheme (FAISS-style, expressed in shard_map + lax collectives):

* **Items** are sharded over the ``data`` mesh axis -- each data shard owns a
  contiguous range of the database.
* **Tables** are sharded over the ``model`` mesh axis -- each model shard draws
  its own independent hash family (fold_in by device index), so the global
  index has L_local x n_model tables.  More model shards => more OR-amplified
  tables => higher recall, for free.
* **Build** is fully local: every device hashes only its own items into its own
  tables.  Zero collective traffic (the property that makes LSH indexing
  scale to 1000+ nodes).
* **Query**: queries arrive replicated (or are all-gathered once, O(nq N));
  every device probes its local tables over its local items, re-ranks exactly,
  and emits a local top-k; a single ``all_gather`` over both axes + local merge
  produces the global top-k.  Collective volume is O(ndev * nq * k), independent
  of database size.

State layout: every leaf carries leading (D, M) device axes sharded over
('data', 'model'), so the same code path works on 1 device, an 8-device CPU
test mesh, and the 512-chip production mesh.

This module also hosts the *serve layer's* collective query
(:func:`query_segments_sharded`): the SPMD companion of
``serve.segments.SegmentedIndex`` operating on a
``sharding.placement.SegmentPlacement`` (sealed segments round-robin over a
1-D serve axis, delta replicated).  Unlike the build/query pair above -- an
independent per-device hash family for OR-amplified recall -- the serve
path shards one *shared-family* index, which is what makes its results
bit-identical to the single-device path.  The collective is keyed on the
placement's ``per_dev`` (its physical slot stride, headroom included), so
in-place placement diffs that keep the stride constant reuse the compiled
program -- padded/freed slots are simply inactive in the mask.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import compat
from ..kernels import ops
from . import index as lsh_index
from .index import IndexConfig, LSHIndexState

Array = jax.Array


def _local(create_fn, key, cfg, n_local_cap):
    return create_fn(key, cfg, n_local_cap)


def build_distributed(key: jax.Array, cfg: IndexConfig, embeddings: Array,
                      mesh: Mesh, data_axis: str = "data",
                      model_axis: str = "model"):
    """Build a sharded index.

    embeddings: (n_items, N), n_items divisible by the data-axis size.
    Returns a pytree of arrays with leading (D, M) axes, sharded over
    ('data', 'model').
    """
    n_items = embeddings.shape[0]
    d = mesh.shape[data_axis]
    m = mesh.shape[model_axis]
    n_local = n_items // d

    def shard_fn(emb_local):
        # emb_local: (n_local, N) block of this data shard (same for all model
        # shards of the same data index).
        di = jax.lax.axis_index(data_axis)
        mi = jax.lax.axis_index(model_axis)
        dev_key = jax.random.fold_in(jax.random.fold_in(key, di), mi)
        state = lsh_index.create_index(dev_key, cfg, n_local)
        state = lsh_index.build_index(state, cfg, emb_local)
        return jax.tree.map(lambda x: x[None, None], state)

    fn = compat.shard_map(
        shard_fn, mesh=mesh,
        in_specs=P(data_axis, None),
        out_specs=jax.tree.map(lambda _: P(data_axis, model_axis),
                               _state_structure()),
        check_vma=False)
    return fn(embeddings)


def _state_structure():
    """Tree-structure token for out_specs (leaves are placeholders)."""
    return LSHIndexState(alpha=0, b=0, mix=0, table=0, counts=0, db=0)


def query_distributed(state_dm, cfg: IndexConfig, queries: Array, k: int,
                      mesh: Mesh, n_probes: int = 1, data_axis: str = "data",
                      model_axis: str = "model") -> Tuple[Array, Array]:
    """Global k-NN over the sharded index.

    queries: (nq, N) replicated.  Returns (ids (nq, k), dists (nq, k)) with
    *global* item ids, replicated across the mesh.
    """
    d = mesh.shape[data_axis]

    def shard_fn(state_local, q):
        state = jax.tree.map(lambda x: x[0, 0], state_local)
        di = jax.lax.axis_index(data_axis)
        n_local = state.db.shape[0]
        ids, dists = lsh_index.query_index(state, cfg, q, k, n_probes=n_probes)
        gids = jnp.where(ids >= 0, ids + di * n_local, -1)
        # Merge across every device: one all-gather of (nq, k) pairs per axis.
        all_ids = jax.lax.all_gather(gids, (data_axis, model_axis))   # (D*M, nq, k)
        all_d = jax.lax.all_gather(dists, (data_axis, model_axis))
        nd = all_ids.shape[0]
        flat_ids = all_ids.transpose(1, 0, 2).reshape(q.shape[0], nd * k)
        flat_d = all_d.transpose(1, 0, 2).reshape(q.shape[0], nd * k)
        # Dedup global ids (same item can surface from several model shards).
        order = jnp.argsort(flat_ids, axis=-1)
        s_ids = jnp.take_along_axis(flat_ids, order, axis=-1)
        s_d = jnp.take_along_axis(flat_d, order, axis=-1)
        dup = jnp.concatenate([jnp.zeros_like(s_ids[:, :1], dtype=bool),
                               s_ids[:, 1:] == s_ids[:, :-1]], axis=-1)
        s_d = jnp.where(dup | (s_ids < 0), jnp.inf, s_d)
        neg, pick = jax.lax.top_k(-s_d, k)
        out_ids = jnp.take_along_axis(s_ids, pick, axis=-1)
        out_d = -neg
        out_ids = jnp.where(jnp.isinf(out_d), -1, out_ids)
        return out_ids, out_d

    fn = compat.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(data_axis, model_axis),
                               _state_structure()), P()),
        out_specs=(P(), P()),
        check_vma=False)
    return fn(state_dm, queries)


@functools.lru_cache(maxsize=64)
def _sharded_segment_query_fn(cfg: IndexConfig, k: int, n_probes: int,
                              backend: Optional[str], mesh: Mesh, axis: str,
                              per_dev: int, quantized: bool = False):
    """One compiled collective program per (cfg, k, n_probes, backend, mesh,
    per-device segment count) -- the sharded analogue of the serve layer's
    ``_segment_query_fn``.  Each device runs the *same* per-segment
    hash -> probe -> gather -> rerank program as the unsharded path over its
    local ``per_dev`` sealed segment *instances* plus the replicated delta
    (contributed by rank 0 only, or every device would duplicate the delta's
    rows in the merge), local-merges, then all-gathers the (nq, k) shards
    for the global merge -- collective volume O(n_dev * nq * k), independent
    of database size.

    Replica-awareness is two runtime inputs, not a new program: the
    ``active`` mask (one flag per local instance, sharded like the sealed
    stack) silences instances the :class:`repro.serve.router.QueryRouter`
    did not route this micro-batch to, and the collective fan-in dedups by
    gid (``ops.merge_topk_unique``) so that when several replicas of one
    segment *do* answer (all-active mode, or no router), their bit-identical
    rows collapse to one.  Either way the merged top-k equals the
    unreplicated path's (invariant 6).

    ``quantized=True`` is the precision tier's collective: sealed segments
    score through the dequant-free code-space tail
    (``query_index_gids`` with a scale, fed per-instance scales sharded like
    the sealed stack) while the replicated fp32 delta keeps the exact tail,
    and ``k`` is the serve layer's survivor width m rather than the user's
    k -- the merged (nq, m) survivors are rescored exactly on the host
    (``serve.segments``).  ``quantized=False`` builds byte-for-byte the
    pre-tier program, which is what keeps fp32 sharded serving bit-exact."""

    def one_segment(state: LSHIndexState, gids: Array, live: Array, q: Array,
                    scale: Optional[Array] = None):
        # same per-segment body as the unsharded fan-out -- parity by
        # construction
        return lsh_index.query_index_gids(state, cfg, q, k, gids,
                                          n_probes=n_probes, backend=backend,
                                          live_mask=live, scale=scale)

    # the function's name is the program's name in a profiler trace
    def segment_query_sharded(sealed_state, sealed_gids, sealed_live,
                              sealed_scales, active, delta_state, delta_gids,
                              delta_live, q):
        # sealed_* leaves: this device's (per_dev, ...) block; delta_*
        # replicated.  Static unroll over the local segments -- identical
        # shapes, so it is one fused program, not per_dev compilations.
        parts_g, parts_d = [], []
        for i in range(per_dev):
            seg = jax.tree.map(lambda x: x[i], sealed_state)
            g, d = one_segment(seg, sealed_gids[i], sealed_live[i], q,
                               scale=sealed_scales[i] if quantized else None)
            parts_g.append(jnp.where(active[i], g, -1))
            parts_d.append(jnp.where(active[i], d, jnp.inf))
        g, d = one_segment(delta_state, delta_gids, delta_live, q)
        rank = jax.lax.axis_index(axis)
        parts_g.append(jnp.where(rank == 0, g, -1))
        parts_d.append(jnp.where(rank == 0, d, jnp.inf))
        d_loc, g_loc = ops.merge_topk(jnp.concatenate(parts_d, axis=1),
                                      jnp.concatenate(parts_g, axis=1), k)
        # Collective fan-in: one all-gather of the (nq, k) local winners.
        all_g = jax.lax.all_gather(g_loc, axis)               # (n_dev, nq, k)
        all_d = jax.lax.all_gather(d_loc, axis)
        nd = all_g.shape[0]
        flat_g = all_g.transpose(1, 0, 2).reshape(q.shape[0], nd * k)
        flat_d = all_d.transpose(1, 0, 2).reshape(q.shape[0], nd * k)
        d_out, g_out = ops.merge_topk_unique(flat_d, flat_g, k)
        return g_out, d_out

    state_sharded = jax.tree.map(lambda _: P(axis), _state_structure())
    state_repl = jax.tree.map(lambda _: P(), _state_structure())
    fn = compat.shard_map(
        segment_query_sharded, mesh=mesh,
        in_specs=(state_sharded, P(axis), P(axis), P(axis), P(axis),
                  state_repl, P(), P(), P()),
        out_specs=(P(), P()),
        check_vma=False)
    return jax.jit(fn)


def query_segments_sharded(placement, cfg: IndexConfig, queries: Array,
                           k: int, n_probes: int = 1,
                           backend: Optional[str] = None,
                           active: Optional[Array] = None,
                           quantized: bool = False
                           ) -> Tuple[Array, Array]:
    """Collective cross-segment k-NN over a ``SegmentPlacement``.

    Args:
        placement: :class:`repro.sharding.placement.SegmentPlacement` --
            sealed segments stacked/sharded over ``placement.axis``, delta
            replicated (see that module for the layout).
        cfg: the index config shared by every segment.
        queries: (nq, N) replicated across the mesh.
        k, n_probes: as in ``core.index.query_index``.
        backend: re-rank tail backend (resolve via
            ``kernels.dispatch.query_backend`` first, as the serve layer
            does, so the compile cache never keys on a raw None).
        active: (n_dev * per_dev,) bool, one flag per placed segment
            instance in device-stripe order -- the router's per-micro-batch
            replica selection.  None = every instance answers (replicas are
            deduped by gid at the fan-in, so this is always correct, just
            unrouted).
        quantized: run the precision tier's collective -- sealed instances
            score dequant-free against their int8/bf16 codes using
            ``placement.sealed_scales``; pass the survivor width m as
            ``k`` and rescore the result exactly (the serve layer does).

    Returns:
        (gids (nq, k) int32, dists (nq, k) f32), replicated; -1/inf padded.
        Bit-identical to the unsharded ``SegmentedIndex.query`` over the
        same live items -- replicated or not (the serve layer's sharding +
        replication invariants, enforced by tests/test_sharded_serve.py,
        tests/test_replicated_serve.py and the serve benchmarks).
    """
    fn = _sharded_segment_query_fn(cfg, k, n_probes, backend,
                                   placement.mesh, placement.axis,
                                   placement.per_dev, quantized)
    if active is None:
        active = jnp.ones((placement.n_dev * placement.per_dev,), jnp.bool_)
    else:
        active = jnp.asarray(active, jnp.bool_)
    scales = placement.sealed_scales
    if scales is None:
        scales = jnp.ones((placement.n_dev * placement.per_dev,), jnp.float32)
    return fn(placement.sealed_state, placement.sealed_gids,
              placement.sealed_live, scales, active, placement.delta_state,
              placement.delta_gids, placement.delta_live,
              jnp.asarray(queries, jnp.float32))


def brute_force_distributed(embeddings: Array, queries: Array, k: int,
                            mesh: Mesh, p: float = 2.0,
                            data_axis: str = "data",
                            model_axis: str = "model") -> Tuple[Array, Array]:
    """Sharded exact k-NN baseline (the 'without the paper' comparison):
    full pairwise distances on each data shard + global merge."""
    d = mesh.shape[data_axis]
    n_local = embeddings.shape[0] // d

    def shard_fn(emb_local, q):
        di = jax.lax.axis_index(data_axis)
        ids, dists = lsh_index.brute_force_topk(emb_local, q, k, p)
        gids = ids + di * n_local
        all_ids = jax.lax.all_gather(gids, data_axis)
        all_d = jax.lax.all_gather(dists, data_axis)
        nd = all_ids.shape[0]
        flat_ids = all_ids.transpose(1, 0, 2).reshape(q.shape[0], nd * k)
        flat_d = all_d.transpose(1, 0, 2).reshape(q.shape[0], nd * k)
        neg, pick = jax.lax.top_k(-flat_d, k)
        return jnp.take_along_axis(flat_ids, pick, axis=-1), -neg

    fn = compat.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(data_axis, None), P()),
        out_specs=(P(), P()),
        check_vma=False)
    return fn(embeddings, queries)
