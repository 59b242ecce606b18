"""Streaming serve layer: segment lifecycle, cross-segment parity, batcher,
registry snapshot/restore.

The load-bearing test is ``test_cross_segment_parity``: for p in {1, 2} and
single-/multi-probe, a segmented index (multiple sealed segments + delta +
tombstones) must return ids *bit-identical* to one static ``build_index``
over the union of live items -- i.e. segmentation and streaming mutation are
semantically invisible.  This holds because all segments share one hash
family and relies on no bucket overflowing (asserted inside the test so a
config change can't silently weaken it).
"""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _fanout_support import (assert_paths_agree, both_paths,
                             fanout_batches, fanout_dedups, lifecycle_parity)
from repro.core import index as lidx
from repro.kernels import ops
from repro.serve import (MicroBatcher, SegmentedIndex, ServableRegistry,
                         ServableSpec, ServingStats, occupancy_report,
                         recall_proxy)
from repro.serve import segments as segmod
from repro.sharding import placement as seg_placement

N_DIMS = 16


def _cfg(p=2.0):
    return lidx.IndexConfig(n_dims=N_DIMS, n_tables=4, n_hashes=4,
                            log2_buckets=8, bucket_capacity=64, r=2.0, p=p)


def _data(n, seed=0, scale=1.0):
    return (np.random.default_rng(seed).normal(size=(n, N_DIMS)) *
            scale).astype(np.float32)


def _union_reference(si, emb, live, q, k, n_probes):
    """Ground truth: one static build over live items, ids mapped to gids."""
    live_rows = np.flatnonzero(live)
    state = lidx.create_index(jax.random.PRNGKey(0), si.cfg, len(live_rows),
                              family=si.family)
    state = lidx.build_index(state, si.cfg, jnp.asarray(emb[live_rows]))
    # parity precondition: no bucket overflow on EITHER side -- segment
    # buckets also hold tombstoned items, so check them too, or dead items
    # could crowd a live insert out of a segment table while the union
    # build (live items only) keeps it
    assert int(state.counts.max()) <= si.cfg.bucket_capacity
    for seg in si.segments:
        assert int(seg.state.counts.max()) <= si.cfg.bucket_capacity
    ids, dists = lidx.query_index(state, si.cfg, q, k, n_probes=n_probes)
    ids = np.asarray(ids)
    return np.where(ids >= 0, live_rows[np.clip(ids, 0, None)], -1), \
        np.asarray(dists)


@pytest.mark.parametrize("p", [1.0, 2.0])
@pytest.mark.parametrize("n_probes", [1, 4])
def test_cross_segment_parity(p, n_probes):
    """Acceptance criterion: segmented query == single build_index over the
    union of live items, bit-identical ids, for p in {1,2} x {1,multi}-probe."""
    cfg = _cfg(p)
    si = SegmentedIndex(cfg, segment_capacity=128, insert_chunk=64, seed=3)
    emb = _data(300, seed=1)
    gids = si.insert(emb)
    assert len(si.segments) == 3            # 128 + 128 + 44: real fan-out
    si.delete(gids[::7])                    # tombstones in every segment
    live = np.ones(300, bool)
    live[::7] = False
    q = _data(9, seed=2, scale=0.9)

    got_ids, got_d = si.query(q, 10, n_probes=n_probes)
    want_ids, want_d = _union_reference(si, emb, live, q, 10, n_probes)
    np.testing.assert_array_equal(np.asarray(got_ids), want_ids)
    np.testing.assert_array_equal(np.asarray(got_d), want_d)


def test_parity_survives_compaction():
    si = SegmentedIndex(_cfg(), segment_capacity=128, insert_chunk=64, seed=3)
    emb = _data(300, seed=1)
    gids = si.insert(emb)
    si.delete(gids[100:200])
    live = np.ones(300, bool)
    live[100:200] = False
    q = _data(6, seed=2, scale=0.9)
    before, _ = si.query(q, 10, n_probes=4)

    si.compact()
    assert si.n_live == 200
    assert si.n_items == 200                # tombstones physically gone
    after, after_d = si.query(q, 10, n_probes=4)
    np.testing.assert_array_equal(np.asarray(before), np.asarray(after))
    want, _ = _union_reference(si, emb, live, q, 10, 4)
    np.testing.assert_array_equal(np.asarray(after), want)
    # compacted segments are repacked to standard capacity (shape reuse)
    assert all(s.capacity == 128 for s in si.segments)


@pytest.mark.parametrize("p", [1.0, 2.0])
def test_stacked_fan_out_matches_per_segment_path(p, tmp_path):
    """The one stacked program answers bit-identically to one program per
    segment, across seal, a delete spanning two segments, compaction and
    WAL replay."""
    lifecycle_parity(_cfg(p), "fp32", str(tmp_path / "t.wal"),
                     np.random.default_rng(11))


def test_seal_into_headroom_adds_no_compilation():
    """The stack grows by doubling; a seal into its headroom is a slot
    write and reuses the compiled program, and a sealed delete is one
    scatter whose shape depends only on the gid count."""
    si = SegmentedIndex(_cfg(), segment_capacity=64, insert_chunk=32, seed=3,
                        tenant="headroom")
    gids = si.insert(_data(200, seed=1))        # 3 sealed + delta
    q = _data(8, seed=2, scale=0.9)
    si.query(q, 10, n_probes=4)                  # a stack of 3 slots
    si.insert(_data(64, seed=4))                 # 4 sealed: grows to 6
    si.query(q, 10, n_probes=4)
    width = si._placement.per_dev
    assert width == 6
    fn = segmod._stacked_query_fn(si.cfg, 10, 4, si.backend, False)
    programs = fn._cache_size()
    for seed in (5, 6):                          # 5 and 6 sealed
        si.insert(_data(64, seed=seed))
        assert_paths_agree(si, q)
        pl = si._placement
        assert pl.per_dev == width and pl.diffed
        assert pl.n_sealed == sum(s.sealed for s in si.segments)
    assert fn._cache_size() == programs

    clear = seg_placement._live_clearer(si._placement.mesh,
                                        si._placement.axis)
    si.delete(gids[:3])                          # three rows of segment 0
    cleared = clear._cache_size()
    si.delete(gids[[70, 140, 199]])              # segments 1, 2 and 3
    assert clear._cache_size() == cleared
    got = assert_paths_agree(si, q)
    assert not np.isin(got[0], gids[[0, 1, 2, 70, 140, 199]]).any()
    assert fn._cache_size() == programs


def test_one_fan_out_program_per_batch():
    reg = ServableRegistry()
    sv = reg.register(_spec("one-program", segment_capacity=64,
                            chunk_sizes=(8,)))
    sv.insert(_data(300, seed=1))
    before = fanout_batches("one-program", "stacked")
    sv.query(_data(5, seed=2), 10, n_probes=4)
    sv.query(_data(8, seed=3), 10, n_probes=4)
    assert fanout_batches("one-program", "stacked") == before + 2
    assert fanout_batches("one-program", "per_segment") == 0


def test_fanout_counts_where_candidates_are_deduped():
    """A stacked batch over segments whose rows fit the block kernel
    drops repeated and tombstoned candidates in the kernel's walk
    (``where="kernel"``) and answers as an index on the reference
    backend, whose first-seen table does it (``where="table"``)."""
    data, q = _data(300, seed=1), _data(5, seed=2, scale=0.9)
    out = {}
    for backend, where in (("interpret", "kernel"), ("reference", "table")):
        si = SegmentedIndex(_cfg(), segment_capacity=64, insert_chunk=32,
                            seed=3, tenant=f"dedup-{where}", backend=backend)
        gids = si.insert(data)
        si.delete(gids[::7])
        before = fanout_batches(si.tenant, "stacked")
        out[where] = [np.asarray(x) for x in si.query(q, 10, n_probes=2)]
        assert fanout_batches(si.tenant, "stacked") == before + 1
        assert fanout_dedups(si.tenant, where) == 1
        assert fanout_dedups(si.tenant, {"kernel": "table",
                                         "table": "kernel"}[where]) == 0
    (g_k, d_k), (g_t, d_t) = out["kernel"], out["table"]
    np.testing.assert_array_equal(g_k, g_t)
    np.testing.assert_allclose(d_k, d_t, rtol=1e-6)
    assert (g_k >= 0).any() and not np.isin(g_k, gids[::7]).any()
    for row in g_k:
        assert len(set(row[row >= 0].tolist())) == int((row >= 0).sum())


def test_fanout_telemetry_matches_per_segment_path():
    """Wins and per-segment candidate counts come out the same from the
    stacked program's one count vector as from the per-segment shards."""
    calls = []
    si = SegmentedIndex(_cfg(), segment_capacity=64, insert_chunk=32, seed=3,
                        tenant="tele-paths",
                        on_fanout=lambda *a: calls.append(a))
    gids = si.insert(_data(300, seed=1))
    si.delete(gids[::9])
    stacked, per_segment = both_paths(si, _data(9, seed=2, scale=0.9))
    for a, b in zip(stacked, per_segment):
        np.testing.assert_array_equal(a, b)
    (wins, dev_wins, cands), again = calls
    assert again == (wins, dev_wins, cands)
    assert dev_wins is None
    assert len(wins) == len(cands) == len(si.segments)
    assert sum(wins) == int((stacked[0] >= 0).sum())
    assert all(c >= w for c, w in zip(cands, wins)) and sum(cands) > 0


def test_segment_lifecycle_and_occupancy():
    si = SegmentedIndex(_cfg(), segment_capacity=64, insert_chunk=32)
    g1 = si.insert(_data(40, seed=5))
    assert len(si.segments) == 1 and not si.delta.sealed
    g2 = si.insert(_data(40, seed=6))
    assert len(si.segments) == 2            # rolled over at 64
    assert si.segments[0].sealed and not si.delta.sealed
    assert si.n_items == 80
    si.delete(np.concatenate([g1[:10], g2[-5:]]))
    rep = occupancy_report(si)
    assert rep["n_live"] == 65
    assert 0 < rep["tombstone_frac"] < 1
    # deleting twice is a no-op; unknown gids are ignored
    assert si.delete(g1[:10]) == 0
    assert si.delete([10 ** 6]) == 0


def test_empty_and_single_item_queries():
    si = SegmentedIndex(_cfg(), segment_capacity=64)
    q = _data(4, seed=7)
    ids, dists = si.query(q, 5)
    assert np.all(np.asarray(ids) == -1)
    assert np.all(np.isinf(np.asarray(dists)))
    si.insert(_data(1, seed=8))
    ids, dists = si.query(np.asarray(_data(1, seed=8)), 5)
    assert int(np.asarray(ids)[0, 0]) == 0
    assert np.asarray(dists)[0, 0] < 1e-5
    assert np.all(np.asarray(ids)[0, 1:] == -1)


def test_user_supplied_gids_and_duplicates():
    si = SegmentedIndex(_cfg(), segment_capacity=64)
    si.insert(_data(3, seed=9), gids=[100, 200, 300])
    with pytest.raises(ValueError):
        si.insert(_data(1, seed=10), gids=[200])
    with pytest.raises(ValueError, match="duplicate"):
        si.insert(_data(2, seed=10), gids=[400, 400])
    with pytest.raises(ValueError, match="sentinel"):
        si.insert(_data(1, seed=10), gids=[-1])
    ids, _ = si.query(_data(3, seed=9), 1)
    assert sorted(np.asarray(ids)[:, 0].tolist()) == [100, 200, 300]


def test_delete_duplicate_gids_in_one_call():
    """Duplicate gids in a single delete must count (and decrement) once."""
    si = SegmentedIndex(_cfg(), segment_capacity=64)
    g = si.insert(_data(10, seed=20))
    assert si.delete([g[3], g[3], g[3], g[4]]) == 2
    assert si.n_live == 8
    rep = occupancy_report(si)
    assert rep["n_live"] == 8 and rep["tombstone_frac"] == pytest.approx(0.2)


def test_merge_topk_helper():
    d = jnp.asarray([[0.5, 0.1, np.inf, 0.3, 0.2]])
    i = jnp.asarray([[7, 3, -1, 9, 4]])
    md, mi = ops.merge_topk(d, i, 3)
    assert mi.tolist() == [[3, 4, 9]]
    np.testing.assert_allclose(np.asarray(md), [[0.1, 0.2, 0.3]])
    # fewer shards than k -> -1/inf padded
    md, mi = ops.merge_topk(d[:, :2], i[:, :2], 4)
    assert mi.tolist() == [[3, 7, -1, -1]]
    # deterministic distance-tie break by id
    md, mi = ops.merge_topk(jnp.asarray([[0.5, 0.5, 0.5]]),
                            jnp.asarray([[9, 2, 5]]), 2)
    assert mi.tolist() == [[2, 5]]


# ---------------------------------------------------------------------------
# shard_balance telemetry edge cases (the auto replication policy's input)
# ---------------------------------------------------------------------------


def test_shard_balance_zero_candidate_reports():
    """A merge where no segment offered a candidate (empty index, all
    tombstoned, cold probe set) must report cleanly -- no division by zero,
    empty win rates, zero imbalance -- because "auto" replication reads
    these fields verbatim."""
    st = ServingStats()
    st.record_fanout([0, 0], dev_wins=[0], seg_candidates=[0, 0])
    bal = st.shard_balance()
    assert bal["n_sampled"] == 1
    assert bal["per_segment_wins"] == [0, 0]
    assert bal["per_segment_candidates"] == [0, 0]
    assert bal["merge_win_rate"] == []
    assert bal["device_imbalance"] == 0.0
    assert bal["device_load_imbalance"] == 0.0
    # an index with no live items produces exactly such a report
    si = SegmentedIndex(_cfg(), segment_capacity=64,
                        on_fanout=st.record_fanout)
    si.insert(_data(5, seed=0))
    si.delete(list(range(5)))
    ids, _ = si.query(_data(3, seed=1), 5)
    assert np.all(np.asarray(ids) == -1)
    assert sum(st.shard_balance()["per_segment_wins"]) == 0


def test_shard_balance_single_device_imbalance_is_exactly_one():
    """On a 1-device mesh every win lands on device 0, so max/mean must be
    exactly 1.0 (not approximately): the baseline "perfectly balanced"
    anchor the auto policy compares against."""
    st = ServingStats()
    for wins in ([3], [11], [5]):
        st.record_fanout([wins[0]], dev_wins=wins, dev_load=[1])
    bal = st.shard_balance()
    assert bal["device_imbalance"] == 1.0
    assert bal["device_load_imbalance"] == 1.0

    from repro import compat
    st2 = ServingStats()
    si = SegmentedIndex(_cfg(), segment_capacity=64, insert_chunk=32,
                        on_fanout=st2.record_fanout)
    emb = _data(150, seed=2)
    si.insert(emb)
    si.shard(compat.make_mesh((1,), ("serve",)))
    si.query(emb[:6] * 0.98, 10, n_probes=4)
    bal = st2.shard_balance()
    assert sum(bal["per_device_wins"]) > 0
    assert bal["device_imbalance"] == 1.0


def test_shard_balance_wins_after_compact_replacement():
    """Counters are positional and survive a compact() re-placement: the
    post-compaction segment set keeps accumulating into the same slots, the
    report stays internally consistent, and the delta's trailing slot (what
    Servable.compact strips before deriving auto factors) is still last."""
    st = ServingStats()
    si = SegmentedIndex(_cfg(), segment_capacity=64, insert_chunk=32,
                        on_fanout=st.record_fanout)
    emb = _data(200, seed=3)
    gids = si.insert(emb)                        # 3 sealed + delta
    q = emb[:6] * 0.98
    si.query(q, 10, n_probes=4)
    pre = st.shard_balance()
    n_slots_pre = len(pre["per_segment_wins"])
    assert n_slots_pre == len(si.segments)

    si.delete(gids[::4])
    si.compact()                                 # re-placement: new segments
    si.query(q, 10, n_probes=4)
    post = st.shard_balance()
    assert post["n_sampled"] == 2
    # positional accumulation: slot count only grows to the max seen
    assert len(post["per_segment_wins"]) >= n_slots_pre
    assert sum(post["per_segment_wins"]) > sum(pre["per_segment_wins"])
    assert sum(abs(r) for r in post["merge_win_rate"]) == pytest.approx(
        1.0, abs=0.01)
    # the sealed-only prefix the auto policy consumes is well-formed
    sealed_wins = post["per_segment_wins"][:-1]
    assert len(sealed_wins) == len(post["per_segment_wins"]) - 1
    assert all(w >= 0 for w in sealed_wins)


# ---------------------------------------------------------------------------
# micro-batcher
# ---------------------------------------------------------------------------


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _echo_query_fn(calls):
    """Fake query fn recording padded shapes; 'ids' echo row checksums so
    per-request slicing is verifiable."""
    def fn(q, k, n_probes):
        calls.append(q.shape)
        ids = np.tile(np.round(q.sum(axis=1)).astype(np.int32)[:, None],
                      (1, k))
        return ids, np.zeros((q.shape[0], k), np.float32)
    return fn


def test_batcher_coalesces_and_pads_to_palette():
    calls = []
    clock = _FakeClock()
    b = MicroBatcher(_echo_query_fn(calls), chunk_sizes=(4, 16),
                     max_delay_ms=5.0, clock=clock)
    futs = [b.submit(np.full((3, 8), i, np.float32), k=2) for i in range(3)]
    assert b.pump() == 0                    # 9 rows < 16, deadline not hit
    clock.t = 0.006
    assert b.pump() == 1                    # deadline flush, one batch
    assert calls == [(16, 8)]               # padded to palette, not to 9
    for i, f in enumerate(futs):            # rows routed back correctly
        ids, _ = f.result(timeout=1)
        assert ids.shape == (3, 2) and np.all(ids == 8 * i)


def test_batcher_full_chunk_flushes_without_deadline():
    calls = []
    b = MicroBatcher(_echo_query_fn(calls), chunk_sizes=(4, 16),
                     max_delay_ms=10_000.0, clock=_FakeClock())
    b.submit(np.zeros((20, 8), np.float32), k=1)
    assert b.pump() == 2                    # 16 + pad(4): no deadline needed
    assert calls == [(16, 8), (4, 8)]


def test_batcher_segregates_signatures_and_bounds_shapes():
    calls = []
    b = MicroBatcher(_echo_query_fn(calls), chunk_sizes=(4, 16),
                     max_delay_ms=5.0, clock=_FakeClock())
    rng = np.random.default_rng(0)
    for i in range(40):
        b.submit(rng.normal(size=(int(rng.integers(1, 7)), 8)), k=2,
                 n_probes=1 + (i % 2))
    b.flush_all()
    # 40 heterogeneous requests, but only palette x signatures shapes
    assert set(c[0] for c in calls) <= {4, 16}
    assert b.unique_shapes() <= 2 * 2
    assert b.pending() == 0


def test_batcher_propagates_errors():
    def boom(q, k, n_probes):
        raise RuntimeError("kernel exploded")
    b = MicroBatcher(boom, chunk_sizes=(4,), max_delay_ms=0.0,
                     clock=_FakeClock())
    f = b.submit(np.zeros((2, 8), np.float32), k=1)
    b.flush_all()
    with pytest.raises(RuntimeError, match="kernel exploded"):
        f.result(timeout=1)


def test_batcher_malformed_request_fails_futures_not_batcher():
    """A width-mismatched request poisons np.concatenate; every co-queued
    future must resolve with the error (not hang) and the batcher must keep
    serving afterwards."""
    calls = []
    b = MicroBatcher(_echo_query_fn(calls), chunk_sizes=(4,),
                     max_delay_ms=0.0, clock=_FakeClock())
    f1 = b.submit(np.zeros((2, 8), np.float32), k=1)
    f2 = b.submit(np.zeros((2, 16), np.float32), k=1)   # wrong width
    b.flush_all()
    with pytest.raises(ValueError):
        f1.result(timeout=1)
    with pytest.raises(ValueError):
        f2.result(timeout=1)
    f3 = b.submit(np.full((2, 8), 4.0, np.float32), k=1)
    b.flush_all()
    ids, _ = f3.result(timeout=1)
    assert np.all(ids == 32)                            # still serving


def test_batcher_matches_direct_query():
    si = SegmentedIndex(_cfg(), segment_capacity=128)
    si.insert(_data(100, seed=11))
    b = MicroBatcher(lambda q, k, npb: tuple(
        map(np.asarray, si.query(q, k, n_probes=npb))), chunk_sizes=(8, 32))
    q = _data(13, seed=12, scale=0.9)
    f1 = b.submit(q[:5], 10, 2)
    f2 = b.submit(q[5:], 10, 2)
    b.flush_all()
    got = np.concatenate([f1.result()[0], f2.result()[0]])
    want, _ = si.query(q, 10, n_probes=2)
    np.testing.assert_array_equal(got, np.asarray(want))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def _spec(name, **kw):
    base = dict(name=name, n_dims=N_DIMS, r=2.0, log2_buckets=8,
                bucket_capacity=64, segment_capacity=128, insert_chunk=64,
                chunk_sizes=(8, 32), max_delay_ms=2.0)
    base.update(kw)
    return ServableSpec(**base)


def test_registry_multi_tenant_isolation():
    reg = ServableRegistry()
    a = reg.register(_spec("l2", p=2.0, embedder="basis"))
    c = reg.register(_spec("l1", p=1.0, embedder="qmc"))
    with pytest.raises(ValueError):
        reg.register(_spec("l2"))
    emb = _data(50, seed=13)
    a.insert(emb)
    assert c.index.n_items == 0             # tenants share nothing
    ids_a, _ = a.query(emb[:4], 5)
    assert np.all(np.asarray(ids_a)[:, 0] == np.arange(4))
    rep = reg.report()
    assert rep["l2"]["occupancy"]["n_live"] == 50
    assert rep["l1"]["occupancy"]["n_live"] == 0
    assert rep["l2"]["spec"]["p"] == 2.0 and rep["l1"]["spec"]["p"] == 1.0
    reg.unregister("l1")
    assert reg.names() == ["l2"]
    with pytest.raises(KeyError):
        reg.get("l1")


def test_registry_snapshot_restore_roundtrip():
    reg = ServableRegistry()
    sv = reg.register(_spec("t", p=1.0))
    emb = _data(200, seed=14)
    gids = sv.insert(emb)
    sv.delete(gids[::3])
    q = _data(5, seed=15, scale=0.9)
    want, want_d = sv.index.query(q, 10, n_probes=4)

    with tempfile.TemporaryDirectory() as d:
        reg.snapshot(d, step=7)
        reg2 = ServableRegistry()
        assert reg2.restore(d) == ["t"]
        sv2 = reg2.get("t")
        got, got_d = sv2.index.query(q, 10, n_probes=4)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))
        # restored instance stays mutable and gid-consistent
        new = sv2.insert(_data(8, seed=16))
        assert new.min() == 200
        assert sv2.index.delete(gids[1:2]) == 1


def test_recall_proxy_and_embedders():
    reg = ServableRegistry()
    sv = reg.register(_spec("t", embedder="basis"))
    rng = np.random.default_rng(17)
    fvals = rng.normal(size=(120, N_DIMS))
    emb = np.asarray(sv.embed(fvals))
    assert emb.shape == (120, N_DIMS)
    sv.insert(emb)
    rec = recall_proxy(sv.index, emb[:10], 1, n_probes=4)
    assert rec == 1.0                       # self-queries always collide
    qsv = reg.register(_spec("q", embedder="qmc", p=1.0))
    assert np.asarray(qsv.embed(fvals)).shape == (120, N_DIMS)
    with pytest.raises(ValueError):
        ServableSpec(name="bad", embedder="nope")
