"""Shared checks of the unsharded fan-out's two paths (``serve/segments.py``):
the stacked program over the stacked sealed segments, and the per-segment
programs it falls back to when the sealed segments cannot stack."""

import numpy as np
import pytest

from repro.obs import metrics as obs_metrics
from repro.serve import SegmentedIndex
from repro.serve import segments as segmod
from repro.serve import wal as walmod


def fanout_batches(tenant: str, path: str) -> float:
    return obs_metrics.registry().value("serve_fanout_batches_total",
                                        tenant=tenant, path=path) or 0


def fanout_dedups(tenant: str, where: str) -> float:
    return obs_metrics.registry().value("serve_fanout_dedup_total",
                                        tenant=tenant, where=where) or 0


def both_paths(si, q, k=10, n_probes=4):
    """``(stacked, per_segment)`` answers to ``q``, each as host arrays;
    asserts each batch took its path exactly once."""
    before = fanout_batches(si.tenant, "stacked")
    stacked = [np.asarray(x) for x in si.query(q, k, n_probes=n_probes)]
    assert fanout_batches(si.tenant, "stacked") == before + 1
    before = fanout_batches(si.tenant, "per_segment")
    with pytest.MonkeyPatch.context() as m:
        # no stack this once: the fallback, with the stack left as it was
        m.setattr(segmod.SegmentedIndex, "_current_placement",
                  lambda self: None)
        per_segment = [np.asarray(x)
                       for x in si.query(q, k, n_probes=n_probes)]
    assert fanout_batches(si.tenant, "per_segment") == before + 1
    return stacked, per_segment


def assert_paths_agree(si, q, k=10, n_probes=4):
    """The stacked answer is bit-identical to the per-segment one, and
    every segment's host live mask is its device mask; returns the
    answer."""
    for seg in si.segments:
        np.testing.assert_array_equal(seg.live_np(), np.asarray(seg.live))
        assert int(seg.live_np().sum()) == seg.n_live
    stacked, per_segment = both_paths(si, q, k, n_probes)
    for a, b in zip(stacked, per_segment):
        np.testing.assert_array_equal(a, b)
    return stacked


def lifecycle_parity(cfg, precision, wal_path, rng):
    """Both paths agree after a bulk insert, a seal, a delete spanning two
    sealed segments and the delta, a compaction and a WAL replay."""
    tenant = f"stack-{precision}-{cfg.p}"
    si = SegmentedIndex(cfg, segment_capacity=64, insert_chunk=32, seed=3,
                        tenant=tenant, precision=precision)
    si.attach_wal(walmod.WriteAheadLog(wal_path, fsync_every=0))
    q = rng.normal(size=(9, cfg.n_dims)).astype(np.float32)
    gids = si.insert(rng.normal(size=(300, cfg.n_dims)).astype(np.float32))
    assert sum(s.sealed for s in si.segments) == 4
    assert_paths_agree(si, q)
    si.insert(rng.normal(size=(20, cfg.n_dims)).astype(np.float32))
    si.maintenance.seal()
    assert_paths_agree(si, q)
    fresh = si.insert(rng.normal(size=(10, cfg.n_dims)).astype(np.float32))
    # slots 60..69 straddle sealed segments 0 and 1; ``fresh`` is the delta
    assert si.delete(np.concatenate([gids[60:70], fresh[:3]])) == 13
    assert_paths_agree(si, q)
    si.delete(gids[100:101])
    got = assert_paths_agree(si, q)
    assert not np.isin(got[0], gids[60:70]).any()
    si.maintenance.compact()
    got = assert_paths_agree(si, q)
    re = SegmentedIndex(cfg, segment_capacity=64, insert_chunk=32, seed=3,
                        tenant=tenant + "-replay", precision=precision)
    re.replay(wal_path)
    again = assert_paths_agree(re, q)
    for a, b in zip(got, again):
        np.testing.assert_array_equal(a, b)
