"""Serve-layer tests for the quantized storage tier (invariant 10).

Two halves of the contract:

* **fp32 is bit-exact opt-in** -- a tenant with ``precision="fp32"``
  (explicit or default) returns results byte-for-byte identical to the
  pre-tier code path, unsharded and on a real 8-device mesh (subprocess:
  host device count locks at first jax init);
* **int8/bf16 are bounded-loss** -- the survivor-rerank engine keeps
  recall@10 vs the exact fp32 answer within the regression gate's 0.02
  budget, sharded results match unsharded results, deletes/compaction/
  WAL replay keep working, and the sealed store actually shrinks >= 3x.
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from _fanout_support import fanout_batches, lifecycle_parity
from repro.core.index import IndexConfig
from repro.serve import SegmentedIndex, ServableRegistry, ServableSpec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = IndexConfig(n_dims=16, n_tables=8, n_hashes=2, log2_buckets=8,
                  bucket_capacity=32)


def _recall(got: np.ndarray, want: np.ndarray) -> float:
    hits = [len(set(a[a >= 0]) & set(b[b >= 0])) / max(1, (b >= 0).sum())
            for a, b in zip(got, want)]
    return float(np.mean(hits))


def _pair(precision, n=400, seed=3):
    rng = np.random.default_rng(seed)
    db = rng.normal(size=(n, CFG.n_dims)).astype(np.float32)
    q = rng.normal(size=(5, CFG.n_dims)).astype(np.float32)
    base = SegmentedIndex(CFG, segment_capacity=64, seed=1)
    tier = SegmentedIndex(CFG, segment_capacity=64, seed=1,
                          precision=precision)
    base.insert(db)
    tier.insert(db)
    return base, tier, q


def test_fp32_tier_bit_identical_unsharded():
    base, tier, q = _pair("fp32")
    gb, db = base.query(q, 10, n_probes=4)
    gt, dt = tier.query(q, 10, n_probes=4)
    np.testing.assert_array_equal(np.asarray(gb), np.asarray(gt))
    np.testing.assert_array_equal(np.asarray(db), np.asarray(dt))
    # structurally untouched: no quantized representation was ever built
    assert all(s.scale is None and s.pool is None for s in tier.segments)
    assert all(s.state.db.dtype == jnp.float32 for s in tier.segments)


def test_int8_recall_and_bytes_unsharded():
    base, tier, q = _pair("int8")
    gb, _ = base.query(q, 10, n_probes=4)
    gt, _ = tier.query(q, 10, n_probes=4)
    assert _recall(np.asarray(gt), np.asarray(gb)) >= 0.98
    sealed_t = [s for s in tier.segments if s.sealed]
    sealed_b = [s for s in base.segments if s.sealed]
    assert sealed_t, "test needs sealed segments to quantize"
    bt = sum(int(s.state.db.nbytes) for s in sealed_t)
    bb = sum(int(s.state.db.nbytes) for s in sealed_b)
    assert bt * 3 <= bb                      # >= 3x sealed-store reduction
    assert all(s.state.db.dtype == jnp.int8 for s in sealed_t)


def test_quantized_delete_compact_and_exact_live_items():
    _, tier, q = _pair("int8")
    emb0, gid0 = tier.live_items()
    assert emb0.dtype == np.float32          # pools serve exact rows
    tier.delete(gid0[:50])
    tier.compact()
    emb1, gid1 = tier.live_items()
    # compaction rebuilt from the pools: surviving rows are bit-exact
    keep = np.isin(gid0, gid1)
    order0 = np.argsort(gid0[keep])
    order1 = np.argsort(gid1)
    np.testing.assert_array_equal(emb0[keep][order0], emb1[order1])
    g, d = tier.query(q, 10, n_probes=4)
    assert not np.isin(np.asarray(g), gid0[:50]).any()


def test_survivor_k_knob_widens_pool():
    rng = np.random.default_rng(0)
    db = rng.normal(size=(300, CFG.n_dims)).astype(np.float32)
    q = rng.normal(size=(2, CFG.n_dims)).astype(np.float32)
    narrow = SegmentedIndex(CFG, segment_capacity=64, seed=1,
                            precision="int8", survivor_k=10)
    wide = SegmentedIndex(CFG, segment_capacity=64, seed=1,
                          precision="int8", survivor_k=100)
    narrow.insert(db)
    wide.insert(db)
    gn, _ = narrow.query(q, 10, n_probes=4)
    gw, _ = wide.query(q, 10, n_probes=4)
    # both are valid answers; the knob must at least be accepted and
    # produce full top-k result sets
    assert (np.asarray(gn) >= 0).all() and (np.asarray(gw) >= 0).all()


@pytest.mark.parametrize("p", [1.0, 2.0])
def test_int8_stacked_fan_out_matches_per_segment_path(p, tmp_path):
    """The stacked program scores the int8 codes slot by slot exactly as
    the per-segment programs do: same survivors, same exact rerank."""
    lifecycle_parity(dataclasses.replace(CFG, p=p), "int8",
                     str(tmp_path / "q.wal"), np.random.default_rng(12))


def test_mixed_precision_tenant_falls_back_to_per_segment():
    """fp32 segments sealed before the tenant's tier changed cannot stack
    with its int8 ones: the batch takes one program per segment."""
    rng = np.random.default_rng(5)
    db = rng.normal(size=(300, CFG.n_dims)).astype(np.float32)
    q = rng.normal(size=(5, CFG.n_dims)).astype(np.float32)
    si = SegmentedIndex(CFG, segment_capacity=64, seed=1, tenant="mixed")
    si.insert(db[:150])                      # two fp32 seals
    si.precision = "int8"                    # later seals encode to int8
    si.insert(db[150:])
    assert {s.state.db.dtype for s in si.segments if s.sealed} == {
        jnp.dtype(jnp.float32), jnp.dtype(jnp.int8)}
    g, _ = si.query(q, 10, n_probes=4)
    assert fanout_batches("mixed", "per_segment") == 1
    assert fanout_batches("mixed", "stacked") == 0
    assert si._placement is None
    base = SegmentedIndex(CFG, segment_capacity=64, seed=1)
    base.insert(db)
    gb, _ = base.query(q, 10, n_probes=4)
    assert _recall(np.asarray(g), np.asarray(gb)) >= 0.98


def test_registry_resolves_env_override_once(monkeypatch):
    monkeypatch.setenv("REPRO_STORE_DTYPE", "int8")
    reg = ServableRegistry()
    sv = reg.register(ServableSpec(name="envq", n_dims=16,
                                   segment_capacity=64))
    # the RESOLVED precision is recorded on the spec (what snapshots and
    # the WAL REGISTER record will carry), not re-read at query time
    assert sv.spec.precision == "int8"
    assert sv.index.precision == "int8"
    monkeypatch.delenv("REPRO_STORE_DTYPE")
    assert sv.index.precision == "int8"      # sticky: resolution was once


# ---------------------------------------------------------------------------
# subprocess: real 8-device mesh (device count locks at first jax init)
# ---------------------------------------------------------------------------


def _run(code: str, timeout=560) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_quantized_serve_8dev_mesh():
    """fp32 sharded stays bit-identical to unsharded; int8 sharded equals
    int8 unsharded and keeps recall@10 vs exact fp32 within the gate."""
    stdout = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core.index import IndexConfig
        from repro.serve import SegmentedIndex

        cfg = IndexConfig(n_dims=16, n_tables=8, n_hashes=2,
                          log2_buckets=8, bucket_capacity=32)
        rng = np.random.default_rng(3)
        db = rng.normal(size=(500, 16)).astype(np.float32)
        q = rng.normal(size=(5, 16)).astype(np.float32)
        mesh = Mesh(np.array(jax.devices()), ("serve",))
        assert len(jax.devices()) == 8

        def build(precision, shard):
            idx = SegmentedIndex(cfg, segment_capacity=64, seed=1,
                                 precision=precision)
            idx.insert(db)
            if shard:
                idx.shard(mesh)
            return idx

        g_ref, d_ref = build("fp32", False).query(q, 10, n_probes=4)
        g_f, d_f = build("fp32", True).query(q, 10, n_probes=4)
        assert np.array_equal(np.asarray(g_ref), np.asarray(g_f))
        assert np.array_equal(np.asarray(d_ref), np.asarray(d_f))

        g_q1, d_q1 = build("int8", False).query(q, 10, n_probes=4)
        g_q8, d_q8 = build("int8", True).query(q, 10, n_probes=4)
        assert np.array_equal(np.asarray(g_q1), np.asarray(g_q8))

        ref = np.asarray(g_ref)
        got = np.asarray(g_q8)
        rec = np.mean([len(set(a[a >= 0]) & set(b[b >= 0]))
                       / max(1, (b >= 0).sum())
                       for a, b in zip(got, ref)])
        assert rec >= 0.98, rec
        print("recall", rec)
        print("OK8")
    """)
    assert "OK8" in stdout
