"""LSH index: recall, multi-probe, static-shape build/query."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import functional, index as lidx, wasserstein
from repro.kernels import ref


def _build(key, n_db=1024, n_dims=32, **kw):
    cfg = lidx.IndexConfig(n_dims=n_dims, n_tables=kw.get("n_tables", 8),
                           n_hashes=4, log2_buckets=9,
                           bucket_capacity=kw.get("cap", 64),
                           r=kw.get("r", 0.5))
    db = jax.random.normal(jax.random.fold_in(key, 1), (n_db, n_dims))
    state = lidx.create_index(jax.random.fold_in(key, 2), cfg, n_db)
    state = lidx.build_index(state, cfg, db)
    return cfg, db, state


def test_self_query_recall(rng_key):
    """Every item must find itself (distance 0 -> always collides)."""
    cfg, db, state = _build(rng_key, n_db=256)
    ids, dists = lidx.query_index(state, cfg, db[:64], k=1)
    assert float((ids[:, 0] == jnp.arange(64)).mean()) == 1.0
    np.testing.assert_allclose(np.asarray(dists[:, 0]), 0.0, atol=1e-5)


def test_recall_vs_bruteforce(rng_key):
    # r must match the distance scale: random 32-d normals have nearest
    # neighbours at c ~ 5, so r ~ c gives per-hash p1 ~ 0.5.
    cfg, db, state = _build(rng_key, n_db=2048, n_tables=16, r=4.0)
    q = jax.random.normal(jax.random.fold_in(rng_key, 3), (32, 32)) * 0.9
    exact, _ = lidx.brute_force_topk(db, q, 10)
    ids, _ = lidx.query_index(state, cfg, q, 10, n_probes=6)
    rec = float(lidx.recall_at_k(ids, exact))
    assert rec > 0.5, rec


def test_multiprobe_improves_recall(rng_key):
    cfg, db, state = _build(rng_key, n_db=2048, n_tables=4)
    q = jax.random.normal(jax.random.fold_in(rng_key, 3), (32, 32)) * 0.9
    exact, _ = lidx.brute_force_topk(db, q, 10)
    r1 = float(lidx.recall_at_k(
        lidx.query_index(state, cfg, q, 10, n_probes=1)[0], exact))
    r4 = float(lidx.recall_at_k(
        lidx.query_index(state, cfg, q, 10, n_probes=6)[0], exact))
    assert r4 >= r1


def test_build_and_query_are_jittable(rng_key):
    cfg, db, state = _build(rng_key, n_db=512)
    jq = jax.jit(lambda s, q: lidx.query_index(s, cfg, q, 5, n_probes=2))
    ids, dists = jq(state, db[:8])
    assert ids.shape == (8, 5)


def test_bucket_counts_match_items(rng_key):
    cfg, db, state = _build(rng_key, n_db=512)
    counts = np.asarray(state.counts)
    assert counts.sum() == 512 * cfg.n_tables  # every item counted per table


def test_w2_retrieval_end_to_end(rng_key):
    """Gaussian W2 search: LSH top-1 close to true nearest in W2."""
    mu, s = functional.random_gaussians(jax.random.fold_in(rng_key, 1), 2048)
    qmu, qs = functional.random_gaussians(jax.random.fold_in(rng_key, 2), 16)
    nodes, vol = wasserstein.icdf_nodes_qmc(64)
    db = wasserstein.w2_embedding_gaussian(mu, s, nodes, vol, "mc")
    q = wasserstein.w2_embedding_gaussian(qmu, qs, nodes, vol, "mc")
    cfg = lidx.IndexConfig(n_dims=64, n_tables=16, n_hashes=4, log2_buckets=10,
                           bucket_capacity=64, r=0.5)
    state = lidx.create_index(jax.random.fold_in(rng_key, 3), cfg, 2048)
    state = lidx.build_index(state, cfg, db)
    ids, dists = lidx.query_index(state, cfg, q, 1, n_probes=4)
    true_w2 = wasserstein.gaussian_w2(qmu[:, None], qs[:, None],
                                      mu[None, :], s[None, :])
    best_true = jnp.min(true_w2, axis=1)
    got = jnp.where(ids[:, 0] >= 0,
                    true_w2[jnp.arange(16), jnp.clip(ids[:, 0], 0, 2047)],
                    jnp.inf)
    # LSH's top-1 W2 within 0.25 of the true optimum for most queries
    ok = float(((got - best_true) < 0.25).mean())
    assert ok > 0.7, ok


def test_bucket_distribution_uniformity(rng_key):
    """Bucket ids from the universal mixer spread ~uniformly (no systematic
    clustering: max bucket load within 8x of mean for gaussian data)."""
    cfg, db, state = _build(rng_key, n_db=4096, n_tables=4)
    counts = np.asarray(state.counts)           # (L, B)
    mean = 4096 / counts.shape[1]
    assert counts.max() < 8 * max(mean, 1.0) + 16
    # and hashing is deterministic: rebuilding gives identical tables
    from repro.core import index as lidx2
    state2 = lidx2.build_index(
        lidx2.create_index(jax.random.fold_in(rng_key, 2), cfg, 4096), cfg, db)
    np.testing.assert_array_equal(np.asarray(state.table),
                                  np.asarray(state2.table))


def test_dedup_drops_duplicates_and_tombstones_on_both_paths(rng_key,
                                                             monkeypatch):
    """The scatter dedup (tombstones ride in its first-seen table) and the
    sort fallback keep the same candidates: each live item once, no dead
    item, whichever slot of the stack is read."""
    cfg, db, state = _build(rng_key, n_db=512, r=4.0)
    q = jax.random.normal(jax.random.fold_in(rng_key, 3), (8, 32))
    live = jnp.asarray(np.random.default_rng(0).random(512) > 0.3)
    buckets = lidx.probe_queries(lidx.hash_family(state), cfg, q, 4)

    def kept(slot=None):
        table, mask = state.table, live
        if slot is not None:       # the segment at ``slot`` of a stack
            table = jnp.stack([jnp.full_like(table, -1), table])
            mask = jnp.stack([jnp.zeros_like(live), live])
        c = np.asarray(lidx.gather_stage(table, buckets, cfg, 512, mask,
                                         slot=slot))
        return [sorted(row[row >= 0].tolist()) for row in c]

    scatter = kept()
    assert kept(slot=jnp.int32(1)) == scatter
    monkeypatch.setattr(ref, "DEDUP_SCATTER_MAX_ELEMS", 0)
    assert kept() == scatter
    raw = np.asarray(state.table)[np.arange(cfg.n_tables)[:, None, None],
                                  np.asarray(buckets).transpose(1, 0, 2)]
    raw = raw.transpose(1, 0, 2, 3).reshape(8, -1)
    alive = np.asarray(live)
    for got, row in zip(scatter, raw):
        want = sorted({int(i) for i in row if i >= 0 and alive[i]})
        assert got == want and got
