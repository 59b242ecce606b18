"""Fused query engine: kernel path vs jnp reference path parity.

The acceptance contract for the query engine is *bit-identical ids* (and
fp-tolerance distances) between ``backend="interpret"`` (the fused Pallas
kernel under the interpreter -- same code path the TPU compiles) and
``backend="reference"`` (HBM gather + jnp re-rank + lax.top_k).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import index as lidx
from repro.kernels import dispatch, ops, ref


def _build(key, p=2.0, cap=16, n_db=512, n_dims=32):
    cfg = lidx.IndexConfig(n_dims=n_dims, n_tables=4, n_hashes=4,
                           log2_buckets=9, bucket_capacity=cap, r=2.0, p=p)
    db = jax.random.normal(jax.random.fold_in(key, 1), (n_db, n_dims))
    state = lidx.create_index(jax.random.fold_in(key, 2), cfg, n_db)
    state = lidx.build_index(state, cfg, db)
    return cfg, db, state


def _assert_query_parity(state, cfg, q, k, **kw):
    ids_r, d_r = lidx.query_index(state, cfg, q, k, backend="reference", **kw)
    ids_f, d_f = lidx.query_index(state, cfg, q, k, backend="interpret", **kw)
    np.testing.assert_array_equal(np.asarray(ids_r), np.asarray(ids_f))
    dr, df = np.asarray(d_r), np.asarray(d_f)
    finite = np.isfinite(dr)
    assert (finite == np.isfinite(df)).all()
    np.testing.assert_allclose(df[finite], dr[finite], atol=1e-5, rtol=1e-5)
    return ids_r


@pytest.mark.parametrize("p", [1.0, 2.0])
@pytest.mark.parametrize("n_probes", [1, 4])
def test_fused_matches_reference(rng_key, p, n_probes):
    cfg, db, state = _build(rng_key, p=p)
    q = jax.random.normal(jax.random.fold_in(rng_key, 3), (8, 32))
    _assert_query_parity(state, cfg, q, 10, n_probes=n_probes)


def test_parity_with_overflowed_and_padded_buckets(rng_key):
    """capacity=2 forces bucket overflow (dropped items) AND many -1-padded
    slots; undersized db forces fewer-than-k results (-1 ids, +inf dists)."""
    cfg, db, state = _build(rng_key, cap=2, n_db=256)
    q = jax.random.normal(jax.random.fold_in(rng_key, 3), (8, 32))
    ids = _assert_query_parity(state, cfg, q, 10, n_probes=2)
    # with C = 4*2*2 = 16 slots, some queries genuinely come up short of 10
    assert (np.asarray(ids) == -1).any()


def test_parity_with_valid_items_mask(rng_key):
    cfg, db, state = _build(rng_key)
    q = jax.random.normal(jax.random.fold_in(rng_key, 3), (6, 32))
    _assert_query_parity(state, cfg, q, 5, n_probes=2, valid_items=300)


# (C, M, k, valid_items, ids, duplicated rows): the block kernel scores
# blocks of 128 candidates, so these cut C, the -1 runs and valid_items
# across blocks; the last database is past the block kernel's VMEM budget,
# so the tile kernel answers it
_OP_CASES = {
    "unit": (40, 100, 7, 60, "random", False),
    "c_not_a_block_multiple": (300, 200, 10, None, "random", False),
    "blocks_of_minus_one": (384, 200, 40, None, "empty_blocks", False),
    "valid_items_mid_block": (256, 200, 1, 150, "dense", False),
    "duplicate_rows_tie": (256, 200, 128, None, "dense", True),
    "rows_past_vmem": (64, 4100, 10, 4090, "random", False),
}


@pytest.mark.parametrize("dtype", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("p", [1.0, 2.0])
@pytest.mark.parametrize("case", sorted(_OP_CASES))
def test_fused_topk_op_unit(rng_key, case, p, dtype):
    """The query kernels on handcrafted ids against their jnp reference:
    ids bit-identical (equal distances, in the block kernel: the lower
    candidate position first, as ``lax.top_k``), distances to fp
    tolerance.  Rows f32, or bf16 / int8 codes scored in code space."""
    from repro.kernels import fused_query, quantize

    c, m, k, valid, layout, dup = _OP_CASES[case]
    nq, n = 4, 24
    assert fused_query._resident(m, n) is (case != "rows_past_vmem")
    q = jax.random.normal(jax.random.fold_in(rng_key, 1), (nq, n))
    db = jax.random.normal(jax.random.fold_in(rng_key, 2), (m, n))
    if dup:                          # rows 2r and 2r+1 equal: every id ties
        db = db.at[1::2].set(db[0::2])
    lo = -1 if layout == "random" else 0
    ids = jax.random.randint(jax.random.fold_in(rng_key, 3), (nq, c), lo, m)
    if layout == "empty_blocks":     # whole blocks of -1, and a row of them
        ids = ids.at[:, :128].set(-1).at[:, 256:].set(-1).at[2].set(-1)
    if dtype == "f32":
        d_k, i_k = fused_query.fused_query_topk(q, db, ids, k, p=p,
                                                valid_items=valid)
        d_r, i_r = ref.fused_query_topk_ref(q, db, ids, k, p=p,
                                            valid_items=valid)
    else:
        codes, scale = quantize.encode(db, "int8" if dtype == "int8" else
                                       "bf16")
        d_k, i_k = quantize.quantized_query_topk(q, codes, scale, ids, k,
                                                 p=p, valid_items=valid)
        d_r, i_r = quantize.quantized_topk_ref(q, codes, scale, ids, k, p,
                                               valid)
    np.testing.assert_array_equal(np.asarray(i_k), np.asarray(i_r))
    fin = np.isfinite(np.asarray(d_r))
    assert (fin == np.isfinite(np.asarray(d_k))).all()
    np.testing.assert_allclose(np.asarray(d_k)[fin], np.asarray(d_r)[fin],
                               atol=1e-5, rtol=1e-5)
    if layout == "empty_blocks":
        assert (np.asarray(i_k)[2] == -1).all()


# In-walk dedup (``dedup=True``): (layout, M, valid_items, nq).  C = 8
# tables x 32 slots, as a served segment's candidates come in table order.
_DEDUP_CASES = {
    "mixed": ("mixed", 200, None, 4),
    "repeated_across_tables": ("tables", 200, None, 4),
    "all_dead": ("all_dead", 200, None, 4),
    "all_empty": ("all_empty", 200, None, 4),
    "valid_items_lt_m": ("mixed", 200, 120, 4),
    "several_smem_calls": ("mixed", 200, None, 8),
}


def _dedup_inputs(key, layout, m, nq, c=256, n=24):
    """Queries, rows (rows 2r and 2r+1 equal, so every distance ties with
    another id's), candidate ids with repeats and -1 slots, a live mask."""
    q = jax.random.normal(jax.random.fold_in(key, 1), (nq, n))
    db = jax.random.normal(jax.random.fold_in(key, 2), (m, n))
    db = db.at[1::2].set(db[0::2])
    ids = jax.random.randint(jax.random.fold_in(key, 3), (nq, c), -1, m // 2)
    live = jax.random.uniform(jax.random.fold_in(key, 4), (m,)) >= 0.3
    if layout == "tables":        # each table's 32 slots in every table
        ids = jnp.tile(ids[:, :c // 8], (1, 8))
    elif layout == "all_dead":
        live = jnp.zeros((m,), bool)
    elif layout == "all_empty":
        ids = jnp.full((nq, c), -1, jnp.int32)
    return q, db, ids, live


def _assert_dedup_parity(q, db, ids, live, k, p, dtype, valid=None):
    """The kernel's in-walk dedup against the first-seen table: ids equal
    the reference top-k over ``ref.dedup_candidates``' survivors, and
    distances equal, bit for bit, the kernel's over those survivors (the
    path the table fed before); returns the ids."""
    from repro.kernels import fused_query, quantize

    m = db.shape[0]
    kept = ref.dedup_candidates(ids, m, live)
    if dtype == "f32":
        d_k, i_k = fused_query.fused_query_topk(q, db, ids, k, p=p,
                                                valid_items=valid, live=live,
                                                dedup=True)
        d_t, i_t = fused_query.fused_query_topk(q, db, kept, k, p=p,
                                                valid_items=valid)
        d_r, i_r = ref.fused_query_topk_ref(q, db, kept, k, p, valid)
    else:
        codes, scale = quantize.encode(db, dtype)
        d_k, i_k = quantize.quantized_query_topk(q, codes, scale, ids, k,
                                                 p=p, valid_items=valid,
                                                 live=live, dedup=True)
        d_t, i_t = quantize.quantized_query_topk(q, codes, scale, kept, k,
                                                 p=p, valid_items=valid)
        d_r, i_r = quantize.quantized_topk_ref(q, codes, scale, kept, k, p,
                                               valid)
    i_k, d_k = np.asarray(i_k), np.asarray(d_k)
    np.testing.assert_array_equal(i_k, np.asarray(i_r))
    np.testing.assert_array_equal(i_k, np.asarray(i_t))
    np.testing.assert_array_equal(d_k, np.asarray(d_t))
    fin = np.isfinite(np.asarray(d_r))
    assert (fin == np.isfinite(d_k)).all()
    np.testing.assert_allclose(d_k[fin], np.asarray(d_r)[fin], atol=1e-5,
                               rtol=1e-5)
    return i_k


@pytest.mark.parametrize("dtype", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("p", [1.0, 2.0])
@pytest.mark.parametrize("k", [10, 40])
def test_block_kernel_dedup_matches_first_seen_table(rng_key, k, p, dtype):
    """Repeats, 30% tombstones, -1 slots and tied distances: the block
    kernel keeps exactly the first-seen table's survivors."""
    q, db, ids, live = _dedup_inputs(rng_key, "mixed", 200, 4)
    got = _assert_dedup_parity(q, db, ids, live, k, p, dtype)
    dead = np.flatnonzero(~np.asarray(live))
    assert (got >= 0).any() and not np.isin(got, dead).any()
    for row in got:
        real = row[row >= 0]
        assert len(real) == len(set(real.tolist()))


@pytest.mark.parametrize("dtype", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("case", sorted(_DEDUP_CASES))
def test_block_kernel_dedup_layouts(rng_key, monkeypatch, case, dtype):
    """Every id repeated across the 8 tables, every row dead, every slot
    empty, ``valid_items < M``, and queries split over several kernel
    calls (each call fills its own first-seen table)."""
    from repro.kernels import fused_query

    layout, m, valid, nq = _DEDUP_CASES[case]
    q, db, ids, live = _dedup_inputs(rng_key, layout, m, nq)
    assert fused_query._resident(m, q.shape[1])
    if case == "several_smem_calls":   # three queries' ids a call
        monkeypatch.setattr(fused_query, "_SMEM_ID_BYTES",
                            3 * 4 * ids.shape[1])
    got = _assert_dedup_parity(q, db, ids, live, 10, 2.0, dtype, valid)
    if layout in ("all_dead", "all_empty"):
        assert (got == -1).all()


@pytest.mark.parametrize("where", ["tile", "reference"])
def test_dedup_outside_the_block_kernel(rng_key, where):
    """Rows past the block kernel's VMEM budget (the tile kernel) and the
    reference backend dedup through the first-seen table ahead of the
    scoring: the same answers as scoring its survivors."""
    from repro.kernels import fused_query

    m = 4100 if where == "tile" else 200
    q, db, ids, live = _dedup_inputs(rng_key, "mixed", m, 4)
    assert fused_query._resident(m, q.shape[1]) is (where != "tile")
    backend = "interpret" if where == "tile" else "reference"
    site = ops.dedup_site(m, q.shape[1], backend)
    assert site == "table"
    d, i = ops.fused_query_topk(q, db, ids, 10, backend=backend, live=live,
                                dedup=True)
    kept = ref.dedup_candidates(ids, m, live)
    d_t, i_t = ops.fused_query_topk(q, db, kept, 10, backend=backend)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_t))
    np.testing.assert_array_equal(np.asarray(d), np.asarray(d_t))
    got = np.asarray(i)
    assert not np.isin(got, np.flatnonzero(~np.asarray(live))).any()
    for row in got:
        real = row[row >= 0]
        assert len(real) == len(set(real.tolist()))
    assert ops.dedup_site(200, q.shape[1], "interpret") == "kernel"


def test_batched_query_matches_unbatched(rng_key):
    cfg, db, state = _build(rng_key)
    q = jax.random.normal(jax.random.fold_in(rng_key, 3), (37, 32))
    ids, dists = lidx.query_index(state, cfg, q, 5, n_probes=2)
    ids_b, dists_b = lidx.query_index_batched(state, cfg, q, 5, n_probes=2,
                                              batch_size=16)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_b))
    fin = np.isfinite(np.asarray(dists))
    np.testing.assert_allclose(np.asarray(dists_b)[fin],
                               np.asarray(dists)[fin], atol=1e-6)


def _assert_batched_parity(state, cfg, q, k, batch_size, **kw):
    ids, dists = lidx.query_index(state, cfg, q, k, **kw)
    ids_b, dists_b = lidx.query_index_batched(state, cfg, q, k,
                                              batch_size=batch_size, **kw)
    assert ids_b.shape == (q.shape[0], k)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_b))
    d, db_ = np.asarray(dists), np.asarray(dists_b)
    fin = np.isfinite(d)
    assert (fin == np.isfinite(db_)).all()
    np.testing.assert_allclose(db_[fin], d[fin], atol=1e-6)


def test_batched_query_ragged_last_chunk(rng_key):
    """nq not divisible by batch_size: the zero-padded tail chunk must not
    leak padding rows or corrupt real results."""
    cfg, db, state = _build(rng_key)
    q = jax.random.normal(jax.random.fold_in(rng_key, 4), (21, 32))
    _assert_batched_parity(state, cfg, q, 5, batch_size=8, n_probes=2)
    # pad rows are all-zeros queries; a pathological all-zero real query in
    # the ragged chunk must still round-trip
    q0 = q.at[20].set(0.0)
    _assert_batched_parity(state, cfg, q0, 5, batch_size=8, n_probes=2)


def test_batched_query_smaller_than_one_chunk(rng_key):
    """nq < batch_size delegates to the unbatched path, shapes intact."""
    cfg, db, state = _build(rng_key)
    q = jax.random.normal(jax.random.fold_in(rng_key, 5), (3, 32))
    _assert_batched_parity(state, cfg, q, 5, batch_size=64, n_probes=2)
    # exact multiple boundary: nq == batch_size (no pad chunk at all)
    q16 = jax.random.normal(jax.random.fold_in(rng_key, 6), (16, 32))
    _assert_batched_parity(state, cfg, q16, 5, batch_size=16, n_probes=2)


def test_batched_query_empty_index(rng_key):
    """All buckets empty (create without build): every id must be -1 with
    +inf distance, identically in batched and unbatched paths."""
    cfg = lidx.IndexConfig(n_dims=32, n_tables=4, n_hashes=4, log2_buckets=9,
                           bucket_capacity=16, r=2.0)
    state = lidx.create_index(rng_key, cfg, 512)   # no build_index
    q = jax.random.normal(jax.random.fold_in(rng_key, 7), (21, 32))
    for bs in (8, 64):
        ids_b, dists_b = lidx.query_index_batched(state, cfg, q, 5,
                                                  n_probes=2, batch_size=bs)
        assert np.all(np.asarray(ids_b) == -1)
        assert np.all(np.isinf(np.asarray(dists_b)))
    _assert_batched_parity(state, cfg, q, 5, batch_size=8, n_probes=2)


def test_batched_query_live_mask(rng_key):
    """live_mask flows through the batched path (chunked + delegated)."""
    cfg, db, state = _build(rng_key)
    q = jax.random.normal(jax.random.fold_in(rng_key, 8), (21, 32))
    dead = np.zeros(512, bool)
    dead[::3] = True
    mask = jnp.asarray(~dead)
    for bs in (8, 64):
        ids_b, _ = lidx.query_index_batched(state, cfg, q, 5, n_probes=2,
                                            batch_size=bs, live_mask=mask)
        got = np.asarray(ids_b)
        assert not np.isin(got[got >= 0], np.flatnonzero(dead)).any()
    _assert_batched_parity(state, cfg, q, 5, batch_size=8, n_probes=2,
                           live_mask=mask)


def test_hash_proj_kernel_matches_reference(rng_key):
    """The multi-probe pair (hashes, projections) from the kernel epilogue."""
    x = jax.random.normal(jax.random.fold_in(rng_key, 1), (33, 48))
    alpha = jax.random.normal(jax.random.fold_in(rng_key, 2), (48, 24))
    b = jax.random.uniform(jax.random.fold_in(rng_key, 3), (24,))
    h_k, p_k = ops.pstable_hash_proj(x, alpha, b, 0.7, backend="interpret")
    h_r, p_r = ref.hash_mm_proj_ref(x, alpha, b, 0.7)
    np.testing.assert_array_equal(np.asarray(h_k), np.asarray(h_r))
    np.testing.assert_allclose(np.asarray(p_k), np.asarray(p_r),
                               atol=1e-5, rtol=1e-5)


def test_dedup_is_exact(rng_key):
    """After _candidate_ids, no id (except -1) appears twice for a query."""
    cfg, db, state = _build(rng_key, n_db=256)
    q = jax.random.normal(jax.random.fold_in(rng_key, 3), (16, 32))
    cands = np.asarray(lidx._candidate_ids(state, cfg, q.astype(jnp.float32), 4))
    for row in cands:
        real = row[row >= 0]
        assert len(real) == len(set(real.tolist()))


def test_dispatch_resolution(monkeypatch):
    assert dispatch.kernel_mode(use_kernel=False) == "reference"
    assert dispatch.kernel_mode("interpret") == "interpret"
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "reference")
    assert dispatch.kernel_mode() == "reference"
    monkeypatch.setenv("REPRO_QUERY_BACKEND", "reference")
    assert dispatch.query_backend() == "reference"
    monkeypatch.setenv("REPRO_QUERY_BACKEND", "interpret")
    assert dispatch.query_backend() == "interpret"
    with pytest.raises(ValueError):
        dispatch.kernel_mode("mosaic")
    # per-shape blocks: saturated dims -> 128; small dims -> 8-quantum
    assert dispatch.matmul_blocks(512, 64, 300) == (128, 64, 128)
    assert dispatch.rerank_blocks(4, 200) == (8, 128)


@pytest.mark.parametrize("op", ["fused", "quantized"])
def test_topk_wider_than_kernel_scratch_is_refused(rng_key, op):
    """k past the kernels' 128-lane top-k scratch raises in the kernel
    modes (no quiet fall-back to the HBM-gather path); the reference path
    still serves it when asked for by name."""
    nq, c, n, m, k = 2, 160, 8, 200, 129
    q = jax.random.normal(jax.random.fold_in(rng_key, 1), (nq, n))
    db = jax.random.normal(jax.random.fold_in(rng_key, 2), (m, n))
    ids = jax.random.randint(jax.random.fold_in(rng_key, 3), (nq, c), -1, m)

    def call(backend):
        if op == "fused":
            return ops.fused_query_topk(q, db, ids, k, backend=backend)
        return ops.quantized_query_topk(q, db.astype(jnp.bfloat16),
                                        jnp.float32(1.0), ids, k,
                                        backend=backend)

    for mode in ("interpret", "compiled", "fused"):
        with pytest.raises(ValueError, match="top-k scratch"):
            call(mode)
    d, i = call("reference")
    assert i.shape == d.shape == (nq, k)
    fin = np.isfinite(np.asarray(d))
    assert (np.diff(np.asarray(d)[0][fin[0]]) >= 0).all()
