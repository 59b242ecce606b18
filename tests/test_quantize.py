"""Property tests for the quantized storage tier (kernels/quantize.py).

The tier's whole contract is a *bounded-loss* ladder (invariant 10):

* int8 encode -> decode round-trip error is <= scale/2 per coordinate
  (symmetric rounding) plus one f32 ulp of the segment's largest
  magnitude (decode's f32 arithmetic), property-checked by hypothesis over adversarial
  value ranges (tiny scales, huge scales, all-zero segments);
* code-space scoring equals the reference oracle, and with a wide-enough
  survivor pool the reranked answer equals the exact fp32 answer;
* segments containing NaN/Inf are rejected AT SEAL (defense in depth --
  insert validation already refuses them at the door) and a failed seal
  leaves the delta mutable and unquantized;
* empty / single-item / all-zero segments seal without dividing by zero.
"""

import dataclasses
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))
from _hypothesis_support import given, settings, st  # noqa: E402

from repro.core.index import IndexConfig  # noqa: E402
from repro.kernels import quantize  # noqa: E402
from repro.serve.segments import SegmentedIndex  # noqa: E402

CFG = IndexConfig(n_dims=8, n_tables=4, n_hashes=2, log2_buckets=6,
                  bucket_capacity=16)


# ---------------------------------------------------------------------------
# encode/decode round trip
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(st.data())
def test_int8_round_trip_error_bounded(data):
    n = data.draw(st.integers(1, 20), label="rows")
    scale_mag = data.draw(st.sampled_from([1e-6, 1e-2, 1.0, 1e3]),
                          label="magnitude")
    vals = data.draw(
        st.lists(st.lists(st.floats(-1.0, 1.0, width=32),
                          min_size=4, max_size=4),
                 min_size=n, max_size=n))
    db = np.asarray(vals, np.float32) * np.float32(scale_mag)
    codes, scale = quantize.encode(jnp.asarray(db), "int8")
    assert codes.dtype == jnp.int8
    back = np.asarray(quantize.decode(codes, scale))
    # scale/2 is the rounding step of the code; on top of it, decode's
    # f32 product code*scale (with scale itself rounded from max|x|/127)
    # may land up to one f32 ulp of the largest magnitude away
    ulp = float(np.spacing(np.float32(np.max(np.abs(db)))))
    bound = float(scale) / 2 + ulp
    assert np.max(np.abs(back - db)) <= bound


def test_all_zero_segment_uses_unit_scale():
    codes, scale = quantize.encode(jnp.zeros((5, 4), jnp.float32), "int8")
    assert float(scale) == 1.0
    assert not np.asarray(codes).any()


def test_bf16_is_cast_with_unit_scale():
    db = np.linspace(-2, 2, 12, dtype=np.float32).reshape(3, 4)
    codes, scale = quantize.encode(jnp.asarray(db), "bf16")
    assert codes.dtype == jnp.bfloat16
    assert float(scale) == 1.0
    np.testing.assert_allclose(np.asarray(codes, np.float32), db,
                               rtol=1e-2, atol=1e-2)


def test_fp32_never_encodes():
    with pytest.raises(ValueError, match="fp32"):
        quantize.encode(jnp.zeros((2, 2), jnp.float32), "fp32")


def test_bytes_per_item_ladder():
    assert quantize.bytes_per_item("fp32", 64) == 256
    assert quantize.bytes_per_item("bf16", 64) == 128
    assert quantize.bytes_per_item("int8", 64) == 64


# ---------------------------------------------------------------------------
# code-space scoring + survivor rerank
# ---------------------------------------------------------------------------


def test_quantized_scoring_matches_oracle_and_rerank_exact():
    rng = np.random.default_rng(0)
    db = rng.normal(size=(64, 8)).astype(np.float32)
    q = rng.normal(size=(3, 8)).astype(np.float32)
    ids = np.tile(np.arange(64, dtype=np.int32), (3, 1))
    codes, scale = quantize.encode(jnp.asarray(db), "int8")

    d_ref, i_ref = quantize.quantized_topk_ref(
        jnp.asarray(q), codes, scale, jnp.asarray(ids), 32)
    # survivor rerank over the quantized top-32 must reproduce the exact
    # fp32 top-5 whenever the survivors contain it (here they always do)
    rows = db[np.asarray(i_ref)]
    g, d = quantize.rerank_survivors(jnp.asarray(q), jnp.asarray(rows),
                                     i_ref, 5)
    exact = np.linalg.norm(q[:, None, :] - db[None, :, :], axis=-1)
    want = np.argsort(exact, axis=1)[:, :5]
    np.testing.assert_array_equal(np.sort(np.asarray(g), axis=1),
                                  np.sort(want, axis=1))
    np.testing.assert_allclose(
        np.asarray(d), np.sort(exact, axis=1)[:, :5], rtol=1e-5, atol=1e-5)


def test_survivor_width_resolution():
    assert quantize.survivor_width(10, 0, 10_000) == 40       # default 4k
    assert quantize.survivor_width(10, 64, 10_000) == 64      # explicit
    assert quantize.survivor_width(10, 0, 16) == 16           # candidate cap
    assert quantize.survivor_width(10, 500, 10_000) == 128    # kernel cap
    assert quantize.survivor_width(10, 4, 10_000) == 10       # never < k


# ---------------------------------------------------------------------------
# seal-time behavior
# ---------------------------------------------------------------------------


def test_nan_rejected_at_seal_leaves_delta_mutable():
    idx = SegmentedIndex(CFG, segment_capacity=16, precision="int8")
    idx.insert(np.ones((4, 8), np.float32))
    # corrupt the device state directly -- insert() validation already
    # refused NaN at the door, this is the seal-time defense
    bad = idx.delta.state.db.at[0, 0].set(jnp.nan)
    idx.delta.state = dataclasses.replace(idx.delta.state, db=bad)
    with pytest.raises(ValueError, match="non-finite"):
        idx.seal()
    assert not idx.delta.sealed
    assert idx.delta.scale is None and idx.delta.pool is None


def test_empty_seal_is_noop_and_single_item_seals():
    idx = SegmentedIndex(CFG, segment_capacity=16, precision="int8")
    idx.seal()                                    # empty: no-op
    assert len(idx.segments) == 1
    idx.insert(np.full((1, 8), 0.5, np.float32))
    idx.seal()
    sealed = idx.segments[0]
    assert sealed.sealed and sealed.scale is not None
    assert sealed.state.db.dtype == jnp.int8
    assert sealed.pool is not None and sealed.pool.dtype == np.float32
    g, d = idx.query(np.full((1, 8), 0.5, np.float32), 1, n_probes=2)
    assert int(np.asarray(g)[0, 0]) == 0
    assert float(np.asarray(d)[0, 0]) == pytest.approx(0.0, abs=1e-6)


def test_unknown_precision_rejected():
    with pytest.raises(ValueError, match="precision"):
        SegmentedIndex(CFG, segment_capacity=16, precision="fp8")
