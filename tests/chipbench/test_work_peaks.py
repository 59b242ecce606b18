"""The work function and the peaks table, pinned at the launcher's shapes:
8 padded rows, C = 8 tables x 4 probes x 32 slots = 1024, N = 64."""

import pytest

from chipbench import bench as benchmod
from chipbench import run
from chipbench.work import fused_query

V5E = run.peaks_for(benchmod.ROOT, "TPU v5 lite")


def test_v5e_peaks_are_the_published_ones():
    assert V5E == {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                   "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        run.peaks_for(benchmod.ROOT, "TPU v9 imaginary")


@pytest.mark.parametrize("nq,k,itemsize,ops,nbytes", [
    # fp32 tier, k = 10: 8*1024 candidate rows of 256 B
    (8, 10, 4, 1_572_864, 8 * 1024 * 64 * 4 + 8 * 64 * 4 + 8 * 10 * 8),
    # int8 tier, survivor width 40: rows of 64 B
    (8, 40, 1, 1_572_864, 8 * 1024 * 64 + 8 * 64 * 4 + 8 * 40 * 8),
    # the palette's widest chunk
    (128, 10, 4, 25_165_824, 128 * 1024 * 64 * 4 + 128 * 64 * 4
     + 128 * 10 * 8),
])
def test_work_at_the_launcher_shapes(nq, k, itemsize, ops, nbytes):
    assert fused_query.work(nq, 1024, 64, k, itemsize) == (ops, nbytes)


def test_least_time_is_bandwidth_bound_at_these_shapes():
    t = fused_query.least_seconds(8, 1024, 64, 10, 4, V5E)
    assert t == pytest.approx((8 * 1024 * 256 + 8 * 256 + 640) / 819e9)
    # the int8 tier reads a quarter of the row bytes
    t8 = fused_query.least_seconds(8, 1024, 64, 40, 1, V5E)
    assert t8 == pytest.approx((8 * 1024 * 64 + 8 * 256 + 8 * 40 * 8)
                               / 819e9)
