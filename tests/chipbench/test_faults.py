"""A whole run, on the CPU at a tiny size, with the timed path broken
underneath: ``correct`` has to come out false for each fault the cells can
have, and true with none."""

import time

import pytest
import tinybench

from chipbench import bench as benchmod
from chipbench import faults, run


def _run(tmp_path, cell, seed=11):
    bench = benchmod.Benchmark(tinybench.make_root(tmp_path))
    return run.run_cell(bench, bench.cell(cell), seed, 1.0, False,
                        require_tpu=False, t_start=time.perf_counter())


@pytest.mark.parametrize("cell", ["w2q-stream-open", "l1q8-search-closed"])
def test_sound_run_is_correct(tmp_path, cell):
    out = _run(tmp_path, cell)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["compiled_in_window"]["value"] == 0
    assert {"late_max_ms", "sender_preempted", "server_preempted"} \
        <= set(out["load"])


@pytest.mark.parametrize("fault,cell,caught_by", [
    ("alter_answers", "w2q-stream-open", "dist_err"),
    ("alter_answers", "l1q8-search-closed", "dist_err"),
    ("half_batch", "w2q-stream-open", "dist_err"),
    ("half_batch", "l1q8-search-closed", "dist_err"),
    ("drop_inserts", "w2q-stream-open", "readback_miss"),
    ("drop_one_insert_in_8", "w2q-stream-open", "readback_miss"),
    ("ignore_deletes", "w2q-stream-open", "deleted_served"),
])
def test_broken_path_is_not_correct(tmp_path, monkeypatch, fault, cell,
                                    caught_by):
    faults.FAULTS[fault](monkeypatch.setattr)
    out = _run(tmp_path, cell)
    assert out["correct"] is False
    c = out["checks"][caught_by]
    assert c["value"] > c["limit"]
