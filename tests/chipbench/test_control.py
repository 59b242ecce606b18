"""The controls of each cell's limits, at a size a test run can hold: the
plain reference put in the program's place in a lower precision (bf16 below
fp32; int4 below int8, and int8 codes without the exact rerank) comes out
not correct through the run's own check, and the program's answers pass."""

import pytest
import tinybench

from chipbench import bench as benchmod
from chipbench import check as checkmod
from chipbench import control


@pytest.mark.parametrize("cell,kinds", [
    ("w2q-stream-open", ["bf16"]),
    ("l1q8-search-closed", ["int4", "int8"]),
])
def test_control_fails_the_limit_the_program_passes(tmp_path, cell, kinds):
    bench = benchmod.Benchmark(tinybench.make_root(tmp_path))
    got = control.readings(bench, bench.cell(cell), 2 ** 33 + 7, 1.0,
                           require_tpu=False)
    assert got["correct"]
    limit = got["checks"]["dist_err"]["limit"]
    assert got["checks"]["dist_err"]["value"] <= limit
    assert sorted(got["controls"]) == sorted(kinds)
    for kind in kinds:
        assert got["controls"][kind]["correct"] is False
        assert got["controls"][kind]["dist_err"] > limit


def test_every_stated_precision_has_a_control():
    bench = benchmod.Benchmark()
    for name in bench.workloads:
        precision = bench.cell(name).config["precision"]
        assert checkmod.CONTROLS[precision]
