"""A cell, its configuration, its traffic mix and a per-layer metric added
as new files alone: the harness finds each by its name and runs the cell
(on the CPU, at a tiny size)."""

import json
import os
import time

import tinybench

from chipbench import bench as benchmod
from chipbench import run

METRIC = '''"""batcher.batches: ``batch`` spans in the window (a metric
added by a file of its own)."""


def read(ctx):
    n = sum(1 for s in ctx.spans if s["name"] == "batch")
    return n or None
'''


def test_new_cell_from_new_files_only(tmp_path):
    root = tinybench.make_root(tmp_path)
    before = {os.path.relpath(os.path.join(d, f), root)
              for d, _, fs in os.walk(os.path.join(root, "chipbench"))
              for f in fs}
    with open(os.path.join(root, "chipbench", "configs",
                           "w2-quantile-262k.json"), encoding="utf-8") as f:
        config = json.load(f)
    config["name"] = "w2-quantile-tiny-probes2"
    config["query"]["n_probes"] = 2
    traffic = {"mode": "closed", "clients": 1, "query": {"rows": 1},
               "pool_per_client": 4, "warm_chunks": [8], "probe_rows": 8,
               "probe_request_rows": 8, "check_rows": 0}
    tinybench.add_cell(root, "w2q2-search-one", config["name"],
                       "one-client", config, traffic, "batcher.batches",
                       METRIC)
    after = {os.path.relpath(os.path.join(d, f), root)
             for d, _, fs in os.walk(os.path.join(root, "chipbench"))
             for f in fs}
    # nothing that was there changed its name; three files were added
    assert before < after and len(after - before) == 3

    bench = benchmod.Benchmark(root)
    cell = bench.cell("w2q2-search-one")
    assert cell.config["query"]["n_probes"] == 2
    assert cell.traffic["clients"] == 1
    assert [m.name for m in cell.per_layer] == ["batcher.batches"]
    assert "query_rows_per_s" not in [m.name for m in cell.end_to_end]

    out = run.run_cell(bench, cell, 5, 1.0, True, require_tpu=False,
                       t_start=time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["metrics"]["batcher.batches"]["value"] >= 1
    assert list(out)[-1] == "checks"


COLLECTIVES = '''"""fanout.collectives: ``query.collective`` spans in the window."""


def read(ctx):
    n = sum(1 for s in ctx.spans if s["name"] == "query.collective")
    return n or None
'''


def test_new_mesh_cell_from_new_files_only(tmp_path):
    """A configuration that states a serve mesh, added by files alone, runs
    sharded: here over a one-device mesh, the degenerate case of the same
    SPMD program."""
    root = tinybench.make_root(tmp_path)
    with open(os.path.join(root, "chipbench", "configs",
                           "w2-quantile-262k.json"), encoding="utf-8") as f:
        config = json.load(f)
    config["name"] = "w2-quantile-tiny-mesh1"
    traffic = {"mode": "closed", "clients": 1, "query": {"rows": 1},
               "pool_per_client": 4, "warm_chunks": [8], "probe_rows": 8,
               "probe_request_rows": 8, "check_rows": 0}
    tinybench.add_cell(root, "w2q-mesh1-search-one", config["name"],
                       "one-client-mesh", config, traffic,
                       "fanout.collectives", COLLECTIVES, chips=1,
                       mesh={"axis": "serve", "devices": 1})

    bench = benchmod.Benchmark(root)
    cell = bench.cell("w2q-mesh1-search-one")
    assert cell.chips == 1
    assert cell.config["mesh"] == {"axis": "serve", "devices": 1}
    assert cell.config["spec"]["shard_axis"] == "serve"
    out = run.run_cell(bench, cell, 6, 1.0, True, require_tpu=False,
                       t_start=time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["metrics"]["fanout.collectives"]["value"] >= 1
