"""A benchmark root at a size the CPU runs in seconds, for the tests.

``make_root(tmp)`` copies the ``chipbench`` package into ``tmp``, links the
repository's ``src`` beside it, and writes a ``BENCHMARK.json`` whose cells
keep the committed configurations' shapes (a serve mesh among them), chips
and traffic mixes but hold 2,048 items (two sealed segments per device of
a mesh) and send a few requests.  New cells, configurations, traffic mixes
and metrics are added as files, exactly as a later change would.
"""

from __future__ import annotations

import copy
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_ITEMS = 2048


def _write(path: str, obj) -> None:
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f, indent=1)


def make_root(tmp: str) -> str:
    root = os.path.join(str(tmp), "bench")
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    for entry in bench["configs"]:
        path = os.path.join(root, entry["file"])
        with open(path, encoding="utf-8") as f:
            cfg = json.load(f)
        cfg["items"] = TINY_ITEMS
        if "mesh" in cfg:
            cfg["items"] = (2 * int(cfg["mesh"]["devices"])
                            * int(cfg["spec"]["segment_capacity"]))
        cfg["load_rows_per_call"] = 1024
        _write(path, cfg)
    tdir = os.path.join(root, "chipbench", "traffic")
    for name in os.listdir(tdir):
        path = os.path.join(tdir, name)
        with open(path, encoding="utf-8") as f:
            traffic = json.load(f)
        if traffic["mode"] == "open":
            traffic["query"]["rate_per_s"] = 4.0
            traffic["connections"] = 4
            if "writes" in traffic:
                # enough inserts in a second that one lost in eight shows
                traffic["writes"]["rate_per_s"] = 32.0
        else:
            traffic["clients"] = 2
            traffic["pool_per_client"] = 8
        traffic["probe_rows"] = 16
        if "check_loaded" in traffic:
            traffic["check_loaded"] = 64
        _write(path, traffic)
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


def add_cell(root: str, cell: str, config: str, traffic: str,
             config_obj: dict, traffic_obj: dict, metric: str,
             metric_src: str, chips: int = 1, mesh=None) -> None:
    """A new cell from new files only: configuration, traffic mix and a
    per-layer metric, plus their entries in ``BENCHMARK.json``.  A
    ``mesh`` (``{"axis": ..., "devices": n}``) is written into the
    configuration, and its spec shards over that axis."""
    pkg = os.path.join(root, "chipbench")
    if mesh is not None:
        config_obj = copy.deepcopy(config_obj)
        config_obj["mesh"] = mesh
        config_obj["spec"]["shard_axis"] = mesh["axis"]
    _write(os.path.join(pkg, "configs", f"{config}.json"), config_obj)
    _write(os.path.join(pkg, "traffic", f"{traffic}.json"), traffic_obj)
    with open(os.path.join(pkg, "metrics", f"{metric}.py"), "w",
              encoding="utf-8") as f:
        f.write(metric_src)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": config, "source": "https://arxiv.org/abs/2002.03909",
        "file": f"chipbench/configs/{config}.json", "reduced": ["items"],
        "why": "a configuration added by files alone"})
    bench["workloads"].append({
        "name": cell, "config": config, "traffic": traffic, "chips": chips,
        "why": "a cell added by files alone"})
    metric_entry = copy.deepcopy(bench["per_layer"][0])
    metric_entry.update(name=metric, workloads=[cell])
    bench["per_layer"].append(metric_entry)
    _write(path, bench)
