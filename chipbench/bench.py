"""``BENCHMARK.json`` and the files it names, found by name.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix; each per-layer metric names its reader.  Each lives in a file of its
own, so a later change adds a cell, a configuration, a traffic mix or a
metric by adding files and entries, never by editing one that exists:

* configuration ``<name>``: the ``file`` its ``configs`` entry gives
  (``chipbench/configs/<name>.json``);
* traffic mix ``<name>``: ``chipbench/traffic/<name>.json``;
* data generator ``<kind>`` (a configuration's ``data.kind``):
  ``chipbench/data/<kind>.py``, exposing ``generate(key, n, n_dims,
  params)``;
* per-layer metric ``<name>``: ``chipbench/metrics/<name>.py``, exposing
  ``read(ctx)`` that returns a number or None (nothing to read).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict, List, Optional

PACKAGE_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PACKAGE_DIR)


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    workloads: Optional[List[str]]     # None: every cell

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload entry, with its configuration and traffic resolved."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import the Python file at ``path`` under a private module name."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench._by_name.{name}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Benchmark:
    """The parsed ``BENCHMARK.json`` of a checkout rooted at ``root``."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.raw = _load_json(os.path.join(root, "BENCHMARK.json"))
        self.package = os.path.join(root, "chipbench")
        self.configs = {c["name"]: c for c in self.raw["configs"]}
        self.workloads = {w["name"]: w for w in self.raw["workloads"]}

    def _metrics(self, key: str) -> List[Metric]:
        return [Metric(name=m["name"], unit=m["unit"],
                       workloads=m.get("workloads"))
                for m in self.raw[key]]

    def cell(self, name: str) -> Cell:
        if name not in self.workloads:
            raise KeyError(f"no workload {name!r}; have "
                           f"{sorted(self.workloads)}")
        w = self.workloads[name]
        entry = self.configs[w["config"]]
        config = _load_json(os.path.join(self.root, entry["file"]))
        traffic = _load_json(os.path.join(self.package, "traffic",
                                          f"{w['traffic']}.json"))
        return Cell(
            name=name, chips=int(w["chips"]), config=config,
            traffic=traffic,
            end_to_end=[m for m in self._metrics("end_to_end")
                        if m.applies_to(name)],
            per_layer=[m for m in self._metrics("per_layer")
                       if m.applies_to(name)])

    def data_generator(self, kind: str):
        return load_module(os.path.join(self.package, "data", f"{kind}.py"),
                           f"data.{kind}")

    def metric_readers(self, cell: Cell) -> Dict[str, object]:
        return {m.name: load_module(
                    os.path.join(self.package, "metrics", f"{m.name}.py"),
                    f"metrics.{m.name}")
                for m in cell.per_layer}
