"""What a cell is, read from ``BENCHMARK.json`` and the data files.

Nothing here names a cell, a configuration, a mix or a metric: each is
found by its name in ``BENCHMARK.json``.

- ``configs[].file``: the deployment (sizes, engine settings, policy);
- ``bench/traffic/<traffic>.json``: the mix's parameters;
- ``bench/cells/<workload>.json``: the cell's fixed select rate and the
  limits of its compared numbers;
- ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    cell: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(root, "bench", "traffic",
                                      f"{w['traffic']}.json"))
    cell = _load_json(os.path.join(root, "bench", "cells",
                                   f"{workload}.json"))
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", cells)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return Cell(workload, int(w["chips"]), config, traffic, cell, e2e,
                layer)


def reader(metric: str):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(BENCH, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
