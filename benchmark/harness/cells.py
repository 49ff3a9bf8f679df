"""Find a cell's files by the names BENCHMARK.json gives."""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
BENCHMARK_JSON = os.path.join(ROOT, "BENCHMARK.json")


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list          # metric entries of BENCHMARK.json for this cell
    per_layer: list
    layer_files: dict = field(default_factory=dict)   # metric name -> dict


def _load(path):
    with open(path) as f:
        return json.load(f)


def _in_cell(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name, benchmark_json=None):
    """The cell `name` with its configuration, traffic and metric files.
    The benchmark's own directory is the one BENCHMARK.json sits beside
    (its first `paths` entry), so a test can point at a copy."""
    path = benchmark_json or BENCHMARK_JSON
    bench = _load(path)
    root = os.path.dirname(os.path.abspath(path))
    base = os.path.join(root, bench["paths"][0])
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in {path}; "
                         f"it has {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load(os.path.join(base, "traffic", w["traffic"] + ".json"))
    per_layer = [m for m in bench["per_layer"] if _in_cell(m, name)]
    layer_files = {
        m["name"]: _load(os.path.join(base, "layer_metrics",
                                      m["name"] + ".json"))
        for m in per_layer}
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                traffic_name=w["traffic"], config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _in_cell(m, name)],
                per_layer=per_layer, layer_files=layer_files)
