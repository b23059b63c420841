"""A cell, read from data: ``BENCHMARK.json`` names it, and every piece that
belongs to it is a file found by name under the benchmark's directory.

    configs[].file                         the configuration as it is run
    benchmarks/traffic/<traffic>.json      the traffic mix's parameters
    benchmarks/workloads/<cell>.json       family, engine fields, check limits
    benchmarks/families/<family>.py        builds and drives the engine objects
    benchmarks/layer_metrics/<metric>.py   one reader per per-layer metric

A later PR adds files and appends entries; nothing here is keyed on a
cell's name.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file by path (metric names hold dots, so not by package)."""
    if not os.path.exists(path):
        raise FileNotFoundError(f"{name}: no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]        # the configuration's file, whole
    traffic_name: str
    traffic: Dict[str, Any]       # the mix's parameters
    workload: Dict[str, Any]      # family, engine fields, limits
    end_to_end: List[dict]        # BENCHMARK.json entries this cell reports
    per_layer: List[dict]
    root: str                     # the checkout
    bench_dir: str                # <root>/benchmarks

    @property
    def family(self) -> str:
        return self.workload["family"]


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(root: str, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(has {[w['name'] for w in spec['workloads']]})")
    cfg_entry = next(c for c in spec["configs"] if c["name"] == entry["config"])
    bench_dir = os.path.join(root, spec["paths"][0])
    end_to_end = [m for m in spec["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in end_to_end}
    # a per-layer metric is read in the cells it lists, or, with no list,
    # in every cell that reports the end-to-end metric it moves
    per_layer = [m for m in spec["per_layer"]
                 if _applies(m, name) and m["moves"] in reported]
    return Cell(
        name=name, chips=int(entry["chips"]), config_name=entry["config"],
        config=_read_json(os.path.join(root, cfg_entry["file"])),
        traffic_name=entry["traffic"],
        traffic=_read_json(os.path.join(bench_dir, "traffic",
                                        entry["traffic"] + ".json")),
        workload=_read_json(os.path.join(bench_dir, "workloads",
                                         name + ".json")),
        end_to_end=end_to_end, per_layer=per_layer, root=root,
        bench_dir=bench_dir)


def load_family(cell: Cell):
    return load_module(os.path.join(cell.bench_dir, "families",
                                    cell.family + ".py"),
                       "bench_family_" + cell.family)


def read_layer_metrics(cell: Cell, obs: dict) -> Dict[str, float]:
    """Run each per-layer metric's reader over the run's observations. A
    reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in cell.per_layer:
        mod = load_module(os.path.join(cell.bench_dir, "layer_metrics",
                                       m["name"] + ".py"),
                          "bench_metric_" + m["name"].replace(".", "_"))
        value = mod.read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
