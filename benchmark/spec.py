"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration (``benchmark/configs/<config>.json``) and a
traffic mix (``benchmark/traffic/<traffic>.json``); each per-layer
metric is a reader ``benchmark/metrics/<metric>.py`` with a function
``read(window) -> float | None``.  A metric split by the end-to-end
metric it moves (``device_idle_share.put`` / ``.get``) may share one
reader, ``benchmark/metrics/device_idle_share.py``: the full name's file
is taken where there is one, else the name without its last part.
Adding a cell, a mix or a metric is adding files and entries: nothing
here names one.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field


@dataclass
class Metric:
    name: str
    unit: str
    entry: dict
    reader: object = None


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)


def _in_cell(metric: dict, cell: str, reported: set) -> bool:
    """A metric with ``workloads`` is in the cells it lists; one without
    is in every cell that reports the end-to-end metric it moves (or,
    for an end-to-end metric, in every cell)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reader_path(metrics_dir: str, name: str) -> str:
    path = os.path.join(metrics_dir, f"{name}.py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(metrics_dir, f"{name.rsplit('.', 1)[0]}.py")
    return path


def _load_reader(path: str, name: str):
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read


def load(root: str, workload: str) -> Cell:
    """The cell named ``workload`` under the checkout ``root``."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    here = os.path.join(root, "benchmark")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(here, "traffic",
                                      f"{w['traffic']}.json"))
    e2e = [Metric(m["name"], m["unit"], m) for m in bench["end_to_end"]
           if _in_cell(m, workload, set())]
    reported = {m.name for m in e2e}
    layer = []
    for m in bench["per_layer"]:
        if _in_cell(m, workload, reported):
            path = _reader_path(os.path.join(here, "metrics"), m["name"])
            layer.append(Metric(m["name"], m["unit"], m,
                                _load_reader(path, m["name"])))
    return Cell(workload, int(w["chips"]), config, traffic, e2e, layer)
