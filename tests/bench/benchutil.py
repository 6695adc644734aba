"""Tiny copies of the benchmark's cells for CPU rehearsals.

``tiny_root(tmp, cells)`` makes a checkout-like directory: a
``BENCHMARK.json`` that lists the given cells, the real metric readers,
and each cell's configuration and traffic mix cut to a size the CPU
runs in seconds — written as new files, the way a later PR adds a cell.

The sizes are data: each configuration and mix file carries its own
under ``"tiny"``, top-level keys that replace the file's own (a mix's
``"faults"`` there lists the planted faults its cells are rehearsed
with; ``control.py`` names them).  ``run.py`` never reads the key, so a
cell whose files carry it is rehearsed with no test edited.
"""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _dump(path: str, obj: dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def cells(source: str = REPO) -> list[str]:
    """Every workload of ``source``'s BENCHMARK.json."""
    return [w["name"] for w in
            _load(os.path.join(source, "BENCHMARK.json"))["workloads"]]


def workload(cell: str, source: str = REPO) -> dict:
    bench = _load(os.path.join(source, "BENCHMARK.json"))
    return next(w for w in bench["workloads"] if w["name"] == cell)


def _traffic(cell: str, source: str) -> dict:
    return _load(os.path.join(source, "benchmark", "traffic",
                              f"{workload(cell, source)['traffic']}.json"))


def rehearsal_faults(cell: str, source: str = REPO) -> list[str]:
    """The planted faults ``cell``'s mix lists for its rehearsals."""
    return list(_traffic(cell, source)["tiny"].get("faults", []))


def _tiny(obj: dict) -> dict:
    tiny = dict(obj.pop("tiny"))
    tiny.pop("faults", None)
    obj.update(tiny)
    return obj


def tiny_root(tmp: str, cells: list[str], suffix: str = "",
              source: str = REPO) -> str:
    """A checkout at ``tmp`` whose BENCHMARK.json has tiny copies of
    ``source``'s ``cells``, each named ``<cell><suffix>``, with the
    reference modules their configurations name."""
    real = _load(os.path.join(source, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in real["workloads"]}
    bench = dict(real, configs=[], workloads=[])
    shutil.copytree(os.path.join(source, "benchmark", "metrics"),
                    os.path.join(tmp, "benchmark", "metrics"))
    for cell in cells:
        w = dict(by_name[cell], name=cell + suffix)
        cfg = next(c for c in real["configs"] if c["name"] == w["config"])
        conf = _tiny(_load(os.path.join(source, cfg["file"])))
        ref = conf.get("reference", "reference")
        if ref != "reference":
            shutil.copy(os.path.join(source, "benchmark", f"{ref}.py"),
                        os.path.join(tmp, "benchmark", f"{ref}.py"))
        cfg = dict(cfg, name=w["config"] + suffix,
                   file=f"benchmark/configs/{w['config']}{suffix}.json")
        _dump(os.path.join(tmp, cfg["file"]), conf)
        traffic = _tiny(_traffic(cell, source))
        _dump(os.path.join(tmp, "benchmark", "traffic",
                           f"{w['traffic']}{suffix}.json"), traffic)
        w.update(config=cfg["name"], traffic=w["traffic"] + suffix)
        bench["configs"].append(cfg)
        bench["workloads"].append(w)
    for group in ("end_to_end", "per_layer"):
        bench[group] = [
            dict(m, workloads=[c + suffix for c in m["workloads"]
                               if c in cells])
            if "workloads" in m else m for m in real[group]]
    _dump(os.path.join(tmp, "BENCHMARK.json"), bench)
    return tmp
