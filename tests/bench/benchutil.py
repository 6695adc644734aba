"""Tiny copies of the benchmark's cells for CPU rehearsals.

``tiny_root(tmp, cells)`` makes a checkout-like directory: a
``BENCHMARK.json`` that lists the given cells, the real metric readers,
and each cell's configuration and traffic mix cut to a size the CPU
runs in seconds — written as new files, the way a later PR adds a cell.
"""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")

#: per config: the profile and backend the rehearsal uses
TINY_CONFIGS = {
    "rs10-4-1m": {"profile": {"data": 10, "parity": 4, "chunk_size": 12}},
    "d8p3-512k": {"profile": {"data": 8, "parity": 3, "chunk_size": 12}},
    "d20p6-4m": {"profile": {"data": 20, "parity": 6, "chunk_size": 12}},
}

#: per mix: the keys the rehearsal changes
TINY_TRAFFIC = {
    "put-256m": {"object_bytes": 10 * 10 * 4096 + 5 * 4096 + 123,
                 "clients": 3, "warmup_seconds": 0.5, "warm_batches": 2},
    "put-4m": {"object_bytes": 8 * 4096, "clients": 4,
               "warmup_seconds": 0.5, "warm_batches": 2},
    "get-degraded-10m": {"store_objects": 12,
                         "store_object_bytes": 10 * 4096, "clients": 4,
                         "warmup_seconds": 0.5, "warm_batches": 2},
    "put-320m": {"object_bytes": 2 * 20 * 4096, "clients": 1,
                 "warmup_seconds": 0.5, "warm_batches": 2},
    "resilver-1disk": {"store_objects": 6,
                       "store_object_bytes": 3 * 10 * 4096 + 5 * 4096 + 123,
                       "clients": 3, "warmup_seconds": 0.5,
                       "check_objects": 2},
}


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _dump(path: str, obj: dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def tiny_root(tmp: str, cells: list[str], suffix: str = "") -> str:
    """A checkout at ``tmp`` whose BENCHMARK.json has tiny copies of
    ``cells``, each named ``<cell><suffix>``."""
    real = _load(os.path.join(REPO, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in real["workloads"]}
    bench = dict(real, configs=[], workloads=[])
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(tmp, "benchmark", "metrics"))
    for cell in cells:
        w = dict(by_name[cell], name=cell + suffix)
        cfg = next(c for c in real["configs"] if c["name"] == w["config"])
        conf = _load(os.path.join(REPO, cfg["file"]))
        conf.update(TINY_CONFIGS[w["config"]])
        cfg = dict(cfg, name=w["config"] + suffix,
                   file=f"benchmark/configs/{w['config']}{suffix}.json")
        _dump(os.path.join(tmp, cfg["file"]), conf)
        traffic = _load(os.path.join(BENCH, "traffic",
                                     f"{w['traffic']}.json"))
        traffic.update(TINY_TRAFFIC[w["traffic"]])
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
