"""Each cell rehearsed on the CPU at a tiny size, through the real
gateway, load generator and reference check; and the entry's refusals.

The cells are every workload of BENCHMARK.json, and their tiny sizes
are the ``"tiny"`` keys of their own files: ``benchutil.tiny_root``
writes them as new files under new names, so a cell is added, and
rehearsed, by data alone."""

import json
import os
import subprocess
import sys

import pytest

from benchutil import BENCH, REPO, cells, tiny_root, workload

sys.path.insert(0, BENCH)

import run  # noqa: E402
import spec  # noqa: E402
import trace_reduce  # noqa: E402

CELLS = cells()
SEED = 2**33 + 11


def run_tiny(tmp_path, capsys, cell, *, trace=0, fault=None, main=None,
             extra=(), source=REPO):
    """Run ``cell``'s tiny copy on the CPU; returns the result line."""
    from chunky_bits_tpu.obs import metrics as obs_metrics
    from chunky_bits_tpu.ops.backend import get_backend

    root = tiny_root(str(tmp_path), [cell], suffix=".tiny", source=source)
    tiny = spec.load(root, cell + ".tiny")
    mp = pytest.MonkeyPatch()
    # a fresh metrics registry, as a run's own process has: a simulator
    # run earlier in this process swaps registries, and the program's
    # cached event families would still count into the one it left
    mp.setattr(obs_metrics, "_REGISTRY", None)
    mp.setattr(obs_metrics, "_EVENT_FAMILIES", {})
    if tiny.config["backend"] == "mesh":
        # the chip run spreads over the cell's chips; the virtual mesh
        # has 8
        mp.setattr(get_backend("mesh"), "n_devices", tiny.chips)
    try:
        argv = ["--workload", cell + ".tiny", "--seed", str(SEED),
                "--seconds", "1", "--trace", str(trace), *extra]
        if main is None:
            rc = run.main(argv, root=root, require_chip=False, fault=fault)
        else:
            rc = main(argv, root=root, require_chip=False)
    finally:
        mp.undo()
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _bench(source):
    with open(os.path.join(source, "BENCHMARK.json")) as f:
        return json.load(f)


def rehearse(tmp_path, capsys, cell, source=REPO):
    """The cell's tiny copy is correct and reports every end-to-end
    metric it lists."""
    out = run_tiny(tmp_path, capsys, cell, source=source)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in _bench(source)["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    assert not os.path.exists(os.path.join(tmp_path, ".bench_work"))
    return out


def rehearse_traced(tmp_path, capsys, cell, monkeypatch, source=REPO):
    """``--trace 1`` on the CPU with the reduction fed the recorded v5e
    trace (a CPU trace has no device plane): every per-layer metric the
    cell lists is in its line, with the breakdown."""
    recorded = trace_reduce.reduce(
        os.path.join(BENCH, "testdata", "v5e-codec.xplane.pb"),
        "TPU v5 lite", 1)
    recorded.chips = recorded.chips * int(workload(cell, source)["chips"])
    monkeypatch.setattr(trace_reduce, "reduce", lambda *a: recorded)
    out = run_tiny(tmp_path, capsys, cell, trace=1, source=source)
    assert out["correct"] is True, out["checks"]
    want = {m["name"] for m in _bench(source)["per_layer"]
            if cell in m["workloads"]}
    assert set(out["metrics"]) == want
    for name, m in out["metrics"].items():
        assert m["value"] >= 0, name
        if "roofline" in name:
            assert 0 < m["value"] <= 100
    assert 0 < out["device"]["busy_s"] < out["device"]["window_s"]
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_cell_on_cpu(tmp_path, capsys, cell):
    rehearse(tmp_path, capsys, cell)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_cell_reports_its_layers(tmp_path, capsys, cell,
                                        monkeypatch):
    rehearse_traced(tmp_path, capsys, cell, monkeypatch)


def _entry(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "d8p3-512k.put-4m", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_entry_refuses_the_cpu():
    r = _entry(REPO)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    assert "needs 1 TPU chip" in r.stderr


def test_entry_fails_without_the_program(tmp_path):
    """Alone with BENCHMARK.json and its own files, the benchmark has no
    system to run."""
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark")
    r = _entry(str(tmp_path), {"PYTHONPATH": ""})
    assert r.returncode != 0
    assert '"correct"' not in r.stdout


NEW_MIXES = {
    # Open questions row 2: healthy GETs, no device work
    "get-4m": {"op": "get", "clients": 3, "store_objects": 6,
               "store_object_bytes": 8 * 4096 + 17, "warmup_seconds": 0.5},
    # rows 3 and 5: weighted sizes, an open loop and a mix of operations
    "mixed-open": {"op": [["get", 45], ["head", 30], ["put", 15]],
                   "object_bytes": [[4096, 3], [8 * 4096 + 5, 1]],
                   "rate_per_s": 40, "clients": 8, "store_objects": 5,
                   "store_object_bytes": 8 * 4096, "warmup_seconds": 0.5,
                   "check_objects": 3},
}


@pytest.mark.parametrize("mix", sorted(NEW_MIXES))
def test_a_cell_added_as_files_alone(tmp_path, capsys, mix):
    """A later PR's cell: a traffic file and a BENCHMARK.json entry, no
    code."""
    root = tiny_root(str(tmp_path), ["d8p3-512k.put-4m"], suffix=".tiny")
    with open(os.path.join(root, "benchmark", "traffic",
                           f"{mix}.json"), "w") as f:
        json.dump(NEW_MIXES[mix], f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    name = f"d8p3-512k.{mix}"
    bench["workloads"].append({"name": name, "config": "d8p3-512k.tiny",
                               "traffic": mix, "chips": 1, "why": "test"})
    with open(path, "w") as f:
        json.dump(bench, f)
    rc = run.main(["--workload", name, "--seed", str(SEED), "--seconds",
                   "1", "--trace", "0"], root=root, require_chip=False)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0
    assert "setup_s" in out["metrics"]
