"""The ``scrub`` repair mix on the CPU at a tiny size, at ``rs`` and at
``pm-msr``, in a checkout where both cells exist only as files: a mix
(``scrub-1disk``), a configuration with its own reference for the
``pm-msr`` one, and BENCHMARK.json entries.  They are rehearsed by the
same code as every listed cell, with no test naming them."""

import json
import os
import shutil
import sys

import pytest

from benchutil import BENCH, REPO, rehearsal_faults
from test_perfbench_cells import rehearse, rehearse_traced, run_tiny

sys.path.insert(0, BENCH)

import control  # noqa: E402
import run  # noqa: E402

RS = "rs10-4-1m.scrub-1disk"
MSR = "d5p4-msr-1m.scrub-1disk"

#: the cold-archive profile of examples/tpu.yaml
MSR_CONFIG = {
    "name": "d5p4-msr-1m",
    "profile": {"data": 5, "parity": 4, "chunk_size": 20, "code": "pm-msr"},
    "destinations": 9,
    "backend": "jax",
    "chips": 1,
    "reduced": [],
    "reference": "msr_reference",
    "tiny": {"profile": {"data": 5, "parity": 4, "chunk_size": 12,
                         "code": "pm-msr"}},
}

MSR_REFERENCE = '''"""The product-matrix MSR code (Rashmi, Shah and Kumar, 2011) at
d' = 2(k-1), in systematic form, from its definition: node i of n = k+p
stores psi_i^T M, with psi_i = (1, x_i, ..., x_i^(2a-1)), x_i = 2^i in
GF(2^8) over 0x11d, a = k-1, and M = [S1; S2] for symmetric a x a S1
and S2.  The code's systematic generator is unique, so any basis of the
messages gives it.  A chunk is a stripes of chunk/a bytes, stripe j
being bytes [j*chunk/a, (j+1)*chunk/a); a part's shards are
ceil(n/k) bytes rounded up to a multiple of a, zero-padded."""

import hashlib

import numpy as np

import reference as gf


def parity_matrix(k, p):
    a, n = k - 1, k + p
    psi = [[gf.power(gf.power(2, i), j) for j in range(2 * a)]
           for i in range(n)]
    columns = []
    for half in (0, a):
        for i in range(a):
            for j in range(i, a):
                m = [[0] * a for _ in range(2 * a)]
                m[half + i][j] = m[half + j][i] = 1
                content = gf.matmul(psi, m)
                columns.append([content[node][c] for node in range(n)
                                for c in range(a)])
    t = [list(row) for row in zip(*columns)]
    return gf.matmul(t, gf.invert(t[:k * a]))[k * a:]


def expected_chunks(data, d, p, chunk):
    a = d - 1
    rows = parity_matrix(d, p)
    view = memoryview(data)
    out = []
    for lo in range(0, len(view), d * chunk):
        n = min(d * chunk, len(view) - lo)
        s = -(-n // d)
        s = -(-s // a) * a
        buf = np.zeros(d * s, dtype=np.uint8)
        buf[:n] = np.frombuffer(view[lo:lo + n], dtype=np.uint8)
        shards = buf.reshape(d, s)
        parity = gf.encode(shards.reshape(d * a, s // a), rows)
        out.append((s, [hashlib.sha256(r).hexdigest() for r in
                        list(shards) + list(parity.reshape(p, s))]))
    return out
'''

#: the metrics the two cells report besides ``setup_s``, as a later PR's
#: entries would list them
METRICS = ("repair_gib_s", "host_verify_busy_share",
           "backend_compiles.repair", "codec_dispatch_ms.repair",
           "device_idle_share.repair", "gf_codec_roofline.repair")


@pytest.fixture
def source(tmp_path):
    """A checkout whose BENCHMARK.json also lists the two scrub cells,
    given as files alone."""
    src = tmp_path / "src"
    shutil.copytree(BENCH, src / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfg = {k: v for k, v in MSR_CONFIG.items() if k != "name"}
    with open(src / "benchmark" / "configs" / "d5p4-msr-1m.json",
              "w") as f:
        json.dump(cfg, f)
    (src / "benchmark" / "msr_reference.py").write_text(MSR_REFERENCE)
    bench["configs"].append({
        "name": "d5p4-msr-1m", "source": "examples/tpu.yaml cold-archive",
        "file": "benchmark/configs/d5p4-msr-1m.json", "reduced": [],
        "why": "test"})
    for name, config in ((RS, "rs10-4-1m"), (MSR, "d5p4-msr-1m")):
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": "scrub-1disk", "chips": 1,
                                   "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in METRICS and "workloads" in m:
            m["workloads"] += [RS, MSR]
    with open(src / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return str(src)


@pytest.fixture
def counters(monkeypatch):
    """The window's counters of the run, as the harness takes them."""
    seen = {}
    take = run.window_counters

    def spy(*args):
        seen.update(take(*args))
        return seen

    monkeypatch.setattr(run, "window_counters", spy)
    return seen


@pytest.mark.parametrize("cell", [RS, MSR])
def test_scrub_cell_given_as_files_rehearses(tmp_path, capsys, source,
                                             counters, cell):
    out = rehearse(tmp_path / "run", capsys, cell, source=source)
    assert counters["compiles"] == 0
    assert out["checks"]["repair_plans"]["value"] >= 1
    assert "decode_dispatches" not in out["checks"]
    if cell == MSR:
        assert counters["repair.pm-msr.plans_msr"] >= 1
        assert counters["repair.pm-msr.helper_bytes_msr"] > 0
        assert counters["repair.pm-msr.plans_decode"] == 0
    else:
        assert counters["repair.rs.plans_decode"] >= 1
        assert counters["repair.rs.helper_bytes_decode"] > 0
    assert sum(v for k, v in counters.items()
               if k.endswith("plans_fallback")) == 0


@pytest.mark.parametrize("cell", [RS, MSR])
def test_traced_scrub_cell_reports_its_layers(tmp_path, capsys, source,
                                              monkeypatch, cell):
    rehearse_traced(tmp_path / "run", capsys, cell, monkeypatch,
                    source=source)


with open(os.path.join(BENCH, "traffic", "scrub-1disk.json")) as f:
    FAULTS = json.load(f)["tiny"]["faults"]


@pytest.mark.parametrize("cell,fault", [(cell, fault) for cell in (RS, MSR)
                                        for fault in FAULTS])
def test_scrub_fault_is_not_correct(tmp_path, capsys, source, cell, fault):
    assert fault in rehearsal_faults(cell, source)
    out = run_tiny(tmp_path / "run", capsys, cell, main=control.main,
                   extra=("--fault", fault), source=source)
    assert out["correct"] is False, out["checks"]
    bad = [k for k, c in out["checks"].items()
           if c["value"] != 0 and c["limit"] == 0]
    assert bad, out["checks"]
