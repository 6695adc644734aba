"""Each cell's control and planted faults, on the CPU at a tiny size:
the harness's look for a chip is skipped, the rest of a run is driven,
and ``correct`` must come out false."""

import sys

import pytest

from benchutil import BENCH
from test_perfbench_cells import run_tiny

sys.path.insert(0, BENCH)

import control  # noqa: E402

#: per cell, the faults it can have
CASES = [
    (cell, fault)
    for cell, faults in [
        ("rs10-4-1m.put-256m", ["control", "altered", "unchanged",
                                "half_batch"]),
        ("d8p3-512k.put-4m", ["control", "altered", "unchanged",
                              "half_batch"]),
        ("rs10-4-1m.get-degraded-10m", ["control", "unchanged",
                                        "half_batch"]),
        ("d20p6-4m.put-320m", ["control", "altered", "no_exchange"]),
        ("rs10-4-1m.resilver-1disk", ["control", "altered", "unchanged",
                                      "half_batch"]),
    ]
    for fault in faults
]


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_not_correct(tmp_path, capsys, cell, fault):
    out = run_tiny(tmp_path, capsys, cell, main=control.main,
                   extra=("--fault", fault))
    assert out["correct"] is False, out["checks"]
    bad = [k for k, c in out["checks"].items()
           if c["value"] != 0 and c["limit"] == 0]
    assert bad, out["checks"]
