"""Each cell's control and planted faults, on the CPU at a tiny size:
the harness's look for a chip is skipped, the rest of a run is driven,
and ``correct`` must come out false.  The faults of a cell are those
its mix's ``"tiny"`` key lists."""

import sys

import pytest

from benchutil import BENCH, cells, rehearsal_faults
from test_perfbench_cells import run_tiny

sys.path.insert(0, BENCH)

import control  # noqa: E402

#: per cell, the faults it can have
CASES = [(cell, fault) for cell in cells()
         for fault in rehearsal_faults(cell)]


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_not_correct(tmp_path, capsys, cell, fault):
    out = run_tiny(tmp_path, capsys, cell, main=control.main,
                   extra=("--fault", fault))
    assert out["correct"] is False, out["checks"]
    bad = [k for k, c in out["checks"].items()
           if c["value"] != 0 and c["limit"] == 0]
    assert bad, out["checks"]
