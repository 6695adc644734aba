#!/usr/bin/env python3
"""Run a cell with its control or a planted fault (``faults.py``) in
place: every check of ``run.py`` still applies, and ``correct`` must come
out false.  The benchmark's own runs never do this.

    python3 benchmark/control.py --fault <control|altered|unchanged|
        half_batch|no_exchange> --workload <cell> --seed <n> \
        --seconds <s> --trace 0
"""

from __future__ import annotations

import argparse
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import faults  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402


def main(argv=None, *, root: str | None = None,
         require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fault", required=True,
                    choices=("control",) + faults.FAULTS)
    args, rest = ap.parse_known_args(argv)
    root = root or os.path.dirname(BENCH)
    cell = spec.load(root, run.parse_args(rest).workload)
    return run.main(rest, root=root, require_chip=require_chip,
                    fault=faults.plant(args.fault, cell.traffic))


if __name__ == "__main__":
    sys.exit(main())
