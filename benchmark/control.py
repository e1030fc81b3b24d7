#!/usr/bin/env python3
"""The control of a cell's comparison: it has to come out NOT correct.

  python3 benchmark/control.py --workload <name> --seed <n> [--seed <n> ...]

The plain reference is put in the program's place, computed in float32,
the nearest precision below the exact decimal (int64) arithmetic the
configurations promise and the step that would tempt a later PR (32-bit
lanes in place of the emulated int64).  Its answer goes through the very
comparison a run's answers go through (harness/check.py), against the
int64 reference of the same seed's data at the cell's own size.  Needs no
chip and no engine; the benchmark's own runs do not run it."""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def control(cell, seed: int) -> dict:
    """harness/check.py's verdict on the float32 reference of ``seed``."""
    from benchmark.harness import check
    from benchmark.harness.cell import make_tables

    tables = make_tables(cell, seed)
    want = cell.query.reference(tables)
    low = cell.query.reference_lowp(tables)
    return check.compare([low], want, failed=0, fallbacks=0,
                         compiles_in_window=0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)

    from benchmark.harness import check
    from benchmark.harness.manifest import Manifest

    cell = Manifest(ROOT).cell(args.workload)
    still_correct = 0
    for seed in args.seed:
        compared = control(cell, seed)
        ok = check.is_correct(compared)
        still_correct += ok
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control_correct": ok, "compared": compared}),
              flush=True)
    # the control failing is the expected outcome: exit 0 then
    return 1 if still_correct else 0


if __name__ == "__main__":
    sys.exit(main())
