"""The control of each cell: the plain reference put in the program's
place, computed in the precision below the configuration's.

    python bench/control.py --workload er18_ic.round --seeds 1 2 3

IC coins and edge probabilities, or LT draws and running weight sums,
are taken in bfloat16 instead of float32; everything else is the
reference.  The control's outputs go through the cell's own check, as
the program's would, and each compared number is printed beside its
limit: a sound check reads the control as not correct.  Host only; it
needs no chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def control(cell, seed: int) -> dict:
    """The cell's checks with the control in the program's place."""
    from bench.find import load_module
    from bench.window import Window
    driver = load_module("drivers", cell.traffic["driver"])
    state = driver.control_state(cell, seed, "bf16")
    w = Window(unit="control")
    checks, failed = driver.check(state, w)
    return {"correct": all(c["value"] <= c["limit"]
                           for c in checks.values()) and not failed,
            "failed": failed, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import run
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = run.Cell(bench, args.workload)
    for seed in args.seeds:
        out = control(cell, seed)
        print(json.dumps({"workload": cell.name, "seed": seed, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
