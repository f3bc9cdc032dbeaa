#!/usr/bin/env python3
"""Read the control of a cell: the reference with its tie order broken, put
in the program's place, over the windows a run compares.

    python3 chipbench/control.py --workload <cell> --windows <n> --seeds 1,2,3

Prints one JSON line per seed with each number the check compares and its
limit, and whether the control came out correct (it must not). Runs on the
host alone; at a cell's own size it needs the memory of the chip's host.
"""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE)]

from harness import check, control, layout  # noqa: E402


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--windows", type=int, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    cell = layout.resolve_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        nums = control.readings(cell, seed, args.windows)
        print(json.dumps(dict(workload=args.workload, seed=seed,
                              windows=args.windows, correct=check.passed(nums),
                              checks=nums)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
