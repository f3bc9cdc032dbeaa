#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and per-layer metrics are named in
BENCHMARK.json at the root of the checkout. Exits non-zero, printing no
result, where JAX finds no TPU. The last line of standard output is the
result as one JSON object; the numbers the check compared, each with its
limit, are the last lines of standard error.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from harness import cell  # noqa: E402

if __name__ == "__main__":
    sys.exit(cell.main(t_start=T_START))
