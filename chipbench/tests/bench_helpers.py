"""Shared by the benchmark's CPU tests: import paths and a small copy of
the benchmark (same files, fleets cut to a size the CPU runs in seconds)."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL_FLEET = dict(guests=3, pages_per_guest=65536, hp_ratio=128)


def small_copy(dst: Path) -> Path:
    """A copy of BENCHMARK.json and chipbench/ under ``dst`` whose
    configurations are cut to CPU size (their traffic follows the size)."""
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(BENCH, dst / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for f in (dst / "chipbench" / "configs").glob("*.json"):
        c = json.loads(f.read_text())
        c["fleet"].update(SMALL_FLEET)
        f.write_text(json.dumps(c))
    return dst
