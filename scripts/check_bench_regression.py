#!/usr/bin/env python
"""Bench regression gate: fail CI when the engine's at-scale speedup drops.

Compares a freshly written BENCH_engine.json against the committed baseline
(CI snapshots it with `git show HEAD:BENCH_engine.json` before the bench
runs) and fails when the minimum engine-vs-seed speedup at n_guests >= 8
falls below TOLERANCE x the baseline's. The tolerance absorbs shared-CI
wall-clock noise; benchmarks/bench_engine.py times every runner in one
process (on a TPU no child process could reach the chip). A real regression
in the scan-fused driver shows up as a >15% drop across every at-scale
case.

Also gates the steady-state churn engine: `churn_vs_engine` (the fault
machinery's overhead ratio vs the plain driver) must hold the same
tolerance against the baseline, and `reclaim_complete`
(INV-CRASH-RECLAIM-COMPLETE on the benchmark's final carry) must be true
outright -- a correctness bit, not a wall-clock number.

Usage: check_bench_regression.py <baseline.json> <fresh.json>
"""
import json
import sys

TOLERANCE = 0.85
AT_SCALE_GUESTS = 8


def min_at_scale_speedup(payload: dict) -> float:
    # pod-size rows run only the SynthTrace path (the seed reference would
    # need a host-materialized trace) and carry no "speedup"; the gate
    # compares the cases that time both paths
    cases = [c["speedup"] for c in payload["cases"]
             if c["n_guests"] >= AT_SCALE_GUESTS and "speedup" in c]
    if not cases:
        raise SystemExit("no at-scale (n_guests >= 8) cases in payload")
    return min(cases)


def main(baseline_path: str, fresh_path: str) -> int:
    with open(baseline_path) as f:
        baseline = json.load(f)
    with open(fresh_path) as f:
        fresh = json.load(f)
    base = min_at_scale_speedup(baseline)
    new = min_at_scale_speedup(fresh)
    floor = TOLERANCE * base
    print(f"engine-vs-seed speedup at n_guests >= {AT_SCALE_GUESTS}: "
          f"baseline {base:.2f}x, fresh {new:.2f}x, "
          f"floor {floor:.2f}x ({TOLERANCE}x baseline)")
    failed = False
    if new < floor:
        print(f"FAIL: at-scale speedup regressed below {TOLERANCE}x baseline")
        failed = True
    if fresh.get("reclaim_complete") is False:
        print("FAIL: churn benchmark left orphaned near blocks "
              "(INV-CRASH-RECLAIM-COMPLETE violated)")
        failed = True
    if "churn_vs_engine" in baseline and "churn_vs_engine" in fresh:
        cb, cf = baseline["churn_vs_engine"], fresh["churn_vs_engine"]
        cfloor = TOLERANCE * cb
        print(f"churn-vs-engine overhead ratio: baseline {cb:.2f}x, "
              f"fresh {cf:.2f}x, floor {cfloor:.2f}x")
        if cf < cfloor:
            print(f"FAIL: churn driver overhead regressed below "
                  f"{TOLERANCE}x baseline")
            failed = True
    if "tco" in fresh:
        # informational only: the TCO column (ISSUE 7) tracks the churn
        # fleet's $-weighted placement; baselines from before the tier
        # subsystem have no such column, so never gate on it
        if "tco" in baseline:
            print(f"churn fleet TCO: baseline {baseline['tco']:.4g}, "
                  f"fresh {fresh['tco']:.4g} (informational)")
        else:
            print(f"churn fleet TCO: fresh {fresh['tco']:.4g} "
                  f"(baseline predates the tco column)")
    if "min_overlap_speedup_at_scale" in fresh:
        # informational only: the stride-4 overlapped-exchange column
        # (DESIGN.md §17). Shared-CI wall clock of a collective-heavy path
        # is too noisy to gate; the bit-exactness pin is
        # INV-MULTIHOST-EXACT, and baselines from before the multi-host
        # runtime have no such column
        ov = fresh["min_overlap_speedup_at_scale"]
        if "min_overlap_speedup_at_scale" in baseline:
            print(f"overlap (stride-4) speedup at scale: baseline "
                  f"{baseline['min_overlap_speedup_at_scale']:.2f}x, fresh "
                  f"{ov:.2f}x (informational)")
        else:
            print(f"overlap (stride-4) speedup at scale: fresh {ov:.2f}x "
                  f"(baseline predates the overlap column)")
    if "multihost_s" in fresh:
        # informational only: 2-process coordinated-launch wall clock --
        # dominated by the workers' cold jit compiles
        if "multihost_s" in baseline:
            print(f"multihost launch wall: baseline "
                  f"{baseline['multihost_s']:.1f} s, fresh "
                  f"{fresh['multihost_s']:.1f} s (informational)")
        else:
            print(f"multihost launch wall: fresh {fresh['multihost_s']:.1f} s "
                  f"(baseline predates the multihost column)")
    if "pallas_vs_engine" in fresh:
        # informational only: the pallas-interpret cost ratio (DESIGN.md
        # §16) on the smallest grid row; interpret-mode wall clock says
        # nothing about TPU lowering, and baselines from before the kernel
        # registry have no such column, so never gate on it
        if "pallas_vs_engine" in baseline:
            print(f"pallas-vs-engine interpret ratio: baseline "
                  f"{baseline['pallas_vs_engine']:.1f}x, fresh "
                  f"{fresh['pallas_vs_engine']:.1f}x (informational)")
        else:
            print(f"pallas-vs-engine interpret ratio: fresh "
                  f"{fresh['pallas_vs_engine']:.1f}x "
                  f"(baseline predates the pallas column)")
    if failed:
        return 1
    print("OK: no bench regression")
    return 0


if __name__ == "__main__":
    if len(sys.argv) != 3:
        raise SystemExit(__doc__)
    sys.exit(main(sys.argv[1], sys.argv[2]))
