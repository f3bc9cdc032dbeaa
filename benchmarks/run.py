"""Run the full benchmark suite (one module per paper table/figure) and print
a summary against the paper's claims. ``python -m benchmarks.run``.

The suite table lives in :mod:`benchmarks.registry` (one registry shared by
this driver and the individual modules). ``--list`` enumerates the registered
suites; ``--only <name>`` (repeatable) runs a subset -- e.g. CI's fast lane is
``--only bench_engine --only fig2_skew_cdf``; ``--json <path>`` dumps a
machine-readable summary (per-benchmark results, timings, failures) so CI can
archive it alongside ``BENCH_engine.json``."""
from __future__ import annotations

import argparse
import json
import sys
import time

from benchmarks import registry


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--list", action="store_true",
        help="list registered benchmark suites and exit")
    ap.add_argument(
        "--only", action="append", metavar="NAME",
        help="run only this benchmark (repeatable); see --list for names")
    ap.add_argument(
        "--json", metavar="PATH",
        help="write a machine-readable run summary to PATH")
    args = ap.parse_args(argv)
    if args.list:
        width = max(map(len, registry.names()))
        for name in registry.names():
            print(f"{name:<{width}}  {registry.describe(name)}")
        return 0
    if args.only:
        unknown = sorted(set(args.only) - set(registry.names()))
        if unknown:
            ap.error(
                f"unknown benchmark(s) {unknown}; have {sorted(registry.names())}")
    suite = [n for n in registry.names() if not args.only or n in args.only]
    if args.json:
        try:  # fail before the suite runs, not minutes after -- append mode
            open(args.json, "a").close()  # checks writability w/o truncating
        except OSError as e:
            ap.error(f"cannot write --json path {args.json!r}: {e}")

    from repro.launch import compile_cache

    print(f"compile cache: {compile_cache.enable()}")
    results = {}
    timings = {}
    t_total = time.time()
    failures = []
    for name in suite:
        t0 = time.time()
        print(f"\n=== {name} " + "=" * (60 - len(name)))
        try:
            results[name] = registry.load(name).run()
            timings[name] = time.time() - t0
            print(f"    ok ({timings[name]:.1f}s)")
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            timings[name] = time.time() - t0
            print(f"    FAILED: {e!r}")

    print("\n" + "=" * 70)
    print("SUMMARY vs paper claims")
    print("=" * 70)
    r = results
    if "fig8_dram_reduction" in r:
        d = r["fig8_dram_reduction"]
        print(f"Fig 8  near-memory reduction (skewed avg): "
              f"{d['avg_near_reduction_skewed']:.1%} (paper ~72%), "
              f"perf {d['avg_perf_delta_skewed']:+.2%} (paper -0.86%)")
    if "fig9_at_scale" in r:
        d = r["fig9_at_scale"]
        for p in ("memtierd", "tpp", "autonuma"):
            print(f"Fig 9  {p}+GPAC throughput: {d[p]['avg_delta']:+.1%} "
                  f"(paper {d['paper_target'][p]:+.1%})")
    if "fig11_migration" in r:
        d = r["fig11_migration"]
        print(f"Fig 11 promoted {d['promoted_reduction']:.1%} less "
              f"(paper 64%), demoted {d['demoted_reduction']:.1%} less "
              f"(paper 87%)")
    if "fig13_tier_pairs" in r:
        d = r["fig13_tier_pairs"]
        print(f"Fig 13 DRAM/CXL {d['dram_cxl']['delta']:+.1%} (paper +6.3%); "
              f"Fig 14 HBM/DRAM {d['hbm_dram']['delta']:+.1%} (paper +5.3%)")
    if "fig17_pressure" in r:
        d = r["fig17_pressure"]
        print(f"Fig 17 benefit shrinks with more near memory: "
              f"{d['benefit_shrinks_with_more_near']}")
    if "bench_engine" in r:
        d = r["bench_engine"]
        print(f"Engine  speedup at n_guests>=8: "
              f"{d['min_speedup_at_scale']:.2f}x "
              f"(target >= {d['target_speedup_at_scale']}x)")
    total_s = time.time() - t_total
    print(f"\ntotal {total_s:.1f}s; "
          f"{len(suite)-len(failures)}/{len(suite)} benchmarks ok")
    for name, err in failures:
        print(f"  FAILED {name}: {err}")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(
                dict(
                    results=results,
                    timings_s=timings,
                    failures=dict(failures),
                    total_s=total_s,
                    ran=suite,
                ),
                f, indent=1, default=float,
            )
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
