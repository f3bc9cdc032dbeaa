"""Engine perf benchmark: the shared scan-fused engine driver vs the seed
per-guest/per-window reference path, plus the guest-axis device-sharded
driver (``engine.run_sharded``).

Times ``simulate.run_multi_guest`` (now a shim over the unified
``repro.core.engine.run``: guest-batched windows, scan-fused window loop,
chunked host transfer) against ``simulate.run_multi_guest_reference``
(unrolled per-guest ops, one host sync per window) across an
(n_guests, n_logical, n_windows) grid, and -- when more than one device is
visible -- ``engine.run_series(mesh=...)`` sharded over the guest axis, on
both host paths: replicated host state (``engine_sharded_s``) and the
host-partitioned near tier (``host_sharded_s``, DESIGN.md §11, with the
measured per-device host-state bytes).
``n_devices`` comes from ``jax.local_device_count()``; CI forces 8 simulated
CPU devices via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``. Trace
generation and jit compilation are excluded (one warmup run per path, then
best-of-``REPEATS`` wall clock).

Every grid row also times the on-device ``SynthTrace`` source (``synth_s``:
the same guest identities generated inside the scan, DESIGN.md §12), and a
pod-size row (``POD``, n_guests >= 128) runs the synth path alone -- the
array path is skipped with a logged reason, since its host trace would be
O(n_guests * n_windows * k).

Every runner is timed in this one process: on a TPU the process that first
touches JAX holds the chip, so a child process could not reach it.

A steady-state churn case (ISSUE 6) times ``engine.run_churn`` under a
Poisson arrival/departure fault schedule over mixed drift workloads against
the plain driver on the same fleet (``churn_s`` / ``churn_vs_engine``), and
asserts INV-CRASH-RECLAIM-COMPLETE on the final state
(``reclaim_complete``).

Multi-host columns (ISSUE 10, DESIGN.md §17): every at-scale grid row with
``n_windows % 4 == 0`` also times the host-partitioned driver under a
stride-4 overlapped arbitration exchange (``overlap_s`` /
``overlap_speedup`` -- 4 windows ride one psum, with trace synthesis
prefetched behind the in-flight collective), and the payload carries the
wall clock of a small coordinated 2-process launch (``multihost_s``,
informational).

Writes ``BENCH_engine.json`` at the repo root (the perf-trajectory artifact
CI archives) and ``experiments/benchmarks/<NAME>.json`` (``NAME`` comes from
the shared suite registry, ``benchmarks.registry``).
"""
from __future__ import annotations

import json
import os
import time
import warnings

import jax
import numpy as np

from benchmarks import common, registry
from repro.core import engine, faults, simulate
from repro.core.types import allocated_hp_mask
from repro.data import traces as tr

NAME = "bench_engine"
assert NAME in registry.SUITES, "suite must be registered in benchmarks.registry"

REPEATS = 5  # wall clock is noisy on small shared-CPU containers
HP_RATIO = 32
ACCESSES = 2048

# (n_guests, logical_per_guest, n_windows) -- n_guests >= 8 rows are the
# at-scale regime the acceptance criterion targets
GRID = (
    (2, 1024, 12),
    (4, 1024, 12),
    (8, 1024, 12),
    (12, 512, 12),
)

# pod-size configuration (ISSUE 5): only the on-device SynthTrace path runs
# here -- the array path would need a host [n_guests, n_windows, k] trace
# and is skipped with a logged reason
POD = (128, 256, 8)  # (n_guests, logical_per_guest, n_windows)

# steady-state churn fleet (ISSUE 6): Poisson arrival/departure over mixed
# drift workloads with a capacity shrink and a telemetry dropout
CHURN = (8, 512, 12)  # (n_guests, logical_per_guest, n_windows)


def _best_of(make, runner, traces, case, key) -> None:
    # block on the returned *state*, not just the host series: the drivers
    # dispatch asynchronously, and un-awaited final states would credit the
    # engine paths with work still in flight
    mg, state = make()
    t0 = time.perf_counter()
    jax.block_until_ready(runner(mg, state, traces)[0])  # warmup (compile)
    case[f"{key}_warmup_s"] = time.perf_counter() - t0
    best = float("inf")
    for _ in range(REPEATS):
        mg, state = make()
        t0 = time.perf_counter()
        jax.block_until_ready(runner(mg, state, traces)[0])
        best = min(best, time.perf_counter() - t0)
    case[f"{key}_s"] = best


def _bench_case(n_guests: int, logical_per_guest: int, n_windows: int,
                mesh, with_pallas: bool = False) -> dict:
    traces = np.stack([
        tr.generate(tr.TraceSpec(
            "redis", n_logical=logical_per_guest, hp_ratio=HP_RATIO,
            n_windows=n_windows, accesses_per_window=ACCESSES, seed=g))
        for g in range(n_guests)])

    def make():
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            return simulate.make_multi_guest(
                n_guests=n_guests, logical_per_guest=logical_per_guest,
                hp_ratio=HP_RATIO, near_fraction=0.25, base_elems=2, cl=8)

    # one spec for every engine runner and the host-state report: the
    # geometry is static, so rebuilding pools/mappings per reader is waste
    spec = make()[0].spec()

    def run_engine(mg, state, t):
        return engine.run_series(spec, state, t)

    def run_sharded(mg, state, t):
        # replicated host state on every device (DESIGN.md §9)
        return engine.run_series(spec, state, t, mesh=mesh,
                                 host_sharded=False)

    def run_host_sharded(mg, state, t):
        # host state partitioned by block ranges (DESIGN.md §11)
        return engine.run_series(spec, state, t, mesh=mesh,
                                 host_sharded=True)

    def run_overlap(mg, state, t):
        # host-partitioned near tier + stride-4 overlapped arbitration
        # exchange (DESIGN.md §17): 4 windows ride ONE psum, and the next
        # group's trace synthesis issues behind the in-flight collective
        return engine.run_series(spec, state, t, mesh=mesh,
                                 host_sharded=True, arbitration_stride=4)

    # on-device synthesis (DESIGN.md §12): no [n_guests, n_windows, k]
    # array anywhere. Same redis workload at the same shapes as the array
    # rows (symmetric_spec guests all carry seed=0; decorrelation comes
    # from the global-gid key fold), timed on the SAME single-device driver
    # as engine_s so synth_vs_engine isolates the trace-source cost
    synth = engine.SynthTrace(n_windows=n_windows, accesses_per_window=ACCESSES)

    def run_synth(mg, state, t):
        return engine.run_series(spec, state, synth)

    # Pallas hot-path kernels (DESIGN.md §16), interpret mode on CPU: timed
    # on the smallest grid row only (interpretation is orders of magnitude
    # slower than compiled XLA and the ratio is informational -- the
    # bit-exactness pin lives in INV-KERNEL-BACKEND-EXACT, not here)
    def run_pallas(mg, state, t):
        return engine.run_series(spec, state, t, kernel_backend="pallas")

    case = dict(
        n_guests=n_guests, logical_per_guest=logical_per_guest,
        n_logical=n_guests * logical_per_guest, n_windows=n_windows,
        hp_ratio=HP_RATIO, accesses_per_window=ACCESSES,
        n_devices=1 if mesh is None else mesh.shape["guest"])
    if mesh is not None:
        report = common.host_state_report(spec, mesh)
        case["host_state_bytes_replicated"] = report["replicated_bytes_per_device"]
        case["host_state_bytes_per_device"] = report["sharded_bytes_per_device"]
        case["host_state_scaling"] = report["scaling"]
    runners = [
        ("reference", simulate.run_multi_guest_reference),
        ("engine", run_engine),
        ("synth", run_synth),
    ]
    if with_pallas:
        runners.append(("pallas", run_pallas))
    if mesh is not None:
        runners.append(("engine_sharded", run_sharded))
        runners.append(("host_sharded", run_host_sharded))
        if n_windows % 4 == 0:  # host-sharded stride must divide the chunk
            runners.append(("overlap", run_overlap))
    for name, runner in runners:
        _best_of(make, runner, traces, case, name)
    _finalize_case(case)
    return case


def _finalize_case(case: dict) -> None:
    """The cross-runner ratios, computed once every timing key is present."""
    case["speedup"] = case["reference_s"] / case["engine_s"]
    case["synth_vs_engine"] = case["engine_s"] / case["synth_s"]
    if "pallas_s" in case:
        # > 1 means the pallas-interpret path cost that much more than the
        # compiled XLA engine (expected on CPU; informational, never gated)
        case["pallas_vs_engine"] = case["pallas_s"] / case["engine_s"]
    if "engine_sharded_s" in case:
        # > 1 means the sharded driver beat the single-device engine
        case["sharded_speedup"] = case["engine_s"] / case["engine_sharded_s"]
        case["host_sharded_speedup"] = case["engine_s"] / case["host_sharded_s"]
    if "overlap_s" in case:
        # stride-4 overlapped exchange vs the single-device engine (§17);
        # vs host_sharded_speedup this isolates what batching 4 windows
        # into one psum buys back
        case["overlap_speedup"] = case["engine_s"] / case["overlap_s"]


def _pod_case(mesh) -> dict:
    """The >= 128-guest configuration only the SynthTrace path can run:
    each window's accesses are generated inside the scan (per-device
    residency O(n_local_guests * accesses_per_window)), while the array
    path would have to host-materialize the full trace first."""
    n_guests, logical_per_guest, n_windows = POD
    guests = tuple(
        engine.GuestSpec(n_logical=logical_per_guest, cl=8, gpa_slack=1.0,
                         workload="redis", seed=g)
        for g in range(n_guests))
    host = engine.HostSpec(hp_ratio=HP_RATIO, near_fraction=0.25,
                           base_elems=2, cl=8, ipt_min_hits=1)
    spec, _ = engine.build(guests, host)
    synth = engine.SynthTrace(n_windows=n_windows,
                              accesses_per_window=ACCESSES)
    array_mb = n_guests * n_windows * ACCESSES * 4 / 2**20
    skip_reason = (
        f"array path skipped: host-materializing int32[{n_guests}, "
        f"{n_windows}, {ACCESSES}] would allocate {array_mb:.0f} MB and "
        f"ship it through pad_guest_rows every sharded run")
    print(f"  pod row ({n_guests} guests): {skip_reason}")

    def make():
        return None, engine.init_engine_state(spec)

    def run_synth(_, state, t):
        return engine.run_series(spec, state, synth, mesh=mesh)

    case = dict(
        n_guests=n_guests, logical_per_guest=logical_per_guest,
        n_logical=n_guests * logical_per_guest, n_windows=n_windows,
        hp_ratio=HP_RATIO, accesses_per_window=ACCESSES,
        n_devices=1 if mesh is None else mesh.shape["guest"],
        pod=True, array_path=skip_reason,
        # the residency the synth path actually carries per window, vs the
        # host array the array path would need
        trace_bytes_per_window=n_guests * ACCESSES * 4,
        array_trace_bytes=n_guests * n_windows * ACCESSES * 4,
    )
    _best_of(make, run_synth, None, case, "synth")
    return case


def _churn_case() -> dict:
    """The steady-state churn benchmark (ISSUE 6): a Poisson
    arrival/departure fleet over mixed drift workloads, with a mid-run
    capacity shrink and a telemetry dropout, timed against the plain scan
    driver on the same fleet and trace source. ``churn_vs_engine`` isolates
    the fault machinery's overhead; ``reclaim_complete`` asserts
    INV-CRASH-RECLAIM-COMPLETE (no allocated huge page left in a departed
    guest's segment) on the final carry."""
    n_guests, logical_per_guest, n_windows = CHURN
    workloads = ("redis_drift", "hash_drift", "redis", "masim")
    guests = tuple(
        engine.GuestSpec(n_logical=logical_per_guest, cl=8, gpa_slack=1.0,
                         workload=workloads[g % len(workloads)], seed=g)
        for g in range(n_guests))
    host = engine.HostSpec(hp_ratio=HP_RATIO, near_fraction=0.25,
                           base_elems=2, cl=8, ipt_min_hits=1)
    spec, _ = engine.build(guests, host)
    synth = engine.SynthTrace(n_windows=n_windows,
                              accesses_per_window=ACCESSES)
    sched = (faults.poisson_churn(n_guests, n_windows, arrival_rate=0.5,
                                  departure_rate=0.08, seed=0)
             .shrink(n_windows // 2, max(1, int(spec.cfg.n_near * 0.75)))
             .dropout(n_windows // 3))
    case = dict(
        n_guests=n_guests, logical_per_guest=logical_per_guest,
        n_logical=n_guests * logical_per_guest, n_windows=n_windows,
        hp_ratio=HP_RATIO, accesses_per_window=ACCESSES, n_devices=1,
        churn=True, workloads=list(workloads), n_fault_events=sched.n_events)

    def make_plain():
        return None, engine.init_engine_state(spec)

    def run_plain(_, state, t):
        return engine.run(spec, state, synth)

    def make_churn():
        return None, engine.init_churn(spec)

    def run_churned(_, cs, t):
        return engine.run_churn(spec, cs, synth, faults=sched)

    _best_of(make_plain, run_plain, None, case, "engine")
    _best_of(make_churn, run_churned, None, case, "churn")
    case["churn_vs_engine"] = case["engine_s"] / case["churn_s"]
    # INV-CRASH-RECLAIM-COMPLETE on the final carry of an untimed run; the
    # same run carries the TCO collector (ISSUE 7) so the perf-trajectory
    # artifact tracks the fleet's steady-state $-weighted placement
    cs, se = engine.run_churn(spec, engine.init_churn(spec), synth,
                              faults=sched, collect=("hits", "tco"))
    case["tco"] = float(np.asarray(se["tco"])[-3:].mean())
    case["amat_ns"] = float(np.asarray(se["amat_ns"])[-3:].mean())
    _, hp_owner, _, _ = faults.segment_tables(spec.canonical())
    owner = np.asarray(hp_owner)
    active = np.asarray(cs.active)
    alloc = np.asarray(allocated_hp_mask(spec.cfg, cs.state))
    orphans = alloc & (owner >= 0) & ~active[np.clip(owner, 0, None)]
    case["reclaim_complete"] = not bool(orphans.any())
    return case


def _multihost_wall() -> dict:
    """Wall clock of a small coordinated multi-process pod job (DESIGN.md
    §17): 2 processes x 2 CPU devices running
    ``scripts/pod_multihost_worker.py`` (32 guests, one live migration).
    Informational, never gated -- the number is dominated by the two
    workers' cold jit compiles, but its trajectory catches a broken or
    pathologically slow distributed launch path."""
    from repro.launch import multihost

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker = os.path.join(root, "scripts", "pod_multihost_worker.py")
    t0 = time.perf_counter()
    multihost.launch_check(worker, marker="POD MULTIHOST OK",
                           args=("32", "1"), num_processes=2,
                           devices_per_process=2, timeout=900.0, cwd=root)
    return dict(multihost_s=time.perf_counter() - t0,
                multihost_processes=2, multihost_devices_per_process=2,
                multihost_pod_guests=32, multihost_migrations=1)


def run() -> dict:
    mesh = common.default_guest_mesh()
    n_devices = 1 if mesh is None else mesh.shape["guest"]
    cases = []
    for i, (n_guests, logical_per_guest, n_windows) in enumerate(GRID):
        # pallas-interpret is timed on the smallest row only (§16): the
        # interpreter's constant factor would dominate every larger row
        # without adding information
        case = _bench_case(n_guests, logical_per_guest, n_windows, mesh,
                           with_pallas=i == 0)
        cases.append(case)
        sharded = (f" sharded[{n_devices}d] {case['engine_sharded_s']*1e3:8.1f} ms"
                   if "engine_sharded_s" in case else "")
        host = (f" host_sharded {case['host_sharded_s']*1e3:8.1f} ms"
                f" (state/dev {case['host_state_scaling']:.2f}x)"
                if "host_sharded_s" in case else "")
        overlap = (f" overlap[stride4] {case['overlap_s']*1e3:8.1f} ms"
                   f" ({case['overlap_speedup']:.2f}x engine)"
                   if "overlap_s" in case else "")
        pallas = (f" pallas {case['pallas_s']*1e3:8.1f} ms"
                  f" ({case['pallas_vs_engine']:.0f}x engine, interpret)"
                  if "pallas_s" in case else "")
        print(f"  n_guests={n_guests:3d} n_logical={case['n_logical']:6d} "
              f"windows={n_windows:3d}: reference {case['reference_s']*1e3:8.1f} ms"
              f" engine {case['engine_s']*1e3:8.1f} ms"
              f" synth {case['synth_s']*1e3:8.1f} ms"
              f" speedup {case['speedup']:5.2f}x{sharded}{host}{overlap}"
              f"{pallas}")
    pod = _pod_case(mesh)
    cases.append(pod)
    print(f"  n_guests={pod['n_guests']:3d} n_logical={pod['n_logical']:6d} "
          f"windows={pod['n_windows']:3d}: synth {pod['synth_s']*1e3:8.1f} ms "
          f"(pod row; array path skipped)")
    churn = _churn_case()
    cases.append(churn)
    print(f"  churn fleet {churn['n_guests']:3d} guests x "
          f"{churn['n_windows']} windows ({churn['n_fault_events']} fault "
          f"events): engine {churn['engine_s']*1e3:8.1f} ms churn "
          f"{churn['churn_s']*1e3:8.1f} ms ratio "
          f"{churn['churn_vs_engine']:.2f} reclaim "
          f"{'OK' if churn['reclaim_complete'] else 'INCOMPLETE'}")
    at_scale = [
        c["speedup"] for c in cases if c["n_guests"] >= 8 and "speedup" in c]
    sharded_at_scale = [
        c["sharded_speedup"] for c in cases
        if c["n_guests"] >= 8 and "sharded_speedup" in c]
    host_sharded_at_scale = [
        c["host_sharded_speedup"] for c in cases
        if c["n_guests"] >= 8 and "host_sharded_speedup" in c]
    overlap_at_scale = [
        c["overlap_speedup"] for c in cases
        if c["n_guests"] >= 8 and "overlap_speedup" in c]
    payload = dict(
        suite=NAME,
        description=registry.describe(NAME),
        backend=jax.default_backend(),
        n_devices=n_devices,
        repeats=REPEATS,
        cases=cases,
        min_speedup_at_scale=min(at_scale),
        target_speedup_at_scale=3.0,
        meets_target=min(at_scale) >= 3.0,
        pod_guests=pod["n_guests"],
        pod_synth_s=pod["synth_s"],
        churn_vs_engine=churn["churn_vs_engine"],
        reclaim_complete=churn["reclaim_complete"],
        tco=churn["tco"],
        amat_ns=churn["amat_ns"],
    )
    pallas_rows = [c for c in cases if "pallas_vs_engine" in c]
    if pallas_rows:
        # §16 informational column: pallas-interpret cost on the smallest row
        payload["pallas_vs_engine"] = pallas_rows[0]["pallas_vs_engine"]
    if sharded_at_scale:
        # acceptance: the sharded path is no slower than the single-device
        # engine at n_guests >= 8 (wall clock is noisy on shared CPU
        # "devices"; allow 5%)
        payload["min_sharded_speedup_at_scale"] = min(sharded_at_scale)
        payload["sharded_no_slower_at_scale"] = min(sharded_at_scale) >= 0.95
    if host_sharded_at_scale:
        payload["min_host_sharded_speedup_at_scale"] = min(host_sharded_at_scale)
        # the memory-scaling acceptance: per-device host-state bytes of the
        # partitioned carry vs the replicated path (~1/n_devices)
        payload["host_state_scaling"] = max(
            c["host_state_scaling"] for c in cases if "host_state_scaling" in c)
    if overlap_at_scale:
        # §17 acceptance: the stride-4 overlapped exchange recovering the
        # at-scale sharded gap (>= 1.0 means it beats the single-device
        # engine outright; see ROADMAP for the shared-container caveat)
        payload["min_overlap_speedup_at_scale"] = min(overlap_at_scale)
        payload["overlap_recovers_at_scale"] = min(overlap_at_scale) >= 1.0
    if mesh is not None:
        payload.update(_multihost_wall())
        print(f"  multihost launch (2 proc x 2 dev, 32-guest pod + 1 "
              f"migration): {payload['multihost_s']:.1f} s wall")
    with open("BENCH_engine.json", "w") as f:
        json.dump(payload, f, indent=1, default=float)
    return common.save(NAME, payload)


if __name__ == "__main__":
    r = run()
    print(f"min speedup at n_guests>=8: {r['min_speedup_at_scale']:.2f}x "
          f"(target >= {r['target_speedup_at_scale']}x) "
          f"-> {'OK' if r['meets_target'] else 'MISS'}")
    if "min_sharded_speedup_at_scale" in r:
        print(f"sharded vs engine at n_guests>=8: "
              f"{r['min_sharded_speedup_at_scale']:.2f}x on "
              f"{r['n_devices']} devices -> "
              f"{'OK' if r['sharded_no_slower_at_scale'] else 'MISS'}")
    if "min_host_sharded_speedup_at_scale" in r:
        print(f"host-sharded vs engine at n_guests>=8: "
              f"{r['min_host_sharded_speedup_at_scale']:.2f}x; per-device "
              f"host state {r['host_state_scaling']:.2f}x of replicated on "
              f"{r['n_devices']} devices")
    if "min_overlap_speedup_at_scale" in r:
        print(f"overlapped exchange (stride 4) vs engine at n_guests>=8: "
              f"{r['min_overlap_speedup_at_scale']:.2f}x -> "
              f"{'recovered' if r['overlap_recovers_at_scale'] else 'gap'}")
    print(f"churn vs engine: {r['churn_vs_engine']:.2f}x; crash reclaim "
          f"{'complete' if r['reclaim_complete'] else 'INCOMPLETE'}")
