"""Figs. 9/10/12: multi-tenant at-scale benchmark.

Six guests (each a Redis instance) share one host under near-memory pressure;
Memtierd / TPP / AutoNUMA at the host, GPAC optionally in every guest.
Reports per-VM throughput delta (Fig. 9), near-memory distribution (Fig. 10),
and modeled far-memory accesses / stalls (Fig. 12's counters).

Paper: Memtierd+GPAC ~ +13% avg, TPP+GPAC ~ +11%, AutoNUMA+GPAC ~ +1.6%.

:func:`run_pod` is the pod-size variant (ISSUE 5): hundreds of guests on the
host-partitioned engine, driven by an on-device :class:`engine.SynthTrace`
-- each window's accesses are generated inside the scan, so no
``[n_guests, n_windows, k]`` trace is ever host-materialized (at 256 guests
x 24 windows x 8192 accesses that array alone would be ~192 MB, growing
linearly with the fleet).
"""
from __future__ import annotations

import os
import pathlib
import time

import numpy as np

from benchmarks import common
from repro.core import engine

ROOT = pathlib.Path(__file__).resolve().parent.parent

N_GUESTS = 6
LOGICAL_PER_GUEST = 8 * 1024
WINDOWS = 24
ACCESSES = 8192
# scan-fuse the window loop in chunks of this many windows (one device->host
# metric transfer per chunk; see repro.core.engine.run)
WINDOWS_PER_STEP = 12

# pod-size defaults (run_pod): kept CPU-tractable per guest so the fleet
# dimension dominates
POD_GUESTS = 256
POD_LOGICAL_PER_GUEST = 512
POD_WINDOWS = 12
POD_ACCESSES = 1024


def make_engine():
    return common.make_symmetric_engine(N_GUESTS, LOGICAL_PER_GUEST,
                                        near_fraction=0.25)


def run(policies=("memtierd", "tpp", "autonuma"), mesh="auto"):
    """``mesh="auto"`` shards the guest axis over every local device (the
    sharded driver is bit-for-bit equal to the unsharded one, so the figure
    is identical either way); ``mesh=None`` forces single-device."""
    if mesh == "auto":
        mesh = common.default_guest_mesh()
    spec, _ = make_engine()
    traces = engine.guest_traces(spec, n_windows=WINDOWS,
                                 accesses_per_window=ACCESSES)
    out = {}
    for policy in policies:
        res = {}
        for use_gpac in (False, True):
            spec, state = make_engine()
            state, series = engine.run_series(
                spec, state, traces, policy=policy, use_gpac=use_gpac,
                windows_per_step=WINDOWS_PER_STEP, mesh=mesh)
            res["gpac" if use_gpac else "baseline"] = dict(
                tput=series["throughput"][-6:].mean(axis=0).tolist(),
                near_blocks=series["near_blocks"][-1].tolist(),
                hit=series["hit_rate"][-6:].mean(axis=0).tolist(),
            )
        b = np.asarray(res["baseline"]["tput"])
        g = np.asarray(res["gpac"]["tput"])
        res["per_vm_delta"] = ((g - b) / b).tolist()
        res["avg_delta"] = float(((g - b) / b).mean())
        # Fig. 12 counters: far accesses ~ (1-hit) share, stall proxy
        bh = np.asarray(res["baseline"]["hit"])
        gh = np.asarray(res["gpac"]["hit"])
        res["far_access_reduction"] = float(
            1 - (1 - gh).sum() / max((1 - bh).sum(), 1e-9))
        out[policy] = res
    out["paper_target"] = dict(memtierd=0.13, tpp=0.11, autonuma=0.016)
    out["n_devices"] = 1 if mesh is None else mesh.shape["guest"]
    # host-state footprint of this run: the sharded driver partitions the
    # host near tier by block ranges (DESIGN.md §11), so per-device bytes
    # scale ~1/n_devices -- the lever that takes this figure to hundreds of
    # guests on a pod
    out["host_state"] = common.host_state_report(spec, mesh)
    return common.save("fig9_at_scale", out)


def _pod_fleet(n_lanes: int, logical_per_guest: int):
    guests = tuple(
        engine.GuestSpec(n_logical=logical_per_guest, cl=8, gpa_slack=1.0,
                         workload="redis", seed=g)
        for g in range(n_lanes))
    host = engine.HostSpec(hp_ratio=common.HP_RATIO, near_fraction=0.25,
                           base_elems=2, cl=8, ipt_min_hits=1)
    return engine.build(guests, host)


def _pod_migration_run(spec, n_guests: int, migrations: int,
                       n_windows: int, accesses: int,
                       policy: str, mesh) -> dict:
    """Two churn segments with ``migrations`` live handoffs between them.

    Lanes ``n_guests .. n_guests+migrations-1`` boot vacant (crash-style
    reclaim at init); mid-run, guest ``i`` hands off into spare
    ``n_guests + i``. Sources sit at the head of the lane range and spares
    at the tail, so on a sharded mesh every handoff crosses guest shards.
    """
    from repro.launch import migration

    active = np.ones((spec.n_guests,), bool)
    active[n_guests:] = False
    cs = engine.init_churn(spec, active=active)
    half = max(1, n_windows // 2)
    seg = engine.SynthTrace(n_windows=half, accesses_per_window=accesses)
    cs, s1 = engine.run_churn(spec, cs, seg, mesh=mesh, policy=policy,
                              use_gpac=True, windows_per_step=half)
    manifests = []
    for i in range(migrations):
        cs, man = migration.migrate_guest(spec, cs, src=i, dst=n_guests + i)
        manifests.append(dict(src=i, dst=n_guests + i, **man))
    seg2 = engine.SynthTrace(n_windows=n_windows - half,
                             accesses_per_window=accesses)
    cs, s2 = engine.run_churn(spec, cs, seg2, mesh=mesh, policy=policy,
                              use_gpac=True,
                              windows_per_step=n_windows - half)
    nh = np.concatenate([s1["near_hits"], s2["near_hits"]])
    fh = np.concatenate([s1["far_hits"], s2["far_hits"]])
    act = np.concatenate([s1["active"], s2["active"]])
    tail = max(1, n_windows // 4)
    hit = nh.sum(axis=1) / np.maximum((nh + fh).sum(axis=1), 1)
    return dict(
        migrations=manifests,
        migration_window=int(half),
        hit_rate_tail=float(hit[-tail:].mean()),
        active_per_window=act.sum(axis=1).astype(int).tolist(),
        active_final=int(np.asarray(cs.active).sum()),
    )


def run_pod(n_guests: int = POD_GUESTS,
            logical_per_guest: int = POD_LOGICAL_PER_GUEST,
            n_windows: int = POD_WINDOWS,
            accesses: int = POD_ACCESSES,
            policy: str = "memtierd",
            mesh="auto",
            migrations: int = 0):
    """Fig. 9 at pod scale: ``n_guests`` Redis-like guests on the
    host-partitioned engine with on-device trace synthesis.

    Returns the same per-policy delta structure as :func:`run` (one policy,
    GPAC off/on) plus the trace-residency accounting: per-device synthesis
    state is O(n_local_guests * accesses_per_window), vs the
    O(n_guests * n_windows * k) host array the packed path would need.

    ``migrations > 0`` switches to the live-migration protocol (DESIGN.md
    §17): that many vacant spare lanes join the fleet at the tail, the run
    goes through the churn engine in two segments, and between them each
    of the first ``migrations`` guests is handed off live into a spare.
    The payload then reports the per-handoff byte manifests instead of the
    GPAC off/on delta. Either way the payload carries the host-state
    footprint and the collective-volume accounting of the run
    (:func:`repro.core.sharding.collective_bytes`).
    """
    from repro.core import sharding

    if mesh == "auto":
        mesh = common.default_guest_mesh()
    n_shards = 1 if mesh is None else mesh.shape["guest"]
    spec, _ = _pod_fleet(n_guests + migrations, logical_per_guest)
    sharding.reset_collective_bytes()
    if migrations:
        res = _pod_migration_run(spec, n_guests, migrations, n_windows,
                                 accesses, policy, mesh)
        name = "fig9_at_pod_scale_migration"
    else:
        synth = engine.SynthTrace(n_windows=n_windows,
                                  accesses_per_window=accesses)
        res = {}
        for use_gpac in (False, True):
            state = engine.init_engine_state(spec)
            state, series = engine.run_series(
                spec, state, synth, policy=policy, use_gpac=use_gpac,
                windows_per_step=max(1, n_windows // 2), mesh=mesh)
            tail = max(1, n_windows // 4)
            res["gpac" if use_gpac else "baseline"] = dict(
                tput=series["throughput"][-tail:].mean(axis=0).tolist(),
                near_blocks=series["near_blocks"][-1].tolist(),
                hit=series["hit_rate"][-tail:].mean(axis=0).tolist(),
            )
        b = np.asarray(res["baseline"]["tput"])
        g = np.asarray(res["gpac"]["tput"])
        res["avg_delta"] = float(((g - b) / b).mean())
        name = "fig9_at_pod_scale"
    coll = sharding.collective_bytes()
    # exact per-psum payload bytes, recorded at trace time; merge_window /
    # host_exchange fire once per window (stride 1), host_gather once per
    # run (run_series hands run_sharded a replicated state)
    per_window = coll.get("merge_window", 0) + coll.get("host_exchange", 0)
    out = {
        policy: res,
        "n_guests": n_guests,
        "n_migrations": migrations,
        "n_devices": n_shards,
        "host_state": common.host_state_report(spec, mesh),
        "collective": dict(
            per_site_bytes=coll,
            per_window_bytes=per_window,
            bytes_per_run=per_window * n_windows
            + coll.get("host_gather", 0),
        ),
        # no [n_guests, n_windows, k] array exists anywhere on this path
        "synth_trace_bytes_per_device_window":
            -(-n_guests // n_shards) * accesses * 4,
        "array_trace_bytes_avoided": n_guests * n_windows * accesses * 4,
    }
    import jax

    if jax.process_count() > 1 and jax.process_index() != 0:
        return out  # one writer: only the coordinator saves the artifact
    return common.save(name, out)


def run_pod_multihost(n_guests: int = 1024, migrations: int = 2,
                      num_processes: int = 2, devices_per_process: int = 2,
                      timeout: float = 3600.0):
    """:func:`run_pod` under a coordinated multi-process mesh (§17).

    Spawns ``num_processes`` coordinated workers (each pinned to
    ``devices_per_process`` CPU devices) running
    ``scripts/pod_multihost_worker.py`` -- a dedicated entry because
    ``jax.distributed.initialize`` must precede the first jax computation,
    and importing this module already builds ``jnp`` constants. Returns the
    coordinator-saved payload plus the launch wall time.
    """
    from repro.launch import multihost

    t0 = time.perf_counter()
    multihost.launch_check(
        str(ROOT / "scripts" / "pod_multihost_worker.py"),
        marker="POD MULTIHOST OK",
        args=(str(n_guests), str(migrations)),
        num_processes=num_processes,
        devices_per_process=devices_per_process, timeout=timeout,
        cwd=str(ROOT))
    dt = time.perf_counter() - t0
    import json

    with open(os.path.join(str(ROOT), common.OUT_DIR,
                           "fig9_at_pod_scale_migration.json")) as f:
        out = json.load(f)
    out["multihost"] = dict(num_processes=num_processes,
                            devices_per_process=devices_per_process,
                            wall_s=dt)
    return common.save("fig9_at_pod_scale_migration", out)


if __name__ == "__main__":
    r = run()
    for p in ("memtierd", "tpp", "autonuma"):
        d = r[p]
        print(f"{p:9s} avg tput delta {d['avg_delta']:+.1%} "
              f"(paper {r['paper_target'][p]:+.1%}); "
              f"far-access reduction {d['far_access_reduction']:.1%}")
        print(f"          near blocks baseline {d['baseline']['near_blocks']}"
              f" -> gpac {d['gpac']['near_blocks']}")
    p = run_pod()
    print(f"pod scale ({p['n_guests']} guests, {p['n_devices']} device(s)): "
          f"memtierd avg tput delta {p['memtierd']['avg_delta']:+.1%}; "
          f"synth residency/device/window "
          f"{p['synth_trace_bytes_per_device_window']/2**20:.2f} MB vs "
          f"{p['array_trace_bytes_avoided']/2**20:.0f} MB host array avoided")
