#!/usr/bin/env python3
"""Start the tiering engine on a TPU at deployment guest size and check it.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the four-chip phase only

One chip: a fleet of 8 guests of 16 GB each (4,194,304 base pages of 4 KB,
``hp_ratio`` 512 = 2 MB huge pages), redis- and memcached-shaped tenants
alternating, a near tier of 25% of the guests' huge pages, runs 16 windows
of 65,536 accesses per guest through ``engine.run`` (SynthTrace source,
``memtierd`` policy, GPAC on) with ``kernel_backend="auto"``. Checks:

(a) the same fleet with ``kernel_backend="xla"`` gives bit-identical series
    and state; and a reduced fleet with every kernel forced onto its Pallas
    form matches ``"xla"`` bit for bit (covers kernels ``auto`` keeps on XLA);
(b) a reduced fleet (2 guests x 262,144 pages) run by ``engine.run`` matches
    ``engine.run_reference`` bit for bit on an ``ArrayTrace``;
(c) printed, not gating: hit rate and near blocks held with GPAC and without.

Four chips: ``engine.run_sharded(host_sharded=True)`` on a 4-chip ``"guest"``
mesh over a larger fleet, the contracts' state invariants on its result, the
one-chip fleet on 4 chips vs 1 device (bit-identical), one ``run_churn`` plus
``migration.migrate_guest`` on the mesh, and per-device memory.

Exits non-zero when JAX finds no TPU (no CPU fallback) or any check fails.
The last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

GUEST_PAGES = 4_194_304  # 16 GB guest / 4 KB base pages
HP_RATIO = 512  # 2 MB / 4 KB
N_GUESTS = 8
NEAR_FRACTION = 0.25
WINDOWS = 16
ACCESSES = 65_536  # per guest per window
SEED = 0
# (b): the fleet engine.run_reference replays. Its rows are 262,144 wide: on
# v5e, XLA's top_k breaks ties off the lowest index from that width on, so
# (b) covers the tie-exact top-k the filter and host tick use there.
REF_FLEET = (2, 262_144)
# (a'): every kernel on Pallas; bincount's O(ids x bins) form bounds the size
PALLAS_FLEET = (4, 131_072)
# four chips: the host-partitioned fleet (guests of GUEST_PAGES) and the
# churn/migration fleet
SHARDED_GUESTS = 16
CHURN_FLEET = (8, 65_536)


def log(msg: str) -> None:
    print(msg, flush=True)


def tpu_devices(count: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {d.platform!r}); "
                 f"this check runs on the chip only")
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, JAX sees {len(devs)}")
    return devs


class CompileClock:
    """Seconds of XLA compilation (or of fetching the executable from the
    persistent cache), read from JAX's monitoring events. Tracing is left
    out: its events nest, one per inner ``jit``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self.EVENTS:
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def fleet(n_guests: int, pages: int):
    from repro.core import engine
    from repro.data.traces import PAPER_CL

    names = ("redis", "memcached")
    guests = tuple(
        engine.GuestSpec(n_logical=pages, cl=PAPER_CL[names[g % 2]],
                         workload=names[g % 2], seed=SEED + g)
        for g in range(n_guests))
    host = engine.HostSpec(hp_ratio=HP_RATIO, near_fraction=NEAR_FRACTION)
    return engine.build(guests, host)


def same_bits(a, b) -> bool:
    """Bit-for-bit equality of two pytrees (floats compared as bit patterns)."""
    import jax
    import numpy as np

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    if len(la) != len(lb):
        return False
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype:
            return False
        if x.dtype.kind == "f":
            x, y = x.view(f"u{x.itemsize}"), y.view(f"u{y.itemsize}")
        if not np.array_equal(x, y):
            return False
    return True


def tiering_outcome(series: dict) -> tuple[float, int]:
    """(hit rate over the run, near blocks held after the last window)."""
    near = int(series["near_hits"].sum())
    total = near + int(series["far_hits"].sum())
    return near / max(total, 1), int(series["near_blocks"][-1].sum())


def timed(clock: CompileClock, fn):
    import jax

    c0, t0 = clock.seconds, time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0, clock.seconds - c0


def check(ok: bool, what: str, failures: list) -> None:
    log(f"check {what}: {'PASS' if ok else 'FAIL'}")
    if not ok:
        failures.append(what)


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


def one_chip(dev, clock: CompileClock, failures: list) -> None:
    from repro.core import engine
    from repro.kernels import registry

    for name, backend in registry.resolved_backends("auto").items():
        why = registry.get_kernel(name).tpu_auto_why
        log(f"kernel {name}: auto -> {backend}" + (f" ({why})" if why else ""))

    spec, s0 = fleet(N_GUESTS, GUEST_PAGES)
    cfg = spec.cfg
    log(f"fleet: {N_GUESTS} guests x {GUEST_PAGES} pages, hp_ratio "
        f"{cfg.hp_ratio}, n_logical {cfg.n_logical}, n_gpa {cfg.n_gpa}, "
        f"n_near {cfg.n_near} of {cfg.n_gpa_hp} huge pages")
    src = engine.SynthTrace(WINDOWS, ACCESSES)

    def run(**kw):
        return engine.run(spec, s0, src, **kw)

    (st, se), first_s, compile_s = timed(clock, lambda: run(kernel_backend="auto"))
    log(f"engine.run auto: compile {compile_s:.3f} s "
        f"(persistent cache hits {clock.cache_hits}), first call "
        f"{first_s:.3f} s")
    _, run_s, _ = timed(clock, lambda: run(kernel_backend="auto"))
    log(f"engine.run auto: run {run_s:.3f} s for {WINDOWS} windows "
        f"({N_GUESTS * WINDOWS / run_s:.2f} guest-windows/s)")
    log(f"peak_bytes_in_use: {peak_bytes(dev)}")

    (st_x, se_x), _, _ = timed(clock, lambda: run(kernel_backend="xla"))
    check(same_bits(st, st_x) and same_bits(se, se_x),
          "(a) auto == xla at full size", failures)
    del st_x, se_x

    (_, se_off), _, _ = timed(
        clock, lambda: run(kernel_backend="auto", use_gpac=False))
    hit, near = tiering_outcome(se)
    hit0, near0 = tiering_outcome(se_off)
    log(f"(c) GPAC on: hit rate {hit:.6f}, near blocks held {near}")
    log(f"(c) GPAC off: hit rate {hit0:.6f}, near blocks held {near0}")
    del st, se, se_off, s0

    g, pages = PALLAS_FLEET
    pspec, p0 = fleet(g, pages)
    (st_p, se_p), pallas_s, _ = timed(
        clock, lambda: engine.run(pspec, p0, src, kernel_backend="pallas"))
    (st_x, se_x), xla_s, _ = timed(
        clock, lambda: engine.run(pspec, p0, src, kernel_backend="xla"))
    log(f"(a) {g} guests x {pages} pages: pallas {pallas_s:.3f} s, "
        f"xla {xla_s:.3f} s (compile included)")
    check(same_bits(st_p, st_x) and same_bits(se_p, se_x),
          f"(a) pallas == xla, every kernel on Pallas, {g} x {pages}",
          failures)
    del st_p, se_p, st_x, se_x, p0

    g, pages = REF_FLEET
    rspec, r0 = fleet(g, pages)
    traces = engine.guest_traces(rspec, WINDOWS, ACCESSES)
    st_e, se_e = engine.run(rspec, r0, engine.ArrayTrace(traces),
                            kernel_backend="auto")
    st_r, se_r = engine.run_reference(rspec, r0, traces)
    check(same_bits(st_e, st_r) and same_bits(se_e, se_r),
          f"(b) run == run_reference, {g} x {pages}", failures)


def near_overcommit_free(cfg, state) -> bool:
    """The contracts' state invariants: the block table is a permutation of
    the slots, ``slot_owner`` is its inverse, and the near tier holds no
    more allocated blocks than it has slots."""
    import numpy as np

    from repro.core.types import allocated_hp_mask

    bt = np.asarray(state.block_table)
    owner = np.asarray(state.slot_owner)
    perm = np.array_equal(np.sort(bt), np.arange(cfg.n_slots))
    inverse = np.array_equal(owner[bt], np.arange(cfg.n_gpa_hp))
    near = int((np.asarray(allocated_hp_mask(cfg, state)) & (bt < cfg.n_near)).sum())
    return perm and inverse and near <= cfg.n_near


def four_chips(devs, clock: CompileClock, failures: list) -> None:
    import jax

    from repro.core import engine, sharding
    from repro.launch import migration

    mesh = sharding.guest_mesh(4)
    src = engine.SynthTrace(WINDOWS, ACCESSES)

    spec, s0 = fleet(SHARDED_GUESTS, GUEST_PAGES)
    (st, se), first_s, compile_s = timed(clock, lambda: engine.run_sharded(
        spec, s0, src, mesh=mesh, host_sharded=True))
    log(f"run_sharded(host_sharded=True) {SHARDED_GUESTS} guests x "
        f"{GUEST_PAGES} pages on 4 chips: first call {first_s:.3f} s, of "
        f"which compile {compile_s:.3f} s")
    hit, near = tiering_outcome(se)
    log(f"4-chip fleet: hit rate {hit:.6f}, near blocks held {near}")
    check(near_overcommit_free(spec.cfg, st),
          "block table is a permutation, no near overcommit", failures)
    for d in devs:
        log(f"memory {d.id}: peak_bytes_in_use {peak_bytes(d)}")
    del st, se, s0

    spec, s0 = fleet(N_GUESTS, GUEST_PAGES)
    st4, se4 = engine.run_sharded(spec, s0, src, mesh=mesh, host_sharded=True)
    one = jax.device_put(s0, devs[0])
    st1, se1 = engine.run(spec, one, src)
    check(same_bits(st4, st1) and same_bits(se4, se1),
          f"{N_GUESTS} x {GUEST_PAGES} on 4 chips == on 1 device", failures)
    del st4, se4, st1, se1, s0, one

    g, pages = CHURN_FLEET
    cspec, _ = fleet(g, pages)
    spare = g - 2  # vacant lane with guest 0's workload and CL
    cs = engine.init_churn(cspec, active=[lane != spare for lane in range(g)])
    half = engine.SynthTrace(WINDOWS // 2, ACCESSES)
    cs, _ = engine.run_churn(cspec, cs, half, mesh=mesh)
    cs, manifest = migration.migrate_guest(cspec, cs, 0, spare)
    cs, se = engine.run_churn(cspec, cs, half, mesh=mesh)
    active = [bool(a) for a in jax.device_get(cs.active)]
    log(f"churn + migrate guest 0 -> lane {spare}: handoff "
        f"{manifest['total_bytes']} B, active {active}")
    check(active == [lane != 0 for lane in range(g)]
          and near_overcommit_free(cspec.cfg, cs.state),
          "run_churn + migrate_guest on the mesh", failures)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devs = tpu_devices(args.chips)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

    from repro.launch import compile_cache

    log(f"compile cache: {compile_cache.enable()}")
    clock = CompileClock()
    failures: list[str] = []
    if args.chips == 4:
        four_chips(devs[:4], clock, failures)
    else:
        one_chip(devs[0], clock, failures)
    if failures:
        log(f"FAILED: {failures}")
        return 1
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
