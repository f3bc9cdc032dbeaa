"""One run of one cell: set-up, the measured window, the check, the result.

The window drives ``repro.core.engine.run`` once per telemetry window, with
that window's accesses as an ``ArrayTrace`` of shape ``[n_guests, 1, k]``:
the placement decided in window w is in force before window w+1's telemetry
exists, as in a deployment. Window indices run on across calls and the
tiering state carries over. Traffic is made ahead by a pool of threads; the
engine's wait on it is reported.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from harness import check, fleet, layout, peaks, traffic as traffic_mod
from harness.traffic import Feed

TRAFFIC_WORKERS = 4  # threads making traffic ahead of the engine
REFERENCE_WORKERS = 8  # threads making traffic for the reference's replay
TRACE_MIN_S = 3.0  # a traced run profiles at least this long ...
TRACE_MIN_WINDOWS = 3  # ... and this many windows, from the window's start
WINDOW_SPAN = "bench.window"


class NoChip(SystemExit):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def tpu_devices(count: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"chipbench: JAX found no TPU (platform "
                     f"{devs[0].platform!r}); the benchmark runs on the chip only")
    if len(devs) < count:
        raise NoChip(f"chipbench: the cell needs {count} chips, JAX sees "
                     f"{len(devs)}")
    return devs


class CompileClock:
    """Compilations (or loads from the persistent cache) and their seconds,
    from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def peak_bytes(devices) -> int | None:
    stats = [d.memory_stats() or {} for d in devices]
    vals = [s["peak_bytes_in_use"] for s in stats if "peak_bytes_in_use" in s]
    return max(vals) if vals else None


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Outcome:
    result: dict  # the last line of standard output
    notes: list  # earlier lines


def _annotate(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def run_cell(cell: layout.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, system=None,
             trace_dir: Path | None = None) -> Outcome:
    """Set up, measure for ``seconds``, check, and build the result line.
    ``system(config, seed)`` is what the window drives: by default the
    ``Program`` of the configuration's system file, in tests a broken one."""
    import jax

    from repro.launch import compile_cache

    t_enter = time.perf_counter()
    devices = tpu_devices(cell.chips)[: cell.chips]
    t_devices = time.perf_counter()
    compile_cache.enable()
    clock = CompileClock()
    notes = []
    config = cell.config
    G = int(config["fleet"]["guests"])
    with concurrent.futures.ThreadPoolExecutor(TRAFFIC_WORKERS) as pool:
        traffic = traffic_mod.for_cell(cell, seed, pool)
    if system is None:
        system = layout.system_module(cell).Program

    t_build = time.perf_counter()
    program = system(config, seed)
    t_built = time.perf_counter()
    step = program.step
    got = {k: [] for k in check.SERIES}
    step_s = []  # host seconds of each engine call

    def make(w):
        with _annotate("generate"):
            return traffic.window(w)

    def one_window(feed):
        with _annotate(WINDOW_SPAN):
            with _annotate("traffic_wait"):
                _, acc, waited = feed.get()
            t = time.perf_counter()
            with _annotate("engine.run"):
                series = step(acc)
            step_s.append(time.perf_counter() - t)
            for k in check.SERIES:
                got[k].append(np.asarray(series[k]))
        return waited

    with Feed(make, 0, TRAFFIC_WORKERS, depth=2 * TRAFFIC_WORKERS) as feed:
        one_window(feed)  # warm-up: window 0 compiles or loads every program
        stats0 = program.counters()
        compiles0 = clock.compiles
        setup_s = time.perf_counter() - t_start

        tdir = None
        if trace:
            tdir = Path(trace_dir or tempfile.mkdtemp(prefix="chipbench-trace-"))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(tdir), profiler_options=opts)
        tracing = trace
        traced_windows = 0
        waited = 0.0
        n = 0
        t0 = time.perf_counter()
        while True:
            waited += one_window(feed)
            n += 1
            now = time.perf_counter() - t0
            if tracing:
                traced_windows += 1
                if (now >= TRACE_MIN_S and traced_windows >= TRACE_MIN_WINDOWS
                        or now >= seconds):
                    jax.profiler.stop_trace()
                    tracing = False
            if now >= seconds:
                break
        elapsed = time.perf_counter() - t0
    if tdir is not None:
        from harness import trace as trace_mod

        red = trace_mod.reduce(trace_mod.xplane_file(tdir), WINDOW_SPAN)
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)
    compiles_in_window = clock.compiles - compiles0
    peak = peak_bytes(devices)
    stats1 = program.counters()
    final = program.final()
    program.free()
    del program, step
    n_windows = len(got["near_hits"])

    notes.append(dict(
        setup_s=setup_s, start_s=t_enter - t_start,
        devices_s=t_devices - t_enter, traffic_s=t_build - t_devices,
        build_s=t_built - t_build,
        compile_s=clock.seconds, compiles=clock.compiles,
        cache_hits=clock.cache_hits, compiles_in_window=compiles_in_window,
        windows_measured=n, seconds=elapsed,
        step_ms=[1e3 * t for t in step_s],  # the warm-up window first
        traffic_wait_s=waited, traffic_wait_share=waited / elapsed,
        consolidated_pages=stats1["consolidated_pages"] - stats0["consolidated_pages"],
        promoted_blocks=stats1["promoted_blocks"] - stats0["promoted_blocks"]))

    # --- the check: the reference replays every window the program ran ---
    ref_mod = layout.reference_module(cell)
    ref = ref_mod.Fleet(config["fleet"], config["policy"])
    want, ref_s = check.replay(ref, traffic, n_windows, REFERENCE_WORKERS)
    got_s = {k: np.stack(v) for k, v in got.items()}
    numbers = check.compare(config, got_s, want, ref, final)
    correct = check.passed(numbers)
    near = int(got_s["near_hits"].sum())
    total = near + int(got_s["far_hits"].sum())
    notes.append(dict(
        reference_s=ref_s, windows_compared=n_windows,
        hit_rate=near / max(total, 1),
        near_blocks_held=int(got_s["near_blocks"][-1].sum())))

    d0 = devices[0]
    device = dict(platform=d0.platform, kind=d0.device_kind,
                  count=len(devices), memory_peak_bytes=peak)
    if not trace:
        values = dict(
            guest_windows_per_s=G * n / elapsed,
            peak_hbm_gib=(peak or 0) / 2**30,
            setup_s=setup_s)
        metrics = {m["name"]: dict(value=values[m["name"]], unit=m["unit"])
                   for m in cell.end_to_end}
    else:
        ctx = dict(reduction=red, config=config,
                   geometry=fleet.geometry(config),
                   peaks=peaks.lookup(d0.device_kind),
                   moved_traced=int(want["moved"][1: 1 + red.windows].sum()))
        metrics = {}
        for m in cell.per_layer:
            v = layout.metric_reader(m["name"], cell.root)(ctx)
            if v is not None:
                metrics[m["name"]] = dict(value=v, unit=m["unit"])
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        notes.append(dict(traced_windows=red.windows, ops=red.top_ops(25)))
    attempted = n_windows * G
    result = dict(correct=correct, attempted=attempted,
                  failed=0 if correct else attempted,
                  metrics=metrics, device=device)
    if trace:
        result["breakdown"] = dict(device_ops=red.top_ops(10),
                                   idle_gaps=red.top_gaps(10))
    result["checks"] = numbers
    return Outcome(result=result, notes=notes)


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = layout.resolve_cell(args.workload)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_start=t_start)
    for note in out.notes:
        print(json.dumps(note), flush=True)
    for name, v in out.result["checks"].items():
        print(f"check {name}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out.result), flush=True)
    return 0
