"""Device time per engine layer, on traces recorded on a TPU v5e by a traced
run of each cell with the program's layer scopes and driver spans
(``chipbench/tests/data/*.scoped.xplane.pb.gz``), and on the earlier
recordings of a program without them (``*.kv.xplane.pb.gz``), where every
op reads as unscoped and the layer readers find nothing to read."""
from __future__ import annotations

import functools
import gzip
import tempfile
from pathlib import Path

import pytest

from bench_helpers import BENCH, ROOT

from harness import layers, layout, trace
from harness.cell import WINDOW_SPAN

DATA = BENCH / "tests" / "data"
CELLS = ["gpac28x16g.kv", "host28x16g.kv"]
LAYER_METRICS = {
    "translate_ms": "tier.translate", "record_ms": "tier.record",
    "classify_ms": "tier.classify", "score_ms": "tier.score",
    "select_ms": "tier.select", "copy_ms": "tier.copy",
    "tick_ms": "tier.tick", "roll_ms": "tier.roll",
}
NEW_METRICS = (*LAYER_METRICS, "unscoped_ms", "driver_idle_ms")


@functools.lru_cache(maxsize=None)
def reduction(name):
    with tempfile.TemporaryDirectory() as d:
        path = Path(d) / "trace.xplane.pb"
        path.write_bytes(gzip.decompress((DATA / f"{name}.xplane.pb.gz").read_bytes()))
        return trace.reduce(path, WINDOW_SPAN)


def ctx(red):
    return dict(reduction=red)


def read(metric, red):
    return layout.metric_reader(metric, ROOT)(ctx(red))


def busy_ms(red):
    return 1e3 * red.busy_s / red.windows


@pytest.mark.parametrize("cell", CELLS)
def test_scoped_fixture_is_small(cell):
    assert (DATA / f"{cell}.scoped.xplane.pb.gz").stat().st_size < 300 << 10


@pytest.mark.parametrize("cell", CELLS)
def test_every_layer_of_the_cell_reads(cell):
    red = reduction(f"{cell}.scoped")
    names = {m["name"] for m in layout.resolve_cell(cell, ROOT).per_layer}
    assert set(NEW_METRICS) - names == (
        set() if cell.startswith("gpac") else
        {"classify_ms", "score_ms", "select_ms", "copy_ms"})
    for m in names & set(NEW_METRICS):
        v = read(m, red)
        assert v is not None and v >= 0, m
        if m in LAYER_METRICS:
            assert v > 0, m


@pytest.mark.parametrize("cell", CELLS)
def test_layers_and_unscoped_sum_to_busy(cell):
    red = reduction(f"{cell}.scoped")
    per = layers.ms_per_window(red)
    assert set(per) <= {*layers.LAYERS, layers.UNSCOPED}
    assert sum(per.values()) == pytest.approx(busy_ms(red), rel=0.005)
    assert per[layers.UNSCOPED] < 0.03 * busy_ms(red)


def test_gpac_window_is_scoring_and_selection():
    red = reduction("gpac28x16g.kv.scoped")
    share = (read("score_ms", red) + read("select_ms", red)) / busy_ms(red)
    assert 0.85 <= share <= 0.95


def test_host_window_is_translate_and_record():
    red = reduction("host28x16g.kv.scoped")
    share = (read("translate_ms", red) + read("record_ms", red)) / busy_ms(red)
    assert 0.93 <= share <= 0.99
    assert layers.ms_per_window(red).keys().isdisjoint(
        {"tier.classify", "tier.score", "tier.select", "tier.copy"})


def idle_under_driver_spans_ms(name):
    """Device idle ms per window in gaps whose middle lies inside one of the
    driver's ``tier.*`` spans on the benchmark's thread, from the raw
    trace."""
    with tempfile.TemporaryDirectory() as d:
        path = Path(d) / "trace.xplane.pb"
        path.write_bytes(gzip.decompress((DATA / f"{name}.xplane.pb.gz").read_bytes()))
        planes = trace.read_planes(path)
    host = next(evs for p in planes if p.name == "/host:CPU"
                for evs in p.lines.values()
                if any(e.name == WINDOW_SPAN for e in evs))
    wins = [e for e in host if e.name == WINDOW_SPAN]
    t0 = min(e.start_ns for e in wins)
    t1 = max(e.start_ns + e.dur_ns for e in wins)
    ops = [e for p in planes if p.name.startswith(trace.DEVICE_PREFIX)
           for e in p.lines.get(trace.OPS_LINE, [])
           if e.start_ns < t1 and e.start_ns + e.dur_ns > t0]
    edges, end = [], t0
    for e in sorted(ops, key=lambda e: e.start_ns):
        if e.start_ns > end:
            edges.append((end, e.start_ns))
        end = max(end, e.start_ns + e.dur_ns)
    edges.append((end, t1))
    idle = 0.0
    for s, e in edges:
        mid = (s + e) / 2
        if e > s and any(h.name.startswith("tier.") and h.start_ns <= mid
                         <= h.start_ns + h.dur_ns for h in host):
            idle += e - s
    return idle / 1e6 / len(wins)


@pytest.mark.parametrize("cell", CELLS)
def test_driver_idle_counts_the_program_gaps(cell):
    red = reduction(f"{cell}.scoped")
    program = {k: v for k, v in red.gaps.items() if k not in layers.BENCH_SPANS}
    got = read("driver_idle_ms", red)
    assert got == pytest.approx(1e3 * sum(program.values()) / red.windows)
    assert 0 < got < busy_ms(red)
    # what it counts is the idle time under the driver's spans
    assert got == pytest.approx(idle_under_driver_spans_ms(f"{cell}.scoped"))


def test_driver_idle_takes_driver_spans_and_leaves_the_benchmarks():
    red = reduction("host28x16g.kv.scoped")
    gaps = {"tier.dispatch": 0.004, "tier.fetch": 0.002,
            "PjitFunction(_run_chunk)": 0.001, "generate": 0.5,
            "engine.run": 0.25, "traffic_wait": 0.125, "(no span)": 1.0}
    fake = trace.Reduction(red.busy_s, red.window_s, 2, red.ops, gaps, 1)
    assert read("driver_idle_ms", fake) == pytest.approx(1e3 * 0.007 / 2)


def test_containers_are_dropped():
    def ev(name, start, dur, tf_op="", cat=""):
        return trace.Event(name, start, dur, dict(tf_op=tf_op, hlo_category=cat))

    ops = [ev("%while.1 = s32[] while(...)", 0, 100, cat="while"),
           ev("%fusion.2 = s32[] fusion(...)", 10, 40, "jit(f)/tier.copy/gather:"),
           ev("%gather.3 = s32[] gather(...)", 60, 30, "jit(f)/tier.copy/tier.select/gather:"),
           ev("%while.4 = s32[] while(...)", 200, 5, cat="while"),
           ev("%copy.5 = s32[] copy(...)", 300, 7)]
    red = trace.Reduction(1e-7, 1e-6, 1, ops, {}, 1)
    assert [e.name.split(" ")[0] for e in layers.leaf_ops(ops)] == [
        "%fusion.2", "%gather.3", "%while.4", "%copy.5"]
    per = layers.ms_per_window(red)
    assert per == pytest.approx({"tier.copy": 70e-6, layers.UNSCOPED: 12e-6})


@pytest.mark.parametrize("cell", CELLS)
def test_unscoped_program_reads_all_unscoped_and_nothing_per_layer(cell):
    red = reduction(cell)  # recorded before the program had layer scopes
    assert {layers.layer_of(e) for e in red.ops} == {layers.UNSCOPED}
    per = layers.ms_per_window(red)
    assert list(per) == [layers.UNSCOPED]
    assert per[layers.UNSCOPED] == pytest.approx(busy_ms(red), rel=0.005)
    for m in NEW_METRICS:
        assert read(m, red) is None, m


def test_layer_names_are_the_programs():
    from repro.core import engine

    assert layers.LAYERS == engine.LAYERS
