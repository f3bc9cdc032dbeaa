"""The trace reduction, on small traces recorded on a TPU v5e by a traced
run of each cell (``chipbench/tests/data``, gzipped ``*.xplane.pb``):
device busy and idle share, op attribution by the HLO metadata the trace
carries, and idle gaps named by the host span they fall in. A traced
``harness.cell.run_cell(..., trace=True, trace_dir=<dir>)`` on the chip
keeps its trace in ``<dir>``, which is how these were recorded."""
from __future__ import annotations

import functools
import gzip
import json
import tempfile
from pathlib import Path

import pytest

from bench_helpers import BENCH, ROOT

from harness import fleet, layout, peaks, trace
from harness.cell import WINDOW_SPAN

DATA = BENCH / "tests" / "data"
CELLS = ["gpac28x16g.kv", "host28x16g.kv"]


@functools.lru_cache(maxsize=None)
def reduction(cell):
    with tempfile.TemporaryDirectory() as d:
        path = Path(d) / "trace.xplane.pb"
        path.write_bytes(gzip.decompress((DATA / f"{cell}.xplane.pb.gz").read_bytes()))
        return trace.reduce(path, WINDOW_SPAN)


@pytest.mark.parametrize("cell", CELLS)
def test_fixture_is_small(cell):
    assert (DATA / f"{cell}.xplane.pb.gz").stat().st_size < 1 << 20


@pytest.mark.parametrize("cell", CELLS)
def test_busy_share_and_windows(cell):
    red = reduction(cell)
    assert red.windows >= 1 and red.chips == 1
    assert 0 < red.busy_s <= red.window_s
    idle = sum(s for _, s in red.top_gaps(1000))
    assert idle == pytest.approx(red.window_s - red.busy_s, rel=1e-6, abs=1e-9)


@pytest.mark.parametrize("cell", CELLS)
def test_ops_and_gaps_carry_attributed_names(cell):
    red = reduction(cell)
    ops = red.top_ops(10)
    assert ops and all(s > 0 for _, s in ops)
    assert " @ repro/" in ops[0][0]  # op-name path tail @ source line
    assert sum(" @ repro/" in n for n, _ in ops) >= 5
    gaps = red.top_gaps(10)
    assert gaps and all(n != "(no span)" for n, _ in gaps[:1])


def test_gpac_window_names_the_filter_gather_and_the_kernels():
    red = reduction("gpac28x16g.kv")
    names = [n for n, _ in red.top_ops(10)]
    assert any("repro/core/filter.py" in n for n in names)
    assert red.seconds_of("topk") > 0
    assert red.seconds_of("tiered_lookup") > 0


def test_host_window_has_no_gpac_ops():
    red = reduction("host28x16g.kv")
    assert red.seconds_of("topk") > 0
    assert red.seconds_of("tiered_lookup") == 0
    assert not any("filter.py" in op_name for op_name, _ in red.top_ops(1000))


@pytest.mark.parametrize("cell", CELLS)
def test_metric_readers_on_the_recorded_trace(cell):
    c = layout.resolve_cell(cell, ROOT)
    red = reduction(cell)
    ctx = dict(reduction=red, config=c.config, geometry=fleet.geometry(c.config),
               peaks=peaks.lookup("TPU v5 lite"), moved_traced=16384 * red.windows)
    for m in c.per_layer:
        v = layout.metric_reader(m["name"], ROOT)(ctx)
        assert v is not None and 0 < v < 100, (m["name"], v)


def test_readers_return_nothing_without_a_trace():
    c = layout.resolve_cell("gpac28x16g.kv", ROOT)
    empty = trace.Reduction(0.0, 0.0, 0, [], {}, 1)
    ctx = dict(reduction=empty, config=c.config, geometry=fleet.geometry(c.config),
               peaks=peaks.lookup("TPU v5 lite"), moved_traced=0)
    for m in c.per_layer:
        assert layout.metric_reader(m["name"], ROOT)(ctx) is None


def test_wire_decoder_round_trip():
    # XSpace{planes: XPlane{name: "/device:TPU:0"}}
    name = b"/device:TPU:0"
    plane = bytes([0x12, len(name)]) + name
    data = bytes([0x0A, len(plane)]) + plane
    assert [(n, v) for n, _, v in trace._fields(data)] == [(1, plane)]
    assert trace._plane(plane).name == "/device:TPU:0"
    assert json.dumps(trace.op_name(trace.Event("%fusion.3 = s32[] x", 0, 1, {})))
