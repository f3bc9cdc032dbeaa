"""The four-chip cell and the ``kv-t4`` mix: both new cells resolve by name,
a small copy of the four-chip cell runs whole on four CPU devices and is
correct with the sound program and not correct with a fault planted under
the timed path or with the control in its place, and ``collective_ms`` reads
the exchange scope's ops and nothing where there is no exchange."""
from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench_helpers import ROOT, small_copy

from harness import check, control, layout, trace

from test_chipbench_layers import reduction

FOUR = "gpac112x16g.kv.4chip"
T4 = "host28x16g.kv-t4"


@pytest.mark.parametrize("name,chips,traffic", [(FOUR, 4, "kv"), (T4, 1, "kv-t4")])
def test_new_cells_resolve(name, chips, traffic):
    c = layout.resolve_cell(name, ROOT)
    assert c.chips == chips
    assert c.traffic == json.loads(
        (ROOT / "chipbench" / "traffic" / f"{traffic}.json").read_text())
    assert {m["name"] for m in c.end_to_end} == {
        "guest_windows_per_s", "peak_hbm_gib", "setup_s"}
    layer_metrics = {m["name"] for m in c.per_layer}
    assert ("collective_ms" in layer_metrics) == (chips == 4)
    for m in c.per_layer:
        assert callable(layout.metric_reader(m["name"], ROOT))
    assert hasattr(layout.system_module(c), "Program")
    assert hasattr(layout.reference_module(c), "Fleet")


def test_t4_mix_is_kv_touched_four_times():
    kv = json.loads((ROOT / "chipbench/traffic/kv.json").read_text())
    t4 = json.loads((ROOT / "chipbench/traffic/kv-t4.json").read_text())
    assert t4["touches_per_hot_page"] == 4 and kv["touches_per_hot_page"] == 1
    assert {k: v for k, v in t4.items() if k not in ("touches_per_hot_page", "about")} \
        == {k: v for k, v in kv.items() if k not in ("touches_per_hot_page", "about")}


RUN = r'''
import dataclasses, json, sys, tempfile, time
from pathlib import Path
sys.path[:0] = [sys.argv[1]]
from bench_helpers import small_copy
import jax
from harness import cell, layout
from test_chipbench_check import HalfBatch, StateUnchanged

assert jax.device_count() == 4, jax.device_count()
cell.tpu_devices = lambda count: jax.devices()


class PayloadDropped:
    """Pages and blocks are remapped but the partitioned payload is not
    moved."""

    def step(self, acc):
        payload = self.state.payload
        out = super().step(acc)
        self.state = dataclasses.replace(self.state, payload=payload)
        return out


root = small_copy(Path(tempfile.mkdtemp()))
c = layout.resolve_cell(sys.argv[2], root)
program = layout.system_module(c).Program
faults = dict(HalfBatch=HalfBatch, StateUnchanged=StateUnchanged,
              PayloadDropped=PayloadDropped)
out = {}
for name in json.loads(sys.argv[3]):
    system = program if name == "sound" else type(name, (faults[name], program), {})
    r = cell.run_cell(c, 2**35 + 91, 0.5, False, t_start=time.perf_counter(),
                      system=system).result
    out[name] = dict(correct=r["correct"], metrics=sorted(r["metrics"]),
                     checks={k: v["value"] for k, v in r["checks"].items()},
                     count=r["device"]["count"], attempted=r["attempted"])
print(json.dumps(out))
'''

RUNS = ("sound", "HalfBatch", "StateUnchanged", "PayloadDropped")


@functools.lru_cache(maxsize=None)
def four_device_runs() -> dict:
    here = Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", RUN, str(here), FOUR, json.dumps(RUNS)],
        env=env, cwd=str(ROOT), capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("run", RUNS)
def test_four_chip_cell_small_copy(run):
    r = four_device_runs()[run]
    assert r["count"] == 4 and r["attempted"] > 0
    assert r["metrics"] == ["guest_windows_per_s", "peak_hbm_gib", "setup_s"]
    if run == "sound":
        assert r["correct"], r["checks"]
        assert set(r["checks"].values()) == {0}, r["checks"]
    else:
        assert not r["correct"], r["checks"]
    if run == "PayloadDropped":
        assert r["checks"]["payload_mismatch"] > 0, r["checks"]


@pytest.mark.parametrize("seed", [11, 12])
def test_four_chip_control_is_not_correct(tmp_path, seed):
    nums = control.readings(layout.resolve_cell(FOUR, small_copy(tmp_path)),
                            seed, n_windows=12)
    assert not check.passed(nums), nums


def ev(name, start, dur, tf_op=""):
    return trace.Event(name, start, dur, dict(tf_op=tf_op))


def read(red):
    return layout.metric_reader("collective_ms", ROOT)(dict(reduction=red))


def test_collective_ms_reads_the_exchange_ops():
    ex = "jit(_host_chunk)/shard_map/while/body/tier.tick/tier.exchange"
    ops = [ev("%all-reduce.1 = s32[] all-reduce(...)", 0, 3000, f"{ex}/psum:"),
           ev("%fusion.2 = s32[] fusion(...)", 5000, 1000, f"{ex}/scatter:"),
           ev("%fusion.3 = s32[] fusion(...)", 7000, 9000,
              "jit(_host_chunk)/shard_map/while/body/tier.tick/top_k:"),
           ev("%all-reduce.4 = s32[] all-reduce(...)", 0, 5000, f"{ex}/psum:"),
           ev("%copy.5 = s32[] copy(...)", 20000, 500)]
    red = trace.Reduction(1e-5, 1e-4, 2, ops, {}, 2)  # 2 windows, 2 chips
    assert read(red) == pytest.approx((3000 + 1000 + 5000) * 1e-6 / 2 / 2)


@pytest.mark.parametrize("fixture", ["gpac28x16g.kv.scoped", "host28x16g.kv.scoped",
                                     "gpac28x16g.kv"])
def test_collective_ms_finds_nothing_on_one_chip(fixture):
    red = reduction(fixture)
    assert red.chips == 1 and red.windows > 0
    assert read(red) is None
    assert read(trace.Reduction(0.0, 0.0, 0, [], {}, 1)) is None
