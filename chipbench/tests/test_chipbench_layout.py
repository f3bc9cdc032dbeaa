"""The benchmark's files resolve by name, and a new configuration, traffic
mix or per-layer metric is picked up from its own file and entry alone."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench_helpers import BENCH, ROOT, small_copy

from harness import fleet, layout, peaks, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def bench():
    return layout.load_benchmark(ROOT)


@pytest.mark.parametrize("cell", [w["name"] for w in bench()["workloads"]])
def test_every_cell_resolves(cell):
    c = layout.resolve_cell(cell, ROOT)
    assert c.chips == 1
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert callable(layout.metric_reader(m["name"], ROOT))
    assert hasattr(layout.reference_module(c), "Fleet")
    assert hasattr(layout.system_module(c), "Program")
    assert callable(layout.generator_module(c).make)


def test_benchmark_entries_are_well_formed():
    b = bench()
    assert b["paths"] == ["chipbench"]
    assert b["command"][1] == "chipbench/run.py"
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in b[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["end_to_end"]:
        assert 0 < m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for c in b["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    for w in b["workloads"]:
        assert len(w["why"]) <= 200


@pytest.mark.parametrize("name", [c["name"] for c in bench()["configs"]])
def test_config_device_bytes_are_reckoned_from_sizes(name):
    config = json.loads(layout.config_file(bench(), name, ROOT).read_text())
    assert config["device_bytes"]["state"] == fleet.device_bytes(config)["state"]
    assert config["device_bytes"]["arrays"] == fleet.device_bytes(config)["arrays"]


def test_device_bytes_match_the_engine_state(tmp_path):
    import jax

    small_copy(tmp_path)
    config = json.loads((tmp_path / "chipbench/configs/gpac28x16g.json").read_text())
    system = layout.load_module(layout.system_file(config["system"], tmp_path), "system")
    _, state = system.build(config, seed=3)
    arrays = fleet.device_bytes(config)["arrays"]
    leaves = {k: v for k, v in vars(state).items() if k != "stats"}
    for k, v in leaves.items():
        assert v.nbytes == arrays[k], k
    stats = sum(x.nbytes for x in jax.tree_util.tree_leaves(state.stats))
    assert stats == arrays["stats"]


def test_peaks_table_is_keyed_by_device_kind():
    assert peaks.lookup("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        peaks.lookup("TPU v9 imaginary")


def test_new_config_mix_and_metric_are_picked_up(tmp_path):
    small_copy(tmp_path)
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    cfg = json.loads((tmp_path / "chipbench/configs/gpac28x16g.json").read_text())
    cfg.update(name="newcfg", system="newsys")
    (tmp_path / "chipbench/configs/newcfg.json").write_text(json.dumps(cfg))
    (tmp_path / "chipbench/systems/newsys.py").write_text(
        "class Program:\n    kind = 'new system'\n")
    mix = {"generator": "newgen", "tenants": {"redis": {}, "memcached": {}}}
    (tmp_path / "chipbench/traffic/newmix.json").write_text(json.dumps(mix))
    (tmp_path / "chipbench/traffic/newgen.py").write_text(
        "def make(mix, tenants, pages, seed, pool=None):\n"
        "    return ('new law', len(tenants), seed)\n")
    (tmp_path / "chipbench/metrics/new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    b["configs"].append(dict(name="newcfg", source="x", file="chipbench/configs/newcfg.json",
                             reduced=[], why="x"))
    b["workloads"].append(dict(name="newcfg.newmix", config="newcfg", traffic="newmix",
                               chips=1, why="x"))
    b["per_layer"].append(dict(name="new_metric", unit="%", better="higher",
                               source="device_trace", layer="device",
                               moves="guest_windows_per_s", workloads=["newcfg.newmix"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    c = layout.resolve_cell("newcfg.newmix", tmp_path)
    assert c.config["name"] == "newcfg" and c.traffic["generator"] == "newgen"
    assert layout.system_module(c).Program.kind == "new system"
    assert traffic.for_cell(c, 5) == ("new law", cfg["fleet"]["guests"], 5)
    assert "new_metric" in [m["name"] for m in c.per_layer]
    assert layout.metric_reader("new_metric", tmp_path)({}) == 42.0
    assert "new_metric" not in [m["name"] for m in
                                layout.resolve_cell("gpac28x16g.kv", tmp_path).per_layer]
    for p, v in before.items():  # no existing file but the index changed
        assert p.read_bytes() == v or p.name == "BENCHMARK.json", p


def test_unknown_names_are_errors(tmp_path):
    small_copy(tmp_path)
    with pytest.raises(layout.LayoutError):
        layout.resolve_cell("nosuch.kv", tmp_path)
    (tmp_path / "chipbench/traffic/hot_set_ipt.py").unlink()
    with pytest.raises(layout.LayoutError, match="generator"):
        layout.resolve_cell("gpac28x16g.kv", tmp_path)
    (tmp_path / "chipbench/systems/engine.py").unlink()
    with pytest.raises(layout.LayoutError, match="system"):
        layout.resolve_cell("gpac28x16g.kv", tmp_path)
    (tmp_path / "chipbench/traffic/kv.json").unlink()
    with pytest.raises(layout.LayoutError, match="traffic mix"):
        layout.resolve_cell("gpac28x16g.kv", tmp_path)


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "gpac28x16g.kv",
         "--seed", "7", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_exits_nonzero_without_a_tpu():
    r = _run(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_run_fails_in_a_checkout_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
