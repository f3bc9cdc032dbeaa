"""Where the benchmark's pieces live, found by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix; a per-layer metric is a
reader of its own. Each resolves to a file by name alone, so adding one is
adding a file and an entry, never editing an existing file:

- configuration ``<c>``: the ``file`` its entry names (``configs/<c>.json``),
  whose ``system`` key names the driver of the system under test,
  ``systems/<s>.py`` (defining ``Program``), and whose ``reference`` key
  names its plain reference, ``reference/<r>.py`` (defining ``Fleet``);
- traffic mix ``<t>``: ``traffic/<t>.json``, a data file whose
  ``generator`` key names the generator that reads it, ``traffic/<g>.py``
  (defining ``make``): a new mix of an existing generator is a data file
  alone, a new law of traffic is a new generator file;
- per-layer metric ``<m>``: ``metrics/<m>.py``, defining ``read(ctx)``,
  which returns the metric's value from a traced run, or None where it finds
  nothing to read. ``ctx`` holds ``reduction`` (``harness.trace.Reduction``
  of the traced windows), ``config`` (the configuration file), ``geometry``
  (``harness.fleet.geometry``), ``peaks`` (``harness.peaks.lookup`` of the
  device) and ``moved_traced`` (pages consolidation moved in the traced
  windows, by the reference).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]  # .../chipbench
ROOT = BENCH_DIR.parent


class LayoutError(ValueError):
    """A name in BENCHMARK.json that resolves to no file, or a bad entry."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict  # the configuration file's contents
    traffic: dict  # the traffic mix file's contents
    chips: int
    end_to_end: tuple  # metric entries this cell reports with --trace 0
    per_layer: tuple  # metric entries this cell reports with --trace 1
    root: Path


def load_benchmark(root: Path = ROOT) -> dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise LayoutError(f"no BENCHMARK.json at {path}")
    return json.loads(path.read_text())


def _read_json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise LayoutError(f"{what}: no file {path}")
    return json.loads(path.read_text())


def config_file(bench: dict, name: str, root: Path = ROOT) -> Path:
    for c in bench["configs"]:
        if c["name"] == name:
            return Path(root) / c["file"]
    raise LayoutError(f"no configuration {name!r} in BENCHMARK.json")


def traffic_file(name: str, root: Path = ROOT) -> Path:
    return Path(root) / "chipbench" / "traffic" / f"{name}.json"


def generator_file(name: str, root: Path = ROOT) -> Path:
    return Path(root) / "chipbench" / "traffic" / f"{name}.py"


def system_file(name: str, root: Path = ROOT) -> Path:
    return Path(root) / "chipbench" / "systems" / f"{name}.py"


def metric_file(name: str, root: Path = ROOT) -> Path:
    return Path(root) / "chipbench" / "metrics" / f"{name}.py"


def reference_file(name: str, root: Path = ROOT) -> Path:
    return Path(root) / "chipbench" / "reference" / f"{name}.py"


def load_module(path: Path, prefix: str):
    """Import a Python file of the benchmark by its path."""
    if not path.is_file():
        raise LayoutError(f"no file {path}")
    mod_name = f"chipbench_{prefix}_{path.stem}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic mix and metrics."""
    bench = load_benchmark(root)
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if len(entries) != 1:
        raise LayoutError(
            f"no cell {name!r} in BENCHMARK.json (have "
            f"{[w['name'] for w in bench['workloads']]})")
    w = entries[0]
    config = _read_json(config_file(bench, w["config"], root),
                        f"configuration {w['config']!r}")
    traffic = _read_json(traffic_file(w["traffic"], root),
                         f"traffic mix {w['traffic']!r}")
    end_to_end = tuple(m for m in bench["end_to_end"] if _applies(m, name))
    per_layer = tuple(m for m in bench["per_layer"] if _applies(m, name))
    for m in per_layer:
        if not metric_file(m["name"], root).is_file():
            raise LayoutError(
                f"per-layer metric {m['name']!r}: no reader "
                f"{metric_file(m['name'], root)}")
    for what, path in (
            (f"configuration {w['config']!r}: system",
             system_file(config.get("system", ""), root)),
            (f"configuration {w['config']!r}: reference",
             reference_file(config.get("reference", ""), root)),
            (f"traffic mix {w['traffic']!r}: generator",
             generator_file(traffic.get("generator", ""), root))):
        if not path.is_file():
            raise LayoutError(f"{what}: no file {path}")
    return Cell(name=name, config=config, traffic=traffic,
                chips=int(w["chips"]), end_to_end=end_to_end,
                per_layer=per_layer, root=Path(root))


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(ctx)`` function of per-layer metric ``name``."""
    return load_module(metric_file(name, root), "metric").read


def reference_module(cell: Cell):
    return load_module(reference_file(cell.config["reference"], cell.root),
                       "reference")


def system_module(cell: Cell):
    return load_module(system_file(cell.config["system"], cell.root), "system")


def generator_module(cell: Cell):
    return load_module(generator_file(cell.traffic["generator"], cell.root),
                       "generator")
