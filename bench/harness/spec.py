"""Find a cell's pieces by name: its configuration, traffic mix, metric
readers and plain reference.

Everything that belongs to one configuration, one mix or one per-layer
metric lives in a file of its own under ``bench/``; this module is the only
place that turns a name from ``BENCHMARK.json`` into that file, so a new
cell is new files plus new entries, never an edit here.

- configuration ``<name>``: the ``file`` that ``BENCHMARK.json`` gives it
  (``bench/configs/<name>.json``), whose ``reference`` key names
  ``bench/references/<reference>.py``;
- traffic mix ``<name>``: ``bench/mixes/<name>.json``;
- per-layer metric ``<name>``: ``bench/metrics/<name>.py`` with
  ``read(ctx) -> float | None``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Any, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: dict  # the configuration file's content
    mix: dict  # the traffic mix file's content
    end_to_end: list[dict]  # metric entries this cell reports (trace 0)
    per_layer: list[dict]  # metric entries this cell reports (trace 1)
    root: pathlib.Path  # directory the file paths are relative to


def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _reports(metric: dict, cell: str, e2e_names: set[str]) -> bool:
    """Whether a metric is reported in ``cell``: its ``workloads`` list
    when it has one; otherwise every cell (for an end-to-end metric) or
    every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in e2e_names
    return True


def load_cell(name: str, bench: Optional[dict] = None,
              root: pathlib.Path = ROOT) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = load_json(root / cfg_entry["file"])
    mix = load_json(root / "bench" / "mixes" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, set())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, e2e_names)]
    return Cell(name, int(w["chips"]), config, mix, e2e, per_layer, root)


def _load_module(path: pathlib.Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: pathlib.Path = ROOT) -> ModuleType:
    """The reader module of a per-layer metric (``read(ctx)``)."""
    return _load_module(
        root / "bench" / "metrics" / f"{name}.py",
        "bench_metric_" + name.replace(".", "_").replace("-", "_"),
    )


def reference(config: dict, root: pathlib.Path = ROOT) -> ModuleType:
    """The plain reference module a configuration names."""
    ref = config["reference"]
    return _load_module(root / "bench" / "references" / f"{ref}.py",
                        "bench_reference_" + ref)
