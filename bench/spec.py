"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); its correctness limits are
``bench/limits/<cell>.json`` and each per-layer metric is read by
``bench/metrics/<metric>.py``.  Nothing here knows any cell by name, so a
later change adds a cell, a configuration, a mix or a metric by adding
files and entries.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """A cell, configuration, mix or metric that the files do not define."""


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: Optional[str] = None
    moves: Optional[str] = None
    workloads: Optional[tuple] = None

    def reported_in(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple
    per_layer: tuple


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def _metric(entry: dict) -> Metric:
    wl = entry.get("workloads")
    return Metric(entry["name"], entry["unit"], entry["better"],
                  entry["source"], entry.get("layer"), entry.get("moves"),
                  tuple(wl) if wl is not None else None)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files read
    from ``<root>/bench``."""
    bench = _load_json(root / "BENCHMARK.json")
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise SpecError(f"no workload {name!r} in {root / 'BENCHMARK.json'}; "
                        f"known: {[w['name'] for w in bench['workloads']]}")
    w = entries[0]
    d = root / "bench"
    config = _load_json(d / "configs" / f"{w['config']}.json")
    traffic = _load_json(d / "traffic" / f"{w['traffic']}.json")
    limits = _load_json(d / "limits" / f"{name}.json")
    e2e = tuple(m for m in map(_metric, bench["end_to_end"])
                if m.reported_in(name))
    per_layer = tuple(m for m in map(_metric, bench["per_layer"])
                      if m.reported_in(name))
    return Cell(name, w["config"], w["traffic"], int(w["chips"]), config,
                traffic, limits, e2e, per_layer)


def list_cells(root: Path = ROOT) -> List[str]:
    return [w["name"] for w in _load_json(root / "BENCHMARK.json")["workloads"]]


def metric_reader(name: str, root: Path = ROOT) -> Callable[[dict], Optional[float]]:
    """``read(record)`` of ``<root>/bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path} for per-layer metric {name!r}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read

