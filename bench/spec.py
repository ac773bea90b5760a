"""Finds a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` at the checkout's root lists the cells. Each piece of a
cell lives in a file of its own, found by the name the cell gives:

- ``configs[].file``: the configuration as it is run (JSON);
- ``bench/traffic/<traffic>.json``: the traffic mix, read by bench/traffic.py;
- ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``.

Adding a configuration, a mix or a metric therefore adds files and entries,
and edits none. Nothing here imports JAX.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def applies(metric: Dict[str, Any], cell: str) -> bool:
    """A metric with a ``workloads`` key is reported in those cells only."""
    return "workloads" not in metric or cell in metric["workloads"]


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    limits: Dict[str, float]  # bench/limits/<cell>.json: limit per check


def resolve(name: str, root: Path = ROOT) -> Cell:
    bm = load_benchmark(root)
    by_name = {w["name"]: w for w in bm["workloads"]}
    if name not in by_name:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    cfg_entry = next(c for c in bm["configs"] if c["name"] == w["config"])
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bm["end_to_end"] if applies(m, name)],
        per_layer=[m for m in bm["per_layer"] if applies(m, name)],
        limits=json.loads(
            (root / "bench" / "limits" / f"{name}.json").read_text())["limits"])


def metric_module(metric: str, root: Path = ROOT):
    """``bench/metrics/<metric>.py``. Its ``read(run)`` gives the metric's
    value, or None where the run holds nothing to read; a tail's module may
    also give ``samples(run)``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
