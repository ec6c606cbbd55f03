"""Finds a cell's pieces by name: nothing here lists a cell, configuration,
mix or metric.

- ``BENCHMARK.json`` (the checkout's root): the cells and the metrics.
- ``configs/<config>.json``: the model's published sizes, the cut, the
  cluster's shape; its ``family`` names ``builders/<family>.py`` (the
  program's config) and ``refs/<family>.py`` (the plain reference).
- ``mixes/<traffic>.json``: the traffic mix.
- ``cells/<workload>.json``: the cell's fixed rate, its knee and its limit.
- ``layer_metrics/<metric>.py``: one per-layer metric, ``read(run)``.
- ``peaks.json``: the chip's peaks by ``device_kind``.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO_DIR = BENCH_DIR.parents[1]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    spec: dict                       # configs/<config>.json
    mix: dict                        # mixes/<traffic>.json
    fixed: dict                      # cells/<workload>.json
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)
    root: Path = BENCH_DIR

    def builder(self) -> ModuleType:
        family = self.spec["family"]
        return load_module(self.root / "builders" / f"{family}.py")

    def reference(self) -> ModuleType:
        return load_module(self.root / "refs" / f"{self.spec['family']}.py")

    def reader(self, metric: str):
        return load_module(self.root / "layer_metrics" / f"{metric}.py").read


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def find_cell(bench: dict, workload: str, root: Path = BENCH_DIR) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"there are {sorted(cells)}")
    w = cells[workload]
    return Cell(
        name=workload, chips=int(w["chips"]),
        spec=load_json(root / "configs" / f"{w['config']}.json"),
        mix=load_json(root / "mixes" / f"{w['traffic']}.json"),
        fixed=load_json(root / "cells" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        root=root)


def peaks(device_kind: str, root: Path = BENCH_DIR) -> dict:
    table = load_json(root / "peaks.json")
    if device_kind not in table["chips"]:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"peaks.json; it has {sorted(table['chips'])}")
    return table["chips"][device_kind]
