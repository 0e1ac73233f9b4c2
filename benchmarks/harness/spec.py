"""Everything a run needs to know, found by name from ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. By name the harness finds
``configs/<config>.json`` (which names the driver that runs its kind of cell,
its factory and its plain reference), ``traffic/<traffic>.json``,
``cells/<cell>.json`` (the cell's limits for `correct`) and, for each
per-layer metric the cell reports, the reader ``layer_metrics/<metric>.py``.
Adding a cell, a configuration, a traffic mix or a metric is adding files and
entries: nothing in the harness names one of them.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_py(path: str):
    """Import a file of the benchmark by its path: metric readers have dots
    in their names, which no package import takes."""
    name = "benchfile_" + "".join(c if c.isalnum() else "_"
                                  for c in os.path.abspath(path))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]
    search_dirs: Sequence[str]

    def find(self, rel: str) -> str:
        for base in self.search_dirs:
            p = os.path.join(base, rel)
            if os.path.exists(p):
                return p
        raise FileNotFoundError(f"{rel} under {list(self.search_dirs)}")

    def load(self, key: str):
        """The module a configuration names under ``key`` (``driver``,
        ``factory``, ``reference``), a path relative to the benchmark."""
        path = self.find(self.config[key])
        rel = os.path.relpath(path, BENCH_DIR)
        stem = rel[:-3]
        if not rel.startswith("..") and "." not in stem:
            # a module of the benchmark's own packages (harness, reference):
            # imported by name, so that its relative imports hold
            return importlib.import_module(stem.replace(os.sep, "."))
        return load_py(path)

    def metric_reader(self, name: str) -> Callable:
        return load_py(self.find(f"layer_metrics/{name}.py")).read


def _reports(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(workload: str, benchmark_json: Optional[str] = None,
              search_dirs: Optional[Sequence[str]] = None) -> Cell:
    """``search_dirs`` (the benchmark's own directory by default) is where
    the named files are looked for, in order; a test adds a directory of its
    own to register a throw-away cell without touching a file that exists."""
    bench = load_json(benchmark_json
                      or os.path.join(REPO_ROOT, "BENCHMARK.json"))
    dirs = list(search_dirs or [BENCH_DIR])
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    w = cells[workload]
    cell = Cell(name=workload, chips=int(w["chips"]), config={}, traffic={},
                limits={},
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, workload)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, workload)],
                search_dirs=dirs)
    cell.config = load_json(cell.find(f"configs/{w['config']}.json"))
    cell.traffic = load_json(cell.find(f"traffic/{w['traffic']}.json"))
    cell.limits = load_json(cell.find(f"cells/{workload}.json"))["limits"]
    return cell
