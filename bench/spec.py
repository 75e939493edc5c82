"""Finding a cell's files by name: ``BENCHMARK.json`` names the cell, the
cell names its configuration and traffic mix, and each of those is a JSON
file under this directory. Adding a cell adds files; nothing here changes."""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
from typing import Dict, Optional

#: this directory; every data file of the benchmark lies under it
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
#: the checkout root, where ``BENCHMARK.json`` and ``src/`` live
ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    """One ``workloads`` entry with its configuration and traffic mix."""

    name: str
    chips: int
    config: Dict             # configs/<config>.json
    traffic: Dict            # traffic/<traffic>.json
    end_to_end: tuple        # the BENCHMARK.json metric entries it reports
    per_layer: tuple
    limits: Optional[Dict]   # limits/<cell>.json, None until calibrated


def metrics_of(entries, cell: str) -> tuple:
    """The metric entries that apply to ``cell`` (all, unless the entry
    lists its cells under ``workloads``)."""
    return tuple(m for m in entries
                 if cell in m.get("workloads", (cell,)))


def load_cell(name: str, bench_dir: str = BENCH_DIR,
              benchmark: Optional[Dict] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` (or of ``benchmark``, for a
    test), with every file it names loaded from ``bench_dir``."""
    if benchmark is None:
        benchmark = _load(os.path.join(os.path.dirname(bench_dir),
                                       "BENCHMARK.json"))
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    limits_path = os.path.join(bench_dir, "limits", f"{name}.json")
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_load(os.path.join(bench_dir, "configs",
                                  f"{w['config']}.json")),
        traffic=_load(os.path.join(bench_dir, "traffic",
                                   f"{w['traffic']}.json")),
        end_to_end=metrics_of(benchmark["end_to_end"], name),
        per_layer=metrics_of(benchmark["per_layer"], name),
        limits=(_load(limits_path) if os.path.exists(limits_path)
                else None))


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """The module ``<bench_dir>/<kind>/<name>.py``: a per-layer metric's
    reader (``layers``), a family's FLOP count (``flops``) or reference
    (``reference``)."""
    if bench_dir == BENCH_DIR:
        return importlib.import_module(f"bench.{kind}.{name}")
    path = os.path.join(bench_dir, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_bench_{kind}_{name}",
                                                  path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
