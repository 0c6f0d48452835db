"""Find a cell and everything that belongs to it, by name.

``BENCHMARK.json`` at the root of the checkout lists the cells.  What
belongs to one configuration, one traffic mix or one per-layer metric
sits in a file of its own beside this module:

* ``configs/<config>.json``: the deployment (graph family, sizes,
  ``nproc``), named by the configuration's ``file`` entry;
* ``traffic/<mix>.json``: the closed-loop parameters that ``loop.py``
  reads;
* ``metrics/<metric>.py``: a reader with ``read(run) -> float | None``.

A later cell, configuration or metric is added with files and entries
only; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


class SpecError(RuntimeError):
    """The benchmark's files do not describe the cell asked for."""


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Optional[Callable] = None     # per-layer metrics only


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_reader(bench_dir: str, name: str) -> Callable:
    """The ``read`` function of ``metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    if not os.path.isfile(path):
        raise SpecError(f"no reader for per-layer metric {name}: {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(root: str, workload: str) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its
    configuration, traffic mix and metric readers."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells: Dict[str, dict] = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload} names unknown config "
                        f"{w['config']!r}")
    bench_dir = os.path.join(root, bench["paths"][0])
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      f"{w['traffic']}.json"))
    if traffic.get("loop") != "closed":
        raise SpecError(f"traffic {w['traffic']}: only closed loops are "
                        f"driven, not {traffic.get('loop')!r}")
    e2e = [Metric(m["name"], m["unit"])
           for m in bench["end_to_end"] if _applies(m, workload)]
    per_layer = [Metric(m["name"], m["unit"],
                        load_reader(bench_dir, m["name"]))
                 for m in bench["per_layer"] if _applies(m, workload)]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, per_layer)
