"""Find a cell's files by the names in BENCHMARK.json's entries.

``workloads/<cell>.json`` names a configuration (``configs/<name>.json``)
and a traffic mix (``traffic/<name>.json``); ``metrics/*.json`` are the
per-layer metrics, each with the cells it can be read in (all, where it
gives no list).  The configuration's ``data.generator`` names a file
``harness/generators/<name>.py``, and the trainer's ``objective`` is
listed in the ``ALIASES`` of one file under ``harness/objectives/``.
A new cell, configuration, generator, objective or metric is a new
file; nothing here names one."""
from __future__ import annotations

import glob
import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    workload: Dict      # chips, expect_tier, control, limits, why, ...
    config: Dict        # source, rows, features, params, data, ...
    traffic: Dict       # params of the job, block, warm-up
    metrics: List[Dict]  # per-layer metrics readable in this cell
    root: str = ROOT    # the benchmark directory the files came from

    @property
    def params(self) -> Dict:
        """The trainer's parameters: the configuration's, then the
        job's, then what every benchmark run sets."""
        return {**self.config["params"], **self.traffic.get("params", {}),
                **self.workload.get("params", {}),
                "verbose": -1, "metric": "None"}

    @property
    def block(self) -> int:
        return int(self.params.get("fused_iters", 1))


def _load_module(path: str, kind: str):
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{os.path.basename(path)[:-3]}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def generator(name: str, root: str = ROOT):
    """The module of ``harness/generators/<name>.py``."""
    path = os.path.join(root, "harness", "generators", f"{name}.py")
    if not os.path.isfile(path):
        raise ValueError(f"benchmark: no generator file {path} for the "
                         f"generator {name!r}")
    return _load_module(path, "generator")


def objective(name: str, root: str = ROOT):
    """The module under ``harness/objectives/`` whose ``ALIASES`` list
    the trainer's objective ``name``."""
    folder = os.path.join(root, "harness", "objectives")
    found = []
    for path in sorted(glob.glob(os.path.join(folder, "[!_]*.py"))):
        module = _load_module(path, "objective")
        if name in module.ALIASES:
            found.append(module)
    if len(found) != 1:
        raise ValueError(f"benchmark: {len(found)} files under {folder}, "
                         f"not one, list the objective {name!r} in their "
                         f"ALIASES (no file "
                         f"{os.path.join(folder, f'{name}.py')}?)")
    return found[0]


def load_cell(name: str, root: str = ROOT) -> Cell:
    path = os.path.join(root, "workloads", f"{name}.json")
    if not os.path.exists(path):
        raise SystemExit(f"benchmark: no cell file {path}")
    workload = _read(path)
    config = _read(os.path.join(root, "configs",
                                f"{workload['config']}.json"))
    traffic = _read(os.path.join(root, "traffic",
                                 f"{workload['traffic']}.json"))
    metrics = []
    for mpath in sorted(glob.glob(os.path.join(root, "metrics", "*.json"))):
        m = _read(mpath)
        if "workloads" not in m or name in m["workloads"]:
            metrics.append(m)
    cell = Cell(name, workload, config, traffic, metrics, root)
    try:
        generator(config["data"].get("generator"), root)
        objective(cell.params.get("objective"), root)
    except ValueError as e:
        raise SystemExit(str(e)) from None
    return cell
