"""Find a cell's files by the names in BENCHMARK.json's entries.

``workloads/<cell>.json`` names a configuration (``configs/<name>.json``)
and a traffic mix (``traffic/<name>.json``); ``metrics/*.json`` are the
per-layer metrics, each with the cells it can be read in (all, where it
gives no list).  A later PR adds files; nothing here names one."""
from __future__ import annotations

import glob
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
    return Cell(name, workload, config, traffic, metrics)
