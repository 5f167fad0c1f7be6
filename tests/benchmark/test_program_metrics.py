"""The per-layer metrics of set-up that read the program's own spans
and counters: each is a file beside the old ones, read by the
``counter`` reader, and finds its counter in a run of the real
trainer; where the program has no such counter it reads nothing; the
metrics the benchmark opened with read what they read before, on both
recorded traces."""
from __future__ import annotations

import json
import os
import shutil
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

from harness import (cells, datagen, readers, reference,   # noqa: E402
                     trainer as trainer_mod, xplane)

NEW = ("bin_s", "xt_host_prep_s", "superstep_compile_s", "jax_trace_s")
CELLS = ["higgs28.fast", "criteo67.fast", "criteo67x4.fast",
         "epsilon2000.fast"]


def _metric(name):
    with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", NEW)
def test_new_metric_is_data_for_a_reader_that_was_there(name):
    m = _metric(name)
    assert m["read"]["kind"] == "counter" and m["read"]["over"] == "setup"
    assert m["moves"] == "setup_s" and m["workloads"] == CELLS
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [e for e in json.load(f)["per_layer"] if e["name"] == name]
    assert entry == [{k: m[k] for k in ("name", "unit", "better", "source",
                                        "layer", "moves", "workloads")}]


@pytest.mark.parametrize("name", NEW)
def test_new_metric_reads_nothing_from_a_program_without_its_counter(name):
    """The parent has no such phase or per-program counter: the line
    leaves the metric out, and never reads 0."""
    had = {"xla_compile_secs": 60.0, "xla_compiles": 32.0}
    ctx = {"counters": {"setup": ({}, had), "window": (had, had)}}
    assert readers.read_all([_metric(name)], ctx) == {}


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench"))
    shutil.copytree(BENCH, root, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns(".build", "__pycache__"))
    shutil.copytree(os.path.join(HERE, "files"), root, dirs_exist_ok=True)
    return cells.load_cell("tiny.fused", root)


def test_new_metrics_find_their_counters_in_a_real_run(tiny_cell, tmp_path):
    """Set-up as a traced run.py drives it (data, ``Trainer`` with its
    telemetry file, iteration 0, one block): every new metric reads
    seconds, ``bin_s`` and ``xt_host_prep_s`` inside what
    ``data_prep_s`` spans."""
    import time
    cfg = tiny_cell.config
    c_start = trainer_mod.counters()
    t0 = time.time()
    x, y = datagen.make_data(int(cfg["rows"]), int(cfg["features"]),
                             cfg["data"], 2 ** 31 + 78)
    tr = trainer_mod.Trainer(tiny_cell.params, x, y,
                             str(tmp_path / "telemetry.jsonl"))
    data_prep_s = time.time() - t0
    for _ in range(1 + tiny_cell.block):
        tr.step()
    tr.trees_done()
    c_open = trainer_mod.counters()
    tr.close()
    ctx = {"counters": {"setup": (c_start, c_open),
                        "window": (c_open, c_open)}}
    got = readers.read_all([_metric(n) for n in NEW], ctx)
    assert sorted(got) == sorted(NEW)
    assert all(v["value"] > 0 and v["unit"] == "s" for v in got.values())
    assert got["bin_s"]["value"] + got["xt_host_prep_s"]["value"] < \
        data_prep_s
    assert got["superstep_compile_s"]["value"] <= \
        c_open["xla_compile_secs"] - c_start.get("xla_compile_secs", 0.0)


# ------------------------------------- the metrics the benchmark opened with
def _chain_tree():
    """Two splits, leaves of 6M, 3M and 1M rows: 15M rows touched."""
    return reference.TreeArrays(
        np.array([0, 1], np.int32), np.zeros(2), np.array([-1, -2],
                                                          np.int32),
        np.array([1, -3], np.int32), np.zeros(3), np.zeros(3),
        np.array([6_000_000, 3_000_000, 1_000_000], np.int64), 1.0)


# what the benchmark's harness read from the two recorded traces, with
# the made-up quantities below, before it kept the program's spans: the
# parent of PR 25 on trace_small.json, and on trace_spans_small.json (a
# block boundary of higgs28.fast with the program's spans) the harness
# whose ``load`` dropped them
PINNED = {
    "trace_small.json": {
        "block_s_per_iter_max": 1.07, "compile_s": 68.4,
        "compiles_in_window": 0.0, "data_prep_s": 10.5,
        "device_idle_pct": 4.214073999993861,
        "hist_kernel_s_per_iter": 0.17531951400000167,
        "hist_passes_per_tree": 22.0, "peak_hbm_gib": 2.4,
        "hist_roofline": 0.2925062368245602,
        "train_mfu": 0.4029304029304044},
    "trace_spans_small.json": {
        "block_s_per_iter_max": 1.07, "compile_s": 68.4,
        "compiles_in_window": 0.0, "data_prep_s": 10.5,
        "device_idle_pct": 28.619164000001508,
        "hist_kernel_s_per_iter": 0.1164451729999989,
        "hist_passes_per_tree": 22.0, "peak_hbm_gib": 2.4,
        "hist_roofline": 0.440396539941177,
        "train_mfu": 0.4029304029304026}}
# what only the program's spans let the harness read there: the gap
# from the block's last operation to the window's end, under
# ``ltpu.superstep.to_tree``, over the one traced iteration
SPANS_ONLY = {"idle_in_host_work_s_per_iter": 0.05721665300000023}


def recorded_ctx(name):
    with open(os.path.join(HERE, name)) as f:
        raw = json.load(f)
    trace = {"devices": {k: [tuple(e) for e in v]
                         for k, v in raw["devices"].items()},
             "host": [tuple(e) for e in raw["host"]]}
    b = xplane.busy(trace)
    return {
        "spans": {"data_prep_s": 10.5, "data_gen_s": 3.0,
                  "warmup_s": 40.0},
        "quantities": {
            "traced_iterations": 1, "traced_window_s": b["window_s"],
            "window_iterations": 24, "window_trees": 24,
            "hist_passes": 528.0, "block_s_per_iter_max": 1.07,
            "idle_pct": 100.0 * (1 - b["busy_s"] / b["window_s"]),
            "peak_gib": 2.4},
        "trace": trace,
        "counters": {
            "setup": ({"xla_compile_secs": 1.0},
                      {"xla_compile_secs": 69.4, "xla_compiles": 32.0}),
            "window": ({"xla_compiles": 32.0}, {"xla_compiles": 32.0})},
        "traced_trees": [_chain_tree()], "features": 28,
        "rows": 10_000_000,
        "peaks": {"hbm_bytes_per_s": 819e9}}


@pytest.mark.parametrize("trace,metric", [(t, m) for t in sorted(PINNED)
                                          for m in sorted(PINNED[t])])
def test_existing_metric_reads_the_same(trace, metric):
    metrics = [_metric(f[:-5])
               for f in sorted(os.listdir(os.path.join(BENCH, "metrics")))]
    got = readers.read_all(metrics, recorded_ctx(trace))
    assert got[metric]["value"] == pytest.approx(PINNED[trace][metric],
                                                 rel=1e-12)
    # the other new ones find no counter here
    extra = SPANS_ONLY if trace == "trace_spans_small.json" else {}
    assert set(got) == set(PINNED[trace]) | set(extra)
    for k, v in extra.items():
        assert got[k]["value"] == pytest.approx(v, rel=1e-12)
