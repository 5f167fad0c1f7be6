"""What BENCHMARK.json declares is what the harness finds: every cell
loads through ``cells.load_cell`` from the real files, its
configuration is declared with the file it names, and the per-layer
metrics added as files name reader kinds that exist.  And the cell on
four devices (``tree_learner=data``, every device its own rows) at a
test size: the trainer agrees with the plain reference, which is
serial, and the collective counters read what the passes moved.
Nothing here touches a JAX backend while it is imported."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import run as bench_run                                    # noqa: E402
from harness import (cells, datagen, readers,              # noqa: E402
                     trainer as trainer_mod)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    DECLARED = json.load(_f)
SEED = 2 ** 31 + 91          # the driver's seeds are large


@pytest.mark.parametrize("entry", DECLARED["workloads"],
                         ids=lambda e: e["name"])
def test_declared_cell_loads(entry):
    assert DECLARED["command"] == ["python3", "benchmark/run.py"]
    cell = cells.load_cell(entry["name"])
    assert cell.workload["config"] == entry["config"]
    assert cell.workload["traffic"] == entry["traffic"]
    assert cell.workload["chips"] == entry["chips"]
    assert entry["chips"] in (1, 4)
    assert cell.workload["why"] == entry["why"]
    config = {c["name"]: c for c in DECLARED["configs"]}[entry["config"]]
    path = os.path.join(ROOT, config["file"])
    assert os.path.exists(path)
    with open(path) as f:
        on_file = json.load(f)
    assert on_file == cell.config
    assert on_file["name"] == config["name"]
    assert on_file["source"] == config["source"]
    assert on_file["reduced"] == config["reduced"]
    assert set(cell.workload["limits"]) <= set(
        "rows_gap count_gap root_hess_gap leaf_value_gap leaf_hess_gap "
        "step_gain_gap score_gap".split())
    # every per-layer metric declared for the cell is read in it, and
    # by a reader that exists
    names = {m["name"] for m in cell.metrics}
    for m in DECLARED["per_layer"]:
        if "workloads" not in m or entry["name"] in m["workloads"]:
            assert m["name"] in names
    for m in cell.metrics:
        assert m["read"]["kind"] in readers.KINDS


@pytest.mark.parametrize("entry", DECLARED["per_layer"],
                         ids=lambda e: e["name"])
def test_declared_metric_has_its_file(entry):
    with open(os.path.join(BENCH, "metrics",
                           f"{entry['name']}.json")) as f:
        m = json.load(f)
    assert m["read"]["kind"] in readers.KINDS
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert m[key] == entry[key]
    assert m.get("workloads") == entry.get("workloads")
    cells_declared = {w["name"] for w in DECLARED["workloads"]}
    assert set(entry.get("workloads", ())) <= cells_declared


def test_four_rank_cell_keeps_its_source_configuration():
    """``criteo67x4`` is ``criteo67`` on four ranks: the same trainer
    parameters and the same data block, rows alone cut (``reduced``)."""
    one = cells.load_cell("criteo67.fast").config
    four = cells.load_cell("criteo67x4.fast").config
    assert four["data"] == one["data"]
    assert {k: four["params"][k] for k in one["params"]} == one["params"]
    assert set(four["params"]) - set(one["params"]) == {
        "tree_learner", "num_machines"}
    assert four["features"] == one["features"] == 67
    assert four["reduced"] == ["rows"]
    assert four["rows"] % 4_000_000 == 0
    assert four["published_rows"] == 4 * four["published_rows_per_rank"]


# ----------------------------------------------- four devices, test size
@pytest.fixture(scope="module")
def bench_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench4"))
    shutil.copytree(BENCH, root, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns(".build", "__pycache__"))
    shutil.copytree(os.path.join(HERE, "files"), root, dirs_exist_ok=True)
    return root


def test_four_rank_trainer_agrees_with_reference(bench_root, monkeypatch):
    """The data learner on four devices, driven as ``run.py`` drives a
    cell, is ``correct`` by the serial reference, and the tier record
    is the one the cell's file expects (``row_state: shard``)."""
    monkeypatch.setattr(trainer_mod, "configure_jax", lambda log: "")
    cell = cells.load_cell("tiny4.fused", bench_root)
    res = bench_run.run_cell(cell, SEED, 0.3, False)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


def test_collective_counters_read_what_the_passes_moved(bench_root):
    """``collective_bytes_per_tree`` and ``collective_ops_per_tree``,
    through the metric files' own readers, are the bytes and psums
    reckoned from the window's passes and the passes' shapes, over the
    window's trees; the set-up's ``shard_upload_s`` is read too."""
    cell = cells.load_cell("tiny4.fused", bench_root)
    metrics = {}
    for name in ("collective_bytes_per_tree", "collective_ops_per_tree",
                 "shard_upload_s"):
        with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
            metrics[name] = json.load(f)
    x, y = datagen.make_data(cell.config["rows"], cell.config["features"],
                             cell.config["data"], SEED)
    c_start = trainer_mod.counters()
    tr = trainer_mod.Trainer(cell.params, x, y)
    for _ in range(1 + cell.block):
        tr.step()
    bench_run.check_tier(cell, tr.tier())
    c_open = trainer_mod.counters()
    for _ in range(2 * cell.block):
        tr.step()
    tr.trees_done()
    c_close = trainer_mod.counters()
    ctx = {"counters": {"setup": (c_start, c_open),
                        "window": (c_open, c_close)}}
    got = readers.read_all(list(metrics.values()), ctx)
    assert got["shard_upload_s"]["value"] > 0

    def grew(name):
        return c_close.get(name, 0) - c_open.get(name, 0)
    # a full-resolution tier (63 bins: no c2f): every batched pass and
    # the root's psum one (lanes, F, 64, 3) float32 tensor; a tree adds
    # the root statistics, two scale maxima and the leaf statistics
    gp = tr.gbdt._dist.params
    trees = grew("trees_grown")
    passes = grew("hist_passes_coarse") + grew("hist_passes_refine")
    assert trees == 2 * cell.block and passes > trees
    lanes = min(gp.speculate, gp.num_leaves)
    a_pass = lanes * tr.gbdt._F_pad * gp.split.max_bin * 3 * 4
    reckoned = (passes + trees) * a_pass
    moved = got["collective_bytes_per_tree"]["value"]
    assert reckoned / trees <= moved <= reckoned / trees * 1.01
    assert moved == (reckoned + trees * (
        3 * 4 + 2 * 4 + gp.num_leaves * 3 * 4 + 4)) / trees
    assert got["collective_ops_per_tree"]["value"] == \
        (passes + trees * (1 + 1 + 3 + 1)) / trees
    tr.close()
