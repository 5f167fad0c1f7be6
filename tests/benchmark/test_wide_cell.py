"""A test cell whose features chunk: ``tinywide.fused`` is wide enough
(192 features at 255 bins, 8192 rows a block) for the tiler to split
the coarse and the refine passes into two feature blocks at its own
``tpu_rows_per_block``, so on the interpret lane the trainer runs what
a 2,000-feature job runs on the chip: the two-column, coarse-to-fine
tier with the wave's rows routed by the routing step
(``ops/histogram.histogram_pallas_route``) ahead of the chunked
passes.  The trainer has to be ``correct`` by the plain reference, and
each of the three faults planted under it must not be."""
from __future__ import annotations

import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import run as bench_run                                    # noqa: E402
from harness import cells, trainer as trainer_mod          # noqa: E402
from test_harness import (_altered_answer, _half_batch,    # noqa: E402
                          _state_unchanged)

SEED = 2 ** 31 + 135         # the driver's seeds are large
CELL = "tinywide.fused"


@pytest.fixture(scope="module")
def bench_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("benchwide"))
    shutil.copytree(BENCH, root, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns(".build", "__pycache__"))
    shutil.copytree(os.path.join(HERE, "files"), root, dirs_exist_ok=True)
    return root


@pytest.fixture
def interpreted(monkeypatch):
    """The Pallas kernels on the interpret lane, and ``run_cell``
    without the look for a chip or a move of the compile cache."""
    monkeypatch.setenv("LTPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(trainer_mod, "configure_jax", lambda log: "")


def test_wide_trainer_agrees_with_reference(bench_root, interpreted):
    """``check_tier`` inside ``run_cell`` holds the tier record to the
    cell's file: ``routed: true``, ``route: gather``."""
    cell = cells.load_cell(CELL, bench_root)
    assert cell.workload["expect_tier"]["route"] == "gather"
    res = bench_run.run_cell(cell, SEED, 0.3, False)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["tier"] == "two_col"


def test_wide_cell_chunks_at_its_rows_per_block(bench_root):
    """The cell is wide enough: both kinds of pass take more than one
    feature block at the cell's own rows per block."""
    trainer_mod.use_program()
    from lightgbm_tpu.ops.histogram import bin_tiling
    cell = cells.load_cell(CELL, bench_root)
    rpb = int(cell.params["tpu_rows_per_block"])
    features = int(cell.config["features"])
    for bins in (16, 32):
        assert bin_tiling(bins, features, 128, rpb).chunks > 1


@pytest.mark.parametrize("plant", [_state_unchanged, _half_batch,
                                   _altered_answer],
                         ids=lambda f: f.__name__.strip("_"))
def test_wide_fault_is_not_correct(bench_root, interpreted, monkeypatch,
                                   plant):
    plant(monkeypatch)
    res = bench_run.run_cell(cells.load_cell(CELL, bench_root), SEED, 0.3,
                             False)
    assert res["correct"] is False, res["checks"]
