"""The per-layer metrics that read where set-up's compile time went and
what the record fetch's copy costs: each is a data file for a reader
the benchmark's harness already has, reads its counter or its gaps
from synthetic counters and a trace by hand, and reads nothing from a
program without its counter or phase; over the set-up of a real run
the compile outcomes add up to what ``compile_s`` reads."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

from harness import (cells, datagen, readers,             # noqa: E402
                     trainer as trainer_mod)

CELLS = ["higgs28.fast", "criteo67.fast", "criteo67x4.fast",
         "epsilon2000.fast"]
SETUP = {"fresh_compile_s": ("xla_fresh_compile_secs", "s"),
         "fresh_compiles": ("xla_fresh_compiles", "count"),
         "cache_load_s": ("xla_cache_load_secs", "s"),
         "superstep_fresh_compile_s":
             ("xla_fresh_compile_secs/jit(superstep)", "s"),
         "lower_s": ("jax_lower_secs", "s")}
WINDOW = {"fetch_copy_s_per_iter": "phase_secs/fetch/copy"}
IDLE = "idle_in_fetch_s_per_iter"
NEW = tuple(SETUP) + tuple(WINDOW) + (IDLE,)


def _metric(name):
    with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", NEW)
def test_metric_file_is_declared_as_it_reads(name):
    m = _metric(name)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [e for e in json.load(f)["per_layer"] if e["name"] == name]
    assert entry == [{k: m[k] for k in ("name", "unit", "better", "source",
                                        "layer", "moves", "workloads")}]
    assert m["workloads"] == CELLS and m["better"] == "lower"
    assert m["read"]["kind"] in readers.KINDS
    if name in SETUP:
        assert (m["layer"], m["moves"], m["source"]) == (
            "Entry", "setup_s", "program_counter")
        assert m["read"] == {"kind": "counter", "counter": SETUP[name][0],
                             "over": "setup"}
        assert m["unit"] == SETUP[name][1]
    else:
        assert (m["layer"], m["moves"], m["unit"]) == (
            "Boosting loop", "train_s_per_iter", "s/iter")


# counters as a run of this program leaves them: set up, then a window
# of 24 iterations
_START = {"xla_fresh_compile_secs": 0.0, "xla_fresh_compiles": 0.0,
          "xla_cache_load_secs": 0.0, "xla_cache_loads": 0.0,
          "jax_lower_secs": 0.0}
_OPEN = {"xla_fresh_compile_secs": 30.5, "xla_fresh_compiles": 3.0,
         "xla_cache_load_secs": 10.25, "xla_cache_loads": 29.0,
         "xla_fresh_compile_secs/jit(superstep)": 18.0,
         "jax_lower_secs": 4.5, "phase_secs/fetch/copy": 0.5}
_CLOSE = dict(_OPEN, **{"phase_secs/fetch/copy": 1.7})
READS = {"fresh_compile_s": 30.5, "fresh_compiles": 3.0,
         "cache_load_s": 10.25, "superstep_fresh_compile_s": 18.0,
         "lower_s": 4.5, "fetch_copy_s_per_iter": (1.7 - 0.5) / 24}


@pytest.mark.parametrize("name", sorted(READS))
def test_counter_metric_reads_its_counter(name):
    ctx = {"counters": {"setup": (_START, _OPEN), "window": (_OPEN, _CLOSE)},
           "quantities": {"window_iterations": 24}}
    got = readers.read_all([_metric(name)], ctx)
    assert got[name]["value"] == pytest.approx(READS[name], rel=1e-12)
    assert got[name]["unit"] == _metric(name)["unit"]


def test_superstep_loaded_whole_reads_zero_not_nothing():
    """A run whose super-step came from the cache: its fresh seconds
    stand at 0 beside its load, and the metric reads 0."""
    hi = dict(_OPEN, **{"xla_fresh_compile_secs/jit(superstep)": 0.0,
                        "xla_cache_load_secs/jit(superstep)": 2.0})
    ctx = {"counters": {"setup": (_START, hi), "window": (hi, hi)}}
    got = readers.read_all([_metric("superstep_fresh_compile_s")], ctx)
    assert got["superstep_fresh_compile_s"]["value"] == 0.0


@pytest.mark.parametrize("name", NEW)
def test_program_without_the_counters_reads_nothing(name):
    """A program before these counters and phases (the old counters
    only, and the fetch's parent phase): the line leaves the metric
    out, and never reads 0."""
    had = {"xla_compile_secs": 60.0, "xla_compiles": 32.0,
           "jax_trace_secs": 5.0, "phase_secs/superstep/fetch": 3.0}
    ctx = {"counters": {"setup": ({}, had), "window": (had, had)},
           "quantities": {"window_iterations": 24, "traced_iterations": 4},
           "trace": _trace(children=False)}
    assert readers.read_all([_metric(name)], ctx) == {}


def _trace(children=True):
    """Four traced iterations: a gap under the wait, one under the copy,
    one under ``superstep/to_tree``, and one under the block's fetch
    outside both of its phases."""
    ops = [("op", 0.0, 0.8), ("op", 1.8, 2.1), ("op", 2.7, 3.2),
           ("op", 3.4, 3.6), ("op", 3.9, 4.0)]
    host = [("bench.window", 0.0, 4.0), ("bench.block", 0.0, 4.0),
            ("ltpu.superstep.fetch", 0.5, 3.0),
            ("ltpu.superstep.to_tree", 3.0, 3.5),
            ("ltpu.superstep.fetch", 3.55, 3.95)]
    if children:
        host += [("ltpu.fetch.wait", 0.6, 2.0),
                 ("ltpu.fetch.copy", 2.0, 2.8)]
    return {"devices": {"/device:TPU:0": ops}, "host": host}


def test_idle_in_fetch_reads_the_gaps_under_its_phases():
    ctx = {"trace": _trace(), "quantities": {"traced_iterations": 4}}
    got = readers.read_all([_metric(IDLE)], ctx)
    # (0.8, 1.8) under the wait and (2.1, 2.7) under the copy; the gap
    # under to_tree and the one named by the parent phase do not count
    assert got[IDLE]["value"] == pytest.approx((1.0 + 0.6) / 4, rel=1e-12)


# ------------------------------------------------ a real run's set-up
@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench"))
    shutil.copytree(BENCH, root, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns(".build", "__pycache__"))
    shutil.copytree(os.path.join(ROOT, "tests", "benchmark", "files"), root,
                    dirs_exist_ok=True)
    return cells.load_cell("tiny.fused", root)


def test_outcomes_add_up_over_a_real_setup(tiny_cell, tmp_path):
    """Set-up as a traced run drives it, with the hooks the benchmark
    installs: fresh compiles and loads add up to ``compile_s`` and to
    the requests, and every new set-up metric reads a number."""
    from lightgbm_tpu.utils.telemetry import install_jax_hooks
    install_jax_hooks()
    cfg = tiny_cell.config
    c_start = trainer_mod.counters()
    x, y = datagen.make_data(int(cfg["rows"]), int(cfg["features"]),
                             cfg["data"], 2 ** 31 + 38)
    tr = trainer_mod.Trainer(dict(tiny_cell.params, num_leaves=13), x, y,
                             str(tmp_path / "telemetry.jsonl"))
    for _ in range(1 + tiny_cell.block):
        tr.step()
    tr.trees_done()
    c_open = trainer_mod.counters()
    tr.close()
    ctx = {"counters": {"setup": (c_start, c_open),
                        "window": (c_open, c_open)}}
    got = {k: v["value"] for k, v in readers.read_all(
        [_metric(n) for n in SETUP] + [_metric("compile_s")], ctx).items()}
    assert sorted(got) == sorted(list(SETUP) + ["compile_s"])
    assert got["fresh_compile_s"] + got["cache_load_s"] == pytest.approx(
        got["compile_s"], rel=1e-6)

    def grew(k):
        return c_open.get(k, 0.0) - c_start.get(k, 0.0)
    assert grew("xla_fresh_compiles") + grew("xla_cache_loads") == \
        grew("xla_compiles") >= 1
    assert got["lower_s"] > 0
    assert 0.0 <= got["superstep_fresh_compile_s"] <= got["fresh_compile_s"]
