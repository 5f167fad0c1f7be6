"""The benchmark's own tests, at a size a CPU test run can hold.

They cover the plain reference against the trainer on both growth
tiers and on query-grouped data under lambdarank, the control (the
reference in the precision below the cell's) and the planted faults
coming out as not correct, the work count, the trace reduction on small
recorded traces and on traces built by hand, the readers of the
program's spans and counters, the loader finding files a later PR
would add (a cell, a metric, a generator and an objective), and
run.py's refusals.  Nothing here touches a JAX backend while it is
imported."""
from __future__ import annotations

import copy
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import run as bench_run                                    # noqa: E402
from harness import (cells, datagen, readers, reference,   # noqa: E402
                     trainer as trainer_mod, work, xplane)

SEED = 2 ** 31 + 77          # the driver's seeds are large
TINY = ("tiny.fused", "tiny.plain", "tinyrank.fused")
RANK = "tinyrank.fused"


@pytest.fixture(scope="module")
def bench_root(tmp_path_factory):
    """A copy of benchmark/ with the test cells added as new files, the
    way a later PR adds a cell, a configuration and a traffic mix."""
    root = str(tmp_path_factory.mktemp("bench"))
    shutil.copytree(BENCH, root, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns(".build", "__pycache__"))
    shutil.copytree(os.path.join(HERE, "files"), root, dirs_exist_ok=True)
    return root


@pytest.fixture
def on_cpu(monkeypatch):
    """run_cell without the look for a chip, and without moving this
    test process's compile cache."""
    monkeypatch.setattr(trainer_mod, "configure_jax", lambda log: "")


def run_tiny(bench_root, name, seed=SEED):
    return bench_run.run_cell(cells.load_cell(name, bench_root), seed,
                              0.3, False)


# ---------------------------------------------------------------- cells
@pytest.mark.parametrize("name", TINY)
def test_trainer_agrees_with_reference(bench_root, on_cpu, name):
    res = run_tiny(bench_root, name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"setup_s", "train_s_per_iter"}
    for value, limit in res["checks"].values():
        assert value <= limit


@pytest.mark.parametrize("name", TINY)
def test_tier_record_is_whole_at_construction(bench_root, name):
    """``run.py`` holds the tier record to the cell's file as soon as
    the booster is built: the record is then already the one the
    warm-up leaves."""
    cell = cells.load_cell(name, bench_root)
    x, y, group = datagen.make(cell.config["rows"], cell.config["features"],
                               cell.config["data"], SEED, bench_root)
    tr = trainer_mod.Trainer(cell.params, x, y, group=group)
    built = copy.deepcopy(tr.tier())
    bench_run.check_tier(cell, built)
    for _ in range(1 + bench_run.warmup_steps(cell)):
        tr.step()
    assert tr.tier() == built
    tr.close()


@pytest.mark.parametrize("name", TINY)
def test_control_is_not_correct(bench_root, name):
    """The reference in the precision below the cell's, put in the
    trainer's place, fails one of the cell's limits."""
    cell = cells.load_cell(name, bench_root)
    x, y, group = datagen.make(cell.config["rows"], cell.config["features"],
                               cell.config["data"], SEED, bench_root)
    c = cell.workload["control"]
    exact = reference.train_in_place(x, y, cell.params, 3, SEED,
                                     group=group)
    cut = reference.train_in_place(x, y, cell.params, 3, SEED,
                                   c["hist"], c["leaf"], group=group)
    limits = cell.workload["limits"]
    n_exact = reference.compare(exact, x, y, cell.params, SEED, 3,
                                group=group)
    n_cut = reference.compare(cut, x, y, cell.params, SEED, 3, group=group)
    assert all(n_exact[k] <= v for k, v in limits.items()), n_exact
    assert any(n_cut[k] > v for k, v in limits.items()), n_cut


def _state_unchanged(monkeypatch):
    monkeypatch.setattr(trainer_mod.Trainer, "step", lambda self: None)


def _half_batch(monkeypatch):
    """The first half of the rows, or of whole queries where there are
    query groups."""
    init = trainer_mod.Trainer.__init__

    def half(self, params, x, y, telemetry_file=None, group=None):
        if group is None:
            n = len(y) // 2
        else:
            group = group[:len(group) // 2]
            n = int(group.sum())
        init(self, params, np.ascontiguousarray(x[:n]), y[:n],
             telemetry_file, group)
    monkeypatch.setattr(trainer_mod.Trainer, "__init__", half)


def _altered_answer(monkeypatch):
    trainer_mod.use_program()
    from lightgbm_tpu.models.gbdt import GBDT
    made = GBDT._records_to_tree

    def altered(self, rec):
        tree = made(self, rec)
        j = int(np.argmax(np.abs(tree.leaf_value[:tree.num_leaves])))
        tree.leaf_value[j] = -tree.leaf_value[j]
        return tree
    monkeypatch.setattr(GBDT, "_records_to_tree", altered)


@pytest.mark.parametrize("plant", [_state_unchanged, _half_batch,
                                   _altered_answer],
                         ids=lambda f: f.__name__.strip("_"))
def test_fault_is_not_correct(bench_root, on_cpu, monkeypatch, plant):
    """The rest of a run with the timed path broken underneath."""
    plant(monkeypatch)
    res = run_tiny(bench_root, "tiny.fused")
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("plant", [_state_unchanged, _half_batch,
                                   _altered_answer],
                         ids=lambda f: f.__name__.strip("_"))
def test_rank_fault_is_not_correct(bench_root, on_cpu, monkeypatch, plant):
    """Lambdarank's cell, with the timed path broken underneath: half
    the batch is the first half of the queries, whole."""
    plant(monkeypatch)
    res = run_tiny(bench_root, RANK)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "altered_answer"])
def test_reference_fault_is_not_correct(bench_root, fault):
    cell = cells.load_cell("tiny.plain", bench_root)
    x, y = datagen.make_data(cell.config["rows"], cell.config["features"],
                             cell.config["data"], SEED)
    bad = reference.train_in_place(x, y, cell.params, 3, SEED, fault=fault)
    nums = reference.compare(bad, x, y, cell.params, SEED, 3)
    assert any(not nums[k] <= v
               for k, v in cell.workload["limits"].items()), nums


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "altered_answer"])
def test_rank_reference_fault_is_not_correct(bench_root, fault):
    """The faults planted in the lambdarank reference, put in the
    trainer's place, fail the rank cell's limits; half the batch keeps
    whole queries, so it shows as rows the trees never saw."""
    cell = cells.load_cell(RANK, bench_root)
    x, y, group = datagen.make(cell.config["rows"], cell.config["features"],
                               cell.config["data"], SEED, bench_root)
    bad = reference.train_in_place(x, y, cell.params, 3, SEED, fault=fault,
                                   group=group)
    nums = reference.compare(bad, x, y, cell.params, SEED, 3, group=group)
    assert any(not nums[k] <= v
               for k, v in cell.workload["limits"].items()), nums
    if fault == "half_batch":
        half = int(group[:len(group) // 2].sum())
        assert nums["count_gap"] == pytest.approx(
            (len(y) - half) / len(y), rel=1e-12)


# ------------------------------------------------------------ reference
def test_same_seed_same_data():
    spec = {"generator": "higgs_shaped", "integer_columns": 2}
    a = datagen.make_data(5000, 7, spec, SEED)
    b = datagen.make_data(5000, 7, spec, SEED)
    c = datagen.make_data(5000, 7, spec, SEED + 1)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    assert a[0].dtype == np.float32 and set(np.unique(a[1])) == {0.0, 1.0}


def test_unknown_generator_is_an_error():
    with pytest.raises(ValueError):
        datagen.make_data(10, 3, {"generator": "other"}, 1)


def test_bins_route_like_thresholds():
    """A row's bin and the raw threshold of a split agree: the tree the
    reference grows on bins routes raw rows to the same leaves."""
    x, y = datagen.make_data(4000, 5, {"generator": "higgs_shaped",
                                       "integer_columns": 1}, 3)
    params = {"num_leaves": 9, "learning_rate": 0.1, "min_data_in_leaf": 1,
              "min_sum_hessian_in_leaf": 1.0, "max_bin": 31}
    p = reference.GrowParams.from_config(params)
    uppers, nbins = reference.make_bins(x, p.max_bin, 3)
    bins = reference.native.bin_rows(x, uppers, nbins)
    assert bins.max() < 31 and nbins.max() <= 31
    g, h = cells.objective("binary").gradients(np.zeros(len(y)), y, None,
                                               params)
    tree, leaf_of = reference.grow_tree(bins, uppers, nbins, g, h, g, h, p)
    assert tree.num_leaves == 9
    assert np.array_equal(tree.route(x), leaf_of)
    assert np.array_equal(np.bincount(leaf_of, minlength=9),
                          tree.leaf_count)


def _brute_rows_touched(tree, x):
    """Count by routing: rows at the root, then at every split the
    smaller side."""
    def rows_at(node, idx):
        if node < 0:
            return 0
        go = x[idx, tree.feature[node]] <= tree.threshold[node]
        li, ri = idx[go], idx[~go]
        return (min(len(li), len(ri)) + rows_at(tree.left[node], li)
                + rows_at(tree.right[node], ri))
    idx = np.arange(len(x))
    return len(x) + rows_at(0, idx)


def test_rows_touched_against_brute_force():
    x, y = datagen.make_data(3000, 4, {"generator": "higgs_shaped"}, 11)
    params = {"objective": "binary", "num_leaves": 12, "learning_rate": 0.1,
              "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 1.0,
              "max_bin": 63}
    made = reference.train_in_place(x, y, params, 1, 11)
    tree = made.trees[0]
    assert work.rows_touched(tree) == _brute_rows_touched(tree, x)
    assert work.hist_bytes([tree], 4, 3000) == 4 * work.rows_touched(tree)
    assert work.iteration_bytes([tree], 4, 3000) == \
        12 * work.rows_touched(tree) + 12 * 3000


def test_unknown_device_kind_is_an_error():
    assert work.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        work.peaks_for("a chip nobody has")


# ---------------------------------------------------------------- trace
def load_recorded(name):
    """A recorded trace (``xplane.excerpt`` of a chip run) as ``load``
    gives it."""
    with open(os.path.join(HERE, name)) as f:
        raw = json.load(f)
    return {"devices": {k: [tuple(e) for e in v]
                        for k, v in raw["devices"].items()},
            "host": [tuple(e) for e in raw["host"]]}


RECORDED = ("trace_small.json", "trace_spans_small.json")


@pytest.fixture(scope="module")
def small_trace():
    return load_recorded("trace_small.json")


@pytest.fixture(scope="module")
def spans_trace():
    return load_recorded("trace_spans_small.json")


@pytest.mark.parametrize("name", RECORDED)
def test_trace_busy_union(name):
    trace = load_recorded(name)
    b = xplane.busy(trace)
    lo, hi = xplane.window_of(trace)
    evs = xplane.clip(next(iter(trace["devices"].values())), lo, hi)
    assert 0 < b["busy_s"] <= b["window_s"] == pytest.approx(hi - lo)
    # the union never exceeds the sum, and a grid over the window agrees
    assert b["busy_s"] <= sum(e - s for _, s, e in evs) + 1e-12
    grid = np.linspace(lo, hi, 20001)
    mid = (grid[:-1] + grid[1:]) / 2
    on = np.zeros(len(mid), bool)
    for _, s, e in evs:
        on |= (mid >= s) & (mid < e)
    assert b["busy_s"] == pytest.approx(on.mean() * (hi - lo), rel=5e-3)


def test_trace_name_patterns(small_trace):
    with open(os.path.join(BENCH, "metrics",
                           "hist_kernel_s_per_iter.json")) as f:
        spec = json.load(f)["read"]
    lo, hi = xplane.window_of(small_trace)
    evs = xplane.clip(next(iter(small_trace["devices"].values())), lo, hi)
    got = xplane.reduce_events(evs, spec["patterns"], "sum")
    by_hand = sum(e - s for n, s, e in evs
                  if n.startswith(("histogram_pallas", "leaf_stats_pallas")))
    assert got == pytest.approx(by_hand) and got > 0
    assert xplane.reduce_events(evs, ["no such kernel"], "sum") is None
    assert xplane.reduce_events(evs, spec["patterns"], "union") <= got + 1e-12


@pytest.mark.parametrize("name", RECORDED)
def test_trace_breakdown(name):
    trace = load_recorded(name)
    ops = xplane.top_ops(trace)
    gaps = xplane.idle_gaps(trace)
    assert 0 < len(ops) <= 10 and len(gaps) <= 10
    assert ops == sorted(ops, key=lambda o: -o[1])
    assert all(g[1] > 0 for g in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])


def test_load_keeps_the_program_spans_and_drops_the_rest(tmp_path):
    """A real profile, on the CPU: the program's phase (``timed``, its
    span prefix the literal ``xplane`` keeps) and the benchmark's spans
    land on a host plane and are kept; any other annotation is not."""
    import jax
    trainer_mod.use_program()
    from lightgbm_tpu.utils.profiling import SPAN_PREFIX, timed
    assert SPAN_PREFIX in xplane.HOST_SPAN_PREFIXES
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # as run.py traces
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
            with timed("superstep/to_tree", iter=3, k=8):
                with jax.profiler.TraceAnnotation("other.phase"):
                    jax.numpy.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    got = xplane.load(xplane.find_xplane(str(tmp_path)))
    assert sorted(h[0] for h in got["host"]) == [
        "bench.window", "ltpu.superstep.to_tree"]
    (_, ws, we), (_, ts, te) = sorted(got["host"])
    assert ws <= ts < te <= we


def _by_hand_trace():
    """Device operations with gaps (1, 2), (3, 5), (6, 8), (9, 10) and
    (11, 12) in a window of 12 s, under nested host spans."""
    ops = [("op", 0.0, 1.0), ("op", 2.0, 3.0), ("op", 5.0, 6.0),
           ("op", 8.0, 9.0), ("op", 10.0, 11.0)]
    host = [("bench.window", 0.0, 12.0), ("bench.block", 0.0, 9.2),
            # (1, 2): to_tree covers 0.8 of it, the span inside it 0.2
            ("ltpu.superstep.to_tree", 1.0, 1.8),
            ("ltpu.tree.inner", 1.1, 1.3),
            # (3, 5): the program's span covers a quarter
            ("ltpu.superstep.dispatch", 4.5, 5.0),
            # (6, 8): both program spans cover over half: the shorter
            ("ltpu.superstep.fetch", 5.5, 8.5),
            ("ltpu.tree.fetch", 6.0, 7.5)]
    return {"devices": {"/device:TPU:0": ops}, "host": host}


def test_gap_named_by_the_innermost_span_under_it():
    trace = _by_hand_trace()
    assert xplane.named_gaps(trace) == [
        ("ltpu.superstep.to_tree", 1.0, 2.0),
        ("bench.block", 3.0, 5.0),      # no program span covers half
        ("ltpu.tree.fetch", 6.0, 8.0),
        ("bench.block", 9.0, 10.0),     # none covers half: the most
        ("no_host_span", 11.0, 12.0)]
    assert xplane.idle_gaps(trace, 2) == [["bench.block", 2.0],
                                          ["ltpu.tree.fetch", 2.0]]


def _metric(name):
    with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
        return json.load(f)


def test_idle_kind_on_a_trace_by_hand():
    """Only the gap under ``superstep/to_tree``, host work, counts:
    1 s over 4 traced iterations."""
    spec = _metric("idle_in_host_work_s_per_iter")
    ctx = {"trace": _by_hand_trace(),
           "quantities": {"traced_iterations": 4}}
    assert readers.read_all([spec], ctx)[spec["name"]]["value"] == 0.25
    # a window with no host-work span reads nothing, not 0
    bare = _by_hand_trace()
    bare["host"] = [h for h in bare["host"] if "dispatch" not in h[0]
                    and "to_tree" not in h[0]]
    assert readers.read_all([spec], dict(ctx, trace=bare)) == {}


def _named_by_hand(s, e, spans):
    """``xplane.name_gap`` written the slow way: candidates by length."""
    def cover(h):
        return max(0.0, min(e, h[2]) - max(s, h[1]))
    over_half = sorted((h for h in spans if 2 * cover(h) >= e - s),
                       key=lambda h: h[2] - h[1])
    if over_half:
        return over_half[0][0]
    most = max(spans, key=cover, default=None)
    return most[0] if most is not None and cover(most) > 0 \
        else "no_host_span"


def test_idle_kind_on_the_recorded_trace(spans_trace):
    """The recorded block boundary: the gaps found on a grid of the
    window, each named the slow way, summed where the name is host
    work; the longest gap is named by a program phase."""
    spec = _metric("idle_in_host_work_s_per_iter")
    lo, hi = xplane.window_of(spans_trace)
    evs = sorted(xplane.clip(next(iter(spans_trace["devices"].values())),
                             lo, hi), key=lambda ev: ev[1])
    spans = [h for h in spans_trace["host"] if h[0] != "bench.window"]
    gaps, end = [], lo
    for _, s, e in evs:
        if s > end:
            gaps.append((s - end, _named_by_hand(end, s, spans)))
        end = max(end, e)
    if hi > end:
        gaps.append((hi - end, _named_by_hand(end, hi, spans)))
    rx = [re.compile(p) for p in spec["read"]["patterns"]]
    by_hand = sum(d for d, n in gaps if any(r.search(n) for r in rx))
    ctx = {"trace": spans_trace, "quantities": {"traced_iterations": 1}}
    got = readers.read_all([spec], ctx)[spec["name"]]["value"]
    assert got == pytest.approx(by_hand, rel=1e-12) and got > 0
    assert sum(d for d, _ in gaps) == pytest.approx(
        (hi - lo) - xplane.busy(spans_trace)["busy_s"], rel=1e-9)
    assert xplane.idle_gaps(spans_trace, 1)[0][0].startswith("ltpu.")


def test_counter_per_against_raw_pairs():
    """A list of counters is summed; ``per`` divides by another
    counter's growth over the same interval or by a quantity, and
    reads nothing where the divisor is absent or 0."""
    lo = {"grow_waves": 10.0, "trees_grown": 4.0,
          "phase_secs/superstep/fetch": 1.0, "phase_secs/tree/fetch": 0.5}
    hi = {"grow_waves": 100.0, "trees_grown": 13.0,
          "phase_secs/superstep/fetch": 4.0, "phase_secs/tree/fetch": 0.5,
          "phase_secs/tree/device_wait": 2.0}
    ctx = {"counters": {"window": (lo, hi), "setup": ({}, lo)},
           "quantities": {"window_iterations": 10}}
    got = readers.read_all([_metric("waves_per_tree"),
                            _metric("fetch_wait_s_per_iter")], ctx)
    assert got["waves_per_tree"]["value"] == (100 - 10) / (13 - 4)
    assert got["fetch_wait_s_per_iter"]["value"] == (3.0 + 0.0 + 2.0) / 10
    flat = {"counters": {"window": (lo, dict(hi, trees_grown=4.0))},
            "quantities": {"window_iterations": 0}}
    assert readers.read_all([_metric("waves_per_tree"),
                             _metric("fetch_wait_s_per_iter")], flat) == {}
    none = {"counters": {"window": ({"grow_waves": 1.0},
                                    {"grow_waves": 5.0})},
            "quantities": {}}
    assert readers.read_all([_metric("waves_per_tree"),
                             _metric("fetch_wait_s_per_iter")], none) == {}


NEW = ("fetch_wait_s_per_iter", "host_work_s_per_iter",
       "idle_in_host_work_s_per_iter", "waves_per_tree",
       "refine_passes_per_tree", "wave_lane_fill", "routed_waves_per_tree",
       "collective_bytes_per_tree", "collective_ops_per_tree")


def test_reader_without_anything_to_read_returns_nothing(small_trace):
    ctx = {"spans": {}, "quantities": {}, "counters":
           {"setup": ({}, {}), "window": ({}, {})}, "trace": None,
           "traced_trees": [], "features": 4, "rows": 10, "peaks": {}}
    metrics = [json.load(open(os.path.join(BENCH, "metrics", f)))
               for f in sorted(os.listdir(os.path.join(BENCH, "metrics")))]
    assert set(NEW) <= {m["name"] for m in metrics}
    got = readers.read_all(metrics, ctx)
    assert set(got) <= {"compiles_in_window"}     # a count may be 0
    assert not any("roofline" in k or "mfu" in k for k in got)
    # a trace that holds no program span: no idle time is put on one
    ctx.update(trace=small_trace, quantities={"traced_iterations": 1})
    assert "idle_in_host_work_s_per_iter" not in readers.read_all(
        metrics, ctx)


def test_growth_counts_add_up_in_a_traced_run(bench_root, on_cpu,
                                              monkeypatch, small_trace):
    """A traced run of the real trainer through ``run_cell``, the
    profiler and its reading stood in for by a recorded trace: each
    tree's passes are its waves, its refine passes and its root pass,
    and the host's phases fit in the window's blocks."""
    import jax
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(bench_run, "traced_metrics", lambda d, w: (
        dict(small_trace), xplane.busy(small_trace)))
    monkeypatch.setitem(readers.KINDS, "work", lambda spec, ctx: None)
    cell = cells.load_cell("tiny.fused", bench_root)
    names = ("hist_passes_per_tree", "block_s_per_iter_max") + NEW[:6]
    cell.metrics = [_metric(n) for n in names]
    res = bench_run.run_cell(cell, SEED, 0.3, True)
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(m) == set(names) - {"idle_in_host_work_s_per_iter"}
    assert m["hist_passes_per_tree"] == pytest.approx(
        m["waves_per_tree"] + m["refine_passes_per_tree"] + 1, rel=1e-12)
    assert m["waves_per_tree"] >= 1 and 0 < m["wave_lane_fill"] <= 1
    assert m["fetch_wait_s_per_iter"] + m["host_work_s_per_iter"] <= \
        m["block_s_per_iter_max"]


# --------------------------------------------------------------- loader
def test_loader_finds_files_added_later(bench_root):
    cell = cells.load_cell("tiny.fused", bench_root)
    assert cell.config["name"] == "tiny" and cell.block == 4
    assert cell.params["wave_splits"] and cell.params["num_leaves"] == 15
    before = {m["name"] for m in cell.metrics}
    new = {"name": "warmup_s", "layer": "Entry", "unit": "s",
           "better": "lower", "source": "host_clock", "moves": "setup_s",
           "read": {"kind": "span", "span": "warmup_s"},
           "workloads": ["tiny.fused"]}
    with open(os.path.join(bench_root, "metrics", "warmup_s.json"), "w") as f:
        json.dump(new, f)
    after = {m["name"] for m in
             cells.load_cell("tiny.fused", bench_root).metrics}
    other = {m["name"] for m in
             cells.load_cell("tiny.plain", bench_root).metrics}
    assert after == before | {"warmup_s"} and "warmup_s" not in other
    with pytest.raises(SystemExit):
        cells.load_cell("no.such.cell", bench_root)


GENERATOR = '''"""A linear target with noise: a test's generator."""
import numpy as np


def make(rows, features, spec, seed):
    rng = np.random.default_rng([seed, 0x11])
    x = rng.standard_normal((rows, features), dtype=np.float32)
    w = np.arange(1, features + 1, dtype=np.float32) * spec["scale"]
    y = (x @ w + rng.standard_normal(rows, dtype=np.float32)).astype(
        np.float32)
    return x, y, None
'''
OBJECTIVE = '''"""Squared error: a test's objective."""
import numpy as np

ALIASES = ("regression", "l2", "mse")


def init_score(y, group, params):
    return float(np.mean(y, dtype=np.float64))


def gradients(score, y, group, params):
    return score - y, np.ones(len(y))


def loss(score, y, group, params):
    return float(np.mean(0.5 * (score - y) ** 2))
'''


def _add_files(root, files):
    for rel, text in files.items():
        with open(os.path.join(root, rel), "w") as f:
            f.write(text if isinstance(text, str) else json.dumps(text))


@pytest.fixture
def reg_root(tmp_path):
    """A fresh copy of benchmark/ to which a deployment is added as
    files alone: a generator, a reference objective, a configuration
    and a cell."""
    root = str(tmp_path / "bench")
    shutil.copytree(BENCH, root,
                    ignore=shutil.ignore_patterns(".build", "__pycache__"))
    shutil.copytree(os.path.join(HERE, "files"), root, dirs_exist_ok=True)
    with open(os.path.join(root, "configs", "tiny.json")) as f:
        config = json.load(f)
    config.update(name="tinyreg", data={"generator": "linear_target",
                                        "scale": 0.5})
    config["params"]["objective"] = "regression"
    with open(os.path.join(root, "workloads", "tiny.plain.json")) as f:
        workload = json.load(f)
    workload["config"] = "tinyreg"
    _add_files(root, {"harness/generators/linear_target.py": GENERATOR,
                      "harness/objectives/squared.py": OBJECTIVE,
                      "configs/tinyreg.json": config,
                      "workloads/tinyreg.plain.json": workload})
    return root


def test_loader_finds_generator_and_objective_added_later(reg_root, on_cpu):
    """A deployment whose data and objective are new files loads and
    runs, and is ``correct``, with no file of the copy edited."""
    before = {p: open(os.path.join(BENCH, p), "rb").read()
              for p in ("harness/datagen.py", "harness/reference.py",
                        "harness/cells.py", "run.py")}
    cell = cells.load_cell("tinyreg.plain", reg_root)
    assert cell.root == reg_root
    obj = cells.objective("l2", reg_root)
    assert obj.__file__ == os.path.join(reg_root, "harness", "objectives",
                                        "squared.py")
    with pytest.raises(ValueError):
        cells.objective("l2")           # not a file of the real harness
    x, y, group = datagen.make(50, 3, cell.config["data"], SEED, reg_root)
    assert group is None and x.shape == (50, 3)
    res = bench_run.run_cell(cell, SEED, 0.3, False)
    assert res["correct"], res["checks"]
    assert res["observed"]["step_gain_gap"] < 0.05
    for p, text in before.items():
        with open(os.path.join(reg_root, p), "rb") as f:
            assert f.read() == text


@pytest.mark.parametrize("what", ["generator", "objective"])
def test_unknown_names_are_refused_off_jax(reg_root, what):
    """A configuration naming a generator or an objective no file
    gives stops ``run.py`` at the loader, with the missing file in the
    message, before JAX is imported."""
    with open(os.path.join(reg_root, "configs", "tinyreg.json")) as f:
        config = json.load(f)
    if what == "generator":
        config["data"]["generator"] = "no_such_shape"
        missing = os.path.join("generators", "no_such_shape.py")
    else:
        config["params"]["objective"] = "huber"
        missing = os.path.join("objectives", "huber.py")
    _add_files(reg_root, {"configs/tinyreg.json": config})
    code = ("import sys; sys.path.insert(0, sys.argv[1]); import run\n"
            "try:\n    run.main(['--workload', 'tinyreg.plain', '--seed',"
            " '1', '--seconds', '1'])\n"
            "except SystemExit as e:\n"
            "    print('jax' in sys.modules, e, file=sys.stderr)\n"
            "    sys.exit(3)\n")
    p = subprocess.run([sys.executable, "-c", code, reg_root],
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 3 and p.stdout.strip() == ""
    last = p.stderr.strip().splitlines()[-1]
    assert last.startswith("False ") and missing in last, p.stderr


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("entry", _manifest()["workloads"],
                         ids=lambda e: e["name"])
def test_manifest_names_files_that_exist(entry):
    m = _manifest()
    assert m["command"] == ["python3", "benchmark/run.py"]
    c = next(c for c in m["configs"] if c["name"] == entry["config"])
    with open(os.path.join(ROOT, c["file"])) as f:
        cfg = json.load(f)
    assert cfg["source"] == c["source"]
    assert cfg["reduced"] == c["reduced"]
    cell = cells.load_cell(entry["name"])
    assert cell.workload["config"] == entry["config"]
    assert cell.workload["traffic"] == entry["traffic"]
    assert cell.workload["chips"] == entry["chips"] in (1, 4)
    assert cell.workload["why"] == entry["why"]


@pytest.mark.parametrize("metric", [m["name"] for m in
                                    _manifest()["per_layer"]])
def test_manifest_metric_has_its_reader(metric):
    entry = next(m for m in _manifest()["per_layer"] if m["name"] == metric)
    with open(os.path.join(BENCH, "metrics", f"{metric}.json")) as f:
        spec = json.load(f)
    for key in ("layer", "unit", "better", "source", "moves"):
        assert spec[key] == entry[key]
    assert spec.get("workloads") == entry.get("workloads")
    assert spec["read"]["kind"] in readers.KINDS


# ------------------------------------------------------------- refusals
def test_run_refuses_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", "higgs28.fast", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr and p.stdout.strip() == ""


def test_run_refuses_another_tier(bench_root):
    cell = cells.load_cell("tiny.fused", bench_root)
    good = dict(cell.workload["expect_tier"])
    bench_run.check_tier(cell, good)
    with pytest.raises(SystemExit):
        bench_run.check_tier(cell, dict(good, tier="exact"))
