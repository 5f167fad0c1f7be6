"""The growth-tier plan (``models/tier.py``) on plain values: no
``Booster``, no data set, no device.

``tier_plan_golden.json`` is the parent commit's answer (PR 29, before
the plan existed) for a table of configurations: the facts
``GBDT.__init__`` had worked out, ``dataclasses.asdict(grow_params)``
and ``tier_decision``, dumped from real boosters — the ``@tpu`` rows
with ``jax.default_backend`` patched for the constructor alone, the
``@interpret`` rows under ``LTPU_PALLAS_INTERPRET=1`` — less the
``split_fused`` and ``vals_i8`` fields that went with their code.  The
plan must give the same, but for the ``routed`` corrections named
below: there the record now says what ``build_tree`` does.  A pass's
``prologue`` key (PR 31) is younger than the dump and follows its
``mxu``: the int8-valued passes build their right-hand side by words.
So is ``row_state`` (PR 33): the dump's rows take the plan's
(``test_row_state_ladder`` says what it has to be); the
``criteo67x4.*`` rows were dumped with it and pin it.  A pass's
``onehot`` (PR 34: the order the int8 one-hot is built in) was written
into the dump's rows when it came: ``slabs`` at the coarse passes' 16
and 8 bins, ``words`` on the 32-bin grid, ``plain`` where the pass
contracts in bf16; the refine passes' 32 bins now read ``slabs`` too,
and ``words`` is left at 64 bins and up (``test_onehot_follows_the_bins``
says so of every row).  A pass's ``chunks`` (``f_pad // fc``) and the record's
``route`` (PR 35: where a wave's rows are routed) were written into
the rows the same way, ``route`` by the row's own ``routed``
(``kernel`` where it is true: every routed pass of the dump fits one
chunk); ``fast2000.max_bin255@tpu`` is the plan's own answer for the
shape whose coarse pass chunks, which
``test_wide_set_routes_by_gather`` holds to what it has to be.
"""
import dataclasses
import glob
import json
import os
import sys

import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.models.tier import TierFacts, plan_tier
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.ops.grow import c2f_bins, routed_gate

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmark")
with open(os.path.join(HERE, "tier_plan_golden.json")) as _f:
    GOLDEN = json.load(_f)

NO_BATCHED_PASS = "no batched pass (single-leaf passes route nothing)"
# where the parent's record and build_tree disagreed; build_tree wins.
# The record asked neither for a batched pass (it said ``routed: true``
# on tiers whose single-leaf passes route nothing) nor, under c2f,
# about the coarse pass that is the one routed (it tested the
# full-resolution bin count, which no c2f pass streams).
ROUTED_CORRECTIONS = {
    "fast28.forced@tpu": NO_BATCHED_PASS,
    "fast28.pool_over@tpu": NO_BATCHED_PASS,
    "fast28.data2d4@tpu": NO_BATCHED_PASS,
    "defaults28.data4@tpu": NO_BATCHED_PASS,
    "fast120.max_bin63@tpu": None,
    "fast2000.max_bin63@tpu": None,
}


def _tuples(x):
    return tuple(_tuples(v) for v in x) if isinstance(x, list) else x


def _facts(row) -> TierFacts:
    return TierFacts(**{k: _tuples(v) for k, v in row["facts"].items()})


def _plain(x):
    """A deep copy with tuples as lists, as the fixture's JSON holds
    them."""
    return json.loads(json.dumps(x))


@pytest.mark.parametrize("case", sorted(GOLDEN))
def test_plan_matches_parent(case):
    row = GOLDEN[case]
    plan = plan_tier(Config(dict(row["params"], verbose=-1)), _facts(row))
    assert _plain(dataclasses.asdict(plan.grow_params)) == \
        row["grow_params"]
    want = _plain(row["record"])
    for rec in want["hist_tiling"].values():
        rec["prologue"] = "words" if rec["mxu"] == "int8" else "rows"
    if "row_state" not in want:
        want["row_state"] = plan.record["row_state"]
        if "row_state" in plan.record["gates"]:
            want["gates"]["row_state"] = plan.record["gates"]["row_state"]
    if case in ROUTED_CORRECTIONS:
        why = ROUTED_CORRECTIONS[case]
        want["routed"] = why is None
        want["route"] = "kernel" if why is None else "xla"
        want["gates"].pop("routed", None)
        if why is not None:
            want["gates"]["routed"] = why
    assert _plain(plan.record) == want


def test_onehot_follows_the_bins():
    """Every row's ``onehot`` is what its pass's type and bins give,
    reckoned here from the row's facts: bf16 passes build the plain
    one-hot; int8 ones slab by slab up to 32 bins and off the 32-bin
    grid, by words feature by feature at 64 bins and up."""
    seen = set()
    for case, row in GOLDEN.items():
        gp = row["grow_params"]
        bins = row["facts"]["max_bin"]
        kinds = {"full": bins, "root": bins}
        if gp["refine_shift"]:
            kinds["coarse"], kinds["refine"] = c2f_bins(
                bins, gp["refine_shift"], gp["split"]["any_missing"])
        for kind, rec in row["record"]["hist_tiling"].items():
            b_pad = -(-kinds[kind] // 8) * 8
            want = ("plain" if rec["mxu"] == "bf16" else
                    "words" if b_pad % 32 == 0 and b_pad >= 64 else
                    "slabs")
            assert rec["onehot"] == want, (case, kind)
            seen.add((kind, b_pad, want))
    assert {("coarse", 16, "slabs"), ("refine", 32, "slabs"),
            ("full", 64, "words"), ("full", 256, "words"),
            ("root", 256, "plain")} <= seen
    assert {k for k, b, w in seen if w == "slabs"} == {
        "coarse", "refine", "full"}


def test_wide_set_routes_by_gather():
    """2,000 dense features on the ``fast`` job: at 255 bins the 16
    coarse bins tile as 25 chunks of 80 and the 32-bin window as 50 of
    40, and the rows are routed by the routing step (``gather``); at 63
    bins the 8 coarse bins stay one chunk and the routed kernel routes
    them (``kernel``).  Both are the two-column, c2f, routed tier."""
    wide, narrow = (GOLDEN[f"fast2000.max_bin{b}@tpu"] for b in (255, 63))
    for row, route, chunks in ((wide, "gather", (25, 50)),
                               (narrow, "kernel", (1, 25))):
        record = plan_tier(Config(dict(row["params"], verbose=-1)),
                           _facts(row)).record
        assert record["tier"] == "two_col" and record["c2f"]
        assert record["routed"] and "routed" not in record["gates"]
        assert record["route"] == route
        til = record["hist_tiling"]
        assert (til["coarse"]["chunks"], til["refine"]["chunks"]) == chunks
        for rec in til.values():
            assert rec["chunks"] * rec["fc"] == rec["f_pad"] >= rec["f"]
    assert wide["record"]["hist_tiling"]["coarse"]["fc"] == 80
    assert wide["record"]["hist_tiling"]["refine"]["fc"] == 40
    assert wide["record"]["refine_shift"] == 4


def test_route_follows_routed():
    """Every row: ``route`` is ``xla`` exactly where ``routed`` is
    false, and ``kernel`` or ``gather`` by whether the routed pass (the
    coarse one under c2f, else the full one) fits one chunk."""
    seen = set()
    for case, row in GOLDEN.items():
        record = plan_tier(Config(dict(row["params"], verbose=-1)),
                           _facts(row)).record
        til = record["hist_tiling"]
        if not record["routed"]:
            want = "xla"
        else:
            kind = "coarse" if record["c2f"] else "full"
            want = "kernel" if til[kind]["chunks"] == 1 else "gather"
        assert record["route"] == want, case
        seen.add(want)
    assert seen == {"xla", "kernel", "gather"}


def test_corrections_are_cases():
    assert set(ROUTED_CORRECTIONS) <= set(GOLDEN)


@pytest.mark.parametrize("case", sorted(GOLDEN))
def test_record_routed_is_build_trees(case):
    """The record's ``routed`` and ``gates.routed`` are what
    ``build_tree_impl`` asks of its own predicate for the plan's
    ``GrowParams``: the coarse pass under c2f, else the full one."""
    facts = _facts(GOLDEN[case])
    gp, record = plan_tier(
        Config(dict(GOLDEN[case]["params"], verbose=-1)), facts)
    bins = facts.max_bin
    if gp.wave and gp.refine_shift:
        bins = c2f_bins(bins, gp.refine_shift, gp.split.any_missing)[0]
    why = routed_gate(gp, record["learner"], bins, facts.local_cols)
    assert record["routed"] == (why is None)
    assert record["gates"].get("routed") == why


ROW_STATE = {
    # the four ranks of the benchmark's cell: every device its own rows
    "criteo67x4.fast@tpu": None,
    "criteo67x4.fast@cpu": None,
    "fast28.data4@tpu": None,
    # what the ladder refuses, each with the first reason that does
    "criteo67x4.goss@tpu": "GOSS ranks the whole job's gradients",
    "criteo67x4.lambdarank@tpu": "objective=lambdarank: a row's "
                                 "gradient reads other rows' scores",
    "criteo67.fast@tpu": "tree_learner=serial: one device holds every "
                         "row",
    "fast28.voting4@tpu": "tree_learner=voting keeps the replicated",
    "fast28.data2d4@tpu": "tree_learner=data2d keeps the replicated",
    "defaults28.data4@tpu": "fused_iters <= 1",
}


@pytest.mark.parametrize("case", sorted(ROW_STATE))
def test_row_state_ladder(case):
    """Where the per-row state lives, and the first reason where it is
    not the shard (``models/tier.py`` ``_row_state_gate``)."""
    row = GOLDEN[case]
    record = plan_tier(Config(dict(row["params"], verbose=-1)),
                       _facts(row)).record
    why = ROW_STATE[case]
    assert record["row_state"] == ("shard" if why is None
                                   else "replicated")
    assert record["gates"].get("row_state", "").startswith(why or "") \
        and ("row_state" in record["gates"]) == (why is not None)


@pytest.mark.parametrize("extra, why", [
    ({"bagging_fraction": 0.8, "bagging_freq": 1}, "the bagging mask"),
    ({"boosting": "mvs"}, "MVS thresholds"),
    ({"boosting": "dart"}, "boosting=dart"),
    ({"fused_iters": 1}, "fused_iters <= 1"),
    ({"hbm_budget_mb": 64.0}, "paged training"),
])
def test_row_state_refusals(extra, why):
    row = GOLDEN["criteo67x4.fast@tpu"]
    record = plan_tier(Config(dict(row["params"], verbose=-1, **extra)),
                       _facts(row)).record
    assert record["row_state"] == "replicated"
    assert record["gates"]["row_state"].startswith(why)


def test_rank_layout_only_where_pairs_are_laid_out():
    """``rank_layout`` is in the record where the objective lays out
    pairs (lambdarank's ``buckets``: the ``mslr137.fast@tpu`` row, the
    plan's own answer) and in no other row; without it the plan is the
    same record less that key."""
    for row in GOLDEN.values():
        assert ("rank_layout" in row["record"]) == \
            (row["facts"].get("rank_layout") is not None)
    row = GOLDEN["mslr137.fast@tpu"]
    config = Config(dict(row["params"], verbose=-1))
    assert create_objective(config.objective, config).layout == \
        row["record"]["rank_layout"] == "buckets"
    record = plan_tier(config, _facts(row)).record
    without = plan_tier(config,
                        _facts(row)._replace(rank_layout=None)).record
    assert without == {k: v for k, v in record.items()
                       if k != "rank_layout"}
    for name, extra in (("binary", {}), ("regression", {}),
                        ("multiclass", {"num_class": 3}),
                        ("xentropy", {})):
        cfg = Config(dict(extra, objective=name))
        assert create_objective(name, cfg).layout is None


def _cells():
    return sorted(os.path.basename(p)[:-len(".json")]
                  for p in glob.glob(os.path.join(BENCH, "workloads",
                                                  "*.json")))


@pytest.mark.parametrize("cell_name", _cells())
def test_workload_expect_tier(cell_name):
    """What ``benchmark/run.py`` ``check_tier`` holds every cell to on
    the TPU, said here without one: the plan for the cell's parameters
    with Pallas on and the configuration's feature count contains the
    workload file's ``expect_tier``."""
    if BENCH not in sys.path:       # as tests/benchmark/ imports it
        sys.path.insert(0, BENCH)
    from harness.cells import load_cell
    cell = load_cell(cell_name)
    from lightgbm_tpu.parallel.learners import pad_features_for
    config = Config(dict(cell.params))
    features = int(cell.config["features"])
    # 255 bins a feature at the cells' rows: the device width is the
    # next power of two
    max_bin = 1 << (int(config.max_bin) - 1).bit_length()
    # a cell on several chips names its learner and its ranks
    chips = int(cell.workload["chips"])
    learner = config.tree_learner if chips > 1 else "serial"
    record = plan_tier(config, TierFacts(
        use_pallas=True, learner=learner, num_shards=chips,
        mesh_shape2d=None, features=features,
        g_cols=pad_features_for(learner, chips, features),
        max_bin=max_bin, any_cat=False, any_missing=False, efb_groups=0,
        forced=(), use_pool=True,
        rows_per_block=int(config.tpu_rows_per_block), monotone=(),
        penalty=(),
        rank_layout=create_objective(config.objective, config).layout)
    ).record
    expect = cell.workload["expect_tier"]
    assert {k: record[k] for k in expect} == expect
