"""The one span source inside the training path (utils/profiling.py):
a phase is a profiler annotation ``ltpu.<phase>`` and a pair of
process counters; compile and trace time keep their program's name;
the growth loop counts its own waves, passes and lanes."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops.grow import GROW_COUNTERS, GrowParams, build_tree
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.utils import profiling, telemetry

# the sizes of tests/benchmark/files/configs/tiny.json
ROWS, FEATURES, LEAVES, BINS = 6000, 6, 15, 63
PARAMS = {"objective": "binary", "num_leaves": LEAVES, "max_bin": BINS,
          "learning_rate": 0.1, "min_data_in_leaf": 1,
          "min_sum_hessian_in_leaf": 5.0, "verbose": -1, "metric": "None"}
FAST = dict(PARAMS, wave_splits=True, use_quantized_grad=True, fused_iters=4)


def _data(seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(ROWS, FEATURES).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2] + 0.3 * rng.randn(ROWS) > 0)
    return x, y.astype(np.float32)


def _booster(params, seed=0):
    x, y = _data(seed)
    ds = lgb.Dataset(x, label=y, params=params)
    ds.construct()
    return lgb.Booster(params, ds)


def _grown(before):
    now = telemetry.counters_snapshot()
    return {k: v - before.get(k, 0.0) for k, v in now.items()
            if v != before.get(k, 0.0)}


def _program_spans(logdir):
    """The ``ltpu.*`` events of a CPU profiler session, with their
    stats: [(name, {stat: value})]."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(e.name, {k: v for k, v in e.stats})
                    for e in line.events
                    if e.name.startswith(profiling.SPAN_PREFIX)]
    return out


# ---------------------------------------------------------------- phases
@pytest.mark.filterwarnings("ignore:builtin type event_stats")
@pytest.mark.parametrize("params, entered", [
    (FAST, ("dataset/bin", "dataset/xt_host_prep", "boost/init",
            "boosting/gradients", "tree/prep", "tree/dispatch",
            "tree/score_update", "superstep/dispatch", "superstep/fetch",
            "superstep/to_tree")),
    (PARAMS,
     ("dataset/bin", "dataset/xt_host_prep", "boost/init",
      "boosting/gradients", "tree/prep", "tree/dispatch",
      "tree/score_update", "tree/fetch")),
    (dict(PARAMS, boosting="dart"),     # no pipelining: the classic loop
     ("dataset/bin", "dataset/xt_host_prep", "boost/init",
      "boosting/gradients", "tree/build", "tree/prep", "tree/dispatch",
      "tree/fetch", "tree/to_tree", "tree/score_update")),
], ids=["fused", "pipelined", "classic"])
def test_training_grows_phase_counters_and_annotations(tmp_path, params,
                                                       entered):
    before = telemetry.counters_snapshot()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        bst = _booster(params)
        for _ in range(6):
            bst.update()
    finally:
        jax.profiler.stop_trace()
    grown = _grown(before)
    for phase in entered:
        assert grown[f"phase_secs/{phase}"] > 0, phase
        assert grown[f"phase_calls/{phase}"] >= 1, phase
    # every phase that ran left both counters, and nothing else did
    secs = {k.split("/", 1)[1] for k in grown if k.startswith("phase_secs/")}
    calls = {k.split("/", 1)[1] for k in grown
             if k.startswith("phase_calls/")}
    assert secs == calls and set(entered) <= secs
    # the views read the same counters
    total, count = profiling.get(entered[-1])
    assert total >= grown[f"phase_secs/{entered[-1]}"] and count >= 1
    assert entered[-1] in profiling.summary()

    spans = _program_spans(tmp_path)
    names = {n for n, _ in spans}
    assert {"ltpu." + p.replace("/", ".") for p in entered} <= names
    loop = [(n, st) for n, st in spans
            if not n.startswith(("ltpu.dataset.", "ltpu.boost."))]
    assert loop and all("iter" in st for _, st in loop)
    blocks = [st for n, st in spans if n == "ltpu.superstep.fetch"]
    if params is FAST:
        # spans of one block share its first iteration and its size
        assert [(st["iter"], st["k"]) for st in blocks] == [(1, 4), (5, 4)]
        dispatched = {st["iter"] for n, st in spans
                      if n == "ltpu.superstep.dispatch"}
        assert {1, 5} <= dispatched


def test_phase_without_recorder_or_session_emits_no_record():
    seen = []

    def observer(rec, recorder):
        seen.append(rec)
    telemetry.add_emit_observer(observer)
    try:
        before = telemetry.counters_snapshot()
        snap = profiling.snapshot()
        with profiling.timed("test/quiet", iter=3):
            pass
    finally:
        telemetry.remove_emit_observer(observer)
    assert seen == []
    assert _grown(before) == {
        "phase_calls/test/quiet": 1.0,
        "phase_secs/test/quiet": pytest.approx(0.0, abs=0.05)}
    assert set(profiling.delta_ms(snap)) == {"test/quiet"}
    assert profiling.get("test/quiet")[1] >= 1
    assert not hasattr(profiling, "_acc")
    assert not hasattr(profiling, "jax_trace")


# ------------------------------------------------- compile time by program
def test_fresh_jit_grows_its_own_compile_counter():
    telemetry.install_jax_hooks()

    def ltpu_test_program_a(x):
        return jnp.cumsum(x * 3.0) - 1.0

    before = telemetry.counters_snapshot()
    jax.jit(ltpu_test_program_a)(jnp.arange(7.0)).block_until_ready()
    grown = _grown(before)
    assert grown["xla_compiles/jit(ltpu_test_program_a)"] == 1
    assert grown["xla_compile_secs/jit(ltpu_test_program_a)"] > 0
    assert grown["jax_trace_secs/ltpu_test_program_a"] > 0
    assert grown["xla_compiles"] >= 1
    # the second call is served by the executable cache
    before = telemetry.counters_snapshot()
    jax.jit(ltpu_test_program_a)(jnp.arange(7.0)).block_until_ready()
    assert "xla_compiles/jit(ltpu_test_program_a)" not in _grown(before)


def test_tail_block_recompile_names_its_program():
    """``num_iterations`` no multiple of ``fused_iters``: the shorter
    tail block recompiles the super-step once, and its record says
    which program that was."""
    params = dict(FAST, num_iterations=7, superstep_pipeline_depth=0)
    bst = _booster(params, seed=3)
    rec = telemetry.RunRecorder()
    bst._gbdt.attach_telemetry(rec)
    for _ in range(7):
        bst.update()
    steps = [r for r in rec.records if r["type"] == "superstep"]
    assert [(r["iter"], r["k"]) for r in steps] == [(1, 4), (5, 2)]
    for r in steps:
        assert r["counters"]["xla_compiles/jit(superstep)"] == 1
        assert r["counters"]["xla_compile_secs/jit(superstep)"] > 0
        # a record carries its phases once, as phases_ms
        assert r["phases_ms"]["superstep/fetch"] > 0
        assert not [k for k in r["counters"] if k.startswith("phase_")]
    rec.close(log=False)


# ------------------------------------------ the growth loop's own counts
def _tree_case():
    rng = np.random.RandomState(5)
    xt = rng.randint(0, BINS, size=(FEATURES, ROWS)).astype(np.int32)
    y = (xt[0] + xt[2] > BINS).astype(np.float32) + \
        0.3 * rng.randn(ROWS).astype(np.float32)
    grad = (0.5 - y).astype(np.float32)
    return xt, grad, np.ones(ROWS, np.float32)


TIERS = {
    "wave": dict(wave=True, speculate=8),
    "c2f": dict(wave=True, speculate=8, refine_shift=3),
    "speculative": dict(speculate=7),
}


@pytest.mark.parametrize("tier", sorted(TIERS))
def test_growth_loop_counts_its_own_work(tier):
    xt, grad, hess = _tree_case()
    p = GrowParams(split=SplitParams(max_bin=BINS, min_data_in_leaf=5,
                                     any_cat=False, any_missing=False),
                   num_leaves=LEAVES, hist_impl="segsum", **TIERS[tier])
    rec = build_tree(jnp.asarray(xt), jnp.asarray(grad), jnp.asarray(hess),
                     jnp.ones(ROWS, jnp.float32), jnp.ones(FEATURES, bool),
                     jnp.full(FEATURES, BINS, jnp.int32),
                     jnp.zeros(FEATURES, jnp.int32),
                     jnp.zeros(FEATURES, bool), p)
    c = {k: int(rec[k]) for k in GROW_COUNTERS}
    assert int(rec["n_leaves"]) == LEAVES
    assert 1 <= c["n_waves"] <= c["n_arm_passes"]
    refine = c["n_arm_passes"] - c["n_waves"]
    if tier == "c2f":
        # one or two windowed groups a wave
        assert c["n_waves"] <= refine <= 2 * c["n_waves"]
    else:
        # every pass after the root's is a wave's (an arming) pass
        assert refine == 0
    if tier != "speculative":
        # a wave fills a lane for every split it commits
        assert LEAVES - 1 <= c["n_waves"] * p.speculate


@pytest.mark.parametrize("params", [FAST, dict(FAST, fused_iters=1)],
                         ids=["fused", "per_iteration"])
def test_growth_counters_commit_with_the_trees(params):
    """The process counters at the block's or tree's commit: the two
    invariants of the wave tiers, and ``telemetry_summary()`` agreeing
    with ``hist_passes`` over the same trees."""
    bst = _booster(params, seed=1)
    gbdt = bst._gbdt
    gbdt.attach_telemetry(telemetry.RunRecorder())
    assert gbdt.tier_decision["wave"]
    bst.update()                    # iteration 0 runs unfused
    _ = gbdt.models                 # and lands here
    before = telemetry.counters_snapshot()
    seen0 = gbdt.telemetry_summary().get("hist_passes", 0)
    n0 = len(gbdt.models)
    for _ in range(8):
        bst.update()
    trees = gbdt.models[n0:]
    grown = _grown(before)
    refine = grown.get("hist_passes_refine", 0.0)   # c2f alone refines
    if params is FAST:
        # a block is counted when it lands, whole
        assert grown["trees_grown"] == len(trees) == 8
        # what the records report is the root's pass, a routing pass a
        # wave and the windowed passes
        assert gbdt.telemetry_summary()["hist_passes"] - seen0 == \
            grown["hist_passes_coarse"] + refine + grown["trees_grown"]
    else:
        assert grown["trees_grown"] == len(trees)
    assert grown["hist_passes_coarse"] == grown["grow_waves"]
    assert refine <= 2 * grown["grow_waves"]
    assert grown["grow_lanes_live"] == \
        sum(t.num_leaves - 1 for t in trees)
    assert grown["grow_lanes_offered"] == \
        grown["grow_waves"] * gbdt.grow_params.speculate
    assert 0 < grown["grow_lanes_live"] <= grown["grow_lanes_offered"]
    gbdt._telemetry.close(log=False)
