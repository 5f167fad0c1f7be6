"""Sharded fused super-steps: the distributed learners ride INSIDE
the one compiled K-iteration ``lax.scan`` (``GBDT._build_superstep_fn``
wraps the scan in ``shard_map`` over the learner's mesh, with the
strategy collectives in-program) instead of falling back to per-
iteration per-shard dispatch.

Correctness bar (ISSUE 7): bit-exact parity with the unfused sharded
path across {data, feature, voting} x {none, GOSS, MVS, bagging} x
``fused_iters`` {1, 4} on the forced 8-device CPU mesh, including
checkpoint/resume from a mid-fused-block snapshot taken under a
sharded learner.  The row count (601) is deliberately NOT divisible by
the mesh width so the padded-row stitching of the stacked leaf table
is exercised (the replay-slice regression).

The 2-D lane (ISSUE 18): ``tree_learner=data2d`` shards the binned
matrix on BOTH axes of a (data x feature) mesh — fused == unfused
BIT-exact on {2x4, 4x2} x the same sampling matrix, the same
non-dividing row count, mid-block checkpoint/resume under the 2-D
mesh, and the superstep telemetry carrying the full (R, F) shape plus
per-axis collective accounting.

Fast lane: one representative per property.  The full matrix is @slow.
"""
import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb

N_ROWS = 601          # deliberately not divisible by the 8-way mesh


@pytest.fixture(scope="module")
def data601():
    rng = np.random.RandomState(0)
    X = rng.random_sample((N_ROWS, 8))
    y = (X[:, 0] + 0.5 * (X[:, 1] > 0.5) +
         0.1 * rng.randn(N_ROWS) > 0.7).astype(float)
    return X, y


SAMPLING = {
    "none": {},
    "bagging": {"bagging_fraction": 0.8, "bagging_freq": 2},
    "goss": {"boosting": "goss"},
    "mvs": {"boosting": "mvs", "bagging_fraction": 0.6},
}


def _train(X, y, learner, fused, extra=None, rounds=6, **kw):
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "metric": "None", "tree_learner": learner,
              "fused_iters": fused, "num_iterations": rounds}
    params.update(extra or {})
    params.update(kw)
    d = lgb.Dataset(X, label=y, params=params)
    return lgb.train(params, d, verbose_eval=False)


def _assert_fused_sharded(bst, learner):
    g = bst._gbdt
    assert g._dist is not None and g._dist.kind == learner
    assert g._fused_ok(), "sharded learner must be fused-eligible"
    # the scan really ran: a fused block was dispatched and served
    assert g._trees_dispatched >= 1 and g._fused_block is not None


@pytest.mark.slow
def test_data_goss_fused_equals_unfused(data601):
    """Representative parity pin: the GOSS mask draw, the sharded
    histogram psum and the leaf-assignment all-gather all ride inside
    the scan, and the model is BIT-identical to the unfused sharded
    path (same ops, same order, same PRNG folds)."""
    X, y = data601
    b1 = _train(X, y, "data", 1, SAMPLING["goss"])
    b4 = _train(X, y, "data", 4, SAMPLING["goss"])
    _assert_fused_sharded(b4, "data")
    assert b4.model_to_string() == b1.model_to_string()


def test_feature_parallel_fused_equals_serial(data601):
    """Feature-parallel reduces no float histograms, so its fused
    model must be byte-identical to the SERIAL fused model too, not
    just to its own unfused run."""
    X, y = data601
    serial = _train(X, y, "serial", 4)
    feat = _train(X, y, "feature", 4)
    _assert_fused_sharded(feat, "feature")
    assert feat.model_to_string() == serial.model_to_string()


@pytest.mark.slow
@pytest.mark.parametrize("learner", ["data", "feature", "voting"])
@pytest.mark.parametrize("sampling", sorted(SAMPLING))
def test_fused_matrix(data601, learner, sampling):
    """The acceptance matrix: {data, feature, voting} x {none,
    bagging, GOSS, MVS} x fused_iters {1, 4} — fused == unfused
    bit-exactly under every sharded learner."""
    X, y = data601
    b1 = _train(X, y, learner, 1, SAMPLING[sampling])
    b4 = _train(X, y, learner, 4, SAMPLING[sampling])
    _assert_fused_sharded(b4, learner)
    assert b4.model_to_string() == b1.model_to_string()


@pytest.mark.slow
def test_data_fused_matches_serial_structure(data601):
    """Under QUANTIZED wave histograms the data-parallel psum sums
    small integers — exact in f32 in any reduction order — so the
    fused sharded model's STRUCTURE (features, thresholds) must equal
    the serial learner's exactly (the test_parallel.py guarantee, now
    through the fused scan; float histograms may flip a late-tree
    split on a psum rounding tie, which is why this pin rides the
    quantized tier)."""
    X, y = data601
    fast = {"wave_splits": True, "use_quantized_grad": True,
            "min_data_in_leaf": 1, "max_bin": 63}
    serial = _train(X, y, "serial", 4, fast)
    data = _train(X, y, "data", 4, fast)
    assert data._gbdt.grow_params.wave
    assert data._gbdt._dist is not None and data._gbdt._fused_ok()
    for ts, td in zip(serial._gbdt.models, data._gbdt.models):
        n = ts.num_leaves - 1
        assert td.num_leaves == ts.num_leaves
        np.testing.assert_array_equal(td.split_feature[:n],
                                      ts.split_feature[:n])
        np.testing.assert_array_equal(td.threshold_bin[:n],
                                      ts.threshold_bin[:n])
    np.testing.assert_allclose(data.predict(X), serial.predict(X),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.slow
def test_midblock_checkpoint_resume_sharded(data601, tmp_path):
    """A periodic snapshot landing MID fused block under a sharded
    learner (snapshot_freq=3, fused_iters=4: block [1-4] in flight at
    the boundary) must resume BIT-identically — this pins the served-
    boundary replay slicing the PADDED stacked leaf table of the
    row-sharded learners down to the real row count."""
    X, y = data601
    extra = dict(SAMPLING["bagging"], num_iterations=10)
    oracle = _train(X, y, "data", 4, extra, rounds=10)
    ck = str(tmp_path / "ck")
    _train(X, y, "data", 4, dict(extra, checkpoint_dir=ck,
                                 snapshot_freq=3, keep_last_n=8),
           rounds=10)
    snap = os.path.join(ck, "ckpt_00000003")
    assert os.path.isdir(snap)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "metric": "None", "tree_learner": "data",
              "fused_iters": 4, "num_iterations": 10}
    params.update(SAMPLING["bagging"])
    d = lgb.Dataset(X, label=y, params=params)
    resumed = lgb.train(params, d, verbose_eval=False,
                        resume_from=snap)
    assert resumed.model_to_string() == oracle.model_to_string()


def test_superstep_telemetry_and_device_call_budget(data601, tmp_path):
    """The sharded super-step telemetry record carries the per-block
    collective counters + mesh identity (the weak-scaling triage
    reads them), and the device-call budget per K-block matches the
    serial fused path: 2 calls (one scan dispatch, one packed fetch)
    per K iterations at ANY mesh size."""
    from lightgbm_tpu.utils import telemetry as _telemetry
    from lightgbm_tpu.utils.telemetry import lint_file

    X, y = data601
    tele = str(tmp_path / "tele.jsonl")
    c0 = _telemetry.counters_snapshot()
    bst = _train(X, y, "data", 4, {"telemetry_file": tele}, rounds=9)
    c1 = _telemetry.counters_snapshot()
    bst._gbdt._telemetry.close(log=False)

    # 9 rounds = 1 unfused bias iteration + 2 fused blocks of 4:
    # exactly 2 scan dispatches + 2 packed fetches
    assert c1["superstep_dispatches"] - c0.get(
        "superstep_dispatches", 0) == 2
    assert c1["superstep_fetches"] - c0.get(
        "superstep_fetches", 0) == 2

    n, errs = lint_file(tele)
    assert errs == [] and n > 0
    ss = [json.loads(l) for l in open(tele)
          if '"type": "superstep"' in l]
    assert len(ss) == 2
    for r in ss:
        assert r["learner"] == "data"
        assert r["num_shards"] == 8
        assert r["mesh_shape"] == [8]
        assert r["collective_bytes"] > 0
        assert r["collective_ops"] > 0
    # run_end rolls the in-scan collective estimate up
    end = [json.loads(l) for l in open(tele)
           if '"type": "run_end"' in l]
    assert end and end[-1]["summary"]["collective_bytes"] > 0
    assert end[-1]["summary"]["collective_ops"] > 0


@pytest.mark.slow
def test_data2d_goss_fused_equals_unfused(data601):
    """2-D fast-lane representative: the row-axis histogram psum, the
    feature-axis best-split gather and the feature-axis routing psum
    all ride inside the scan on the 4x2 (data x feature) mesh, and
    the fused model is BIT-identical to the unfused 2-D path."""
    X, y = data601
    b1 = _train(X, y, "data2d", 1, SAMPLING["goss"])
    b4 = _train(X, y, "data2d", 4, SAMPLING["goss"])
    _assert_fused_sharded(b4, "data2d")
    g = b4._gbdt
    assert (g._dist.row_shards, g._dist.feat_shards) == (4, 2)
    assert b4.model_to_string() == b1.model_to_string()


@pytest.mark.slow
@pytest.mark.parametrize("shape", ["2x4", "4x2"])
@pytest.mark.parametrize("sampling", sorted(SAMPLING))
def test_data2d_fused_matrix(data601, shape, sampling):
    """The 2-D acceptance matrix: {2x4, 4x2} x {none, bagging, GOSS,
    MVS} x fused_iters {1, 4} — fused == unfused bit-exactly on the
    same 2-D mesh, with the 601-row count dividing neither axis."""
    X, y = data601
    extra = dict(SAMPLING[sampling], mesh_shape=shape)
    b1 = _train(X, y, "data2d", 1, extra)
    b4 = _train(X, y, "data2d", 4, extra)
    _assert_fused_sharded(b4, "data2d")
    r, f = (int(s) for s in shape.split("x"))
    g = b4._gbdt
    assert (g._dist.row_shards, g._dist.feat_shards) == (r, f)
    assert b4.model_to_string() == b1.model_to_string()


@pytest.mark.slow
def test_data2d_fused_matches_serial_structure(data601):
    """Quantized-tier serial-structure pin through the 2-D mesh: the
    row-axis psum sums small integers — exact in f32 in any reduction
    order — and the feature-axis merge reproduces the serial
    feature-major tie-break, so the data2d model's STRUCTURE equals
    the serial learner's exactly."""
    X, y = data601
    fast = {"use_quantized_grad": True, "min_data_in_leaf": 1,
            "max_bin": 63}
    serial = _train(X, y, "serial", 4, fast)
    b2d = _train(X, y, "data2d", 4, fast)
    assert b2d._gbdt._dist is not None and b2d._gbdt._fused_ok()
    for ts, td in zip(serial._gbdt.models, b2d._gbdt.models):
        n = ts.num_leaves - 1
        assert td.num_leaves == ts.num_leaves
        np.testing.assert_array_equal(td.split_feature[:n],
                                      ts.split_feature[:n])
        np.testing.assert_array_equal(td.threshold_bin[:n],
                                      ts.threshold_bin[:n])
    np.testing.assert_allclose(b2d.predict(X), serial.predict(X),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.slow
def test_midblock_checkpoint_resume_data2d(data601, tmp_path):
    """Mid-fused-block snapshot/resume under the 2-D mesh: the
    served-boundary replay must stitch the doubly-padded (row x
    feature) state back to the real row count bit-exactly."""
    X, y = data601
    extra = dict(SAMPLING["bagging"], num_iterations=10)
    oracle = _train(X, y, "data2d", 4, extra, rounds=10)
    ck = str(tmp_path / "ck")
    _train(X, y, "data2d", 4, dict(extra, checkpoint_dir=ck,
                                   snapshot_freq=3, keep_last_n=8),
           rounds=10)
    snap = os.path.join(ck, "ckpt_00000003")
    assert os.path.isdir(snap)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "metric": "None", "tree_learner": "data2d",
              "fused_iters": 4, "num_iterations": 10}
    params.update(SAMPLING["bagging"])
    d = lgb.Dataset(X, label=y, params=params)
    resumed = lgb.train(params, d, verbose_eval=False,
                        resume_from=snap)
    assert resumed.model_to_string() == oracle.model_to_string()


@pytest.mark.slow
def test_data2d_cross_shape_resume(data601, tmp_path):
    """A checkpoint taken on the 4x2 mesh restored into a 2x4 booster
    (EQUAL shard counts — only the shape differs) re-shards and
    continues; the manifest's full (R, F) topology is what makes the
    mismatch detectable at all."""
    X, y = data601
    ck = str(tmp_path / "ck")
    _train(X, y, "data2d", 4, {"mesh_shape": "4x2",
                               "checkpoint_dir": ck,
                               "snapshot_freq": 4, "keep_last_n": 8},
           rounds=8)
    snap = os.path.join(ck, "ckpt_00000004")
    assert os.path.isdir(snap)
    # the 2x4 oracle: same data, same params, trained clean
    oracle = _train(X, y, "data2d", 4, {"mesh_shape": "2x4"},
                    rounds=8)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "metric": "None", "tree_learner": "data2d",
              "mesh_shape": "2x4", "fused_iters": 4,
              "num_iterations": 8}
    d = lgb.Dataset(X, label=y, params=params)
    resumed = lgb.train(params, d, verbose_eval=False,
                        resume_from=snap)
    g = resumed._gbdt
    assert (g._dist.row_shards, g._dist.feat_shards) == (2, 4)
    # the resumed trees from the boundary on were grown on the 2x4
    # mesh: prediction parity with the clean 2x4 oracle within float
    # psum-reordering noise (the first 4 trees are byte-identical
    # carried state)
    np.testing.assert_allclose(resumed.predict(X), oracle.predict(X),
                               rtol=1e-4, atol=1e-6)


def test_data2d_telemetry_mesh_shape_and_budget(data601, tmp_path):
    """The data2d superstep record carries the full 2-D mesh shape
    plus PER-AXIS collective accounting (the 2-D weak-scaling triage
    keys on it), and the device-call budget stays 2 per K-block."""
    from lightgbm_tpu.utils import telemetry as _telemetry
    from lightgbm_tpu.utils.telemetry import lint_file

    X, y = data601
    tele = str(tmp_path / "tele.jsonl")
    c0 = _telemetry.counters_snapshot()
    bst = _train(X, y, "data2d", 4, {"telemetry_file": tele},
                 rounds=9)
    c1 = _telemetry.counters_snapshot()
    bst._gbdt._telemetry.close(log=False)

    assert c1["superstep_dispatches"] - c0.get(
        "superstep_dispatches", 0) == 2
    assert c1["superstep_fetches"] - c0.get(
        "superstep_fetches", 0) == 2

    n, errs = lint_file(tele)
    assert errs == [] and n > 0
    ss = [json.loads(l) for l in open(tele)
          if '"type": "superstep"' in l]
    assert len(ss) == 2
    for r in ss:
        assert r["learner"] == "data2d"
        assert r["num_shards"] == 8
        assert r["mesh_shape"] == [4, 2]
        axb = r["collective_bytes_axis"]
        axo = r["collective_ops_axis"]
        assert set(axb) == {"data", "feature"} == set(axo)
        assert axb["data"] > 0 and axb["feature"] > 0
        assert axo["data"] > 0 and axo["feature"] > 0
        assert r["collective_bytes"] > 0


def test_data2d_mesh_resident_state(data601):
    """The binned matrix is sharded on BOTH axes at construction —
    each device holds an R-th of rows x an F-th of feature tiles —
    while per-row state shards on the data axis only."""
    X, y = data601
    bst = _train(X, y, "data2d", 4, rounds=4)
    g = bst._gbdt
    shd = g._dist.shardings()
    assert g._xt.sharding == shd["xt"]
    assert not g._xt.sharding.is_fully_replicated
    assert g._base_mask.sharding == shd["row"]
    assert g._score.sharding.is_fully_replicated
    # per-device block really is (F/Fx, N/R)
    F_pad, n_pad = g._F_pad, g._n_pad
    shard_shapes = {tuple(s.data.shape) for s in g._xt.addressable_shards}
    assert shard_shapes == {(F_pad // 2, n_pad // 4)}


def test_mesh_resident_state_sharded(data601):
    """The persistent training tensors are placed with the learner's
    NamedSharding ONCE at construction — the binned matrix must be
    sharded over the mesh (not replicated host-placed per call), and
    under the data learner's fused scan so is the per-row state: the
    score carry and the objective's row tensors, each device its own
    rows (``row_state: shard``, models/tier.py)."""
    X, y = data601
    bst = _train(X, y, "data", 4, rounds=4)
    g = bst._gbdt
    shd = g._dist.shardings()
    assert g.tier_decision["row_state"] == "shard"
    assert g._xt.sharding == shd["xt"]
    assert g._base_mask.sharding == shd["row"]
    assert g._score.sharding == shd["rows2d"]
    assert g._score.shape == (1, g._n_pad)
    assert g.train_score.shape == (1, N_ROWS)
    # a learner the ladder refuses keeps the carry replicated
    v = _train(X, y, "voting", 4, rounds=4)._gbdt
    assert v.tier_decision["row_state"] == "replicated"
    assert v._score.sharding.is_fully_replicated


# ---- the row state on the shard (row_state: shard) --------------------
N4 = 2001             # not divisible by the four devices: n_pad 2004
FAST4 = {"wave_splits": True, "use_quantized_grad": True, "max_bin": 63,
         "num_machines": 4}


@pytest.fixture(scope="module")
def data2001():
    rng = np.random.RandomState(1)
    X = rng.random_sample((N4, 8)).astype(np.float32)
    y = (X[:, 0] + 0.5 * (X[:, 1] > 0.5) +
         0.1 * rng.randn(N4) > 0.7).astype(float)
    return X, y


@pytest.mark.parametrize("min_data", [20, 1])
def test_data4_fast_matches_serial_structure(data2001, min_data):
    """The fast job (wave growth, quantized gradients, fused blocks)
    on four devices, every device holding its own rows' state, grows
    the serial learner's trees over iteration 0 and two fused blocks,
    on the count-carrying tier (``min_data_in_leaf`` 20) and on the
    two-column one (1): the histograms are sums of small integers,
    exact in any order, and a row draws the rounding bits it draws in
    the serial scan (hashed from its index in the job).  Leaf values
    are renewed from float sums, whose order over shards differs: the
    quantized tier's tolerance."""
    X, y = data2001
    extra = dict(FAST4, min_data_in_leaf=min_data)
    serial = _train(X, y, "serial", 4, extra, rounds=9)
    data = _train(X, y, "data", 4, extra, rounds=9)
    g = data._gbdt
    assert g.tier_decision["row_state"] == "shard"
    assert g.tier_decision["num_shards"] == 4
    assert g.tier_decision["tier"] == ("two_col" if min_data == 1
                                       else "wave_quant")
    assert g._fused_ok() and g._fused_block is not None
    assert len(g.models) == len(serial._gbdt.models) == 9
    for ts, td in zip(serial._gbdt.models, g.models):
        n = ts.num_leaves - 1
        assert td.num_leaves == ts.num_leaves
        np.testing.assert_array_equal(td.split_feature[:n],
                                      ts.split_feature[:n])
        np.testing.assert_array_equal(td.threshold_bin[:n],
                                      ts.threshold_bin[:n])
    np.testing.assert_allclose(data.predict(X), serial.predict(X),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(g.train_score, serial._gbdt.train_score,
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("extra", [
    {"min_data_in_leaf": 20}, {"min_data_in_leaf": 1},
    {"objective": "regression", "use_quantized_grad": False}],
    ids=["wave_quant", "two_col", "regression_float"])
def test_shard_state_is_the_replicated_states_model(data2001, extra,
                                                    monkeypatch):
    """Each device computing its own rows' gradients, leaf index and
    score update gives, bit for bit, the model and the training score
    of the replicated state (every device computing all rows and the
    score delta gathered): the math is elementwise over rows."""
    from lightgbm_tpu.models import tier
    X, y = data2001
    extra = dict(FAST4, **extra)
    own = _train(X, y, "data", 4, extra, rounds=9)
    assert own._gbdt.tier_decision["row_state"] == "shard"
    monkeypatch.setattr(tier, "_row_state_gate",
                        lambda config, facts: "forced by the test")
    rep = _train(X, y, "data", 4, extra, rounds=9)
    assert rep._gbdt.tier_decision["row_state"] == "replicated"
    assert rep._gbdt._score.sharding.is_fully_replicated
    assert own.model_to_string() == rep.model_to_string()
    np.testing.assert_array_equal(own._gbdt.train_score,
                                  rep._gbdt.train_score)


def _shapes_of(hlo_text):
    """Every array shape in a compiled module's text, as tuples."""
    import re
    return {tuple(int(d) for d in m.split(","))
            for m in re.findall(r"\[(\d+(?:,\d+)*)\]", hlo_text)}


def test_per_device_program_holds_no_array_of_the_job(data2001,
                                                      monkeypatch):
    """After construction and after a block the score carry, the
    objective's row tensors and the stacked leaf index are sharded
    over rows, and the compiled per-device program of the fused
    super-step has no operand, constant or result with a dimension of
    the job's rows (``n``) or its padded rows (``n_pad``)."""
    import jax
    from lightgbm_tpu.models.gbdt import GBDT
    X, y = data2001
    seen = {}
    build = GBDT._build_superstep_fn

    def spy(self):
        fn = build(self)

        def call(*args):
            # shapes as placed: what sits on one device is uncommitted
            seen["jit"], seen["args"] = fn, jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype,
                    sharding=(a.sharding
                              if len(a.sharding.device_set) > 1
                              else None)), args)
            return fn(*args)
        return call
    monkeypatch.setattr(GBDT, "_build_superstep_fn", spy)

    extra = dict(FAST4, min_data_in_leaf=20)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "metric": "None", "tree_learner": "data",
              "fused_iters": 4, "num_iterations": 9, **extra}
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    g = bst._gbdt
    n_pad = g._n_pad
    assert (N4, n_pad) == (2001, 2004)

    def own_rows(a):
        return a.addressable_shards[0].data.shape[-1] == n_pad // 4

    def state():
        return [g._score, g._base_mask, *g.objective.rows().values()]
    assert set(g.objective.rows()) == {"label", "sign_label",
                                       "cls_weight"}
    assert all(own_rows(a) for a in state())          # as constructed
    for _ in range(5):                                # iteration 0 + a block
        bst.update()
    assert all(own_rows(a) for a in state())
    blk = g._fused_block
    assert own_rows(blk["leaf_idx"]) and own_rows(blk["start_score"])
    # the gradients' buffers: what the objective makes of that state
    grad, hess = g._gradient_fn()(g._score)
    assert own_rows(grad) and own_rows(hess)

    text = seen["jit"].lower(*seen["args"]).compile().as_text()
    # what crosses devices: sums and maxima, nothing gathered
    assert "all-reduce" in text and "all-gather" not in text
    whole = {s for s in _shapes_of(text) if N4 in s or n_pad in s}
    assert not whole, sorted(whole)
    assert any(n_pad // 4 in s for s in _shapes_of(text))
    # the replicated state's program, for what the check can see: it
    # holds the job's rows (scores, labels as constants, the gather)
    monkeypatch.setattr("lightgbm_tpu.models.tier._row_state_gate",
                        lambda config, facts: "forced by the test")
    rep = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    for _ in range(5):
        rep.update()
    text = seen["jit"].lower(*seen["args"]).compile().as_text()
    assert any(N4 in s for s in _shapes_of(text))
    assert "all-gather" in text


@pytest.mark.parametrize("n", [2000, 4000])
def test_row_state_bytes_a_chip_do_not_grow_with_the_job(n):
    """The same job on two and on four devices holds the same bytes of
    per-row state in all (``row_state_bytes_per_chip`` x devices): a
    chip holds its share and nothing that grows with the job.  The
    replicated state holds the job's on every chip."""
    from lightgbm_tpu.utils.telemetry import counters_snapshot
    rng = np.random.RandomState(0)
    X = rng.random_sample((n, 8)).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(float)

    def per_chip(devices, learner="data"):
        params = {"objective": "binary", "num_leaves": 15,
                  "verbose": -1, "metric": "None",
                  "tree_learner": learner, "fused_iters": 4,
                  **dict(FAST4, num_machines=devices)}
        g = lgb.Booster(params,
                        lgb.Dataset(X, label=y, params=params))._gbdt
        assert counters_snapshot()["row_state_bytes_per_chip"] == \
            g.row_state_bytes_per_chip
        return g.row_state_bytes_per_chip
    # score, mask, label, sign_label, cls_weight: five float32 a row
    assert per_chip(2) * 2 == per_chip(4) * 4 == 5 * 4 * n
    assert per_chip(4, "voting") > per_chip(4) * 3


def test_shard_state_midblock_resume_and_rollback(data2001, tmp_path):
    """The state kept on the shard goes through the same doors as the
    replicated one: a snapshot taken mid fused block (stored without
    padding rows) resumes bit-identically onto the mesh and onto the
    serial learner's width, and a rollback lands on a carry that is
    still each device's own rows."""
    X, y = data2001
    extra = dict(FAST4, min_data_in_leaf=20, num_iterations=10)
    oracle = _train(X, y, "data", 4, extra, rounds=10)
    assert oracle._gbdt.tier_decision["row_state"] == "shard"
    ck = str(tmp_path / "ck")
    _train(X, y, "data", 4, dict(extra, checkpoint_dir=ck,
                                 snapshot_freq=3, keep_last_n=8),
           rounds=10)
    snap = os.path.join(ck, "ckpt_00000003")
    assert os.path.isdir(snap)

    def resume(learner):
        params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
                  "metric": "None", "tree_learner": learner,
                  "fused_iters": 4, **extra}
        return lgb.train(params, lgb.Dataset(X, label=y, params=params),
                         verbose_eval=False, resume_from=snap)
    assert resume("data").model_to_string() == oracle.model_to_string()
    assert len(resume("serial")._gbdt.models) == 10

    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "metric": "None", "tree_learner": "data", "fused_iters": 4,
              **extra}
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    for _ in range(6):
        bst.update()
    bst.rollback_one_iter()
    bst.update()
    g = bst._gbdt
    assert len(g.models) == 6
    assert g._score.sharding == g._dist.shardings()["rows2d"]
    assert g.train_score.shape == (1, N4)
