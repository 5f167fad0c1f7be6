"""Elastic mesh training (ISSUE 10): shard-loss detection, re-mesh
over the survivors, and bit-exact recovery (``parallel/elastic.py``,
``GBDT.remesh``, cross-width checkpoint resume).

Parity contract (docs/Distributed.md): the recovered model is
BYTE-identical to a clean continuation at the surviving width from
the rewind boundary — the oracle for data/voting shares the prefix
(their float histogram psum groups rows per shard, so prefixes
TRAINED at different widths differ in float low bits), while
feature-parallel reduces no float histograms and is byte-identical to
serial at EVERY width, prefix included.

The 2-D lane (ISSUE 18): ``tree_learner=data2d`` degrades by whole
mesh rows/columns (``degrade_mesh_shape`` — whichever loses fewer
devices, ties preferring the row so the feature axis survives), with
row-drop AND column-drop recovery each byte-equal to the clean
shape-remesh oracle and the full (R, F) topology on checkpoint
manifests.

Fast lane: one representative per property on the forced 8-device CPU
mesh (feature-parallel cross-width resume, the healthy-path
supervisor, remesh-to-serial fallback, the 2-D shape entrypoint).  The full cross-width resume
matrix ({data, feature, voting} x fused_iters {1, 4} x resume width
{4, 1}) and the heaviest ~20 s bit-exact recovery pins (same-width
roundtrip, supervisor error recovery with/without an outstanding
block, data-parallel cross-width resume) are @slow — the quick gate
must fit a 1-core container's tier-1 budget.
"""
import glob
import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.utils import faults

N_ROWS = 601          # deliberately not divisible by the 8-way mesh
ROUNDS = 10


@pytest.fixture(scope="module")
def data601():
    rng = np.random.RandomState(0)
    X = rng.random_sample((N_ROWS, 8))
    y = (X[:, 0] + 0.5 * (X[:, 1] > 0.5) +
         0.1 * rng.randn(N_ROWS) > 0.7).astype(float)
    return X, y


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    faults.reset()
    yield
    faults.clear()
    faults.reset()


def _params(learner="data", fused=4, rounds=ROUNDS, **kw):
    p = {"objective": "binary", "num_leaves": 15, "verbose": -1,
         "metric": "None", "tree_learner": learner,
         "fused_iters": fused, "num_iterations": rounds}
    p.update(kw)
    return p


def _mesh(width):
    import jax
    return jax.sharding.Mesh(np.asarray(jax.devices()[:width]),
                             ("shard",))


def _booster(X, y, learner="data", fused=4, width=8, rounds=ROUNDS,
             **kw):
    p = _params(learner, fused, rounds, **kw)
    d = lgb.Dataset(X, label=y, params=p)
    d.construct()
    return lgb.Booster(params=p, train_set=d, mesh=_mesh(width))


def _train_to(bst, boundary):
    while bst._gbdt.completed_iterations() < boundary:
        bst.update()
    return bst


def _oracle_remesh_at(X, y, boundary, to_shards, learner="data",
                      fused=4, rounds=ROUNDS, **kw):
    """Clean continuation oracle: uninterrupted to ``boundary`` at 8
    shards, explicit remesh, uninterrupted to the end — what elastic
    recovery (and cross-width resume) must equal byte-for-byte."""
    b = _booster(X, y, learner, fused, 8, rounds, **kw)
    _train_to(b, boundary)
    b._gbdt.remesh(num_shards=to_shards)
    _train_to(b, rounds)
    return b.model_to_string()


# ----------------------------------------------------------------------
# remesh entry point
# ----------------------------------------------------------------------
@pytest.mark.slow
def test_remesh_same_width_roundtrip_identity(data601):
    """remesh is lossless: snapshot -> reconstruct -> restore at the
    SAME width mid-run (under bagging: host RNG stream + bagging-cycle
    cache both cross the rebuild) yields a byte-identical final
    model."""
    X, y = data601
    bag = {"bagging_fraction": 0.8, "bagging_freq": 2}
    oracle = _train_to(_booster(X, y, **bag), ROUNDS).model_to_string()
    b = _booster(X, y, **bag)
    _train_to(b, 5)
    assert b._gbdt.remesh(num_shards=8) == 8
    _train_to(b, ROUNDS)
    assert b.model_to_string() == oracle


def test_remesh_to_one_falls_back_to_serial(data601):
    """A survivor set of one device drops to the serial learner (and
    re-derives serial-only construction decisions), continuing to a
    well-formed model."""
    X, y = data601
    b = _booster(X, y)
    _train_to(b, 5)
    assert b._gbdt.remesh(num_shards=1) == 1
    assert b._gbdt._dist is None
    _train_to(b, ROUNDS)
    assert b._gbdt.iter == ROUNDS


def test_make_mesh_for_overwidth_raises():
    """Asking for a wider mesh than the visible device set must raise
    actionably, not silently return a narrower mesh (the opaque
    cross-width placement failure)."""
    from lightgbm_tpu.parallel import make_mesh_for
    with pytest.raises(ValueError, match="device.*visible"):
        make_mesh_for(64)


def test_mesh_fault_points_registered():
    """The elastic fault points are in KNOWN_POINTS: arming them must
    not trip the unknown-point typo warning."""
    from lightgbm_tpu.utils.faults import KNOWN_POINTS
    assert {"mesh.collective", "mesh.heartbeat",
            "elastic.remesh"} <= KNOWN_POINTS


# ----------------------------------------------------------------------
# elastic supervisor
# ----------------------------------------------------------------------
@pytest.mark.slow
def test_supervisor_error_recovery_bit_exact(data601, tmp_path):
    """An injected collective failure (a shard dying mid-fused-block)
    is detected, the mesh rebuilds over the survivors, and the final
    model is BYTE-identical to a clean remesh continuation at the
    same served boundary — with detect/remesh recovery records on a
    lint-clean telemetry stream."""
    from lightgbm_tpu.utils.telemetry import lint_file
    X, y = data601
    tele = str(tmp_path / "tele.jsonl")
    faults.configure("mesh.collective:error@2")
    p = _params(elastic_training=True, telemetry_file=tele)
    d = lgb.Dataset(X, label=y, params=p)
    bst = lgb.train(p, d, verbose_eval=False, mesh=_mesh(8))
    bst._gbdt._telemetry.close(log=False)
    faults.clear()
    g = bst._gbdt
    assert g._dist is not None and g._dist.num_shards == 7
    assert g.iter == ROUNDS

    recov = [json.loads(l) for l in open(tele)
             if '"type": "recovery"' in l]
    events = [r["event"] for r in recov]
    assert events == ["detect", "remesh"], recov
    assert recov[0]["cause"] == "error"
    assert recov[0]["num_shards"] == 8
    assert recov[1]["from_shards"] == 8 and recov[1]["to_shards"] == 7
    n, errs = lint_file(tele)
    assert errs == [] and n > 0
    end = [json.loads(l) for l in open(tele) if '"type": "run_end"' in l]
    assert end[-1]["summary"]["recovery_detects"] == 1
    assert end[-1]["summary"]["recovery_remeshes"] == 1

    boundary = recov[1]["iter"]
    assert bst.model_to_string() == _oracle_remesh_at(X, y, boundary, 7)


@pytest.mark.slow
def test_supervisor_recovery_with_outstanding_block(data601, tmp_path):
    """A shard failure on block K+2's dispatch while block K+1 is
    still IN FLIGHT (superstep_pipeline_depth=1: dispatched, records
    unfetched) and block K is fully served: the abort must restore
    the dispatch fence across BOTH outstanding dispatches'
    RNG/quantization-stream consumption, die on the captured
    generation token, and recover bit-exactly from the served
    boundary — the pipeline x elastic contract (docs/Distributed.md).
    """
    X, y = data601
    tele = str(tmp_path / "tele.jsonl")
    # ordinals with depth 1: dispatch b1 (@1) + pre-seed b2 (@2)
    # inside update 2, then b3's dispatch (@3) fires while b2 is the
    # queued outstanding block and b1 is fully served
    faults.configure("mesh.collective:error@3")
    p = _params(elastic_training=True, superstep_pipeline_depth=1,
                telemetry_file=tele)
    d = lgb.Dataset(X, label=y, params=p)
    bst = lgb.train(p, d, verbose_eval=False, mesh=_mesh(8))
    bst._gbdt._telemetry.close(log=False)
    faults.clear()
    g = bst._gbdt
    assert g._dist is not None and g._dist.num_shards == 7
    assert g.iter == ROUNDS and g._sq == []

    recov = [json.loads(l) for l in open(tele)
             if '"type": "recovery"' in l]
    assert [r["event"] for r in recov] == ["detect", "remesh"], recov
    boundary = recov[1]["iter"]
    # block 1 ([1, 5)) was fully served when the fault hit: recovery
    # lands on its end, discarding the queued block 2 wholesale
    assert boundary == 5, recov
    assert bst.model_to_string() == _oracle_remesh_at(
        X, y, boundary, 7, superstep_pipeline_depth=1)


def test_supervisor_healthy_path_noop_and_budget(data601):
    """On a healthy run supervision is invisible: the model is
    byte-identical to the unsupervised run, no recovery records are
    emitted, and the device-call budget stays 2 per K-block (one scan
    dispatch + one packed fetch)."""
    from lightgbm_tpu.utils import telemetry as _telemetry
    X, y = data601
    c0 = _telemetry.counters_snapshot()
    p = _params(rounds=9, elastic_training=True)
    d = lgb.Dataset(X, label=y, params=p)
    sup = lgb.train(p, d, verbose_eval=False, mesh=_mesh(8))
    c1 = _telemetry.counters_snapshot()
    # 9 rounds = 1 unfused bias iteration + 2 fused blocks of 4
    assert c1["superstep_dispatches"] - c0.get(
        "superstep_dispatches", 0) == 2
    assert c1["superstep_fetches"] - c0.get("superstep_fetches", 0) == 2
    assert c1.get("recovery_detects", 0) == c0.get("recovery_detects", 0)
    p2 = _params(rounds=9)
    d2 = lgb.Dataset(X, label=y, params=p2)
    plain = lgb.train(p2, d2, verbose_eval=False, mesh=_mesh(8))
    assert sup.model_to_string() == plain.model_to_string()


@pytest.mark.slow
def test_supervisor_hang_watchdog_recovery(data601, tmp_path):
    """A hung collective (the dispatch blocks forever) is abandoned by
    the stall watchdog, classified as cause=hang, re-meshed, and the
    final model equals the clean-remesh oracle byte-for-byte."""
    X, y = data601
    tele = str(tmp_path / "tele.jsonl")
    faults.configure("mesh.collective:hang@2")
    p = _params(elastic_training=True, elastic_stall_timeout_s=4.0,
                telemetry_file=tele)
    d = lgb.Dataset(X, label=y, params=p)
    bst = lgb.train(p, d, verbose_eval=False, mesh=_mesh(8))
    bst._gbdt._telemetry.close(log=False)
    faults.clear()
    recov = [json.loads(l) for l in open(tele)
             if '"type": "recovery"' in l]
    assert [r["event"] for r in recov] == ["detect", "remesh"]
    assert recov[0]["cause"] == "hang"
    boundary = recov[1]["iter"]
    assert bst.model_to_string() == _oracle_remesh_at(X, y, boundary, 7)


@pytest.mark.slow
def test_suppressed_heartbeat_trips_watchdog(data601):
    """mesh.heartbeat:suppress + a slow dispatch: the watchdog trips
    on silence even though the block would eventually land, and the
    abandoned zombie attempt (which DOES wake up later) must not
    corrupt the recovered state — the captured-generation hardening."""
    import time
    X, y = data601
    faults.configure(
        "mesh.heartbeat:suppress@*,mesh.collective:sleep_8000@2")
    p = _params(elastic_training=True, elastic_stall_timeout_s=3.0)
    d = lgb.Dataset(X, label=y, params=p)
    bst = lgb.train(p, d, verbose_eval=False, mesh=_mesh(8))
    faults.clear()
    time.sleep(6.0)   # the sleeping zombie wakes; it must die unseen
    g = bst._gbdt
    assert g._dist.num_shards == 7 and g.iter == ROUNDS
    assert bst.model_to_string() == _oracle_remesh_at(X, y, 5, 7)


@pytest.mark.slow
def test_remesh_fault_degrades_further(data601):
    """A failing re-mesh attempt (elastic.remesh:error) degrades to a
    narrower survivor set instead of wedging, still bit-exact."""
    X, y = data601
    faults.configure("mesh.collective:error@2,elastic.remesh:error@1")
    p = _params(elastic_training=True)
    d = lgb.Dataset(X, label=y, params=p)
    bst = lgb.train(p, d, verbose_eval=False, mesh=_mesh(8))
    faults.clear()
    assert bst._gbdt._dist.num_shards == 6
    assert bst.model_to_string() == _oracle_remesh_at(X, y, 5, 6)


@pytest.mark.slow
def test_remesh_retry_after_partial_failure_keeps_state(data601,
                                                        monkeypatch):
    """A remesh that fails AFTER its internal re-construction leaves
    the booster blank — the supervisor's degrade retry must restore
    the snapshot it captured BEFORE the first attempt, never the
    blank state (silently restarting from iteration 0)."""
    from lightgbm_tpu.models.gbdt import GBDT
    X, y = data601
    real_restore = GBDT.restore_training_snapshot
    calls = {"n": 0}

    def flaky_restore(self, snap, raw=None):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("injected post-reconstruction failure")
        return real_restore(self, snap, raw=raw)

    monkeypatch.setattr(GBDT, "restore_training_snapshot",
                        flaky_restore)
    faults.configure("mesh.collective:error@2")
    p = _params(elastic_training=True)
    d = lgb.Dataset(X, label=y, params=p)
    bst = lgb.train(p, d, verbose_eval=False, mesh=_mesh(8))
    faults.clear()
    monkeypatch.undo()
    assert calls["n"] >= 2
    assert bst._gbdt._dist.num_shards == 6   # degraded past the flake
    assert bst._gbdt.iter == ROUNDS
    assert bst.model_to_string() == _oracle_remesh_at(X, y, 5, 6)


@pytest.mark.slow
def test_escalation_bounds(data601):
    """Recovery escalates loudly (ElasticError) past elastic_min_shards
    or elastic_max_remesh — the checkpoint restart story owns the rest."""
    from lightgbm_tpu.parallel import ElasticError
    X, y = data601
    faults.configure("mesh.collective:error@2")
    p = _params(elastic_training=True, elastic_min_shards=8)
    d = lgb.Dataset(X, label=y, params=p)
    with pytest.raises(ElasticError, match="elastic_min_shards"):
        lgb.train(p, d, verbose_eval=False, mesh=_mesh(8))
    faults.clear()
    faults.reset()
    faults.configure("mesh.collective:error@2")
    p = _params(elastic_training=True, elastic_max_remesh=0)
    d = lgb.Dataset(X, label=y, params=p)
    with pytest.raises(ElasticError, match="elastic_max_remesh"):
        lgb.train(p, d, verbose_eval=False, mesh=_mesh(8))


def test_unclassified_failures_propagate(data601):
    """A non-shard failure inside the supervised loop must PROPAGATE,
    never be absorbed into a re-mesh (a NumericalHealthError rewound
    and retried would hide bad data)."""
    from lightgbm_tpu.parallel.elastic import classify_shard_failure
    from lightgbm_tpu.utils.health import NumericalHealthError
    assert classify_shard_failure(
        NumericalHealthError(3, "superstep")) is None
    assert classify_shard_failure(ValueError("shapes mismatch")) is None
    assert classify_shard_failure(
        RuntimeError("collective all_gather timeout on device 3")) \
        is not None
    assert classify_shard_failure(
        faults.InjectedFault("injected collective failure "
                             "(mesh.collective:error)")) is not None


# ----------------------------------------------------------------------
# cross-mesh-width checkpoint resume
# ----------------------------------------------------------------------
def _save_at_8(X, y, ck, learner="data", fused=4, **kw):
    p = _params(learner, fused, checkpoint_dir=ck, snapshot_freq=3,
                keep_last_n=8, **kw)
    d = lgb.Dataset(X, label=y, params=p)
    lgb.train(p, d, verbose_eval=False, mesh=_mesh(8))
    snap = os.path.join(ck, "ckpt_00000003")
    assert os.path.isdir(snap)
    return snap


def _resume_at(X, y, snap, width, learner="data", fused=4, **kw):
    p = _params(learner, fused, **kw)
    d = lgb.Dataset(X, label=y, params=p)
    return lgb.train(p, d, verbose_eval=False, mesh=_mesh(width),
                     resume_from=snap)


def test_manifest_records_mesh_topology(data601, tmp_path):
    """Checkpoint manifests (and the extra.json meta) record the mesh
    the snapshot was taken under — the topology resume validates
    against."""
    X, y = data601
    snap = _save_at_8(X, y, str(tmp_path / "ck"))
    for blob in ("manifest.json", "extra.json"):
        mesh = json.load(open(os.path.join(snap, blob)))["mesh"]
        assert mesh == {"learner": "data", "num_shards": 8,
                        "mesh_shape": [8]}


@pytest.mark.slow
def test_cross_width_resume_data_bit_exact(data601, tmp_path):
    """Save at 8 shards (mid-fused-block boundary), resume at 4: the
    final model is byte-identical to the in-process remesh
    continuation — checkpoint restore at a new width and live re-mesh
    are the same state transition.  The resume emits a ``reshard``
    recovery record."""
    from lightgbm_tpu.utils.telemetry import RunRecorder, set_recorder
    X, y = data601
    snap = _save_at_8(X, y, str(tmp_path / "ck"))
    rec = RunRecorder()
    set_recorder(rec)
    try:
        resumed = _resume_at(X, y, snap, 4)
    finally:
        set_recorder(None)
    reshards = [r for r in rec.records if r.get("type") == "recovery"
                and r.get("event") == "reshard"]
    assert reshards and reshards[0]["from_shards"] == 8 and \
        reshards[0]["to_shards"] == 4
    assert resumed.model_to_string() == _oracle_remesh_at(X, y, 3, 4)


@pytest.mark.slow
def test_cross_width_resume_feature_full_parity(data601, tmp_path):
    """Feature-parallel reduces no float histograms, so its cross-width
    resume is byte-identical to a FROM-SCRATCH run at any width —
    including the serial learner (the strongest width-invariance pin)."""
    X, y = data601
    snap = _save_at_8(X, y, str(tmp_path / "ck"), learner="feature")
    resumed = _resume_at(X, y, snap, 4, learner="feature")
    p = _params("serial")
    d = lgb.Dataset(X, label=y, params=p)
    serial = lgb.train(p, d, verbose_eval=False)
    assert resumed.model_to_string() == serial.model_to_string()


@pytest.mark.slow
@pytest.mark.parametrize("learner", ["data", "feature", "voting"])
@pytest.mark.parametrize("fused", [1, 4])
@pytest.mark.parametrize("width", [4, 1])
def test_cross_width_resume_matrix(data601, tmp_path, learner, fused,
                                   width):
    """The acceptance matrix: save at 8 shards, resume at 4 and at 1,
    bit-exact against the uninterrupted continuation at the resume
    width, across {data, feature, voting} x fused_iters {1, 4}."""
    X, y = data601
    snap = _save_at_8(X, y, str(tmp_path / "ck"), learner=learner,
                      fused=fused)
    resumed = _resume_at(X, y, snap, width, learner=learner,
                         fused=fused)
    oracle = _oracle_remesh_at(X, y, 3, width, learner=learner,
                               fused=fused)
    assert resumed.model_to_string() == oracle
    if learner == "feature":
        # width invariance: also equal to an uninterrupted
        # from-scratch run at the resume width
        p = _params(learner if width > 1 else "serial", fused)
        d = lgb.Dataset(X, label=y, params=p)
        scratch = lgb.train(p, d, verbose_eval=False,
                            mesh=_mesh(max(width, 1)))
        assert resumed.model_to_string() == scratch.model_to_string()


# ----------------------------------------------------------------------
# 2-D (data x feature) elastic re-mesh
# ----------------------------------------------------------------------
def _booster_2d(X, y, shape, fused=4, rounds=ROUNDS, **kw):
    p = _params("data2d", fused, rounds, mesh_shape=shape, **kw)
    d = lgb.Dataset(X, label=y, params=p)
    d.construct()
    return lgb.Booster(params=p, train_set=d)


def _oracle_remesh_2d(X, y, boundary, from_shape, to_shape, fused=4,
                      rounds=ROUNDS, **kw):
    """Clean 2-D continuation oracle: uninterrupted on ``from_shape``
    to the boundary, explicit shape re-mesh, uninterrupted to the
    end."""
    b = _booster_2d(X, y, from_shape, fused, rounds, **kw)
    _train_to(b, boundary)
    b._gbdt.remesh(mesh_shape=[int(s) for s in to_shape.split("x")])
    _train_to(b, rounds)
    return b.model_to_string()


def test_degrade_mesh_shape_policy():
    """The 2-D surviving-set policy: drop the whole mesh row or
    column that loses fewer devices; ties prefer the row drop (the
    feature axis — and with it the collective-byte cut — survives)."""
    from lightgbm_tpu.parallel.elastic import degrade_mesh_shape
    assert degrade_mesh_shape(4, 2) == (3, 2)   # row costs 2, col 4
    assert degrade_mesh_shape(2, 4) == (2, 3)   # col costs 2, row 4
    assert degrade_mesh_shape(2, 2) == (1, 2)   # tie: row drop
    assert degrade_mesh_shape(4, 1) == (3, 1)   # degenerate column
    assert degrade_mesh_shape(1, 4) == (1, 3)   # degenerate row


@pytest.mark.slow
def test_remesh_2d_shape_entrypoint(data601):
    """``GBDT.remesh(mesh_shape=...)`` rebuilds the 2-D builder at the
    new shape mid-run and training continues on it."""
    X, y = data601
    b = _booster_2d(X, y, "4x2", rounds=6)
    _train_to(b, 3)
    assert b._gbdt.remesh(mesh_shape=(2, 2)) == 4
    g = b._gbdt
    assert (g._dist.row_shards, g._dist.feat_shards) == (2, 2)
    _train_to(b, 6)
    assert g.iter == 6


@pytest.mark.slow
def test_supervisor_2d_row_drop_bit_exact(data601, tmp_path):
    """A shard dying on the 4x2 mesh drops the whole mesh ROW (4x2 ->
    3x2: the row costs 2 devices, the column 4) and the recovered
    model is BYTE-identical to a clean shape-remesh continuation at
    the served boundary, with the (R, F) shapes on the recovery
    records."""
    X, y = data601
    tele = str(tmp_path / "tele.jsonl")
    faults.configure("mesh.collective:error@2")
    p = _params("data2d", elastic_training=True, mesh_shape="4x2",
                telemetry_file=tele)
    d = lgb.Dataset(X, label=y, params=p)
    bst = lgb.train(p, d, verbose_eval=False)
    bst._gbdt._telemetry.close(log=False)
    faults.clear()
    g = bst._gbdt
    assert (g._dist.row_shards, g._dist.feat_shards) == (3, 2)
    assert g.iter == ROUNDS

    recov = [json.loads(l) for l in open(tele)
             if '"type": "recovery"' in l]
    assert [r["event"] for r in recov] == ["detect", "remesh"], recov
    assert recov[1]["from_shape"] == [4, 2]
    assert recov[1]["to_shape"] == [3, 2]
    assert recov[1]["from_shards"] == 8 and recov[1]["to_shards"] == 6
    boundary = recov[1]["iter"]
    assert bst.model_to_string() == \
        _oracle_remesh_2d(X, y, boundary, "4x2", "3x2")


@pytest.mark.slow
def test_supervisor_2d_column_drop_bit_exact(data601):
    """On the 2x4 mesh the COLUMN is cheaper (2 devices vs the row's
    4): recovery drops 2x4 -> 2x3, byte-equal to the clean-remesh
    oracle."""
    X, y = data601
    faults.configure("mesh.collective:error@2")
    p = _params("data2d", elastic_training=True, mesh_shape="2x4")
    d = lgb.Dataset(X, label=y, params=p)
    bst = lgb.train(p, d, verbose_eval=False)
    faults.clear()
    g = bst._gbdt
    assert (g._dist.row_shards, g._dist.feat_shards) == (2, 3)
    assert g.iter == ROUNDS
    assert bst.model_to_string() == \
        _oracle_remesh_2d(X, y, 5, "2x4", "2x3")


@pytest.mark.slow
def test_manifest_records_2d_mesh_topology(data601, tmp_path):
    """data2d checkpoints record the FULL (R, F) tuple + learner kind
    — a 4x2 and a 2x4 snapshot are distinguishable even though their
    flat shard counts match."""
    X, y = data601
    ck = str(tmp_path / "ck")
    p = _params("data2d", mesh_shape="4x2", checkpoint_dir=ck,
                snapshot_freq=3, keep_last_n=8)
    d = lgb.Dataset(X, label=y, params=p)
    lgb.train(p, d, verbose_eval=False)
    snap = os.path.join(ck, "ckpt_00000003")
    assert os.path.isdir(snap)
    for blob in ("manifest.json", "extra.json"):
        mesh = json.load(open(os.path.join(snap, blob)))["mesh"]
        assert mesh == {"learner": "data2d", "num_shards": 8,
                        "mesh_shape": [4, 2]}
