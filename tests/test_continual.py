"""Continual training daemon tests (``lightgbm_tpu/cont/``).

Fast lane: validation gates, the batch source's backoff/quarantine
classes, the faults-registry typo warning, the numerical-health guard
(one-shot engine.train AND the daemon's exact rewind), the stall
watchdog, preemption drain + bit-exact resume, and the refit ->
watcher republish hookup.

Slow lane: the scenario matrix — lambdarank with query groups, DART,
monotone constraints, quantized training — each running the full
ingest -> extend/refit -> checkpoint -> publish loop (ROADMAP item 5's
"as many scenarios as you can imagine", pinned).
"""
import json
import os
import threading
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import engine as engine_mod
from lightgbm_tpu.ckpt import CheckpointManager
from lightgbm_tpu.cont import (Batch, BatchValidator, ContinualTrainer,
                               DirectoryBatchSource)
from lightgbm_tpu.utils import faults as _faults
from lightgbm_tpu.utils import telemetry as _telemetry
from lightgbm_tpu.utils.health import NumericalHealthError
from lightgbm_tpu.utils.log import Log


@pytest.fixture(autouse=True)
def _clean_faults_and_preempt():
    _faults.clear()
    _faults.reset()
    engine_mod.clear_preempt()
    yield
    _faults.clear()
    _faults.reset()
    engine_mod.clear_preempt()


def _write_batch(ingest, name, seed=0, rows=400, n_feat=6,
                 nan_labels=False, objective="regression", group=None):
    os.makedirs(ingest, exist_ok=True)
    rng = np.random.RandomState(seed)
    X = rng.randn(rows, n_feat)
    if objective == "binary":
        y = (X[:, 0] + 0.4 * rng.randn(rows) > 0).astype(np.float64)
    elif objective == "rank":
        y = np.clip((X[:, 0] + 0.5 * rng.randn(rows)) * 1.5 + 2,
                    0, 4).astype(np.int64).astype(np.float64)
    else:
        y = X[:, 0] + 0.1 * rng.randn(rows)
    if nan_labels:
        y = np.array(y, np.float64)
        y[::5] = np.nan
    kw = {}
    if group is not None:
        kw["group"] = group
    np.savez(os.path.join(ingest, name), X=X, y=y, **kw)
    return X, y


def _params(tmp_path, **extra):
    p = {"objective": "regression", "num_leaves": 7, "verbose": -1,
         "metric": "None",
         "checkpoint_dir": str(tmp_path / "ck"),
         "continual_ingest_dir": str(tmp_path / "ingest"),
         "continual_rounds_per_batch": 4,
         "continual_idle_exit_s": 0.6,
         "continual_poll_s": 0.05,
         "continual_backoff_base_s": 0.01}
    p.update(extra)
    return p


def _continual_events(path):
    out = {}
    for r in _telemetry.read_records(str(path)):
        if r.get("type") == "continual":
            out.setdefault(r["event"], []).append(r)
    return out


def _run_trainer(tmp_path, recorder=None, **extra):
    tr = ContinualTrainer(_params(tmp_path, **extra), recorder=recorder)
    stats = tr.run()
    return tr, stats


# ======================================================================
# validation gates
# ======================================================================
def test_validator_schema_and_nonfinite():
    v = BatchValidator()
    X = np.random.RandomState(0).randn(50, 4)
    y = np.zeros(50)
    ok = Batch("b", (), X, y)
    assert v.check(ok) == []
    assert v.check(Batch("b", (), X[0], y)) != []          # 1-D X
    assert v.check(Batch("b", (), X, y[:10])) != []        # y mismatch
    assert v.check(Batch("b", (), X.astype("U8"), y)) != []  # dtype
    bad_w = Batch("b", (), X, y, weight=np.ones(7))
    assert any("weight" in e for e in v.check(bad_w))
    bad_g = Batch("b", (), X, y, group=np.asarray([10, 10]))
    assert any("group" in e for e in v.check(bad_g))
    y_nan = y.copy()
    y_nan[3] = np.nan
    assert any("non-finite" in e for e in
               v.check(Batch("b", (), X, y_nan)))
    X_inf = X.copy()
    X_inf[0, 0] = np.inf
    assert any("non-finite" in e for e in
               v.check(Batch("b", (), X_inf, y)))
    # gate off: non-finite flows through (the in-training guard's job)
    v_off = BatchValidator(nonfinite_check=False)
    assert v_off.check(Batch("b", (), X, y_nan)) == []


def test_validator_drift_gates():
    rng = np.random.RandomState(0)
    v = BatchValidator(drift_sigma=4.0, range_factor=2.0)
    for seed in range(3):
        r = np.random.RandomState(seed)
        X = r.randn(300, 4)
        y = X[:, 0] + 0.1 * r.randn(300)
        b = Batch(f"b{seed}", (), X, y)
        assert v.check(b) == []
        v.observe(b)
    # label convention flip: mean jumps far outside the reference
    y_bad = rng.randn(300) + 50.0
    errs = v.check(Batch("drift", (), rng.randn(300, 4), y_bad))
    assert any("label drift" in e for e in errs)
    # unit change: meters -> millimeters
    errs = v.check(Batch("range", (), rng.randn(300, 4) * 1000.0,
                         rng.randn(300) * 0.1))
    assert any("range drift" in e for e in errs)
    # feature-width change is schema drift
    errs = v.check(Batch("wide", (), rng.randn(300, 9),
                         rng.randn(300)))
    assert any("feature width" in e for e in errs)


def test_validator_state_roundtrip():
    rng = np.random.RandomState(1)
    v = BatchValidator(drift_sigma=4.0)
    b = Batch("b", (), rng.randn(200, 3), rng.randn(200))
    assert v.check(b) == []
    v.observe(b)
    v2 = BatchValidator(drift_sigma=4.0)
    v2.restore_state(json.loads(json.dumps(v.state())))
    bad = Batch("bad", (), rng.randn(200, 3), rng.randn(200) + 99.0)
    assert v.check(bad) != [] and v2.check(bad) != []
    assert v2.check(Batch("ok", (), rng.randn(200, 3),
                          rng.randn(200))) == []


# ======================================================================
# batch source
# ======================================================================
def test_source_npz_and_mmap_pair(tmp_path):
    root = str(tmp_path / "in")
    _write_batch(root, "a_batch.npz", seed=1, rows=30)
    rng = np.random.RandomState(2)
    np.save(os.path.join(root, "b_shard.X.npy"), rng.randn(20, 6))
    np.save(os.path.join(root, "b_shard.y.npy"), rng.randn(20))
    src = DirectoryBatchSource(root)
    assert src.pending() == ["a_batch.npz", "b_shard"]
    b1 = src.next_batch()
    assert b1.name == "a_batch.npz" and b1.rows == 30
    src.mark_done(b1)
    b2 = src.next_batch()
    assert b2.name == "b_shard" and b2.rows == 20
    assert isinstance(b2.X, np.memmap)
    src.mark_done(b2)
    assert src.pending() == []
    assert sorted(os.listdir(src.processed_dir)) == [
        "a_batch.npz", "b_shard.X.npy", "b_shard.y.npy"]


def test_source_transient_backoff_then_success(tmp_path):
    root = str(tmp_path / "in")
    _write_batch(root, "b0.npz", rows=20)
    rec = _telemetry.RunRecorder()
    src = DirectoryBatchSource(root, read_retries=3,
                               backoff_base_s=0.01, recorder=rec)
    _faults.configure("ingest.read:error@1")
    b = src.next_batch()
    assert b is not None and b.rows == 20
    backoffs = [r for r in rec.records
                if r.get("type") == "continual"
                and r.get("event") == "backoff"]
    assert len(backoffs) == 1 and backoffs[0]["attempt"] == 1
    assert src.quarantined == 0


def test_source_exhausted_retries_quarantine(tmp_path):
    root = str(tmp_path / "in")
    _write_batch(root, "b0.npz", rows=20)
    rec = _telemetry.RunRecorder()
    src = DirectoryBatchSource(root, read_retries=2,
                               backoff_base_s=0.01, recorder=rec)
    _faults.configure("ingest.read:error@*")
    assert src.next_batch() is None
    assert src.quarantined == 1
    q = [r for r in rec.records if r.get("event") == "quarantine"]
    assert q and q[0]["reason"] == "read"
    assert os.path.exists(os.path.join(src.quarantine_dir, "b0.npz"))
    assert src.pending() == []


def test_source_corrupt_file_quarantined_immediately(tmp_path):
    root = str(tmp_path / "in")
    os.makedirs(root)
    with open(os.path.join(root, "bad.npz"), "wb") as f:
        f.write(b"definitely not a zip archive")
    _write_batch(root, "good.npz", rows=25)
    rec = _telemetry.RunRecorder()
    src = DirectoryBatchSource(root, recorder=rec)
    assert src.next_batch() is None        # bad.npz quarantined
    assert src.quarantined == 1
    b = src.next_batch()                   # stream not wedged
    assert b is not None and b.name == "good.npz"


# ======================================================================
# faults registry: unknown-point warning (satellite)
# ======================================================================
def test_faults_unknown_point_warns_once():
    msgs = []
    Log.reset_callback(lambda s: msgs.append(s))
    level = Log._level
    Log.reset_level(0)   # earlier tests may have left fatal-only
    try:
        base = _telemetry.counters_snapshot().get(
            "faults_unknown_point", 0)
        _faults.configure("ingest.raed:error")   # the typo
        warned = [m for m in msgs if "unregistered point" in m]
        assert len(warned) == 1 and "ingest.raed" in warned[0]
        now = _telemetry.counters_snapshot()
        assert now.get("faults_unknown_point", 0) == base + 1
        # once per point: re-configuring the same typo stays quiet
        _faults.configure("ingest.raed:error@2")
        assert len([m for m in msgs
                    if "unregistered point" in m]) == 1
        # a registered point never warns
        _faults.configure("ingest.read:error")
        assert len([m for m in msgs
                    if "unregistered point" in m]) == 1
    finally:
        Log.reset_callback(None)
        Log.reset_level(level)


def test_faults_known_points_cover_call_sites():
    # the documented table must include every point the continual
    # subsystem fires (a rename would silently orphan the spec)
    for point in ("ingest.read", "ingest.validate", "trainer.step",
                  "trainer.refit", "ckpt.save", "watcher.validate",
                  "watcher.canary"):
        assert point in _faults.KNOWN_POINTS


# ======================================================================
# numerical-health guard (satellite: one-shot engine.train too)
# ======================================================================
def _nan_label_train(fused_iters, boost_round=6):
    rng = np.random.RandomState(0)
    X = rng.randn(400, 6)
    y = X[:, 0] + 0.1 * rng.randn(400)
    y[::5] = np.nan
    rec = _telemetry.RunRecorder()
    d = lgb.Dataset(X, label=y, params={"verbose": -1})
    params = {"objective": "regression", "num_leaves": 7,
              "verbose": -1, "metric": "None",
              "fused_iters": fused_iters}
    with pytest.raises(NumericalHealthError) as ei:
        bst = lgb.Booster(params=params, train_set=d)
        bst._gbdt.attach_telemetry(rec)
        for _ in range(boost_round):
            bst.update()
    return ei.value, rec


def test_nonfinite_guard_sequential():
    err, rec = _nan_label_train(fused_iters=1)
    assert err.iteration == 0 and err.phase in ("tree", "pipelined")
    nf = [r for r in rec.records if r.get("type") == "continual"
          and r.get("event") == "nonfinite"]
    assert len(nf) == 1 and nf[0]["iter"] == 0


def test_nonfinite_guard_fused_rewinds_to_boundary():
    err, rec = _nan_label_train(fused_iters=4)
    assert err.phase in ("superstep", "tree", "pipelined")
    nf = [r for r in rec.records if r.get("event") == "nonfinite"]
    assert len(nf) == 1


def test_nonfinite_guard_fused_midstream_exact_rewind():
    # clean warmup, THEN labels go NaN (post-validation corruption):
    # the IN-SCAN guard must rewind the block exactly to the served
    # boundary (iter / dispatch bookkeeping / host RNG / model list)
    rng = np.random.RandomState(0)
    X = rng.randn(400, 6)
    y = X[:, 0] + 0.1 * rng.randn(400)
    d = lgb.Dataset(X, label=y, params={"verbose": -1})
    params = {"objective": "regression", "num_leaves": 7,
              "verbose": -1, "metric": "None", "fused_iters": 3}
    bst = lgb.Booster(params=params, train_set=d)
    for _ in range(4):
        bst.update()
    g = bst._gbdt
    g._fused_rewind()            # land exactly on a served boundary
    it0, tid0 = g.iter, g._trees_dispatched
    n_models = len(g.models)
    meta = d._constructed.metadata
    lbl = np.asarray(meta.label, np.float64).copy()
    lbl[:] = np.nan
    meta.set_label(lbl)
    g.objective.init(meta, g.num_data)
    g.objective._gradient_fn_jit = None   # drop the baked-in labels
    g._superstep_jit = None               # rebuild the fused scan
    with pytest.raises(NumericalHealthError) as ei:
        for _ in range(3):
            bst.update()
    assert ei.value.phase == "superstep"
    assert ei.value.iteration == it0
    assert g.iter == it0 and g._trees_dispatched == tid0
    assert len(g.models) == n_models


def test_engine_train_fails_loudly_on_nan(tmp_path):
    # the one-shot engine.train entry point (satellite 1)
    rng = np.random.RandomState(0)
    X = rng.randn(300, 5)
    y = X[:, 0].copy()
    y[10] = np.inf
    d = lgb.Dataset(X, label=y, params={"verbose": -1})
    with pytest.raises(NumericalHealthError):
        lgb.train({"objective": "regression", "num_leaves": 7,
                   "verbose": -1, "metric": "None"}, d,
                  num_boost_round=5)


# ======================================================================
# checkpoint manager: prune_after (the rewind primitive)
# ======================================================================
def test_prune_after(tmp_path):
    root = str(tmp_path / "ck")
    ingest = str(tmp_path / "ingest")
    for i in range(3):
        _write_batch(ingest, f"b{i}.npz", seed=i, rows=200)
    tr, stats = _run_trainer(tmp_path, continual_rounds_per_batch=2,
                             keep_last_n=4)
    mgr = CheckpointManager(root)
    iters = [i for i, _ in mgr.candidates()]
    assert iters == [2, 4, 6]
    pruned = mgr.prune_after(2)
    assert len(pruned) == 2
    assert [i for i, _ in mgr.candidates()] == [2]


# ======================================================================
# the daemon loop
# ======================================================================
def test_trainer_loop_telemetry_and_layout(tmp_path):
    ingest = str(tmp_path / "ingest")
    for i in range(3):
        _write_batch(ingest, f"batch_{i:03d}.npz", seed=i)
    tele = str(tmp_path / "t.jsonl")
    rec = _telemetry.RunRecorder(tele)
    tr, stats = _run_trainer(tmp_path, recorder=rec)
    rec.close(log=False)
    assert stats["batches"] == 3 and stats["quarantined"] == 0
    assert stats["status"] == "idle_exit"
    # batch files moved to processed; ckpts at every batch boundary
    src = tr.source
    assert len(os.listdir(src.processed_dir)) == 3
    assert tr._model_iter == 12
    # schema-clean telemetry with the batch stream + rollups
    n, errs = _telemetry.lint_file(tele)
    assert not errs, errs
    evs = _continual_events(tele)
    assert len(evs["batch"]) == 3
    end = _telemetry.read_records(tele)[-1]
    assert end["type"] == "run_end"
    assert end["summary"]["continual_batches"] == 3
    assert end["summary"]["continual_rows"] == 1200


def test_trainer_quarantines_nan_batch_at_validation(tmp_path):
    ingest = str(tmp_path / "ingest")
    _write_batch(ingest, "b0.npz", seed=0)
    _write_batch(ingest, "b1.npz", seed=1, nan_labels=True)
    _write_batch(ingest, "b2.npz", seed=2)
    rec = _telemetry.RunRecorder()
    tr, stats = _run_trainer(tmp_path, recorder=rec)
    assert stats["batches"] == 2 and stats["quarantined"] == 1
    q = [r for r in rec.records if r.get("event") == "quarantine"]
    assert q[0]["reason"] == "validate" and q[0]["batch"] == "b1.npz"
    assert os.path.exists(os.path.join(tr.source.quarantine_dir,
                                       "b1.npz"))


@pytest.mark.slow
def test_trainer_nonfinite_rewind_surviving_batch_parity(tmp_path):
    # validator off -> the NaN batch reaches training; the guard must
    # rewind so the final model EQUALS a run over the surviving
    # batches only (acceptance criterion)
    surv = tmp_path / "surv"
    for td, idxs, nan in ((tmp_path, (0, 1, 2), 1),
                          (surv, (0, 2), None)):
        ingest = str(td / "ingest")
        for i in idxs:
            _write_batch(ingest, f"batch_{i:03d}.npz", seed=100 + i,
                         nan_labels=(i == nan))
    tr, stats = _run_trainer(tmp_path, continual_nonfinite_check=False,
                             fused_iters=3)
    assert stats["nonfinite_rewinds"] == 1 and stats["quarantined"] == 1
    tr_s, _ = _run_trainer(surv, continual_nonfinite_check=False,
                           fused_iters=3)
    assert tr._model_text == tr_s._model_text
    assert tr._model_iter == tr_s._model_iter == 8


def _warm_compile_cache(rows=250, n_feat=6):
    """Train one throwaway booster at the test shape so the stall
    watchdog's clock never races the first-iteration XLA compile."""
    rng = np.random.RandomState(99)
    X = rng.randn(rows, n_feat)
    d = lgb.Dataset(X, label=X[:, 0], params={"verbose": -1})
    lgb.train({"objective": "regression", "num_leaves": 7,
               "verbose": -1, "metric": "None"}, d, num_boost_round=2)


def test_trainer_stall_watchdog_restarts_from_snapshot(tmp_path):
    ingest = str(tmp_path / "ingest")
    for i in range(2):
        _write_batch(ingest, f"b{i}.npz", seed=i, rows=250)
    _warm_compile_cache()
    _faults.configure("trainer.step:hang@2")
    rec = _telemetry.RunRecorder()
    tr, stats = _run_trainer(tmp_path, recorder=rec,
                             continual_stall_timeout_s=2.0)
    assert stats["stall_restarts"] == 1
    assert stats["batches"] == 2 and stats["quarantined"] == 0
    sr = [r for r in rec.records if r.get("event") == "stall_restart"]
    assert len(sr) == 1 and sr[0]["attempt"] == 1


def test_trainer_persistent_stall_quarantines(tmp_path):
    ingest = str(tmp_path / "ingest")
    _write_batch(ingest, "b0.npz", seed=0, rows=250)
    _write_batch(ingest, "b1.npz", seed=1, rows=250)
    # every step from the 2nd hit on hangs: b0 stalls past its
    # retry budget -> quarantined; b1's first step hangs too (the
    # watchdog's first-iteration compile grace applies there)
    _warm_compile_cache()
    _faults.configure("trainer.step:hang@2+")
    tr, stats = _run_trainer(tmp_path, continual_stall_timeout_s=0.8,
                             continual_max_batch_retries=0)
    # spec fires every hit, so b1 would hang too: clear after b0 is
    # quarantined via the 2 armed attempts + b1's first step
    assert stats["quarantined"] >= 1
    assert os.path.exists(os.path.join(tr.source.quarantine_dir,
                                       "b0.npz"))


def test_trainer_step_error_exhausts_retries_and_reverts(tmp_path):
    ingest = str(tmp_path / "ingest")
    _write_batch(ingest, "b0.npz", seed=0)
    _write_batch(ingest, "b1.npz", seed=1)
    # every step of b1 errors (b0's 4 iterations burn hits 1-4...):
    # arm from the 5th hit on, so b0 trains clean and b1 always fails
    _faults.configure("trainer.step:error@5+")
    rec = _telemetry.RunRecorder()
    tr, stats = _run_trainer(tmp_path, recorder=rec,
                             continual_max_batch_retries=1)
    assert stats["batches"] == 1
    assert stats["quarantined"] == 1
    q = [r for r in rec.records if r.get("event") == "quarantine"]
    assert q and q[-1]["reason"] == "error"
    # the model reverted to the pre-batch boundary
    assert tr._model_iter == 4


@pytest.mark.slow
def test_trainer_preempt_drain_and_bitexact_resume(tmp_path):
    oracle_dir = tmp_path / "oracle"
    for td in (tmp_path, oracle_dir):
        ingest = str(td / "ingest")
        for i in range(3):
            _write_batch(ingest, f"batch_{i:03d}.npz", seed=i)
    tr_o, _ = _run_trainer(oracle_dir,
                           continual_rounds_per_batch=6,
                           fused_iters=3)
    # slow the steps so the preempt lands mid-batch deterministically
    _faults.configure("trainer.step:sleep_120@*")
    tr = ContinualTrainer(_params(tmp_path,
                                  continual_rounds_per_batch=6,
                                  fused_iters=3))

    def trigger():
        while tr.stats["batches"] < 1:
            time.sleep(0.02)
        time.sleep(0.2)
        engine_mod.request_preempt()
    th = threading.Thread(target=trigger)
    th.start()
    stats = tr.run()
    th.join()
    _faults.configure("")
    assert stats["status"] == "preempt"
    assert 0 < tr._model_iter < 18
    engine_mod.clear_preempt()
    # restart: bootstrap from ledger + newest snapshot, finish the
    # interrupted batch bit-exactly, then the rest
    tr2, stats2 = _run_trainer(tmp_path, continual_rounds_per_batch=6,
                               fused_iters=3)
    assert tr2._model_iter == tr_o._model_iter == 18
    assert tr2._model_text == tr_o._model_text


def test_trainer_refit_updates_and_watcher_republishes(tmp_path):
    from lightgbm_tpu.serve import (CheckpointWatcher, RegistryTarget,
                                    ServeConfig, Server)
    from lightgbm_tpu.serve.config import FleetConfig
    from lightgbm_tpu.serve.watcher import CanarySet
    ingest = str(tmp_path / "ingest")
    _write_batch(ingest, "b0.npz", seed=0)
    _write_batch(ingest, "b1.npz", seed=1)
    tr, stats = _run_trainer(tmp_path)
    assert stats["batches"] == 2
    server = Server(config=ServeConfig(warmup=False)).start()
    try:
        canary = CanarySet(np.random.RandomState(9).randn(16, 6))
        w = CheckpointWatcher(str(tmp_path / "ck"),
                              RegistryTarget(server),
                              config=FleetConfig(), canary=canary)
        w.poll_once()
        v1 = server.registry.current()
        assert v1 is not None
        # a refit batch re-saves the SAME boundary; the watcher picks
        # up the fingerprint change through the full gate
        _write_batch(ingest, "b2.npz", seed=2)
        tr2, stats2 = _run_trainer(tmp_path, continual_refit_every=1)
        assert stats2["refits"] == 1
        assert tr2._model_iter == tr._model_iter  # no new trees
        w._watchdog = None      # release the observation hold
        w.poll_once()
        v2 = server.registry.current()
        assert v2.model_id != v1.model_id
    finally:
        server.stop()


def test_trainer_ledger_tracks_state(tmp_path):
    ingest = str(tmp_path / "ingest")
    _write_batch(ingest, "b0.npz", seed=0)
    tr, stats = _run_trainer(tmp_path)
    with open(os.path.join(str(tmp_path / "ck"),
                           "continual_state.json")) as f:
        ledger = json.load(f)
    assert ledger["batches_done"] == 1
    assert ledger["inflight"] is None
    assert ledger["model_iter"] == 4
    assert ledger["validator"]["n"] == 400


# ======================================================================
# scenario matrix through the full loop (slow lane)
# ======================================================================
def _scenario_loop(tmp_path, params_extra, objective="regression",
                   with_group=False, refit_every=0):
    from lightgbm_tpu.serve import (CheckpointWatcher, RegistryTarget,
                                    ServeConfig, Server)
    from lightgbm_tpu.serve.config import FleetConfig
    from lightgbm_tpu.serve.watcher import CanarySet
    ingest = str(tmp_path / "ingest")
    rows = 360
    for i in range(3):
        group = None
        if with_group:
            group = np.asarray([30] * (rows // 30))
        _write_batch(ingest, f"batch_{i:03d}.npz", seed=40 + i,
                     rows=rows, objective=objective, group=group)
    tele = str(tmp_path / "t.jsonl")
    rec = _telemetry.RunRecorder(tele)
    extra = dict(params_extra)
    extra["continual_rounds_per_batch"] = 3
    if refit_every:
        extra["continual_refit_every"] = refit_every
    tr, stats = _run_trainer(tmp_path, recorder=rec, **extra)
    rec.close(log=False)
    assert stats["batches"] == 3, stats
    assert stats["quarantined"] == 0, stats
    n, errs = _telemetry.lint_file(tele)
    assert not errs, errs
    server = Server(config=ServeConfig(warmup=False)).start()
    try:
        X_canary = np.random.RandomState(7).randn(24, 6)
        w = CheckpointWatcher(str(tmp_path / "ck"),
                              RegistryTarget(server),
                              config=FleetConfig(),
                              canary=CanarySet(X_canary))
        w.poll_once()
        ver = server.registry.current()
        assert ver is not None, "no version published"
        preds = server.predict(X_canary)
        assert np.all(np.isfinite(np.asarray(preds, np.float64)))
    finally:
        server.stop()
    return tr, stats


@pytest.mark.slow
def test_scenario_lambdarank_with_query_groups(tmp_path):
    tr, _ = _scenario_loop(
        tmp_path,
        {"objective": "lambdarank", "num_leaves": 7},
        objective="rank", with_group=True)
    assert tr._model_iter == 9


@pytest.mark.slow
def test_scenario_dart(tmp_path):
    tr, _ = _scenario_loop(
        tmp_path,
        {"objective": "binary", "boosting": "dart", "num_leaves": 7,
         "drop_rate": 0.5, "drop_seed": 11},
        objective="binary")
    assert tr._model_iter == 9


@pytest.mark.slow
def test_scenario_monotone_constraints(tmp_path):
    tr, _ = _scenario_loop(
        tmp_path,
        {"objective": "regression", "num_leaves": 7,
         "monotone_constraints": [1, -1, 0, 0, 0, 0]},
        refit_every=3)
    # 2 extend batches + 1 refit batch
    assert tr._model_iter == 6 and tr.stats["refits"] == 1
    # the published model honors the constraints it trained under
    bst = lgb.Booster(model_str=tr._model_text)
    rng = np.random.RandomState(3)
    base = rng.randn(50, 6)
    lo, hi = base.copy(), base.copy()
    lo[:, 0] -= 1.0
    hi[:, 0] += 1.0
    assert np.all(bst.predict(hi) >= bst.predict(lo) - 1e-9)


@pytest.mark.slow
def test_scenario_quantized_training(tmp_path):
    tr, _ = _scenario_loop(
        tmp_path,
        {"objective": "binary", "num_leaves": 7,
         "use_quantized_grad": True, "fused_iters": 3},
        objective="binary")
    assert tr._model_iter == 9


@pytest.mark.slow
def test_cli_task_continual_roundtrip(tmp_path):
    import subprocess
    import sys
    ingest = str(tmp_path / "ingest")
    for i in range(2):
        _write_batch(ingest, f"batch_{i:03d}.npz", seed=i)
    tele = str(tmp_path / "t.jsonl")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu", "task=continual",
         "objective=regression", "num_leaves=7", "verbose=-1",
         "metric=None", f"checkpoint_dir={tmp_path / 'ck'}",
         f"continual_ingest_dir={ingest}",
         "continual_rounds_per_batch=3",
         "continual_idle_exit_s=0.5", "continual_poll_s=0.1",
         f"telemetry_file={tele}"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    mgr = CheckpointManager(str(tmp_path / "ck"))
    assert [i for i, _ in mgr.candidates()] == [3, 6]
    n, errs = _telemetry.lint_file(tele)
    assert not errs, errs
    evs = _continual_events(tele)
    assert len(evs["batch"]) == 2
