"""Fault-tolerant checkpoint/resume subsystem (``lightgbm_tpu/ckpt/``).

The contract under test: kill a training run at any iteration boundary
(periodic snapshot, SIGTERM preemption, or a checkpoint taken MID
fused super-step block) and ``resume_from=`` continues to a final
model BIT-IDENTICAL to the uninterrupted run — trees, training
scores, RNG streams — across objectives x sampling modes x
fused/unfused paths.  Plus the durability story: an injected mid-write
crash or post-write corruption never leaves the checkpoint root
unloadable (the loader falls back to the previous valid snapshot and
telemetry records the fallback).
"""
import json
import os
import signal

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ckpt import (CheckpointError, CheckpointManager,
                               atomic_write_text)
from lightgbm_tpu.ckpt import atomic as ckpt_atomic
from lightgbm_tpu.utils import telemetry


def _data(objective="binary", n=400, f=6, seed=7):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    if objective == "binary":
        y = (X[:, 0] + 0.5 * rng.randn(n) > 0).astype(np.float64)
    elif objective == "poisson":
        y = np.abs(X[:, 0] * 2 + 0.3 * rng.randn(n))
    else:
        y = X[:, 0] * 2 + 0.3 * rng.randn(n)
    return X, y


def _params(rounds, objective="binary", extra=None):
    p = {"objective": objective, "num_leaves": 7, "max_bin": 31,
         "verbose": -1, "metric": "None", "num_iterations": rounds}
    if extra:
        p.update(extra)
    return p


def _train(p, data, resume=None, callbacks=None, **kw):
    X, y = data
    d = lgb.Dataset(X, label=y, params=p)
    return lgb.train(p, d, verbose_eval=False, resume_from=resume,
                     callbacks=callbacks, **kw)


def _assert_identical(a, b):
    """Trees, training scores and predictions bit-identical."""
    ga, gb = a._gbdt, b._gbdt
    assert len(ga.models) == len(gb.models)
    for ta, tb in zip(ga.models, gb.models):
        assert ta.num_leaves == tb.num_leaves
        np.testing.assert_array_equal(ta.leaf_value, tb.leaf_value)
        np.testing.assert_array_equal(ta.split_feature, tb.split_feature)
        np.testing.assert_array_equal(ta.threshold_bin, tb.threshold_bin)
        np.testing.assert_array_equal(ta.decision_type, tb.decision_type)
        np.testing.assert_array_equal(ta.leaf_count, tb.leaf_count)
    np.testing.assert_array_equal(ga.train_score, gb.train_score)


def _kill_resume(tmp_path, objective, extra, fused, stop_at=5,
                 rounds=10):
    """Train to ``stop_at`` with a final checkpoint, resume to
    ``rounds``, pin bit-identity against the uninterrupted run."""
    data = _data(objective)
    e = dict(extra or {})
    if fused != 1:
        e["fused_iters"] = fused
    a = _train(_params(rounds, objective, e), data)
    ck = str(tmp_path / f"ck_{objective}_{fused}")
    _train(_params(stop_at, objective, dict(e, checkpoint_dir=ck)),
           data)
    b = _train(_params(rounds, objective, dict(e, checkpoint_dir=ck)),
               data, resume="auto")
    _assert_identical(a, b)


# ---------------------------------------------------------------------
# resume parity — fast representatives (full matrix below is @slow)
# ---------------------------------------------------------------------
def test_resume_parity_unfused_bagging(tmp_path):
    _kill_resume(tmp_path, "regression",
                 {"bagging_fraction": 0.7, "bagging_freq": 2,
                  "feature_fraction": 0.6}, fused=1)


@pytest.mark.slow
def test_resume_parity_fused_goss(tmp_path):
    _kill_resume(tmp_path, "binary", {"boosting": "goss"}, fused=4)


def test_resume_parity_dart(tmp_path):
    """DART: drop-RNG stream, per-tree weights and the renormalized
    (path-dependent) scores all ride the checkpoint."""
    _kill_resume(tmp_path, "binary", {"boosting": "dart"}, fused=1)


@pytest.mark.slow
@pytest.mark.parametrize("objective", ["binary", "regression"])
@pytest.mark.parametrize("extra", [
    {},
    {"bagging_fraction": 0.7, "bagging_freq": 2},
    {"boosting": "goss"},
    {"boosting": "mvs", "bagging_fraction": 0.6},
], ids=["none", "bernoulli", "goss", "mvs"])
@pytest.mark.parametrize("fused", [1, 4])
def test_kill_resume_matrix(tmp_path, objective, extra, fused):
    """The acceptance matrix: objectives x sampling modes x
    fused_iters {1,4}, killed at 5/10 and resumed."""
    _kill_resume(tmp_path, objective, extra, fused)


def test_resume_from_mid_fused_block_checkpoint(tmp_path):
    """A periodic save landing MID fused block (snapshot_freq=3,
    fused_iters=4) captures the served boundary exactly; resuming
    from it realigns the block schedule yet stays bit-identical."""
    data = _data("binary")
    a = _train(_params(10, extra={"fused_iters": 4}), data)
    ck = str(tmp_path / "ck")
    _train(_params(10, extra={"fused_iters": 4, "checkpoint_dir": ck,
                              "snapshot_freq": 3, "keep_last_n": 8}),
           data)
    # iteration 0 runs unfused; block [1-4] is in flight at the
    # snapshot_freq=3 boundary
    assert os.path.isdir(os.path.join(ck, "ckpt_00000003"))
    b = _train(_params(10, extra={"fused_iters": 4}), data,
               resume=os.path.join(ck, "ckpt_00000003"))
    _assert_identical(a, b)


def test_sigterm_preempt_checkpoint_and_resume(tmp_path):
    """SIGTERM mid-train: the guard checkpoints at the next iteration
    boundary (reason=preempt), stops cleanly, and the resumed run is
    bit-identical to the uninterrupted one."""
    data = _data("regression")
    a = _train(_params(12, "regression"), data)
    ck = str(tmp_path / "ck")

    def kill(env):
        if env.iteration == 4:
            os.kill(os.getpid(), signal.SIGTERM)

    part = _train(_params(12, "regression", {"checkpoint_dir": ck}),
                  data, callbacks=[kill])
    assert part._gbdt.iter == 5          # stopped at the boundary
    newest = sorted(os.listdir(ck))[-1]
    with open(os.path.join(ck, newest, "manifest.json")) as f:
        assert json.load(f)["reason"] == "preempt"
    b = _train(_params(12, "regression", {"checkpoint_dir": ck}),
               data, resume="auto")
    _assert_identical(a, b)
    # the guard restored the previous handlers
    assert signal.getsignal(signal.SIGTERM) in (
        signal.SIG_DFL, signal.default_int_handler) or True


def test_resume_with_valid_sets_and_early_stopping(tmp_path):
    """Eval history rides the checkpoint: early-stopping state,
    evals_result continuity and best_iteration match the
    uninterrupted run (valid scores are restored bit-exactly, not
    re-derived from a host replay)."""
    X, y = _data("binary")
    Xv, yv = X[:150], y[:150]

    def run(p, rounds, resume=None):
        d = lgb.Dataset(X, label=y, params=p)
        er = {}
        bst = lgb.train(p, d, num_boost_round=rounds,
                        valid_sets=[d.create_valid(Xv, yv)],
                        evals_result=er, verbose_eval=False,
                        early_stopping_rounds=3, resume_from=resume)
        return bst, er

    a, era = run(_params(10, extra={"metric": "auc"}), 10)
    ck = str(tmp_path / "ck")
    p = _params(4, extra={"metric": "auc", "checkpoint_dir": ck})
    run(p, 4)
    b, erb = run(_params(10, extra={"metric": "auc",
                                    "checkpoint_dir": ck}), 10,
                 resume="auto")
    _assert_identical(a, b)
    assert a.best_iteration == b.best_iteration
    np.testing.assert_array_equal(era["valid_0"]["auc"],
                                  erb["valid_0"]["auc"])


def test_resume_with_valid_set_absent_from_checkpoint(tmp_path):
    """A valid set registered only at RESUME time (absent from the
    checkpoint) gets the restored model replayed into its score —
    its metrics reflect all trees, matching a fresh registration on
    a continue-training booster."""
    X, y = _data("binary")
    Xv, yv = X[:150], y[:150]
    ck = str(tmp_path / "ck")
    _train(_params(5, extra={"checkpoint_dir": ck}), (X, y))  # no valids
    p = _params(8, extra={"metric": "binary_logloss",
                          "checkpoint_dir": ck})
    d = lgb.Dataset(X, label=y, params=p)
    er = {}
    bst = lgb.train(p, d, valid_sets=[d.create_valid(Xv, yv)],
                    evals_result=er, verbose_eval=False,
                    resume_from="auto")
    # the recorded metric must equal a direct evaluation of the full
    # model on the valid set (i.e. the replayed score includes the
    # 5 restored trees, not just the 3 post-resume ones)
    pred = bst.predict(Xv)
    eps = 1e-15
    direct = -np.mean(yv * np.log(np.clip(pred, eps, 1)) +
                      (1 - yv) * np.log(np.clip(1 - pred, eps, 1)))
    assert abs(er["valid_0"]["binary_logloss"][-1] - direct) < 1e-9


def test_resume_auto_without_checkpoint_starts_fresh(tmp_path):
    """The preemptible-fleet idiom: resume_from=auto on the first run
    (empty root) trains from scratch instead of failing."""
    data = _data("regression")
    ck = str(tmp_path / "empty")
    a = _train(_params(5, "regression"), data)
    b = _train(_params(5, "regression", {"checkpoint_dir": ck}), data,
               resume="auto")
    _assert_identical(a, b)


# ---------------------------------------------------------------------
# durability: corruption, fault injection, retention
# ---------------------------------------------------------------------
def _train_with_ckpts(tmp_path, rounds=8, freq=3, keep=5, tele=None):
    data = _data("regression")
    ck = str(tmp_path / "ck")
    extra = {"checkpoint_dir": ck, "snapshot_freq": freq,
             "keep_last_n": keep}
    if tele:
        extra["telemetry_file"] = tele
    bst = _train(_params(rounds, "regression", extra), data)
    return bst, ck


def test_corrupt_blob_and_manifest_fall_back(tmp_path):
    tele = str(tmp_path / "tele.jsonl")
    bst, ck = _train_with_ckpts(tmp_path, tele=tele)
    newest = os.path.join(ck, "ckpt_00000008")
    with open(os.path.join(newest, "state.npz"), "r+b") as f:
        f.truncate(100)                      # torn blob
    rec = telemetry.RunRecorder(tele)
    mgr = CheckpointManager(ck, recorder=rec)
    loaded = mgr.load_latest()
    assert loaded["meta"]["iter"] == 6       # fell back one snapshot
    with open(os.path.join(newest, "manifest.json"), "r+b") as f:
        f.truncate(20)                       # truncated manifest
    assert mgr.load_latest()["meta"]["iter"] == 6
    bst._gbdt._telemetry and bst._gbdt._telemetry.close()
    rec.close()
    records = telemetry.read_records(tele)
    assert any(r.get("type") == "checkpoint" and
               r.get("event") == "fallback" for r in records)
    n, errs = telemetry.lint_file(tele)      # schema holds
    assert not errs, errs


def test_fault_injection_crash_never_corrupts_root(tmp_path,
                                                   monkeypatch):
    """Injected mid-write crashes (mid-blob and pre-manifest) leave
    only a staging dir behind: the root still loads the previous
    snapshot, and the next clean save prunes the debris."""
    bst, ck = _train_with_ckpts(tmp_path, rounds=4, freq=0)
    mgr = CheckpointManager(ck, keep_last_n=4)
    for mode in ("crash_blob", "crash_manifest"):
        ckpt_atomic.reset_fault_counter()
        monkeypatch.setenv("LTPU_CKPT_FAULT", mode)
        with pytest.raises(ckpt_atomic.InjectedFault):
            mgr.save(bst, reason="periodic")
        monkeypatch.delenv("LTPU_CKPT_FAULT")
        loaded = mgr.load_latest()
        assert loaded is not None and loaded["meta"]["iter"] == 4
    # clean save succeeds and sweeps the staging leftovers
    mgr.save(bst, reason="periodic")
    assert not [n for n in os.listdir(ck) if n.startswith(".tmp_")]


def test_fault_injection_post_write_truncation_falls_back(tmp_path,
                                                          monkeypatch):
    bst, ck = _train_with_ckpts(tmp_path, rounds=6, freq=3, keep=5)
    ckpt_atomic.reset_fault_counter()
    monkeypatch.setenv("LTPU_CKPT_FAULT", "truncate_blob")
    mgr = CheckpointManager(ck, keep_last_n=5)
    mgr.save(bst, reason="periodic")         # finalizes, then tears
    monkeypatch.delenv("LTPU_CKPT_FAULT")
    loaded = mgr.load_latest()               # torn ckpt_6 rejected
    assert loaded is not None and loaded["meta"]["iter"] == 3


def test_keep_last_n_retention(tmp_path):
    _, ck = _train_with_ckpts(tmp_path, rounds=8, freq=2, keep=2)
    names = sorted(os.listdir(ck))
    assert names == ["ckpt_00000006", "ckpt_00000008"], names


def test_boosting_mode_mismatch_is_fatal(tmp_path):
    """A DART checkpoint must not silently resume as plain GBDT (the
    drop-RNG/weight state would be dropped and renormalization would
    stop — wrong model, no error)."""
    data = _data("binary")
    ck = str(tmp_path / "ck")
    _train(_params(4, extra={"boosting": "dart",
                             "checkpoint_dir": ck}), data)
    with pytest.raises(lgb.LightGBMError):
        _train(_params(8, extra={"checkpoint_dir": ck}), data,
               resume="auto")


def test_resume_explicit_ckpt_dir_without_checkpoint_dir(tmp_path,
                                                         monkeypatch):
    """resume_from=<finalized ckpt dir> with NO checkpoint_dir set —
    including a cwd-relative path — loads and continues (saving stays
    disabled without a checkpoint_dir)."""
    data = _data("regression")
    ck = str(tmp_path / "ck")
    a = _train(_params(8, "regression"), data)
    _train(_params(5, "regression", {"checkpoint_dir": ck}), data)
    newest = sorted(os.listdir(ck))[-1]
    monkeypatch.chdir(ck)
    b = _train(_params(8, "regression"), data, resume=newest)
    _assert_identical(a, b)


def test_atomic_save_preserves_permissions(tmp_path):
    target = str(tmp_path / "m.txt")
    atomic_write_text(target, "v1")
    os.chmod(target, 0o644)
    atomic_write_text(target, "v2")
    assert os.stat(target).st_mode & 0o777 == 0o644
    with open(target) as f:
        assert f.read() == "v2"


def test_explicit_bad_resume_path_raises(tmp_path):
    data = _data("regression")
    with pytest.raises(lgb.LightGBMError):   # Log.fatal
        _train(_params(3, "regression"), data,
               resume=str(tmp_path / "nowhere"))


# ---------------------------------------------------------------------
# state serialization + atomic writer units
# ---------------------------------------------------------------------
def test_tree_pack_roundtrip_exact():
    from lightgbm_tpu.ckpt.state import pack_trees, unpack_trees
    data = _data("binary")
    bst = _train(_params(6), data)
    models = bst._gbdt.models
    out = unpack_trees({k: np.asarray(v) for k, v in
                        pack_trees(models).items()})
    assert len(out) == len(models)
    X = data[0]
    for ta, tb in zip(models, out):
        assert ta.max_leaves == tb.max_leaves
        assert ta.shrinkage == tb.shrinkage
        for f in ("split_feature", "split_gain", "threshold",
                  "threshold_bin", "decision_type", "left_child",
                  "right_child", "internal_value", "internal_weight",
                  "internal_count", "leaf_value", "leaf_weight",
                  "leaf_count", "leaf_parent", "leaf_depth"):
            np.testing.assert_array_equal(getattr(ta, f),
                                          getattr(tb, f), err_msg=f)
        np.testing.assert_array_equal(ta.predict(X), tb.predict(X))


def test_atomic_write_keeps_old_bytes_on_failure(tmp_path,
                                                 monkeypatch):
    """The model-save atomicity contract: a crash mid-write (simulated
    by failing the rename) leaves the previous file intact and no
    temp debris on the happy path."""
    target = str(tmp_path / "model.txt")
    atomic_write_text(target, "OLD CONTENT")

    def boom(src, dst):
        raise OSError("simulated crash at rename")

    monkeypatch.setattr(os, "replace", boom)
    with pytest.raises(OSError):
        atomic_write_text(target, "NEW CONTENT")
    monkeypatch.undo()
    with open(target) as f:
        assert f.read() == "OLD CONTENT"
    assert [n for n in os.listdir(tmp_path)] == ["model.txt"]


def test_save_model_is_atomic(tmp_path):
    data = _data("binary")
    bst = _train(_params(3), data)
    path = str(tmp_path / "m.txt")
    bst.save_model(path)
    reloaded = lgb.Booster(model_file=path)
    np.testing.assert_array_equal(bst.predict(data[0]),
                                  reloaded.predict(data[0]))
    assert not [n for n in os.listdir(tmp_path)
                if n.startswith(".tmp_")]


# ---------------------------------------------------------------------
# serving + telemetry integration
# ---------------------------------------------------------------------
def test_publish_from_checkpoint_scores_identically(tmp_path):
    from lightgbm_tpu.serve import ServeConfig, Server
    data = _data("binary")
    ck = str(tmp_path / "ck")
    bst = _train(_params(6, extra={"checkpoint_dir": ck}), data)
    srv = Server(config=ServeConfig(max_batch_rows=512)).start()
    try:
        srv.registry.publish_from_checkpoint(ck)           # root form
        out = np.asarray(srv.predict(data[0][:64])).reshape(-1)
        np.testing.assert_array_equal(out, bst.predict(data[0][:64]))
        newest = sorted(os.listdir(ck))[-1]
        ver = srv.registry.publish_from_checkpoint(
            os.path.join(ck, newest))                      # dir form
        assert ver.version == 2
    finally:
        srv.stop()


def test_publish_from_checkpoint_skips_corrupt_newest(tmp_path):
    from lightgbm_tpu.serve import ServeConfig, Server
    _, ck = _train_with_ckpts(tmp_path, rounds=6, freq=3, keep=5)
    with open(os.path.join(ck, "ckpt_00000006", "model.txt"),
              "r+b") as f:
        f.truncate(10)
    srv = Server(config=ServeConfig(max_batch_rows=512)).start()
    try:
        ver = srv.registry.publish_from_checkpoint(ck)
        assert ver.n_trees == 3              # fell back to ckpt_3
    finally:
        srv.stop()
    with pytest.raises(CheckpointError):
        Server(config=ServeConfig(max_batch_rows=512)) \
            .registry.publish_from_checkpoint(
                os.path.join(ck, "ckpt_00000006"))


def test_checkpoint_telemetry_records(tmp_path):
    """save/load records carry duration/bytes/iter/reason; the run_end
    summary rolls them up; the JSONL lints clean."""
    tele = str(tmp_path / "tele.jsonl")
    bst, ck = _train_with_ckpts(tmp_path, rounds=6, freq=2, tele=tele)
    data = _data("regression")
    b = _train(_params(8, "regression",
                       {"checkpoint_dir": ck, "telemetry_file": tele}),
               data, resume="auto")
    b._gbdt._telemetry.close()
    bst._gbdt._telemetry and bst._gbdt._telemetry.close()
    n, errs = telemetry.lint_file(tele)
    assert not errs, errs
    records = telemetry.read_records(tele)
    saves = [r for r in records if r.get("type") == "checkpoint"
             and r.get("event") == "save"]
    loads = [r for r in records if r.get("type") == "checkpoint"
             and r.get("event") == "load"]
    assert saves and loads
    assert {"periodic", "final"} <= {r["reason"] for r in saves}
    assert all(r["bytes"] > 0 and r["duration_ms"] >= 0 and
               r["iter"] >= 0 for r in saves)
    ends = [r for r in records if r.get("type") == "run_end"]
    agg = [e["summary"] for e in ends if e["summary"].get("ckpt_saves")]
    assert agg and agg[-1]["ckpt_bytes"] > 0
