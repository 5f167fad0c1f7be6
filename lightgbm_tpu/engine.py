"""Training/CV entry points (reference ``python-package/lightgbm/engine.py``):
``train()`` with callbacks / early stopping / evals_result / learning-rate
schedules / init_model continue-training, and ``cv()`` with stratified and
group-aware folds + ``CVBooster``."""
from __future__ import annotations

import collections
import copy
import os
import signal
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import numpy as np

from . import callback as callback_mod
from .basic import Booster, Dataset
from .callback import CallbackEnv, EarlyStopException
from .metrics import default_metric_for
from .utils.log import Log

__all__ = ["train", "cv", "CVBooster", "sweep", "SweepResult",
           "request_preempt", "preempt_requested", "clear_preempt",
           "install_preempt_guard"]


# ----------------------------------------------------------------------
# process-wide preemption flag
# ----------------------------------------------------------------------
# Signal handlers are main-thread-only, but the continual daemon
# (lightgbm_tpu/cont/) trains on worker threads: whichever guard DID
# install handlers (the CLI entry point, a test via request_preempt)
# raises this shared flag, and every training loop — whatever thread it
# runs on — observes it at the next served iteration boundary and
# checkpoints-and-drains.
_PREEMPT_LOCK = threading.Lock()
_PREEMPT_SIGNUM: Optional[int] = None


def request_preempt(signum: int = signal.SIGTERM) -> None:
    """Raise the process-wide preemption flag (thread-safe): every
    in-flight ``train`` loop with a checkpoint manager saves a
    ``reason=preempt`` snapshot at its next iteration boundary and
    stops, exactly as if the process had received SIGTERM."""
    global _PREEMPT_SIGNUM
    with _PREEMPT_LOCK:
        if _PREEMPT_SIGNUM is None:
            _PREEMPT_SIGNUM = int(signum)


def preempt_requested() -> Optional[int]:
    """The pending preemption signal number, or None."""
    with _PREEMPT_LOCK:
        return _PREEMPT_SIGNUM


def clear_preempt() -> None:
    global _PREEMPT_SIGNUM
    with _PREEMPT_LOCK:
        _PREEMPT_SIGNUM = None


class _PreemptGuard:
    """SIGTERM/SIGINT -> graceful checkpoint-at-the-next-boundary.

    The first signal only sets a flag — the training loop observes it
    after the in-flight iteration completes, takes a best-effort
    checkpoint (``reason=preempt``) and stops.  A second signal
    restores the original handlers and re-raises, so a stuck save can
    still be force-killed.  Signal handlers are process-global state:
    the guard installs only on the main thread and always restores.
    The flag itself is shared process-wide (``request_preempt``), so a
    training loop running on a WORKER thread — the continual daemon's
    normal mode — still drains when the main thread's guard catches
    the signal."""

    def __init__(self):
        self.signum: Optional[int] = None
        self._orig: Dict[int, Any] = {}

    def install(self) -> "_PreemptGuard":
        if threading.current_thread() is not threading.main_thread():
            return self
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                self._orig[sig] = signal.signal(sig, self._handle)
            except (ValueError, OSError):  # pragma: no cover
                pass
        return self

    def _handle(self, signum, frame):
        if self.signum is not None:
            self.restore()
            signal.raise_signal(signum)
            return
        self.signum = signum
        request_preempt(signum)
        Log.warning("received signal %d: checkpointing at the next "
                    "iteration boundary, then stopping", signum)

    def pending(self) -> Optional[int]:
        """This guard's caught signal, or the process-wide flag."""
        return self.signum if self.signum is not None \
            else preempt_requested()

    def restore(self) -> None:
        for sig, handler in self._orig.items():
            try:
                signal.signal(sig, handler)
            except (ValueError, OSError):  # pragma: no cover
                pass
        self._orig = {}
        if self.signum is not None:
            # this guard's own catch raised the shared flag; clearing
            # it on restore keeps a LATER train() in the same process
            # (the signal was handled, work continued) from stopping
            # on a stale preempt
            clear_preempt()
            self.signum = None


def install_preempt_guard() -> _PreemptGuard:
    """Install SIGTERM/SIGINT handlers feeding the shared preemption
    flag (main thread only; a no-op guard elsewhere).  The continual
    daemon's CLI entry point owns one for the whole loop; callers must
    ``restore()`` it."""
    return _PreemptGuard().install()


def _replay_eval_history(eval_history, cbs_after, booster, params,
                         num_boost_round):
    """Rebuild stateful callback state (early stopping best-rounds,
    ``record_evaluation`` dicts) by replaying the checkpointed eval
    stream.  Only the framework's own stateful callbacks are replayed
    — user callbacks with external side effects must not fire twice.
    Returns True when the replay raised an early stop (the resumed
    run is already complete)."""
    replayable = (callback_mod._EarlyStopping,
                  callback_mod._RecordEvaluation)
    for it, results in eval_history:
        ev = [(d, m, float(v), bool(h)) for d, m, v, h in results]
        try:
            for cb in cbs_after:
                if isinstance(cb, replayable):
                    cb(CallbackEnv(booster, params, int(it), 0,
                                   num_boost_round, ev))
        except EarlyStopException as e:
            booster.best_iteration = e.best_iteration + 1
            for item in e.best_score:
                booster.best_score.setdefault(
                    item[0], {})[item[1]] = item[2]
            return True
    return False


def train(params: Dict[str, Any], train_set: Dataset,
          num_boost_round: int = 100,
          valid_sets: Optional[Sequence[Dataset]] = None,
          valid_names: Optional[Sequence[str]] = None,
          fobj: Optional[Callable] = None, feval: Optional[Callable] = None,
          init_model: Optional[Union[str, Booster]] = None,
          feature_name="auto", categorical_feature="auto",
          early_stopping_rounds: Optional[int] = None,
          evals_result: Optional[Dict] = None,
          verbose_eval: Union[bool, int] = True,
          learning_rates=None, keep_training_booster: bool = True,
          callbacks: Optional[List[Callable]] = None, mesh=None,
          resume_from: Optional[str] = None) -> Booster:
    """Train a booster (``engine.py:19`` in the reference).

    ``mesh``: an explicit 1-D ``jax.sharding.Mesh`` for the parallel
    tree learners (``tree_learner=data|feature|voting``); without it
    the learner shards over all global devices, capped by
    ``num_machines``.  Sharded training runs as ONE compiled SPMD
    program — with ``fused_iters>1`` the whole K-iteration block rides
    a single ``shard_map``-wrapped ``lax.scan`` — see
    ``docs/Distributed.md``.  With ``elastic_training=true`` that
    program is supervised for shard loss: a failed or hung shard
    triggers exact rewind to the served boundary, a re-mesh over the
    surviving devices, and bit-exact continuation (``elastic_*``
    params; ``parallel/elastic.py``).

    With ``checkpoint_dir`` set (params or config file) training is
    preemption-safe: atomic checkpoints every ``snapshot_freq``
    iterations plus a best-effort final one on SIGTERM/SIGINT, and
    ``resume_from`` (param or keyword; ``'auto'`` discovers the newest
    valid snapshot) continues BIT-EXACTLY from the saved boundary —
    even from a snapshot taken mid-fused-block under a sharded
    learner — see ``docs/Checkpointing.md``.

    With ``stream_ingest=true`` the train set is binned OUT-OF-CORE
    (``docs/Streaming.md``): raw rows stream chunk-by-chunk into a
    crash-safe content-keyed mmap cache, the booster uploads it in
    budgeted double-buffered host->device windows, the model is
    byte-identical to the in-memory path, and checkpoint manifests
    record the cache identity so resume never re-bins published
    chunks."""
    from .utils.env import configure_compile_cache
    configure_compile_cache()
    params = dict(params)
    # canonical name first, then aliases (Config resolution order);
    # num_boost_round is accepted for reference-python compatibility
    _round_aliases = ("num_iterations", "num_iteration", "n_iter",
                      "num_tree", "num_trees", "num_round", "num_rounds",
                      "num_boost_round", "n_estimators", "max_iter")
    _seen = [(a, params.pop(a)) for a in _round_aliases if a in params]
    if _seen:
        # highest-priority alias wins, like Config's alias resolution;
        # conflicting values get the reference's "will be ignored" warning
        num_boost_round = int(_seen[0][1])
        for a, v in _seen[1:]:
            if int(v) != num_boost_round:
                Log.warning("%s is set with %s=%d, %s=%s will be ignored",
                            _seen[0][0], _seen[0][0], num_boost_round, a, v)
    if fobj is not None:
        params["objective"] = params.get("objective", "none")
        if params["objective"] not in ("none", "custom"):
            Log.warning("Using custom fobj; 'objective' parameter used only "
                        "for score transform")
    for alias in ("early_stopping_round", "early_stopping_rounds",
                  "early_stopping", "n_iter_no_change"):
        if alias in params and early_stopping_rounds is None:
            early_stopping_rounds = int(params.pop(alias))

    if feature_name != "auto":
        train_set.feature_name = feature_name
    if categorical_feature != "auto":
        train_set.categorical_feature = categorical_feature

    if params.get("objective") in ("none", "custom") and fobj is None:
        Log.fatal("objective=none requires a custom fobj")
    if fobj is not None:
        params["objective"] = "none"
    booster = Booster(params=params, train_set=train_set, mesh=mesh)

    # ---- checkpoint/resume (lightgbm_tpu/ckpt/) ----------------------
    cfg = booster.config
    ckpt_dir = getattr(cfg, "checkpoint_dir", "") or ""
    resume = resume_from if resume_from is not None \
        else (getattr(cfg, "resume_from", "") or "")
    snapshot_freq = int(getattr(cfg, "snapshot_freq", -1) or -1)
    ckpt_mgr = None
    ckpt_loader = None
    loaded_ckpt = None
    if ckpt_dir or resume:
        from .ckpt import CheckpointError, CheckpointManager
        recorder = getattr(booster._gbdt, "_telemetry", None)
        keep_n = int(getattr(cfg, "keep_last_n", 2) or 2)
        if ckpt_dir:
            ckpt_mgr = CheckpointManager(ckpt_dir, keep_n, recorder)
        if resume:
            ckpt_loader = ckpt_mgr
            if ckpt_loader is None:
                if not os.path.isdir(resume):
                    Log.fatal("resume_from=%r: no such checkpoint "
                              "directory (set checkpoint_dir to use "
                              "'auto')", resume)
                ckpt_loader = CheckpointManager(resume, keep_n,
                                                recorder)
            try:
                loaded_ckpt = ckpt_loader.resolve(resume)
            except CheckpointError as exc:
                Log.fatal("cannot resume: %s", exc)
            if loaded_ckpt is None:
                Log.warning("resume_from=%r: no valid checkpoint found; "
                            "training from scratch", resume)

    if init_model is not None and loaded_ckpt is not None:
        Log.warning("init_model is ignored: resuming from checkpoint %s",
                    loaded_ckpt["path"])
        init_model = None
    if init_model is not None:
        prev = init_model if isinstance(init_model, Booster) \
            else Booster(model_file=str(init_model))
        booster._gbdt.init_from_model(prev._gbdt.models,
                                      train_set.raw_mat)

    valid_sets = list(valid_sets) if valid_sets else []
    valid_names = list(valid_names) if valid_names else []
    for i, vs in enumerate(valid_sets):
        if vs is train_set:
            name = "training"
            booster.config.is_provide_training_metric = True
            booster._gbdt.config.is_provide_training_metric = True
            continue
        name = valid_names[i] if i < len(valid_names) else f"valid_{i}"
        booster.add_valid(vs, name)

    cbs = list(callbacks) if callbacks else []
    if evals_result is not None:
        cbs.append(callback_mod.record_evaluation(evals_result))
    if verbose_eval is True:
        cbs.append(callback_mod.print_evaluation())
    elif isinstance(verbose_eval, int) and verbose_eval > 0:
        cbs.append(callback_mod.print_evaluation(verbose_eval))
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        cbs.append(callback_mod.early_stopping(
            early_stopping_rounds,
            first_metric_only=params.get("first_metric_only", False)))
    if learning_rates is not None:
        cbs.append(callback_mod.reset_parameter(
            learning_rate=learning_rates))
    cbs_before = [c for c in cbs if getattr(c, "before_iteration", False)]
    cbs_after = [c for c in cbs if not getattr(c, "before_iteration", False)]
    cbs_before.sort(key=lambda c: getattr(c, "order", 0))
    cbs_after.sort(key=lambda c: getattr(c, "order", 0))

    # resume: install the snapshot AFTER valid sets registered (their
    # path-dependent scores are overwritten from the checkpoint) and
    # replay the recorded eval stream through the stateful callbacks
    start_iter = 0
    eval_history: List = []
    if loaded_ckpt is not None:
        start_iter = ckpt_loader.restore(booster, loaded_ckpt)
        eval_history = [(int(it), [tuple(e) for e in ev]) for it, ev in
                        (loaded_ckpt["meta"].get("eval_history") or [])]
        if _replay_eval_history(eval_history, cbs_after, booster,
                                params, num_boost_round):
            return booster
    # tell the booster its TRUE iteration horizon: the fused
    # super-step auto-sizes its tail block from config.num_iterations,
    # and engine.train popped the round aliases from params above — a
    # continue-training booster (init_model, the continual daemon's
    # per-batch form) otherwise keeps the registry default and
    # dispatches whole blocks past the boundary (wasted device work)
    booster._gbdt.config.num_iterations = num_boost_round \
        if (loaded_ckpt is not None or init_model is None) \
        else booster._gbdt.iter + num_boost_round
    if learning_rates is not None and \
            int(getattr(cfg, "superstep_pipeline_depth", 0) or 0) > 0:
        # a per-iteration learning_rates schedule changes the
        # shrinkage between serves: every pre-dispatched in-flight
        # block would be built at a stale rate and drained on arrival
        # (correct, but pure wasted device work every block) — run
        # the fused path unpipelined instead.  The booster-level
        # drain stays as the correctness backstop for schedules
        # applied through raw callbacks.
        booster._gbdt.config.superstep_pipeline_depth = 0
    # ---- elastic shard-loss recovery (parallel/elastic.py) -----------
    # supervises the mesh-sharded fused path: each fused-block
    # dispatch runs under the collective-stall watchdog; a failed or
    # hung shard triggers exact rewind + re-mesh over the survivors +
    # bit-exact continuation.  elastic_* params, docs/Distributed.md.
    elastic_sup = None
    if getattr(cfg, "elastic_training", False):
        if (fobj is not None or
                getattr(booster._gbdt, "_dist", None) is None or
                int(getattr(cfg, "fused_iters", 1)) <= 1):
            Log.warning(
                "elastic_training requires a distributed tree_learner "
                "(data/feature/voting) with fused_iters>1 and no "
                "custom fobj; training runs unsupervised")
        else:
            from .parallel.elastic import ElasticSupervisor
            elastic_sup = ElasticSupervisor(booster)
    guard = _PreemptGuard()
    if ckpt_mgr is not None:
        guard.install()
    saved_at = start_iter if loaded_ckpt is not None else -1

    def _save_ckpt(reason):
        nonlocal saved_at
        try:
            ckpt_mgr.save(booster, reason=reason,
                          eval_history=[[it, [list(e) for e in ev]]
                                        for it, ev in eval_history])
            saved_at = booster._gbdt.completed_iterations()
        except Exception as exc:  # a full disk must not kill training
            Log.warning("checkpoint save failed (%s): %s", reason, exc)

    import contextlib as _contextlib
    import time as _time

    from .obs import flight as _flight
    from .obs import spans as _spans
    from .utils.profiling import timed

    # obs plane (docs/Observability.md): arm the anomaly-triggered
    # flight recorder when asked, and run the loop under a 'train'
    # span — a daemon batch's ambient trace makes it a child, a bare
    # CLI run roots a fresh trace the checkpoint carries onward
    _flight.ensure_installed(cfg)
    _obs_stack = _contextlib.ExitStack()
    _obs_stack.enter_context(_spans.span(
        "train", recorder=getattr(booster._gbdt, "_telemetry", None),
        announce=True, rounds=int(num_boost_round),
        start_iter=int(start_iter)))
    t_train0 = _time.perf_counter()
    try:
        for i in range(start_iter, num_boost_round):
            for cb in cbs_before:
                cb(CallbackEnv(booster, params, i, 0, num_boost_round, None))
            should_stop = elastic_sup.update(fobj=fobj) \
                if elastic_sup is not None else booster.update(fobj=fobj)
            # per-iteration wall clock (GBDT::Train, gbdt.cpp:253-256)
            Log.debug("%.6f seconds elapsed, finished iteration %d",
                      _time.perf_counter() - t_train0, i + 1)
            evaluation_result_list = []
            if booster._gbdt.metrics and (booster._gbdt.valid_sets or
                                          booster.config.is_provide_training_metric):
                with timed("eval/metrics"):
                    evaluation_result_list = booster.eval_set()
            if feval is not None:
                evaluation_result_list.extend(
                    _run_feval(feval, booster, train_set, valid_sets,
                               valid_names))
            _telemetry_rec = getattr(booster._gbdt, "_telemetry", None)
            if _telemetry_rec is not None and evaluation_result_list:
                # metric stream rides the run record (telemetry JSONL is
                # the artifact docs/Benchmarks.md-class documents come from)
                _telemetry_rec.emit("eval", iter=i, results=[
                    [d, m, float(v), bool(h)]
                    for d, m, v, h in evaluation_result_list])
            if ckpt_mgr is not None and evaluation_result_list:
                eval_history.append(
                    (i, [(d, m, float(v), bool(h))
                         for d, m, v, h in evaluation_result_list]))
            try:
                for cb in cbs_after:
                    cb(CallbackEnv(booster, params, i, 0, num_boost_round,
                                   evaluation_result_list))
            except EarlyStopException as e:
                booster.best_iteration = e.best_iteration + 1
                for item in e.best_score:
                    booster.best_score.setdefault(item[0], {})[item[1]] = item[2]
                break
            if ckpt_mgr is not None:
                if guard.pending() is not None:
                    _save_ckpt("preempt")
                    break
                if snapshot_freq > 0 and (i + 1) % snapshot_freq == 0 \
                        and i + 1 < num_boost_round:
                    _save_ckpt("periodic")
            if should_stop:
                break
        if ckpt_mgr is not None and \
                booster._gbdt.completed_iterations() != saved_at:
            _save_ckpt("preempt" if guard.pending() is not None
                       else "final")
    finally:
        # handlers are process-global: restore them even when an
        # update/eval/callback raises mid-loop.  The span closes with
        # the in-flight exception (sys.exc_info() is live inside a
        # finally) so a crashed run emits status="error", not "ok".
        import sys as _sys
        _obs_stack.__exit__(*_sys.exc_info())
        guard.restore()
        gb = booster._gbdt
        if getattr(gb, "_pager", None) is not None:
            rec = getattr(gb, "_telemetry", None)
            if rec is not None:
                # cumulative rollup: everything the run paged
                rec.emit("pager", event="done", **gb._pager.stats())
    if booster.best_iteration <= 0:
        for item in (booster.eval_set() if booster._gbdt.metrics else []):
            booster.best_score.setdefault(item[0], {})[item[1]] = item[2]
    return booster


def _run_feval(feval, booster, train_set, valid_sets, valid_names):
    """Evaluate a custom metric on training + every validation set
    (reference engine.py:224-225 calls eval_train(feval) and
    eval_valid(feval))."""
    out = []

    def one(name, raw_score, dataset):
        res = feval(np.asarray(raw_score, np.float64), dataset)
        if res is None:
            return
        if isinstance(res, tuple):
            res = [res]
        for metric_name, value, hb in res:
            out.append((name, metric_name, value, hb))

    one("training", booster._gbdt.train_score[0], train_set)
    vs_by_name = {vs.name: vs for vs in booster._gbdt.valid_sets}
    for i, ds in enumerate(valid_sets or []):
        if ds is train_set:
            continue
        name = valid_names[i] if valid_names and i < len(valid_names) \
            else f"valid_{i}"
        if name in vs_by_name:
            one(name, vs_by_name[name].score[0], ds)
    return out


class CVBooster:
    """Container of per-fold boosters (reference ``engine.py`` _CVBooster)."""

    def __init__(self):
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def append(self, booster: Booster) -> None:
        self.boosters.append(booster)

    def __getattr__(self, name):
        def handler(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]
        return handler


def _make_folds(train_set: Dataset, nfold: int, stratified: bool,
                shuffle: bool, seed: int, folds=None):
    train_set.construct()
    n = train_set.num_data()
    group = train_set.get_group()
    if folds is not None:
        if hasattr(folds, "split"):
            y = train_set.get_label()
            it = folds.split(np.zeros(n), y,
                             groups=_group_ids(group, n))
            return list(it)
        return list(folds)
    rng = np.random.RandomState(seed)
    if group is not None:
        # group-aware folds: split whole queries
        nq = len(group)
        order = rng.permutation(nq) if shuffle else np.arange(nq)
        fold_qs = np.array_split(order, nfold)
        bounds = np.concatenate([[0], np.cumsum(group)])
        out = []
        for qs in fold_qs:
            test_idx = np.concatenate(
                [np.arange(bounds[q], bounds[q + 1]) for q in qs]) \
                if len(qs) else np.array([], dtype=np.int64)
            mask = np.ones(n, bool)
            mask[test_idx] = False
            out.append((np.nonzero(mask)[0], test_idx))
        return out
    if stratified:
        y = train_set.get_label()
        out_test = [[] for _ in range(nfold)]
        for cls in np.unique(y):
            idx = np.nonzero(y == cls)[0]
            if shuffle:
                idx = idx[rng.permutation(len(idx))]
            for k, part in enumerate(np.array_split(idx, nfold)):
                out_test[k].append(part)
        out = []
        for k in range(nfold):
            test_idx = np.sort(np.concatenate(out_test[k]))
            mask = np.ones(n, bool)
            mask[test_idx] = False
            out.append((np.nonzero(mask)[0], test_idx))
        return out
    idx = rng.permutation(n) if shuffle else np.arange(n)
    out = []
    for part in np.array_split(idx, nfold):
        mask = np.ones(n, bool)
        mask[part] = False
        out.append((np.nonzero(mask)[0], np.sort(part)))
    return out


def _group_ids(group, n):
    if group is None:
        return None
    ids = np.zeros(n, dtype=np.int64)
    start = 0
    for qi, cnt in enumerate(group):
        ids[start:start + cnt] = qi
        start += cnt
    return ids


def cv(params: Dict[str, Any], train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True,
       shuffle: bool = True, metrics=None, fobj=None, feval=None,
       init_model=None, feature_name="auto", categorical_feature="auto",
       early_stopping_rounds: Optional[int] = None, fpreproc=None,
       verbose_eval=None, show_stdv: bool = True, seed: int = 0,
       callbacks=None, eval_train_metric: bool = False,
       return_cvbooster: bool = False) -> Dict[str, List[float]]:
    """K-fold cross-validation (``engine.py:334``)."""
    params = dict(params)
    if metrics is not None:
        params["metric"] = metrics
    objective = params.get("objective", "regression")
    if stratified and not str(objective).startswith(("binary", "multiclass")):
        stratified = False
    train_set.construct()
    raw = train_set.raw_mat
    if raw is None:
        Log.fatal("cv requires the train set raw data "
                  "(free_raw_data=False)")
    label = train_set.get_label()
    weight = train_set.get_weight()
    group = train_set.get_group()

    folds_idx = _make_folds(train_set, nfold, stratified, shuffle, seed,
                            folds)
    cvbooster = CVBooster()
    fold_data = []
    for tr_idx, te_idx in folds_idx:
        tr = Dataset(raw[tr_idx], label=label[tr_idx],
                     weight=None if weight is None else weight[tr_idx],
                     group=_subset_group(group, tr_idx, train_set),
                     params=dict(train_set.params),
                     categorical_feature=train_set.categorical_feature)
        te_ds = tr.create_valid(
            raw[te_idx], label=label[te_idx],
            weight=None if weight is None else weight[te_idx],
            group=_subset_group(group, te_idx, train_set))
        if fpreproc is not None:
            tr, te_ds, params = fpreproc(tr, te_ds, dict(params))
        fold_data.append((tr, te_ds))

    results = collections.defaultdict(list)
    boosters = []
    for tr, te in fold_data:
        bst = Booster(params=params, train_set=tr)
        bst.add_valid(te, "valid")
        if eval_train_metric:
            bst.config.is_provide_training_metric = True
            bst._gbdt.config.is_provide_training_metric = True
        boosters.append(bst)
        cvbooster.append(bst)

    es_cb = None
    if early_stopping_rounds:
        es_cb = callback_mod.early_stopping(early_stopping_rounds,
                                            verbose=False)
    for i in range(num_boost_round):
        should_stop_all = True
        for bst in boosters:
            s = bst.update(fobj=fobj)
            should_stop_all = should_stop_all and s
        merged = _agg_cv_result(boosters, feval, fold_data)
        for name, metric, mean, hb, std in merged:
            results[f"{name} {metric}-mean"].append(mean)
            results[f"{name} {metric}-stdv"].append(std)
        if verbose_eval:
            Log.info("[%d]\t%s", i + 1,
                     "\t".join(callback_mod._format_eval_result(
                         (n, m, v, h, s), show_stdv)
                         for n, m, v, h, s in merged))
        if es_cb is not None:
            try:
                es_cb(CallbackEnv(cvbooster, params, i, 0, num_boost_round,
                                  merged))
            except EarlyStopException as e:
                cvbooster.best_iteration = e.best_iteration + 1
                for key in list(results.keys()):
                    results[key] = results[key][:cvbooster.best_iteration]
                break
        if callbacks:
            for cb in callbacks:
                cb(CallbackEnv(cvbooster, params, i, 0, num_boost_round,
                               merged))
        if should_stop_all:
            break
    out = dict(results)
    if return_cvbooster:
        out["cvbooster"] = cvbooster
    return out


def _subset_group(group, idx, train_set):
    if group is None:
        return None
    ids = _group_ids(group, train_set.num_data())[idx]
    # idx keeps query blocks contiguous (group-aware folds)
    _, counts = np.unique(ids, return_counts=True)
    return counts


def _agg_cv_result(boosters, feval, fold_data):
    by_key = collections.OrderedDict()
    for bst, (tr, te) in zip(boosters, fold_data):
        for name, metric, value, hb in bst.eval_set():
            by_key.setdefault((name, metric, hb), []).append(value)
        if feval is not None:
            # custom metric on this fold's held-out set
            # (reference cvfolds.eval_valid(feval), engine.py:488)
            score = bst._gbdt.valid_sets[0].score[0].astype(np.float64)
            res = feval(score, te)
            if res is not None:
                if isinstance(res, tuple):
                    res = [res]
                for name, value, hb in res:
                    by_key.setdefault(("valid", name, hb), []).append(value)
    return [(name if name != "valid" else "valid", metric,
             float(np.mean(vals)), hb, float(np.std(vals)))
            for (name, metric, hb), vals in by_key.items()]


# ----------------------------------------------------------------------
# task=sweep: hyperparameter search + k-fold CV as ONE compiled battery
# ----------------------------------------------------------------------
# Candidates x folds stack on the model axis of a vmapped booster
# battery (models/battery.py): the shared binned matrix is resident
# once, fold masks ride as per-model weight vectors, and candidates
# that vary only traced per-model params (learning rate, seeds,
# feature_fraction) share ONE XLA compile.

_SWEEP_METRIC_GREATER = {"auc"}


def _parse_sweep_grid(text: str) -> "collections.OrderedDict":
    """``'learning_rate=0.05,0.1;bagging_seed=1,2'`` -> ordered
    ``{param: [values]}`` with numeric coercion (int before float
    before raw string)."""
    grid: "collections.OrderedDict" = collections.OrderedDict()
    for clause in str(text or "").split(";"):
        clause = clause.strip()
        if not clause:
            continue
        if "=" not in clause:
            Log.fatal("sweep_grid clause %r has no '='", clause)
        name, _, vals = clause.partition("=")
        parsed = []
        for tok in vals.split(","):
            tok = tok.strip()
            if not tok:
                continue
            for cast in (int, float):
                try:
                    parsed.append(cast(tok))
                    break
                except ValueError:
                    continue
            else:
                parsed.append(tok)
        if parsed:
            grid[name.strip()] = parsed
    return grid


def _expand_candidates(grid, num_random: int,
                       seed: int) -> List[Dict[str, Any]]:
    """Candidate override dicts: the grid's cartesian product, or
    ``num_random`` uniform samples from its per-param choices."""
    if not grid:
        return [{}]
    names = list(grid)
    if num_random and num_random > 0:
        rng = np.random.RandomState(seed & 0x7FFFFFFF)
        out = []
        for _ in range(int(num_random)):
            out.append({k: grid[k][rng.randint(len(grid[k]))]
                        for k in names})
        return out
    out = [{}]
    for k in names:
        out = [{**c, k: v} for c in out for v in grid[k]]
    return out


def _sweep_metric(name: str, objective: str, label, weight, sigmoid):
    """``(metric_name, fn(raw_scores, row_indices) -> float,
    greater_is_better)`` — the per-iteration fold scorer, computed in
    f64 on host (the curve itself replays the device f32 scores
    bit-exactly; only the metric reduction is f64)."""
    name = (name or "").strip() or default_metric_for(objective)
    alias = {"mse": "l2", "regression": "l2", "regression_l2": "l2",
             "mae": "l1", "regression_l1": "l1", "l2_root": "rmse"}
    name = alias.get(name, name)
    y = np.asarray(label, np.float64)
    w = None if weight is None else np.asarray(weight, np.float64)
    sig = float(sigmoid or 1.0)

    def wmean(v, rows):
        if w is None:
            return float(np.mean(v))
        return float(np.sum(v * w[rows]) / np.sum(w[rows]))

    if name == "l2":
        fn = lambda s, rows: wmean(  # noqa: E731
            (np.asarray(s, np.float64) - y[rows]) ** 2, rows)
    elif name == "rmse":
        fn = lambda s, rows: float(np.sqrt(wmean(  # noqa: E731
            (np.asarray(s, np.float64) - y[rows]) ** 2, rows)))
    elif name == "l1":
        fn = lambda s, rows: wmean(  # noqa: E731
            np.abs(np.asarray(s, np.float64) - y[rows]), rows)
    elif name == "binary_logloss":
        def fn(s, rows):
            p = 1.0 / (1.0 + np.exp(-sig * np.asarray(s, np.float64)))
            p = np.clip(p, 1e-15, 1.0 - 1e-15)
            yy = y[rows]
            return wmean(-(yy * np.log(p) + (1 - yy) * np.log(1 - p)),
                         rows)
    elif name == "binary_error":
        fn = lambda s, rows: wmean(  # noqa: E731
            (np.asarray(s, np.float64) > 0) != (y[rows] > 0), rows)
    elif name == "auc":
        from .serve.watcher import auc_score
        fn = lambda s, rows: auc_score(y[rows], s)  # noqa: E731
    else:
        Log.warning("sweep_metric %s unsupported for fold scoring; "
                    "falling back to l2", name)
        return _sweep_metric("l2", objective, label, weight, sigmoid)
    return name, fn, name in _SWEEP_METRIC_GREATER


class SweepResult:
    """Outcome of one :func:`sweep` call."""

    def __init__(self, candidates, metric_name, greater_better):
        self.candidates: List[Dict[str, Any]] = candidates
        self.metric_name = metric_name
        self.greater_better = greater_better
        self.cv_curves: List[List[List[float]]] = []  # [cand][fold][it]
        self.scores: List[float] = []        # best mean CV score / cand
        self.best_iters: List[int] = []      # 1-based best iter / cand
        self.best_index: int = -1
        self.best_iteration: int = -1
        self.best_score: float = float("nan")
        self.best_params: Dict[str, Any] = {}
        self.model_text: str = ""
        self.booster: Optional[Booster] = None
        self.report = None                   # battery.BatteryReport

    def _worst(self) -> float:
        return -np.inf if self.greater_better else np.inf


def sweep(params: Dict[str, Any], train_set: Dataset,
          num_boost_round: Optional[int] = None, *,
          grid: Optional[Dict[str, Sequence[Any]]] = None,
          folds=None, metric: Optional[str] = None,
          supervisor=None, tenant: Optional[str] = None) -> SweepResult:
    """Hyperparameter sweep + k-fold CV as one compiled battery.

    Builds candidates from ``grid`` (or ``params['sweep_grid']``),
    scores each on ``sweep_folds``-fold CV — fold masks are per-model
    weight vectors over the ONE shared ``train_set``, never data
    copies — and trains every candidate's full-data model in the same
    battery (``sweep_train_full``).  The winner (best mean CV score at
    its best iteration) is exported as a model string byte-equal to
    solo training, loaded into ``result.booster``, and — when a
    ``supervisor`` (serve.fleet.FleetSupervisor) is passed — published
    under ``tenant`` (default ``watch_tenant``).  Emits one ``sweep``
    telemetry record; steady-state XLA compiles per static group is 1
    (``retraces_per_model`` flags violations, obs/rules.py)."""
    from .config import Config
    from .models import battery as battery_mod
    from .utils import telemetry as _telemetry

    params = dict(params)
    cfg = Config(params)
    T = int(num_boost_round if num_boost_round is not None
            else cfg.num_iterations)
    if grid is None:
        grid = _parse_sweep_grid(cfg.sweep_grid)
    candidates = _expand_candidates(grid, cfg.sweep_random,
                                    cfg.sweep_seed)
    train_set.construct()
    n = train_set.num_data()
    label = train_set.get_label()
    base_w = train_set.get_weight()

    metric_name, metric_fn, greater = _sweep_metric(
        metric if metric is not None else cfg.sweep_metric,
        cfg.objective, label, base_w, getattr(cfg, "sigmoid", 1.0))

    # ---- fold masks over the shared dataset --------------------------
    nfold = max(1, int(cfg.sweep_folds))
    if nfold > 1 or folds is not None:
        stratified = str(cfg.objective).startswith("binary")
        folds_idx = _make_folds(train_set, nfold, stratified, True,
                                cfg.sweep_fold_seed, folds)
    else:
        # nfold=1: one "fold" trains on every row and scores the
        # training metric — the fold member IS the full-data model
        all_idx = np.arange(n)
        folds_idx = [(all_idx, all_idx)]
    nfold = len(folds_idx)
    fold_w, fold_m = [], []
    for tr_idx, te_idx in folds_idx:
        w = np.zeros(n, np.float32)
        w[tr_idx] = 1.0 if base_w is None else \
            np.asarray(base_w, np.float32)[tr_idx]
        m = np.zeros(n, bool)
        m[te_idx] = True
        fold_w.append(w)
        fold_m.append(m)

    # ---- member specs: candidates x (folds [+ full]) -----------------
    want_full = bool(cfg.sweep_train_full) and not \
        (nfold == 1 and folds is None)
    specs: List[battery_mod.MemberSpec] = []
    full_of: Dict[int, int] = {}     # candidate -> full-member index
    fold_of: Dict[int, List[int]] = {}
    for ci, cand in enumerate(candidates):
        merged = {**params, **cand, "num_iterations": T}
        fold_of[ci] = []
        for k in range(nfold):
            fold_of[ci].append(len(specs))
            specs.append(battery_mod.MemberSpec(
                params=merged, weight=fold_w[k], eval_mask=fold_m[k],
                tag=f"c{ci}/fold{k}"))
        if want_full:
            full_of[ci] = len(specs)
            specs.append(battery_mod.MemberSpec(
                params=merged, tag=f"c{ci}/full"))
        else:
            full_of[ci] = fold_of[ci][0]
    Log.info("sweep: %d candidates x %d folds%s = %d battery members",
             len(candidates), nfold, " (+full)" if want_full else "",
             len(specs))

    report = battery_mod.train_battery(
        train_set, specs, metric=metric_fn,
        shard_models=bool(cfg.sweep_shard_models))

    # ---- per-candidate CV aggregation and winner selection -----------
    res = SweepResult(candidates, metric_name, greater)
    res.report = report
    for ci in range(len(candidates)):
        members = [report.results[i] for i in fold_of[ci]]
        curves = [m.curve or [] for m in members]
        res.cv_curves.append(curves)
        depth = min((len(c) for c in curves), default=0)
        if any(m.failed for m in members) or depth == 0:
            res.scores.append(res._worst())
            res.best_iters.append(-1)
            continue
        mean = np.mean([c[:depth] for c in curves], axis=0)
        bi = int(np.argmax(mean) if greater else np.argmin(mean))
        res.scores.append(float(mean[bi]))
        res.best_iters.append(bi + 1)
    order = np.argsort(res.scores)
    best = int(order[-1] if greater else order[0])
    if np.isfinite(res.scores[best]):
        res.best_index = best
        res.best_iteration = res.best_iters[best]
        res.best_score = res.scores[best]
        res.best_params = {**params, **candidates[best],
                           "num_iterations": T}

    # ---- winner export (byte-equal to solo training) -----------------
    if res.best_index >= 0:
        win = report.results[full_of[res.best_index]]
        if not win.failed and win.trees:
            ni = min(res.best_iteration, len(win.trees))
            res.model_text = battery_mod.member_model_string(
                win, Config(dict(win.spec.params)),
                train_set._constructed, num_iteration=ni)
            res.booster = Booster(model_str=res.model_text)
            res.booster.best_iteration = ni

    rec = _telemetry.get_recorder()
    if rec is not None:
        dur = max(report.duration_s, 1e-9)
        rec.emit("sweep", models=len(specs), groups=report.groups,
                 xla_compiles=report.xla_compiles,
                 retraces_per_model=float(report.retraces_per_model),
                 models_per_s=float(len(specs) / dur),
                 vmap_members=report.vmap_members,
                 solo_members=report.solo_members,
                 candidates=len(candidates), folds=nfold,
                 metric=metric_name,
                 best_index=res.best_index,
                 best_iteration=res.best_iteration,
                 best_score=(float(res.best_score)
                             if np.isfinite(res.best_score) else None),
                 best_iters=list(res.best_iters))

    if supervisor is not None and res.model_text:
        name = tenant if tenant is not None else \
            (cfg.watch_tenant or "default")
        supervisor.publish_model(res.model_text, source="sweep",
                                 model=name)
        Log.info("sweep: published winner c%d (score=%.6g) under "
                 "tenant %r", res.best_index, res.best_score, name)
    return res
