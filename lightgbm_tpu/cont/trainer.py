"""The continual training daemon: a preemption-safe, self-healing
ingest -> validate -> train -> checkpoint -> publish loop.

``ContinualTrainer`` closes ROADMAP item 5: it composes the pieces the
repo already has — warm-start continue-training (PR 1/3, bit-exact
mid-bagging-cycle), atomic bit-exact checkpoints (PR 5,
``ckpt/manager.py``), and the validated auto-publish + rollback tier
(PR 6, ``serve/watcher.py``) — into one long-running trainer that
survives the failure modes a days-long run on preemptible TPUs
actually meets:

- **bad input**: every batch passes the :class:`~.validate.
  BatchValidator` gates (schema, non-finite, label/feature drift);
  rejects are MOVED to quarantine and accounted in telemetry.
- **corrupted-past-validation input**: the numerical-health guard
  (``utils/health.py``) trips inside training — fused blocks carry a
  per-iteration finiteness flag in their packed fetch — the batch's
  in-flight checkpoints are pruned (``CheckpointManager.prune_after``)
  and the model rewinds exactly to the pre-batch boundary.
- **wedged steps**: a per-iteration heartbeat feeds the stall
  watchdog; a step silent past ``continual_stall_timeout_s`` is
  abandoned (its thread unblocks and exits via the attempt-generation
  token) and the batch retries from the last snapshot, bounded by
  ``continual_max_batch_retries`` before quarantine.
- **preemption**: SIGTERM/SIGINT raise the process-wide flag
  (``engine.request_preempt``); the in-flight batch checkpoints at
  the next served boundary (``reason=preempt``) and the daemon drains.
  Restart resumes the interrupted batch BIT-exactly (PR 5 resume), so
  the final model equals an uninterrupted run over the same surviving
  batches.
- **crash (SIGKILL)**: nothing graceful runs — the atomic checkpoint
  protocol plus the ledger (``continual_state.json``, written with the
  same tmp+rename discipline) make restart land on the newest valid
  snapshot and re-enter the interrupted batch.

The checkpoint root is also the PUBLISH root: the serve tier's
``CheckpointWatcher`` (same process or another) manifest-verifies and
canary-scores every finalized snapshot before it can serve traffic, so
the daemon never needs to be trusted — only its checkpoints do.

Fault-injection points (``utils/faults.py``): ``ingest.read``,
``ingest.validate`` (in ``source.py``/``validate.py``),
``trainer.step`` (per boosting iteration: ``error`` | ``hang`` |
``sleep_<ms>``) and ``trainer.refit`` (``error``).
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, Optional

import numpy as np

from .. import engine as engine_mod
from ..basic import Booster, Dataset
from ..ckpt import CheckpointManager
from ..config import Config
from ..obs import flight as _flight
from ..obs import spans as _spans
from ..serve.registry import model_fingerprint
from ..utils import faults as _faults
from ..utils import telemetry as _telemetry
from ..utils.health import NumericalHealthError
from ..utils.log import Log
from .config import ContinualConfig
from .source import Batch, BatchSource, DirectoryBatchSource
from .validate import BatchValidator

__all__ = ["ContinualTrainer"]

# engine.train pops these from params and lets them OVERRIDE its
# num_boost_round argument — the daemon owns the per-batch round
# budget, so they must not leak into the engine params
_ROUND_ALIASES = ("num_iterations", "num_iteration", "n_iter",
                  "num_tree", "num_trees", "num_round", "num_rounds",
                  "num_boost_round", "n_estimators", "max_iter")


def _fingerprint(text: Optional[str]) -> str:
    """Content identity of a model text — the serve tier's ONE
    definition (``model_id`` on published versions), so the ledger
    correlates directly with watcher/loadgen output."""
    return "" if not text else model_fingerprint(text)


class _Heartbeat:
    """Monotonic last-sign-of-life timestamp (GIL-atomic float).
    ``steps`` counts iteration-boundary beats: until the SECOND one,
    the attempt is still inside its first iteration — which pays the
    full per-booster XLA compile — and the stall watchdog applies a
    grace multiple instead of reading warmup as a wedge."""

    def __init__(self):
        self.t = time.monotonic()
        self.steps = 0

    def beat(self, step: bool = False) -> None:
        self.t = time.monotonic()
        if step:
            self.steps += 1

    def age(self) -> float:
        return time.monotonic() - self.t


class ContinualTrainer:
    """Drive the continual loop.  ``run()`` blocks until preempted,
    stopped, ``continual_max_batches`` consumed, or idle past
    ``continual_idle_exit_s``; it may run on any thread (tests drive
    it inline, the CLI runs it under a main-thread preempt guard)."""

    def __init__(self, params: Dict[str, Any],
                 config: Optional[ContinualConfig] = None,
                 source: Optional[BatchSource] = None,
                 validator: Optional[BatchValidator] = None,
                 recorder=None):
        self.params = dict(params)
        cfg = Config(self.params)
        self.cont = config or ContinualConfig.from_params(cfg)
        self.cont.validate()
        # obs_flight_recorder=true arms the process-wide anomaly
        # capture ring (obs/flight.py) for the whole daemon lifetime
        _flight.ensure_installed(cfg)
        self.root = str(cfg.checkpoint_dir or "")
        if not self.root:
            raise ValueError("continual training requires "
                             "checkpoint_dir (the checkpoint root is "
                             "also the publish root)")
        self.keep_last_n = max(int(cfg.keep_last_n or 2), 2)
        self.refit_decay = float(cfg.refit_decay_rate)
        # streamed per-batch ingest (docs/Streaming.md): resolved
        # through Config so the registered aliases (stream,
        # out_of_core) work like everywhere else
        self._stream_batches = bool(getattr(cfg, "stream_ingest",
                                            False))
        self._stream_cache_dir = str(
            getattr(cfg, "stream_cache_dir", "") or
            os.path.join(self.root, "_stream_cache"))
        self.recorder = recorder
        self.mgr = CheckpointManager(self.root, self.keep_last_n,
                                     recorder)
        self.source = source or DirectoryBatchSource(
            self.cont.ingest_dir,
            quarantine_dir=self.cont.resolved_quarantine_dir(),
            processed_dir=self.cont.resolved_processed_dir(),
            read_retries=self.cont.read_retries,
            backoff_base_s=self.cont.backoff_base_s,
            backoff_max_s=self.cont.backoff_max_s,
            recorder=recorder)
        self.validator = validator or BatchValidator(
            drift_sigma=self.cont.drift_sigma,
            range_factor=self.cont.range_factor,
            nonfinite_check=self.cont.nonfinite_check)
        self.ledger_path = os.path.join(self.root,
                                        "continual_state.json")
        self._model_text: Optional[str] = None
        self._model_iter = 0
        self._batches_done = 0
        self._inflight: Optional[Dict[str, Any]] = None
        self._stop = threading.Event()
        self._gen_lock = threading.Lock()
        self._generation = 0
        self.stats: Dict[str, Any] = {
            "batches": 0, "rows": 0, "quarantined": 0,
            "stall_restarts": 0, "nonfinite_rewinds": 0,
            "batch_errors": 0, "refits": 0, "status": "",
        }

    # ------------------------------------------------------------------
    # plumbing
    # ------------------------------------------------------------------
    def _emit(self, event: str, **fields) -> None:
        _telemetry.counters.incr(f"continual_{event}s")
        rec = self.recorder or _telemetry.get_recorder()
        if rec is not None:
            rec.emit("continual", event=event, **fields)

    def _engine_params(self) -> Dict[str, Any]:
        eng = dict(self.params)
        for key in _ROUND_ALIASES + ("resume_from", "resume",
                                     "resume_checkpoint"):
            eng.pop(key, None)
        # the shared recorder (telemetry.set_recorder) replaces
        # per-batch telemetry files: one JSONL stream, one file handle
        eng.pop("telemetry_file", None)
        eng["checkpoint_dir"] = self.root
        eng["keep_last_n"] = self.keep_last_n
        eng["snapshot_freq"] = self.cont.snapshot_freq \
            if self.cont.snapshot_freq > 0 else -1
        return eng

    def _make_dataset(self, batch: Batch,
                      eng_params: Dict[str, Any]) -> Dataset:
        kw: Dict[str, Any] = {}
        if batch.weight is not None:
            kw["weight"] = np.asarray(batch.weight)
        if batch.group is not None:
            kw["group"] = np.asarray(batch.group)
        if self._stream_batches:
            # out-of-core batches (docs/Streaming.md): construction
            # routes through the crash-safe binned cache, so a daemon
            # restart mid-batch re-ingests the SAME content key and
            # reuses the fit mappers + every published chunk instead
            # of re-binning — the BatchSource seam's resume contract.
            # mmap-pair shards stay on disk end to end.
            params = dict(eng_params)
            params["stream_cache_dir"] = self._stream_cache_dir
            return Dataset(batch.X, label=np.asarray(batch.y),
                           params=params, **kw)
        return Dataset(np.ascontiguousarray(np.asarray(batch.X)),
                       label=np.asarray(batch.y),
                       params=dict(eng_params), **kw)

    # -- ledger --------------------------------------------------------
    def _write_ledger(self) -> None:
        data = {
            "schema": 1,
            "batches_done": int(self._batches_done),
            "model_iter": int(self._model_iter),
            "model_fingerprint": _fingerprint(self._model_text),
            "inflight": self._inflight,
            "validator": self.validator.state(),
        }
        tmp = self.ledger_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(data, f, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.ledger_path)

    def _read_ledger(self) -> Dict[str, Any]:
        try:
            with open(self.ledger_path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            return {}
        return data if isinstance(data, dict) else {}

    def _sync_from_checkpoints(self) -> None:
        """Adopt the newest VALID checkpoint as the current model —
        the restart (and rewind-fallback) recovery path."""
        self._model_text, self._model_iter = None, 0
        for iter_, path in reversed(self.mgr.candidates()):
            if CheckpointManager.validate(path):
                continue               # corrupt: the loader's fallback
            try:
                with open(os.path.join(path, "model.txt")) as f:
                    self._model_text = f.read()
                self._model_iter = int(iter_)
                return
            except OSError:            # pragma: no cover - torn dir
                continue

    def bootstrap(self) -> None:
        """Recover daemon state after a restart: ledger + newest valid
        checkpoint + the in-flight batch (if its files survived)."""
        os.makedirs(self.root, exist_ok=True)
        ledger = self._read_ledger()
        self._batches_done = int(ledger.get("batches_done", 0))
        self.validator.restore_state(ledger.get("validator"))
        self._sync_from_checkpoints()
        inflight = ledger.get("inflight")
        if inflight and inflight.get("batch") in self.source.pending():
            self._inflight = dict(inflight)
            self._emit("resume", batch=inflight["batch"],
                       start_iter=int(inflight.get("start_iter", 0)),
                       model_iter=self._model_iter)
            Log.info("continual: resuming in-flight batch %s (model "
                     "at iteration %d)", inflight["batch"],
                     self._model_iter)
        else:
            self._inflight = None

    # ------------------------------------------------------------------
    # the loop
    # ------------------------------------------------------------------
    def stop(self) -> None:
        """Programmatic drain (tests/benchmarks): finish the in-flight
        batch boundary and exit the loop."""
        self._stop.set()

    def _stopping(self) -> Optional[str]:
        if self._stop.is_set():
            return "stopped"
        if engine_mod.preempt_requested() is not None:
            return "preempt"
        return None

    def _sleep(self, seconds: float) -> None:
        deadline = time.monotonic() + seconds
        while time.monotonic() < deadline and self._stopping() is None:
            time.sleep(min(0.1, seconds))

    def run(self) -> Dict[str, Any]:
        self.bootstrap()
        prev_recorder = _telemetry.get_recorder()
        if self.recorder is not None and prev_recorder is None:
            # per-batch boosters adopt the daemon's recorder (one
            # stream for the whole loop; models/gbdt.py fallback)
            _telemetry.set_recorder(self.recorder)
        last_activity = time.monotonic()
        status = "idle_exit"
        try:
            while True:
                stop = self._stopping()
                if stop is not None:
                    if stop == "preempt":
                        self._emit("preempt",
                                   signum=int(
                                       engine_mod.preempt_requested()))
                    status = stop
                    break
                if self.cont.max_batches and \
                        self.stats["batches"] >= self.cont.max_batches:
                    status = "max_batches"
                    break
                q_before = getattr(self.source, "quarantined", 0)
                batch = self.source.next_batch()
                if batch is None:
                    if getattr(self.source, "quarantined", 0) != \
                            q_before:
                        # an unreadable file was quarantined: that is
                        # activity, and the NEXT file may be fine
                        last_activity = time.monotonic()
                        continue
                    if self.cont.idle_exit_s > 0 and \
                            time.monotonic() - last_activity > \
                            self.cont.idle_exit_s:
                        self._emit("idle_exit")
                        status = "idle_exit"
                        break
                    self._sleep(self.cont.poll_s)
                    continue
                last_activity = time.monotonic()
                st = self._consume(batch)
                if st == "preempt":
                    self._emit("preempt", batch=batch.name,
                               model_iter=self._model_iter)
                    status = "preempt"
                    break
        finally:
            self._write_ledger()
            self.stats["quarantined"] = \
                int(getattr(self.source, "quarantined", 0))
            if self.recorder is not None and prev_recorder is None:
                _telemetry.set_recorder(None)
        self.stats["status"] = status
        Log.info("continual: loop ended (%s): %d batches, %d "
                 "quarantined, %d stall restarts, %d non-finite "
                 "rewinds, model at iteration %d", status,
                 self.stats["batches"], self.stats["quarantined"],
                 self.stats["stall_restarts"],
                 self.stats["nonfinite_rewinds"], self._model_iter)
        return dict(self.stats)

    # ------------------------------------------------------------------
    # one batch
    # ------------------------------------------------------------------
    def _consume(self, batch: Batch) -> str:
        # one TRACE per batch, rooted here (obs/spans.py): ingest ->
        # validate -> train -> checkpoint happen under it, the
        # checkpoint carries it to the watcher, the watcher to the
        # fleet publish and the first served request — one joinable
        # timeline across processes (tools/trace_view.py)
        rec = self.recorder or _telemetry.get_recorder()
        with _spans.span("batch", recorder=rec, root=True,
                         announce=True, task="continual",
                         batch=batch.name, rows=batch.rows) as sp:
            with _spans.span("validate", recorder=rec,
                             batch=batch.name):
                errs = self.validator.check(batch)
            if errs:
                self.source.quarantine(batch, "validate",
                                       "; ".join(errs)[:300])
                sp.set(outcome="quarantined")
                return "quarantined"
            out = self._train_batch(batch)
            sp.set(outcome=out)
            return out

    def _next_is_refit(self) -> bool:
        return (self.cont.refit_every > 0 and
                self._model_text is not None and
                (self._batches_done + 1) % self.cont.refit_every == 0)

    def _train_batch(self, batch: Batch) -> str:
        t_batch0 = time.perf_counter()
        if self._inflight is not None and \
                self._inflight.get("batch") == batch.name:
            # restart continuation of an interrupted batch
            start_iter = int(self._inflight.get("start_iter",
                                                self._model_iter))
            refit = bool(self._inflight.get("refit", False))
            pre_fp = str(self._inflight.get("pre_fingerprint", ""))
            if refit and pre_fp and \
                    _fingerprint(self._model_text) != pre_fp:
                # the refit re-save landed before the crash: redoing
                # it would decay the leaf values twice
                self._finish_batch(batch, "refit", start_iter, t_batch0)
                return "done"
        else:
            start_iter = self._model_iter
            refit = self._next_is_refit()
            self._inflight = {
                "batch": batch.name,
                "start_iter": int(start_iter),
                "refit": bool(refit),
                "pre_fingerprint": _fingerprint(self._model_text),
            }
            self._write_ledger()
        target_iter = start_iter + \
            (0 if refit else self.cont.rounds_per_batch)
        pre_text, pre_iter = self._model_text, start_iter

        attempt = 0
        while True:
            attempt += 1
            with self._gen_lock:
                self._generation += 1
                gen = self._generation

            def alive(g=gen):
                with self._gen_lock:
                    return self._generation == g
            hb = _Heartbeat()
            box: Dict[str, Any] = {}
            th = threading.Thread(
                target=self._run_attempt,
                args=(batch, refit, start_iter, target_iter, box, hb,
                      alive, _spans.current()),
                name=f"ltpu-continual-{batch.name}", daemon=True)
            th.start()
            stalled = False
            while th.is_alive():
                th.join(0.1)
                limit = self.cont.stall_timeout_s
                if limit > 0 and hb.steps < 2:
                    # first iteration of a fresh per-batch booster:
                    # the fused scan (or first tree program) compiles
                    # here, and compile time is not a wedge
                    limit *= 5
                if limit > 0 and hb.age() > limit:
                    stalled = True
                    break
            if stalled:
                with self._gen_lock:
                    self._generation += 1   # the zombie sees !alive()
                self.stats["stall_restarts"] += 1
                self._emit("stall_restart", batch=batch.name,
                           attempt=attempt,
                           stalled_s=round(hb.age(), 3))
                Log.warning("continual: train step on %s stalled "
                            "(%.1fs without a heartbeat, attempt "
                            "%d/%d) — abandoning the attempt and "
                            "restarting from the last snapshot",
                            batch.name, hb.age(), attempt,
                            self.cont.max_batch_retries + 1)
                if attempt > self.cont.max_batch_retries:
                    return self._quarantine_batch(
                        batch, "stall", pre_text, pre_iter,
                        f"stalled {attempt} attempt(s)")
                self._sync_from_checkpoints()
                if self._refit_already_landed(refit):
                    self._finish_batch(batch, "refit", start_iter,
                                       t_batch0)
                    return "done"
                continue
            err = box.get("error")
            if err is None:
                self._model_text = box["model_text"]
                self._model_iter = int(box["iter"])
                if engine_mod.preempt_requested() is not None and \
                        self._model_iter < target_iter:
                    # the engine checkpointed at the preempt boundary
                    # and returned early: the batch stays in the
                    # ingest dir (and in the ledger) for the restarted
                    # daemon to resume bit-exactly
                    self._write_ledger()
                    return "preempt"
                self._finish_batch(batch,
                                   "refit" if refit else "extend",
                                   start_iter, t_batch0)
                return "done"
            if isinstance(err, NumericalHealthError):
                self.stats["nonfinite_rewinds"] += 1
                return self._quarantine_batch(
                    batch, "nonfinite", pre_text, pre_iter, str(err))
            self.stats["batch_errors"] += 1
            self._emit("batch_error", batch=batch.name,
                       attempt=attempt, error=str(err)[:300])
            Log.warning("continual: train attempt %d/%d on %s failed: "
                        "%s", attempt, self.cont.max_batch_retries + 1,
                        batch.name, err)
            if attempt > self.cont.max_batch_retries:
                return self._quarantine_batch(batch, "error", pre_text,
                                              pre_iter, str(err))
            self._sync_from_checkpoints()
            if self._refit_already_landed(refit):
                self._finish_batch(batch, "refit", start_iter,
                                   t_batch0)
                return "done"

    def _finish_batch(self, batch: Batch, mode: str, start_iter: int,
                      t_batch0: float) -> None:
        # fold the batch into the drift reference BEFORE the ledger
        # write below persists validator.state() — a crash after
        # mark_done must not leave a processed batch permanently
        # missing from the restart's baseline
        self.validator.observe(batch)
        self.source.mark_done(batch)
        self._inflight = None
        self._batches_done += 1
        self.stats["batches"] += 1
        self.stats["rows"] += batch.rows
        if mode == "refit":
            self.stats["refits"] += 1
        self._write_ledger()
        if self._stream_batches:
            # retention for per-batch binned caches: a finished batch
            # no longer needs its cache (only the INFLIGHT batch's
            # restart does); keep a small tail for producers that
            # replay recent shards
            from ..io import stream as stream_mod
            stream_mod.prune_cache_root(self._stream_cache_dir,
                                        keep_last=2)
        self._emit("batch", batch=batch.name, rows=batch.rows,
                   mode=mode, iter=int(self._model_iter),
                   start_iter=int(start_iter),
                   duration_ms=round(
                       (time.perf_counter() - t_batch0) * 1e3, 3))
        Log.info("continual: batch %s done (%s, %d rows, model at "
                 "iteration %d)", batch.name, mode, batch.rows,
                 self._model_iter)

    def _refit_already_landed(self, refit: bool) -> bool:
        """After a stall/error retry resynced from checkpoints: did
        the abandoned attempt's refit re-save already land?  Re-running
        the refit would apply the leaf decay twice (the same guard the
        crash-restart path applies via the ledger fingerprint)."""
        if not refit or self._inflight is None:
            return False
        pre_fp = str(self._inflight.get("pre_fingerprint", ""))
        return bool(pre_fp) and _fingerprint(self._model_text) != pre_fp

    def _quarantine_batch(self, batch: Batch, reason: str,
                          pre_text: Optional[str], pre_iter: int,
                          detail: str) -> str:
        """Exact rewind + quarantine: the batch's in-flight snapshots
        leave the lineage so a restart (or the next batch) continues
        from state the surviving batches produced."""
        self.mgr.prune_after(pre_iter)
        if pre_text is not None:
            self._model_text, self._model_iter = pre_text, pre_iter
        else:
            self._sync_from_checkpoints()
        self.source.quarantine(batch, reason, detail[:300])
        self._inflight = None
        self._write_ledger()
        return "quarantined"

    # ------------------------------------------------------------------
    # one training attempt (worker thread)
    # ------------------------------------------------------------------
    def _step_callback(self, hb: _Heartbeat, alive):
        def cb(env):
            if not alive():
                # the watchdog abandoned this attempt and a retry owns
                # the checkpoint root now: a recovered-but-slow zombie
                # must stop at its next boundary instead of racing the
                # retry's snapshot writes
                raise RuntimeError("attempt abandoned by the stall "
                                   "watchdog")
            hb.beat(step=True)
            mode = _faults.fire("trainer.step")
            if mode == "error":
                raise RuntimeError("injected fault "
                                   "(trainer.step:error)")
            if mode == "hang":
                # block until the watchdog abandons this attempt; the
                # generation token unblocks the zombie so it exits
                # instead of sleeping forever
                while alive():
                    time.sleep(0.05)
                raise RuntimeError("stalled step abandoned by the "
                                   "watchdog")
            if mode.startswith("sleep_"):
                time.sleep(float(mode[len("sleep_"):]) / 1e3)
        cb.before_iteration = True
        cb.order = -100
        return cb

    def _run_attempt(self, batch: Batch, refit: bool, start_iter: int,
                     target_iter: int, box: Dict[str, Any],
                     hb: _Heartbeat, alive, carrier=None) -> None:
        try:
            # contextvars do not flow into thread targets: re-enter
            # the batch trace so engine.train's 'train' span (and the
            # checkpoint saves, whose extra.json carries the context
            # to the watcher) parent under the batch root
            with _spans.use(carrier):
                eng = self._engine_params()
                hb.beat()
                if refit:
                    self._refit_attempt(batch, eng, start_iter, box,
                                        hb)
                    return
                ds = self._make_dataset(batch, eng)
                hb.beat()
                nv = self._newest_valid_iter()
                resume = nv is not None and nv > start_iter
                kw: Dict[str, Any] = {}
                init_model = None
                if resume:
                    # mid-batch snapshot exists (preempt/crash/stall):
                    # continue BIT-exactly from it; num_boost_round is
                    # the absolute target under resume
                    kw["resume_from"] = "auto"
                    rounds = target_iter
                else:
                    rounds = target_iter - start_iter
                    if self._model_text is not None:
                        init_model = Booster(
                            model_str=self._model_text)
                bst = engine_mod.train(
                    eng, ds, num_boost_round=rounds,
                    init_model=init_model,
                    callbacks=[self._step_callback(hb, alive)],
                    verbose_eval=False, **kw)
                if not alive():
                    return             # abandoned: result is stale
                box["model_text"] = bst.model_to_string(
                    num_iteration=-1)
                box["iter"] = int(bst._gbdt.completed_iterations())
        except NumericalHealthError as exc:
            box["error"] = exc
        except BaseException as exc:       # noqa: BLE001 - the loop
            box["error"] = exc             # owns the failure classes

    def _refit_attempt(self, batch: Batch, eng: Dict[str, Any],
                       start_iter: int, box: Dict[str, Any],
                       hb: _Heartbeat) -> None:
        mode = _faults.fire("trainer.refit")
        if mode == "error":
            raise RuntimeError("injected fault (trainer.refit:error)")
        donor = Booster(model_str=self._model_text)
        hb.beat()
        donor.refit(np.asarray(batch.X), np.asarray(batch.y),
                    weight=None if batch.weight is None
                    else np.asarray(batch.weight),
                    decay_rate=self.refit_decay)
        hb.beat()
        refit_text = donor.model_to_string(num_iteration=-1)
        bad = [float(v) for t in donor._gbdt.models
               for v in t.leaf_value[:max(t.num_leaves, 1)]
               if not np.isfinite(v)]
        if bad:
            raise NumericalHealthError(start_iter, "refit",
                                       f"{len(bad)} non-finite leaf "
                                       f"value(s) after refit")
        # re-seed a TRAINING booster on the batch so the checkpoint
        # carries a model-consistent score/RNG state (refit mutates
        # leaf values in place; the donor's replayed score is stale)
        ds = self._make_dataset(batch, eng)
        bst = Booster(params=eng, train_set=ds)
        bst._gbdt.init_from_model(donor._gbdt.models, ds.raw_mat)
        hb.beat()
        self.mgr.save(bst, reason="refit")
        box["model_text"] = refit_text
        box["iter"] = int(start_iter)

    def _newest_valid_iter(self) -> Optional[int]:
        for iter_, path in reversed(self.mgr.candidates()):
            if not CheckpointManager.validate(path):
                return int(iter_)
        return None
