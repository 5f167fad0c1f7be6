"""CLI application: ``python -m lightgbm_tpu config=train.conf [k=v ...]``.

Capability parity with the reference CLI (``src/application/
application.cpp:30``, ``src/main.cpp``): ``key=value`` args merged over
an optional config file, dispatch on ``task`` = train / predict /
convert_model / refit, reading the reference's ``.conf`` format
verbatim (the ``examples/*/train.conf`` files run unmodified); plus
``task=serve`` — the online micro-batching endpoint the reference has
no analog of (``lightgbm_tpu/serve/``).
"""
from __future__ import annotations

import os
import sys
from typing import Dict, List

import numpy as np

from .config import Config
from .utils.log import Log


def _parse_args(argv: List[str]) -> Dict[str, str]:
    """CLI ``key=value`` pairs + optional ``config=`` file
    (``Application::LoadParameters``, ``application.cpp:48``): explicit
    CLI keys win over config-file keys."""
    cli: Dict[str, str] = {}
    for a in argv:
        if "=" not in a:
            Log.fatal("unknown argument %r (expected key=value)", a)
        k, v = a.split("=", 1)
        cli[k.strip()] = v.strip()
    conf_path = cli.get("config", cli.get("config_file", ""))
    params: Dict[str, str] = {}
    if conf_path:
        with open(conf_path) as f:
            params.update(Config.str2dict(f.read()))
        # data paths inside a conf file are relative to the conf's dir
        base = os.path.dirname(os.path.abspath(conf_path))
        for key in ("data", "train", "train_data", "train_data_file",
                    "valid", "test", "valid_data", "valid_data_file",
                    "test_data", "input_model", "output_model",
                    "output_result", "machine_list_filename",
                    "machine_list_file", "machine_list", "mlist",
                    "forcedsplits_filename", "forced_splits_filename",
                    "forced_splits_file", "forced_splits"):
            if key in params and params[key]:
                p = params[key]
                vals = []
                for item in p.split(","):
                    item = item.strip()
                    if item and not os.path.isabs(item) and \
                            not os.path.exists(item):
                        cand = os.path.join(base, item)
                        if os.path.exists(cand):
                            item = cand
                    vals.append(item)
                params[key] = ",".join(vals)
    params.update(cli)
    params.pop("config", None)
    params.pop("config_file", None)
    return params


def _task_train(params: Dict[str, str], config: Config) -> None:
    from .basic import Booster, Dataset
    from .engine import train

    if not config.data:
        Log.fatal("No training data: set data=<file>")
    train_set = Dataset(config.data, params=params)
    if config.save_binary:
        # cache the binned dataset next to the text file
        # (Dataset::SaveBinaryFile; reloaded transparently by
        # data=<file>.bin on later runs); skip when the input already
        # IS a binary cache
        from .io.dataset import TpuDataset
        if not TpuDataset.is_binary_file(config.data):
            train_set.save_binary(config.data + ".bin")
    valid_sets, valid_names = [], []
    if config.valid:
        # valid_data_initscores: one init-score file per valid set
        vinits = [p.strip() for p in
                  str(config.valid_data_initscores or "").split(",")]
        for i, path in enumerate(str(config.valid).split(",")):
            path = path.strip()
            if not path:
                continue
            init = None
            if i < len(vinits) and vinits[i]:
                from .io.parser import load_float_file
                init = load_float_file(vinits[i])
            valid_sets.append(Dataset(path, params=params,
                                      init_score=init,
                                      reference=train_set))
            valid_names.append(os.path.basename(path))

    callbacks = []
    if config.snapshot_freq > 0 and not config.checkpoint_dir:
        # reference save_period behavior: model-text snapshots.  With
        # checkpoint_dir set, snapshot_freq instead drives the full
        # resumable checkpoints inside engine.train (ckpt/manager.py)
        freq, out_path = config.snapshot_freq, config.output_model

        def _snapshot(env):
            i = env.iteration + 1
            if i % freq == 0:
                env.model.save_model(f"{out_path}.snapshot_iter_{i}")
                Log.info("Saved snapshot at iteration %d", i)
        callbacks.append(_snapshot)

    init_model = config.input_model or None
    booster = train(params, train_set,
                    num_boost_round=config.num_iterations,
                    valid_sets=valid_sets or None,
                    valid_names=valid_names or None,
                    init_model=init_model,
                    callbacks=callbacks or None,
                    verbose_eval=max(config.metric_freq, 1))
    booster.save_model(config.output_model)
    Log.info("Finished training; model saved to %s", config.output_model)
    _close_telemetry(booster)


def _task_predict(params: Dict[str, str], config: Config) -> None:
    from .basic import Booster
    from .io.parser import parse_file

    if not config.input_model:
        Log.fatal("No model file: set input_model=<file>")
    if not config.data:
        Log.fatal("No data to predict: set data=<file>")
    from .io.parser import parse_file_full
    booster = Booster(model_file=config.input_model)
    if config.telemetry_file:
        # loaded boosters skip GBDT.__init__; the inference entry
        # points still feed run records once a recorder is attached
        booster._gbdt.attach_telemetry(config.telemetry_file)
    # drop the same non-feature columns training dropped, or feature
    # indices shift against the trained model
    X, _, _, _, _ = parse_file_full(
        config.data, header=config.header,
        label_column=config.label_column,
        ignore_columns=config.ignore_column,
        weight_column=config.weight_column,
        group_column=config.group_column)
    num_iteration = config.num_iteration_predict \
        if config.num_iteration_predict > 0 else None
    kw = {}
    if config.pred_early_stop:
        kw = {"pred_early_stop": True,
              "pred_early_stop_freq": config.pred_early_stop_freq,
              "pred_early_stop_margin": config.pred_early_stop_margin}
    if config.predict_leaf_index:
        out = booster.predict(X, num_iteration=num_iteration,
                              pred_leaf=True)
    elif config.predict_contrib:
        out = booster.predict(X, num_iteration=num_iteration,
                              pred_contrib=True)
    elif config.predict_raw_score:
        out = booster.predict(X, num_iteration=num_iteration,
                              raw_score=True, **kw)
    else:
        out = booster.predict(X, num_iteration=num_iteration, **kw)
    out = np.atleast_1d(np.asarray(out))
    with open(config.output_result, "w") as f:
        if out.ndim == 1:
            f.writelines(f"{v:.18g}\n" for v in out)
        else:
            f.writelines("\t".join(f"{v:.18g}" for v in row) + "\n"
                         for row in out)
    Log.info("Finished prediction; results saved to %s",
             config.output_result)
    _close_telemetry(booster)


def _close_telemetry(booster) -> None:
    """Flush the run_end record + Log summary at task end (the atexit
    hook would also fire, but an explicit close keeps the CLI's JSONL
    complete even when the interpreter is torn down abruptly)."""
    rec = getattr(booster._gbdt, "_telemetry", None)
    if rec is not None:
        rec.close()


def _task_convert_model(params: Dict[str, str], config: Config) -> None:
    from .basic import Booster
    from .models.codegen import model_to_ifelse

    if not config.input_model:
        Log.fatal("No model file: set input_model=<file>")
    if config.convert_model_language not in ("", "cpp"):
        Log.fatal("convert_model_language %r not supported (cpp only)",
                  config.convert_model_language)
    booster = Booster(model_file=config.input_model)
    code = model_to_ifelse(booster._gbdt.models,
                           booster._gbdt.num_tree_per_iteration,
                           booster._objective_string())
    with open(config.convert_model, "w") as f:
        f.write(code)
    Log.info("Finished converting model; code saved to %s",
             config.convert_model)


def _task_serve(params: Dict[str, str], config: Config) -> None:
    """Online serving: load the model, publish it to the registry
    (flatten + pre-warm), serve the threaded JSON endpoint until a
    SIGTERM/SIGINT triggers the graceful drain (``serve/http.py``).
    Pointed at a checkpoint ROOT, a watcher thread additionally
    tracks the root: each new snapshot is manifest-verified and
    canary-scored before auto-publish, with telemetry-driven rollback
    (``serve/watcher.py``, ``docs/Resilience.md``)."""
    from .basic import Booster
    from .ckpt import CheckpointManager
    from .obs import flight as _flight
    from .obs import spans as _spans
    from .serve import (CheckpointWatcher, FleetConfig, RegistryTarget,
                        Server, ServeConfig)
    from .serve.http import serve_http

    if not config.input_model:
        Log.fatal("No model file: set input_model=<file> (a model "
                  "file, a ckpt_* checkpoint directory, or a "
                  "checkpoint root)")
    _flight.ensure_installed(config)
    server = Server(config=ServeConfig.from_params(config))
    # a supervisor-spawned replica marks its boot against the spawn
    # trace (LTPU_TRACE env carrier) without adopting it process-wide
    boot_carrier = _spans.parse(os.environ.get(_spans.ENV_VAR, ""))
    if boot_carrier is not None:
        _spans.point("replica_boot", boot_carrier,
                     recorder=server._recorder, pid=os.getpid())
    watcher = None
    if os.path.isdir(config.input_model):
        # serve straight from a training checkpoint directory/root:
        # manifest-validated, newest-valid-wins (ckpt/manager.py)
        server.registry.publish_from_checkpoint(config.input_model)
        if not CheckpointManager.is_checkpoint_dir(config.input_model):
            # a ROOT is a live deploy pipeline: watch it (validated
            # auto-publish + rollback); an explicit ckpt_* dir is a
            # one-shot serve
            fcfg = FleetConfig.from_params(config)
            watcher = CheckpointWatcher(
                config.input_model,
                RegistryTarget(server, model=fcfg.tenant),
                config=fcfg, recorder=server._recorder).start()
    else:
        server.registry.publish(Booster(model_file=config.input_model))
    try:
        serve_http(server)
    finally:
        if watcher is not None:
            watcher.stop()
        server.stop()


def _task_route(params: Dict[str, str], config: Config) -> None:
    """Routing front (``serve/router.py``, ``docs/Routing.md``): a
    shared-nothing HTTP router balancing over the replica URLs in
    ``route_backends`` with live health/draining/fingerprint
    awareness, bounded retries + hedging, per-backend circuit
    breakers and per-model admission budgets.  Runs until a
    SIGTERM/SIGINT drains it.  Programmatic deployments attach
    FleetSupervisors instead (``Router.add_model``).

    ``slo_enable=true`` runs the SLO engine next to the router
    (burn-rate evaluation over the standard router objectives,
    ``obs/slo.py``); ``autoscale=true`` additionally runs the
    closed-loop controller — with a static backend table its only
    lever is the admission retune (no supervisor to scale), which is
    exactly the degraded-capacity posture the controller is built
    for.  ``docs/Serving.md`` has the full control-policy table."""
    from .serve.config import (AutoscaleConfig, RouterConfig,
                               SloConfig)
    from .serve.router import Router, parse_backends_spec, route_http

    rcfg = RouterConfig.from_params(config)
    table = parse_backends_spec(rcfg.backends)
    if not table:
        Log.fatal("task=route requires route_backends=<url[,name=url+"
                  "url...]> (static table) — programmatic routers use "
                  "Router.add_model")
    recorder = None
    if config.telemetry_file:
        from .utils import telemetry as _telemetry
        recorder = _telemetry.RunRecorder(
            config.telemetry_file, run_info={"task": "route",
                                             "backend": "none"})
    router = Router(rcfg, recorder=recorder)
    for name, urls in table.items():
        router.add_model(name, urls=urls,
                         replica_model="default" if name == "default"
                         else name)
    slo_engine = None
    scaler = None
    scfg = SloConfig.from_params(config)
    acfg = AutoscaleConfig.from_params(config)
    if scfg.enable or acfg.enable:
        from .obs.slo import SloEngine, router_objectives
        slo_engine = SloEngine(router_objectives(router, scfg),
                               config=scfg, recorder=recorder).start()
    if acfg.enable:
        from .serve.autoscaler import Autoscaler
        scaler = Autoscaler(router=router, slo=slo_engine, config=acfg,
                            recorder=recorder).start()
    try:
        route_http(router)
    finally:
        if scaler is not None:
            scaler.stop()
        if slo_engine is not None:
            slo_engine.stop()
        router.stop()
        if recorder is not None:
            recorder.close()


def _task_continual(params: Dict[str, str], config: Config) -> None:
    """Continual training daemon (``docs/Continual.md``): tail
    ``continual_ingest_dir`` for batch shards, gate each through the
    validation pipeline, extend/refit the model, checkpoint into
    ``checkpoint_dir`` — which a serve-tier watcher (``task=serve``
    pointed at the same root) canary-validates and auto-publishes.
    SIGTERM/SIGINT checkpoint at the next served boundary and drain;
    restart resumes bit-exactly."""
    from . import engine as engine_mod
    from .cont import ContinualTrainer
    from .utils import telemetry as _telemetry

    if not config.checkpoint_dir:
        Log.fatal("task=continual requires checkpoint_dir (the "
                  "checkpoint root doubles as the publish root)")
    if not config.continual_ingest_dir:
        Log.fatal("task=continual requires continual_ingest_dir")
    recorder = None
    if config.telemetry_file:
        recorder = _telemetry.RunRecorder(config.telemetry_file)
    # the guard owns SIGTERM/SIGINT on the MAIN thread and raises the
    # process-wide preempt flag the worker-thread training loops
    # observe (engine.request_preempt)
    guard = engine_mod.install_preempt_guard()
    trainer = ContinualTrainer(params, recorder=recorder)
    try:
        stats = trainer.run()
    finally:
        guard.restore()
        if recorder is not None:
            recorder.close()
    Log.info("continual: exit (%s)", stats.get("status", "?"))


def _task_sweep(params: Dict[str, str], config: Config) -> None:
    """Hyperparameter sweep + k-fold CV as one compiled booster
    battery (``engine.sweep``, ``docs/Sweep.md``): candidates from
    ``sweep_grid`` (x ``sweep_random``) score on ``sweep_folds``-fold
    CV over the ONE shared dataset; the winner's full-data model is
    saved to ``output_model``."""
    from .basic import Dataset
    from .engine import sweep
    from .utils import telemetry as _telemetry

    if not config.data:
        Log.fatal("No training data: set data=<file>")
    if not config.sweep_grid and not config.sweep_random:
        Log.warning("task=sweep without sweep_grid: scoring the base "
                    "params on %d-fold CV only", config.sweep_folds)
    recorder = None
    if config.telemetry_file:
        recorder = _telemetry.RunRecorder(
            config.telemetry_file, run_info={"task": "sweep",
                                             "backend": "none"})
        _telemetry.set_recorder(recorder)
    train_set = Dataset(config.data, params=params)
    try:
        res = sweep(params, train_set,
                    num_boost_round=config.num_iterations)
        if res.best_index < 0:
            Log.fatal("sweep: every candidate failed")
        Log.info("sweep: winner c%d %s=%.6g at iteration %d (%s)",
                 res.best_index, res.metric_name, res.best_score,
                 res.best_iteration,
                 ";".join(f"{k}={v}" for k, v in
                          res.candidates[res.best_index].items())
                 or "base params")
        with open(config.output_model, "w") as f:
            f.write(res.model_text)
        Log.info("Finished sweep; winner saved to %s",
                 config.output_model)
    finally:
        if recorder is not None:
            _telemetry.set_recorder(None)
            recorder.close()


def _task_refit(params: Dict[str, str], config: Config) -> None:
    from .basic import Booster
    from .io.parser import parse_file

    if not config.input_model:
        Log.fatal("No model file: set input_model=<file>")
    if not config.data:
        Log.fatal("No data to refit with: set data=<file>")
    from .io.parser import parse_file_full
    booster = Booster(model_file=config.input_model)
    X, y, _, w, _ = parse_file_full(
        config.data, header=config.header,
        label_column=config.label_column,
        ignore_columns=config.ignore_column,
        weight_column=config.weight_column,
        group_column=config.group_column)
    if y is None:
        Log.fatal("refit requires labels in the data file")
    booster.refit(X, y, weight=w, decay_rate=config.refit_decay_rate)
    booster.save_model(config.output_model)
    Log.info("Finished refit; model saved to %s", config.output_model)


def main(argv: List[str] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        print("tasks: train | predict | convert_model | refit | serve "
              "| route | continual | sweep")
        return 0
    from .utils.env import configure_compile_cache
    configure_compile_cache()
    params = _parse_args(argv)
    config = Config(params)
    task = config.task
    if task == "train":
        _task_train(params, config)
    elif task in ("predict", "prediction", "test"):
        _task_predict(params, config)
    elif task == "convert_model":
        _task_convert_model(params, config)
    elif task in ("refit", "refit_tree"):
        _task_refit(params, config)
    elif task == "serve":
        _task_serve(params, config)
    elif task in ("route", "router"):
        _task_route(params, config)
    elif task in ("continual", "continual_train"):
        _task_continual(params, config)
    elif task == "sweep":
        _task_sweep(params, config)
    else:
        Log.fatal("unknown task %r", task)
    return 0


if __name__ == "__main__":
    sys.exit(main())
