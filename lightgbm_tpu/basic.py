"""Public ``Dataset`` / ``Booster`` API.

Capability parity with ``python-package/lightgbm/basic.py``: lazy
``Dataset`` construction with reference alignment for validation sets,
pandas and categorical handling, field get/set; ``Booster`` with
train/eval/predict (raw / leaf index / SHAP contrib), model
save/load/dump and continue-training.

TPU-first: there is no ctypes bridge — the "native" layer is the JAX
device program (``ops/``), and the Dataset pushes one dense binned
matrix to HBM instead of per-feature Bin columns.
"""
from __future__ import annotations

import io
import os
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from .config import Config
from .io.binning import BIN_CATEGORICAL
from .io.dataset import Metadata, TpuDataset
from .io.parser import load_float_file, load_query_file, parse_file_full
from .metrics import Metric, create_metrics, default_metric_for
from .models.gbdt import GBDT
from .models.boosting import create_boosting
from .models import model_io
from .models.tree import Tree
from .objectives import create_objective
from .utils.log import Log
from .utils.profiling import timed

__all__ = ["Dataset", "Booster"]


def _resolve_cat_indices(spec, names):
    """Name-or-index categorical spec -> column indices (shared by the
    file / sparse / matrix construction branches)."""
    cat_idx = []
    for c in spec:
        if isinstance(c, str):
            if not names or c not in names:
                Log.fatal("categorical feature name %s not found", c)
            cat_idx.append(names.index(c))
        else:
            cat_idx.append(int(c))
    return cat_idx


def _to_matrix(data, feature_name="auto", categorical_feature="auto"):
    """Normalize input data to (matrix, feature_names, categorical_idx)."""
    cat_idx: List[int] = []
    names = None
    if hasattr(data, "dtypes") and hasattr(data, "columns"):  # pandas
        import pandas as pd
        df = data.copy()
        names = [str(c) for c in df.columns]
        for i, col in enumerate(df.columns):
            if str(df[col].dtype) == "category":
                df[col] = df[col].cat.codes
                cat_idx.append(i)
            elif df[col].dtype == object:
                Log.fatal("pandas object column %s is not supported; "
                          "use category dtype or numeric", col)
        mat = df.values
        if mat.dtype != np.float32:
            mat = mat.astype(np.float64)
    elif hasattr(data, "toarray"):
        # scipy CSR/CSC/COO: densify (the TPU layout is dense; EFB
        # re-narrows exclusive sparse columns downstream), matching the
        # C API's CSR/CSC construction surface (c_api.h:48-232)
        mat = np.asarray(data.toarray())
        if mat.dtype != np.float32:
            mat = mat.astype(np.float64)
    else:
        # float32 is kept narrow (the reference's python binding casts
        # everything to float32, basic.py:270); other dtypes go f64
        mat = np.asarray(data)
        if mat.dtype != np.float32:
            mat = np.asarray(mat, dtype=np.float64)
        if mat.ndim == 1:
            mat = mat.reshape(-1, 1)
    if feature_name != "auto" and feature_name is not None:
        names = list(feature_name)
    if categorical_feature != "auto" and categorical_feature is not None:
        cat_idx = _resolve_cat_indices(categorical_feature, names)
    return mat, names, cat_idx


class Dataset:
    """Training/validation data container (lazy construction like the
    reference: binning happens at first use, and validation sets align
    their bins with their ``reference`` train set)."""

    def __init__(self, data, label=None, reference: "Dataset" = None,
                 weight=None, group=None, init_score=None,
                 feature_name="auto", categorical_feature="auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = False, silent: bool = False):
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params) if params else {}
        self.free_raw_data = free_raw_data
        self._constructed: Optional[TpuDataset] = None
        self.raw_mat: Optional[np.ndarray] = None
        self.used_indices: Optional[np.ndarray] = None
        # streaming construction (C API PushRows / CreateByReference):
        # a pre-allocated (num_total_row, ncol) buffer filled in chunks;
        # when full it becomes self.data
        self._stream: Optional[Dict[str, Any]] = None
        # bin mappers fixed ahead of data (CreateFromSampledColumn)
        self._preset_mappers = None

    # ------------------------------------------------------------------
    def construct(self) -> "Dataset":
        if self._constructed is not None:
            return self
        cfg = Config(self.params)
        label, weight, group = self.label, self.weight, self.group
        if self.categorical_feature in ("auto", None) and \
                getattr(cfg, "categorical_feature", ""):
            # params/conf-file spec (``categorical_feature=6,7,8`` or
            # ``name:c1,c2`` — io/config.h categorical_feature): the
            # reference honors it for FILE data too, so resolve it
            # before the data-source branches
            spec = cfg.categorical_feature
            if isinstance(spec, str):
                spec = spec[5:] if spec.startswith("name:") else spec
                spec = [s.strip() for s in spec.split(",") if s.strip()]
                spec = [int(s) if s.lstrip("+-").isdigit() else s
                        for s in spec]
            self.categorical_feature = list(spec)

        stream_ok = False
        if getattr(cfg, "stream_ingest", False) and \
                self.reference is None and self.used_indices is None:
            if isinstance(self.data, (str, os.PathLike)):
                # only the streamed loader's own formats: a directory
                # of npz shards or an .X.npy mmap pair.  CSV/LibSVM/
                # binary-dataset paths fall through to the normal
                # loader rather than failing inside the stream path
                path = str(self.data)
                stem = path[:-len(".X.npy")] \
                    if path.endswith(".X.npy") else path
                stream_ok = os.path.isdir(path) or \
                    os.path.exists(stem + ".X.npy")
            else:
                stream_ok = self.data is not None and \
                    not hasattr(self.data, "tocsc")
            if not stream_ok:
                Log.warning("stream_ingest=true ignored: %r is not a "
                            "streamable source (ndarray, <stem>.X.npy "
                            "mmap pair, or npz shard directory); "
                            "using the in-memory loader",
                            type(self.data).__name__
                            if not isinstance(self.data,
                                              (str, os.PathLike))
                            else str(self.data))
        if stream_ok:
            # out-of-core streamed ingest (docs/Streaming.md): the raw
            # matrix is binned chunk-by-chunk into the crash-safe
            # mmap cache and never fully materializes on the host;
            # the trained model is byte-identical to this same data
            # through the in-memory path.  Validation sets (reference
            # is set) stay on the in-memory alignment path.
            from .io import stream as stream_mod
            self._constructed = stream_mod.ingest_dataset(
                self.data, label=label, weight=weight, group=group,
                init_score=self.init_score, config=cfg,
                feature_name=self.feature_name,
                categorical_feature=self.categorical_feature)
            self.raw_mat = None
            if self.feature_name == "auto":
                self.feature_name = self._constructed.feature_names
            return self
        if isinstance(self.data, (str, os.PathLike)):
            from .utils.file_io import is_remote, localize
            remote = is_remote(str(self.data))
            path = localize(str(self.data))
            if TpuDataset.is_binary_file(path):
                self._constructed = TpuDataset.load_binary(path)
                self.raw_mat = None
                return self
            mat, y, names, w, g = parse_file_full(
                path, header=cfg.header, label_column=cfg.label_column,
                ignore_columns=cfg.ignore_column,
                weight_column=cfg.weight_column,
                group_column=cfg.group_column)
            label = y if label is None else label
            if w is not None and weight is None:
                weight = w
            if g is not None and group is None:
                group = g
            # sidecar files ride next to the data; remote datasets skip
            # the probe (a missing remote sidecar is indistinguishable
            # from a fetch failure)
            sw = None if remote else load_float_file(path + ".weight")
            if sw is not None and weight is None:
                weight = sw
            sq = None if remote else load_query_file(path + ".query")
            if sq is not None and group is None:
                group = sq
            # initscore_filename overrides the ``<data>.init`` sidecar
            # for the TRAINING set only; valid sets get theirs from
            # valid_data_initscores (wired in the CLI)
            init_path = ""
            if self.reference is None:
                init_path = getattr(cfg, "initscore_filename", "")
            si = load_float_file(init_path) if init_path else \
                (None if remote else load_float_file(path + ".init"))
            if si is not None and self.init_score is None:
                self.init_score = si
            cat_idx = []
            if self.categorical_feature not in ("auto", None):
                cat_idx = _resolve_cat_indices(self.categorical_feature,
                                               names)
            if self.feature_name == "auto":
                self.feature_name = names
        elif hasattr(self.data, "tocsc") and self.used_indices is None:
            # scipy sparse: chunked CSC binning, no f64 densify (the
            # round-2 verdict's Bosch/Epsilon-scale memory hazard)
            names = self.feature_name \
                if self.feature_name not in ("auto", None) else None
            cat_idx = []
            if self.categorical_feature not in ("auto", None):
                cat_idx = _resolve_cat_indices(self.categorical_feature,
                                               names)
            mappers = None
            if self.reference is not None:
                self.reference.construct()
                mappers = self.reference._constructed.mappers
            with timed("dataset/bin"):
                self._constructed = TpuDataset.from_sparse(
                    self.data, label, cfg, weight=weight, group=group,
                    init_score=self.init_score, feature_names=names,
                    categorical_features=cat_idx, mappers=mappers)
            # raw stays SPARSE; dense consumers densify on demand
            self.raw_mat = None if self.free_raw_data else self.data
            return self
        else:
            mat, names, cat_idx = _to_matrix(self.data, self.feature_name,
                                             self.categorical_feature)
            if self.feature_name == "auto":
                self.feature_name = names

        if self.used_indices is not None:
            mat = mat[self.used_indices]
            label = None if label is None else \
                np.asarray(label)[self.used_indices]
            weight = None if weight is None else \
                np.asarray(weight)[self.used_indices]
            # group subsetting handled by caller providing group directly

        mappers = self._preset_mappers
        if self.reference is not None:
            self.reference.construct()
            mappers = self.reference._constructed.mappers
        with timed("dataset/bin"):
            self._constructed = TpuDataset.from_raw(
                mat, label, cfg, weight=weight, group=group,
                init_score=self.init_score,
                feature_names=self.feature_name or None,
                categorical_features=cat_idx, mappers=mappers)
        self.raw_mat = None if self.free_raw_data else mat
        return self

    # ------------------------------------------------------------------
    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score,
                       params=params or self.params)

    def subset(self, used_indices, params=None) -> "Dataset":
        # bins must MATCH the parent (the reference's CopySubrow shares
        # the parent's mappers): a root dataset becomes its subset's
        # reference; a valid set's subset keeps the original reference
        ds = Dataset(self.data, label=self.label,
                     reference=self.reference if self.reference is not None
                     else self,
                     weight=self.weight, group=None,
                     feature_name=self.feature_name,
                     categorical_feature=self.categorical_feature,
                     params=params or self.params)
        ds.used_indices = np.asarray(used_indices)
        return ds

    def save_binary(self, filename: str) -> "Dataset":
        self.construct()
        from .utils.file_io import is_remote
        filename = str(filename)
        if is_remote(filename):
            import shutil
            import tempfile
            from .utils.file_io import open_output
            with tempfile.NamedTemporaryFile(suffix=".bin") as tmp:
                self._constructed.save_binary(tmp.name)
                with open(tmp.name, "rb") as src, \
                        open_output(filename, "wb") as dst:
                    shutil.copyfileobj(src, dst)
        else:
            self._constructed.save_binary(filename)
        return self

    # ---- field access -------------------------------------------------
    def num_data(self) -> int:
        self.construct()
        return self._constructed.num_data

    def num_feature(self) -> int:
        self.construct()
        return self._constructed.num_total_features

    def get_label(self):
        self.construct()
        return np.asarray(self._constructed.metadata.label)

    def get_weight(self):
        self.construct()
        return self._constructed.metadata.weight

    def get_group(self):
        self.construct()
        qb = self._constructed.metadata.query_boundaries
        return None if qb is None else np.diff(qb)

    def get_init_score(self):
        self.construct()
        return self._constructed.metadata.init_score

    def set_label(self, label):
        self.label = label
        if self._constructed is not None:
            self._constructed.metadata.set_label(label)
        return self

    def set_weight(self, weight):
        self.weight = weight
        if self._constructed is not None:
            self._constructed.metadata.set_weight(weight)
        return self

    def set_group(self, group):
        self.group = group
        if self._constructed is not None:
            self._constructed.metadata.set_query(group)
        return self

    def set_init_score(self, init_score):
        self.init_score = init_score
        if self._constructed is not None:
            self._constructed.metadata.set_init_score(init_score)
        return self

    # ---- streaming construction (C API surface) ----------------------
    def begin_streaming(self, num_total_row: int, ncol: int) -> None:
        """Pre-allocate the push buffer (``LGBM_DatasetCreateByReference``
        + ``LGBM_DatasetPushRows``, ``c_api.h:81-125``)."""
        self._stream = {
            "buf": np.zeros((int(num_total_row), int(ncol)), np.float64),
            "total": int(num_total_row),
        }

    def push_rows(self, rows: np.ndarray, start_row: int) -> None:
        if self._stream is None:
            Log.fatal("push_rows on a dataset not created for streaming")
        if self._constructed is not None:
            Log.fatal("push_rows after dataset construction")
        s = self._stream
        rows = np.asarray(rows, np.float64)
        s["buf"][start_row:start_row + rows.shape[0]] = rows
        # the FinishLoad trigger is POSITIONAL (c_api.h:86: "if nrow +
        # start_row == num_total_row, will call dataset->FinishLoad"),
        # so re-pushed/overlapping chunks cannot finalize early
        if start_row + rows.shape[0] >= s["total"]:
            self.data = s["buf"]
            self._stream = None

    def set_feature_names(self, names) -> "Dataset":
        self.feature_name = [str(n) for n in names]
        if self._constructed is not None:
            self._constructed.feature_names = list(self.feature_name)
        return self

    def get_feature_names(self):
        if self._constructed is not None:
            return list(self._constructed.feature_names)
        return list(self.feature_name) if self.feature_name and \
            self.feature_name != "auto" else []

    def update_params(self, params: Dict[str, Any]) -> "Dataset":
        """``LGBM_DatasetUpdateParam`` (``c_api.h:318``): merge params;
        binning-affecting changes only apply before construction."""
        if self._constructed is not None and params:
            Log.warning("dataset is already constructed; updated "
                        "parameters only affect future operations")
        self.params = {**self.params, **(params or {})}
        return self

    def set_field(self, name, data):
        return {"label": self.set_label, "weight": self.set_weight,
                "group": self.set_group,
                "init_score": self.set_init_score}[name](data)

    def get_field(self, name):
        return {"label": self.get_label, "weight": self.get_weight,
                "group": self.get_group,
                "init_score": self.get_init_score}[name]()


class Booster:
    """Trained model handle (``basic.py:1485`` in the reference)."""

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None, silent: bool = False,
                 mesh=None):
        params = dict(params) if params else {}
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._gbdt: Optional[GBDT] = None
        self._loaded: Optional[Dict] = None
        self.train_set = train_set
        self.params = params

        if train_set is not None:
            if not isinstance(train_set, Dataset):
                Log.fatal("train_set must be a Dataset")
            train_set.params = {**train_set.params, **params}
            train_set.construct()
            self.config = Config(params)
            if self.config.objective in ("none", "custom", "null", "na"):
                objective = None  # custom fobj supplies gradients
            else:
                objective = create_objective(self.config.objective,
                                             self.config)
            self._metric_names = self._resolve_metric_names(self.config)
            metrics = create_metrics(self._metric_names, self.config)
            self._gbdt = create_boosting(self.config, train_set._constructed,
                                         objective, metrics, mesh=mesh)
            self._valid_names: List[str] = []
        elif model_file is not None or model_str is not None:
            if model_file is not None:
                from .utils.file_io import localize
                with open(localize(str(model_file))) as f:
                    model_str = f.read()
            self._load_from_string(model_str)
        else:
            Log.fatal("need train_set, model_file or model_str")

    @staticmethod
    def _resolve_metric_names(config) -> List[str]:
        m = config.metric
        if isinstance(m, str):
            names = [t.strip() for t in m.split(",")] if m else []
        else:
            names = list(m or [])
        if not names:
            if config.objective in ("none", "custom", "null", "na"):
                return []
            names = [default_metric_for(config.objective)]
        if any(n.lower() in ("none", "na", "null") for n in names):
            return []
        return names

    # ------------------------------------------------------------------
    def _load_from_string(self, text: str) -> None:
        info = model_io.load_model_from_string(text)
        self._loaded = info
        obj_str = info["objective"].split()
        cfg_params: Dict[str, Any] = {"objective": obj_str[0] or "regression"}
        for tok in obj_str[1:]:
            if ":" in tok:
                k, v = tok.split(":", 1)
                cfg_params[k] = v
        cfg_params["num_class"] = info["num_class"]
        self.config = Config(cfg_params)
        self._gbdt = GBDT.__new__(GBDT)
        g = self._gbdt
        g.config = self.config
        g.train_set = None
        g.models = info["models"]
        g.num_class = info["num_class"]
        g.num_tree_per_iteration = info["num_tree_per_iteration"]
        g.metrics = []
        g.valid_sets = []
        g.iter = len(info["models"]) // max(info["num_tree_per_iteration"], 1)
        g.average_output = bool(info.get("average_output"))
        g.objective = (create_objective(self.config.objective, self.config)
                       if obj_str and obj_str[0] else None)
        self._feature_names = info["feature_names"]
        self._feature_infos = info["feature_infos"]
        self._max_feature_idx = info["max_feature_idx"]
        self._valid_names = []

    # ------------------------------------------------------------------
    def add_valid(self, data: Dataset, name: str) -> "Booster":
        data.reference = data.reference or self.train_set
        data.construct()
        if not self.train_set._constructed.check_align(data._constructed):
            Log.fatal("validation set %s bins are not aligned with the "
                      "training set (construct it with reference=train_set)",
                      name)
        if data.raw_mat is None:
            Log.fatal("validation set %s needs raw data for evaluation "
                      "(free_raw_data=False)", name)
        self._gbdt.add_valid(name, data.raw_mat, data._constructed.metadata,
                             binned=data._constructed)
        self._valid_names.append(name)
        # kept for re-registration across reset_training_data /
        # reset_parameter (the reference keeps valid sets registered)
        self._valid_pairs = getattr(self, "_valid_pairs", [])
        self._valid_pairs.append((data, name))
        return self

    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting iteration; returns True if training should stop."""
        if train_set is not None and train_set is not self.train_set:
            self.reset_training_data(train_set)
        if fobj is None:
            return self._gbdt.train_one_iter()
        score = self._gbdt.train_score[0]
        grad, hess = fobj(score.astype(np.float64), self.train_set)
        return self._gbdt.train_one_iter(np.asarray(grad, np.float32),
                                         np.asarray(hess, np.float32))

    def rollback_one_iter(self) -> "Booster":
        self._gbdt.rollback_one_iter()
        return self

    # ------------------------------------------------------------------
    def _rebuild_gbdt(self, train_set: Dataset) -> None:
        """Recreate the boosting driver on ``train_set`` and replay the
        existing model into it (``GBDT::ResetTrainingData`` /
        ``ResetConfig``, ``include/LightGBM/boosting.h:52-55``)."""
        train_set.params = {**train_set.params, **self.params}
        train_set.construct()
        if train_set.raw_mat is None:
            Log.fatal("resetting training data requires raw data "
                      "(free_raw_data=False)")
        models = self._gbdt.models if self._gbdt is not None else []
        if self.config.objective in ("none", "custom", "null", "na"):
            objective = None
        else:
            objective = create_objective(self.config.objective, self.config)
        self._metric_names = self._resolve_metric_names(self.config)
        metrics = create_metrics(self._metric_names, self.config)
        g = create_boosting(self.config, train_set._constructed,
                            objective, metrics)
        if models:
            g.init_from_model(models, train_set.raw_mat)
        self._gbdt = g
        self.train_set = train_set
        # re-register the validation sets on the fresh driver — the
        # reference's ResetConfig/ResetTrainingData keep them attached
        pairs = getattr(self, "_valid_pairs", [])
        self._valid_names = []
        self._valid_pairs = []
        for data, name in pairs:
            self.add_valid(data, name)

    def reset_training_data(self, train_set: Dataset) -> "Booster":
        """Re-point the booster at a new training set, keeping the
        model (``LGBM_BoosterResetTrainingData``, ``c_api.h:411``)."""
        if not isinstance(train_set, Dataset):
            Log.fatal("train_set must be a Dataset")
        self._rebuild_gbdt(train_set)
        return self

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        """Update boosting parameters in place
        (``LGBM_BoosterResetParameter``, ``c_api.h:420``)."""
        self.params = {**self.params, **params}
        self.config = Config(self.params)
        if self.train_set is not None:
            self._rebuild_gbdt(self.train_set)
        return self

    def merge(self, other: "Booster") -> "Booster":
        """Merge ``other``'s trees in front of this booster's
        (``LGBM_BoosterMerge``, ``c_api.h:393``)."""
        self._gbdt.merge_from(other._gbdt)
        return self

    def shuffle_models(self, start_iteration: int = 0,
                       end_iteration: int = -1) -> "Booster":
        self._gbdt.shuffle_models(start_iteration, end_iteration)
        return self

    def refit(self, data, label, weight=None,
              decay_rate: float = 0.9) -> "Booster":
        """Refit the trees' leaf values to new data in place
        (``GBDT::RefitTree``, ``gbdt.cpp:265``)."""
        mat, _, _ = _to_matrix(data)
        self._gbdt.refit(mat, label, weight=weight, decay_rate=decay_rate)
        return self

    def current_iteration(self) -> int:
        return self._gbdt.iter

    def num_trees(self) -> int:
        return len(self._gbdt.models)

    # ------------------------------------------------------------------
    def eval_set(self):
        return self._gbdt.eval_set()

    def eval_valid(self):
        return [r for r in self._gbdt.eval_set() if r[0] != "training"]

    def eval_train(self):
        return [r for r in self._gbdt.eval_set() if r[0] == "training"]

    # ------------------------------------------------------------------
    def predict(self, data, num_iteration: Optional[int] = None,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False, **kwargs) -> np.ndarray:
        if isinstance(data, Dataset):
            Log.fatal("predict() takes a raw matrix, not a Dataset")
        if isinstance(data, (str, os.PathLike)):
            from .io.parser import parse_file
            data, _, _ = parse_file(str(data), header=False)
        mat, _, _ = _to_matrix(data)
        # only num_iteration=None defaults to best_iteration; an explicit
        # -1/0 means the full ensemble (reference basic.py semantics)
        if num_iteration is None:
            ni = self.best_iteration if self.best_iteration > 0 else -1
        else:
            ni = num_iteration
        # per-call inference-engine overrides (no shared-config
        # mutation: concurrent predicts on one booster stay safe)
        eng = {k: kwargs[k] for k in ("predict_engine",
                                      "predict_chunk_rows")
               if kwargs.get(k) is not None}
        if pred_leaf:
            return self._gbdt.predict_leaf_index(mat, ni, **eng)
        if pred_contrib:
            return self._gbdt.predict_contrib(mat, ni, **eng)
        es = {}
        if kwargs.get("pred_early_stop"):
            es = {"early_stop": True,
                  "early_stop_freq": int(
                      kwargs.get("pred_early_stop_freq", 10)),
                  "early_stop_margin": float(
                      kwargs.get("pred_early_stop_margin", 10.0))}
        if raw_score:
            return self._gbdt.predict_raw(mat, ni, **es, **eng)
        if es:
            raw = self._gbdt.predict_raw(mat, ni, **es, **eng)
            obj = self._gbdt.objective
            return obj.convert_output(raw) if obj is not None else raw
        return self._gbdt.predict(mat, ni, **eng)

    def predict_cache_info(self) -> Dict[str, int]:
        """Inference-engine compile-cache counters (hits / misses /
        evictions / entries / capacity / traces).  The engine is
        process-wide — boosters with identical layouts share compiled
        predictors — so these are process counters, not per-booster;
        the serve layer and tests use them to pin cache behavior."""
        from .ops.predict import get_engine
        return get_engine().cache_info()

    # ------------------------------------------------------------------
    def _objective_string(self) -> str:
        obj = self.config.objective
        if obj in ("none", "custom", "null", "na"):
            return ""
        if obj == "binary":
            return f"binary sigmoid:{self.config.sigmoid:g}"
        if obj in ("multiclass", "multiclassova"):
            return f"{obj} num_class:{self.config.num_class}"
        if obj == "lambdarank":
            return "lambdarank"
        return obj

    def _model_slice(self, start_iteration: int):
        """Trees from ``start_iteration`` on (``c_api.h`` SaveModel /
        DumpModel start_iteration semantics)."""
        g = self._gbdt
        if start_iteration and start_iteration > 0:
            k = max(g.num_tree_per_iteration, 1)
            return g.models[start_iteration * k:]
        return g.models

    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0) -> str:
        g = self._gbdt
        if g.train_set is not None:
            names = g.train_set.feature_names
            infos = g.train_set.feature_infos()
            max_fi = g.train_set.num_total_features - 1
        else:
            names, infos = self._feature_names, self._feature_infos
            max_fi = self._max_feature_idx
        ni = num_iteration if num_iteration is not None else \
            (self.best_iteration if self.best_iteration > 0 else -1)
        return model_io.save_model_to_string(
            self._model_slice(start_iteration), num_class=g.num_class,
            num_tree_per_iteration=g.num_tree_per_iteration,
            label_index=0, max_feature_idx=max_fi,
            objective_str=self._objective_string(),
            feature_names=names, feature_infos=infos, num_iteration=ni,
            parameters="", average_output=g.average_output)

    def save_model(self, filename: str,
                   num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> "Booster":
        # atomic for local paths (ckpt writer: temp + fsync + rename) —
        # a crash mid-save never leaves a truncated model file
        model_io.write_model_file(
            str(filename),
            self.model_to_string(num_iteration, start_iteration))
        return self

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> Dict:
        g = self._gbdt
        if g.train_set is not None:
            names = g.train_set.feature_names
            max_fi = g.train_set.num_total_features - 1
        else:
            names, max_fi = self._feature_names, self._max_feature_idx
        ni = num_iteration if num_iteration is not None else -1
        return model_io.dump_model_json(
            self._model_slice(start_iteration), num_class=g.num_class,
            num_tree_per_iteration=g.num_tree_per_iteration,
            label_index=0, max_feature_idx=max_fi,
            objective_str=self._objective_string(), feature_names=names,
            num_iteration=ni)

    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        g = self._gbdt
        nf = (g.train_set.num_total_features if g.train_set is not None
              else self._max_feature_idx + 1)
        models = g.models
        if iteration is not None and iteration > 0:
            models = models[:iteration * g.num_tree_per_iteration]
        return model_io.feature_importance(models, importance_type, nf)

    def feature_name(self) -> List[str]:
        g = self._gbdt
        if g.train_set is not None:
            return list(g.train_set.feature_names)
        return list(self._feature_names)

    def __getstate__(self):
        # picklable via model string (reference Booster pickling support)
        state = {"model_str": self.model_to_string(num_iteration=-1),
                 "best_iteration": self.best_iteration,
                 "best_score": self.best_score,
                 "params": self.params}
        return state

    def __setstate__(self, state):
        self.best_iteration = state["best_iteration"]
        self.best_score = state["best_score"]
        self.params = state["params"]
        self.train_set = None
        self._loaded = None
        self._load_from_string(state["model_str"])
