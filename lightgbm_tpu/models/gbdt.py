"""GBDT boosting orchestrator.

Capability parity with ``src/boosting/gbdt.cpp``: Init wires
config/data/objective/metrics and the tree builder; ``TrainOneIter``
(``gbdt.cpp:335``) = gradients → bagging → per-class tree build → leaf
renewal → shrinkage → score update → first-iter bias absorption
(``new_tree->AddBias(init_score)``, ``gbdt.cpp:377``); plus rollback,
refit, and model text I/O hooks.

TPU-first: gradients/scores are device-resident, the tree build is one
jitted call (``ops/grow.py``) whose split records come back to host once
per tree to materialize a :class:`Tree`.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..config import Config
from ..io.dataset import Metadata, TpuDataset
from ..objectives import Objective, create_objective
from ..metrics import Metric
from ..utils.log import Log
from .tree import Tree, cat_bitset

_KEPS = 1e-15


def _host_default_device():
    """Arrays made inside land on the host's CPU backend (a process
    that runs without one keeps its default device)."""
    import contextlib

    import jax
    try:
        return jax.default_device(jax.devices("cpu")[0])
    except RuntimeError:
        return contextlib.nullcontext()


def _threshold_l1(s, l1):
    if l1 <= 0:
        return np.asarray(s, np.float64)
    return np.sign(s) * np.maximum(np.abs(s) - l1, 0.0)


def records_to_tree(rec, config, train_set, counts_proxy=False) -> Tree:
    """Materialize ONE host :class:`Tree` from a fetched split-record
    dict.  Module-level (not a GBDT method) so the battery trainer
    (``models/battery.py``) can assemble per-member trees from stacked
    (B, K, ...) records with per-member configs without instantiating
    B GBDT drivers — the shared TpuDataset supplies the bin mappers."""
    cfg = config
    ds = train_set
    tree = Tree(cfg.num_leaves)

    def out(g, h):
        o = -np.sign(_thl1(g, cfg.lambda_l1)) * abs(
            _thl1(g, cfg.lambda_l1)) / (h + cfg.lambda_l2 + _KEPS)
        if cfg.max_delta_step > 0:
            o = np.clip(o, -cfg.max_delta_step, cfg.max_delta_step)
        return float(o)

    def _thl1(s, l1):
        return np.sign(s) * max(abs(s) - l1, 0.0) if l1 > 0 else s

    L1 = cfg.num_leaves - 1
    for i in range(L1):
        if not bool(rec["valid"][i]):
            break
        leaf = int(rec["leaf"][i])
        inner_f = int(rec["feature"][i])
        real_f = ds.real_feature_index(inner_f)
        mapper = ds.mappers[real_f]
        ls = rec["left_stats"][i]
        rs = rec["right_stats"][i]
        lv, rv = out(ls[0], ls[1]), out(rs[0], rs[1])
        if "rec_left_min" in rec:
            # monotone value constraints (the device loop clamped
            # identically; redo in f64 on the host-side outputs)
            lv = float(np.clip(lv, rec["rec_left_min"][i],
                               rec["rec_left_max"][i]))
            rv = float(np.clip(rv, rec["rec_right_min"][i],
                               rec["rec_right_max"][i]))
        gain = float(rec["gain"][i])
        if bool(rec["is_cat"][i]):
            bins = np.nonzero(rec["left_mask"][i])[0]
            cats = [mapper.bin_2_categorical[b] for b in bins
                    if 0 < b < len(mapper.bin_2_categorical)]
            if not cats:
                cats = [0]
            tree.split_categorical(
                leaf, real_f, cat_bitset(cats), lv, rv,
                float(ls[1]), float(rs[1]), int(round(ls[2])),
                int(round(rs[2])), gain, mapper.missing_type)
        else:
            thr_bin = int(rec["threshold"][i])
            tree.split(leaf, real_f, thr_bin,
                       mapper.bin_to_value(thr_bin), lv, rv,
                       float(ls[1]), float(rs[1])
                       , int(round(ls[2])), int(round(rs[2])), gain,
                       mapper.missing_type,
                       bool(rec["default_left"][i]))
        node = tree.num_leaves - 2
        pg, ph = ls[0] + rs[0], ls[1] + rs[1]
        tree.internal_value[node] = out(pg, ph)
    if "leaf_stats_exact" in rec:
        # quantized training: renew leaf outputs from the
        # full-precision per-leaf sums (RenewIntGradTreeOutput) so
        # leaf values carry no stochastic-rounding noise
        ex = np.asarray(rec["leaf_stats_exact"], np.float64)
        for leaf in range(tree.num_leaves):
            if leaf < len(ex) and ex[leaf, 2] > 0:
                tree.leaf_value[leaf] = out(ex[leaf, 0], ex[leaf, 1])
        if counts_proxy:
            # two-column passes record hess sums in the count slots;
            # restore REAL counts: leaves from the exact renewal
            # sums, internal nodes by one REVERSE-id sweep (a
            # child's node id always exceeds its parent's, so its
            # count is ready first; no recursion — chain-shaped
            # trees can exceed Python's recursion limit)
            for leaf in range(tree.num_leaves):
                if leaf < len(ex):
                    tree.leaf_count[leaf] = int(round(ex[leaf, 2]))

            def child_count(c):
                return tree.leaf_count[~c] if c < 0 else \
                    tree.internal_count[c]

            for node in range(tree.num_leaves - 2, -1, -1):
                tree.internal_count[node] = \
                    child_count(tree.left_child[node]) + \
                    child_count(tree.right_child[node])
    return tree


@dataclasses.dataclass
class ValidSet:
    name: str
    raw: np.ndarray          # raw feature matrix (rows, total_features)
    metadata: Metadata
    score: np.ndarray = None  # accumulated raw score
    xt: object = None        # device (F_pad, rows) binned matrix, or None
    # per-tree leaf assignment (uint8/16), kept only when the boosting
    # mode tracks train leaves (DART): drop/renormalize replays become
    # numpy leaf-table lookups instead of per-tree host traversals
    leaf_idx_per_tree: list = dataclasses.field(default_factory=list)

    def __post_init__(self):
        if self.score is None:
            n = self.raw.shape[0]
            k = 1
            self.score = np.zeros((k, n), dtype=np.float64)


class GBDT:
    """Gradient Boosting Decision Tree driver (single class for now;
    multiclass lands with the multiclass objective)."""

    @property
    def models(self) -> List[Tree]:
        """The tree list.  Pipelined boosting defers the host
        materialization of the newest tree by one iteration (its
        records fetch hides behind the next tree's device build); ANY
        reader flushes first, so the list is always complete from the
        outside."""
        if getattr(self, "_pending", None) is not None:
            self._flush_pending()
        return self._models

    @models.setter
    def models(self, value: List[Tree]) -> None:
        if getattr(self, "_pending", None) is not None:
            self._flush_pending()
        self._models = list(value)
        self._invalidate_predictor()

    def _invalidate_predictor(self) -> None:
        """Drop the flattened-forest cache (ops/predict.py).  Appends
        and pops are covered by the tree-count in the cache key; this
        hook is for IN-PLACE mutations of existing trees — DART
        renormalization, refit, merge splices, model-list swaps.  The
        per-tree handoff rows (``_tree_flats``) are cleared too: an
        in-place mutation invalidates the extracted row, and the
        device-handoff path re-extracts lazily."""
        self._model_version = getattr(self, "_model_version", 0) + 1
        self._flat_cache = None
        self._shap_cache = None
        self._tree_flats = []

    def __init__(self, config: Config, train_set: TpuDataset,
                 objective: Optional[Objective],
                 metrics: Sequence[Metric] = (), mesh=None):
        import jax
        import jax.numpy as jnp
        from ..ops.grow import build_tree
        from ..ops.histogram import _pad_bins
        from .tier import TierFacts, plan_tier

        self.config = config
        self.train_set = train_set
        self.objective = objective
        self.metrics = list(metrics)
        self._models: List[Tree] = []
        self._model_version = 0
        self._flat_cache = None     # (key, FlatForest) — ops/predict.py
        self._shap_cache = None     # (key, ShapForest) — ops/shap.py
        self._tree_flats = []       # per-tree handoff rows (TreeFlat)
        self._pending = None        # in-flight tree (pipelined boosting)
        self._stop_flag = False
        self._pipeline_enabled = True  # DART/RF opt out
        # fused boosting super-steps (config.fused_iters > 1): one
        # jitted lax.scan runs K iterations on device; the block state
        # below serves its trees one per train_one_iter call
        self._superstep_enabled = True  # DART/RF opt out
        self._fused_block = None        # fetched block being served
        self._sq = []                   # dispatched-but-unfetched blocks
        self._superstep_jit = None      # lazily-built jitted scan
        self._fused_has_bagging = False
        self._trees_dispatched = 0  # quantization PRNG stream position
        self.iter = 0
        self.num_class = max(config.num_class, 1)
        self.num_tree_per_iteration = 1
        if objective is not None:
            self.num_tree_per_iteration = getattr(
                objective, "num_model_per_iteration", 1)
        self.shrinkage_rate = config.learning_rate
        self.num_data = train_set.num_data
        self.valid_sets: List[ValidSet] = []
        self._prev_score = None
        self._prev_valid_scores: List[np.ndarray] = []
        # RF averages tree outputs instead of summing (rf.hpp:22)
        self.average_output = False
        # DART needs per-tree train contributions to drop/restore them
        self._track_train_leaf = False
        self._train_leaf_idx: List[Optional[np.ndarray]] = []

        F = len(train_set.used_features)
        self.num_features = F
        mappers = [train_set.mappers[i] for i in train_set.used_features]
        self.max_bin = int(2 ** np.ceil(np.log2(max(
            train_set.max_bin_count, 2))))
        # per-feature static descriptor arrays
        self._num_bins = jnp.asarray([m.num_bin for m in mappers], jnp.int32)
        self._missing_type = jnp.asarray(
            [m.missing_type for m in mappers], jnp.int32)
        from ..io.binning import BIN_CATEGORICAL
        self._is_cat = jnp.asarray(
            [m.bin_type == BIN_CATEGORICAL for m in mappers], bool)

        use_pallas = (config.device_type != "cpu" and
                      jax.default_backend() not in ("cpu",))
        from ..utils.env import pallas_interpret_forced
        if not use_pallas and pallas_interpret_forced():
            # LTPU_PALLAS_INTERPRET: the interpret-mode CPU parity
            # lane — every Pallas kernel (histogram tiers, routed
            # passes, the best-split scan) runs interpreted so tier-1
            # exercises the kernel paths without a TPU.  Correctness
            # only; interpreter wall time is meaningless.
            use_pallas = True
        rpb = int(config.tpu_rows_per_block)
        n = train_set.num_data

        # resolve the tree learner FIRST: the feature-padded width (and
        # with it the static per-feature constraint tuples) depends on
        # the mesh sharding
        learner = config.tree_learner
        num_shards = 1
        mesh_shape2d = None
        if learner not in ("serial", ""):
            from ..parallel import resolve_num_shards
            from ..utils.env import maybe_init_distributed
            # multi-host entry (env-gated, no-op single-host): join the
            # distributed runtime BEFORE counting devices so the mesh
            # factors over the global device set
            maybe_init_distributed()
            num_shards = resolve_num_shards(config, mesh)
            if num_shards <= 1:
                Log.warning("tree_learner=%s requested but only one device "
                            "is available; using the serial learner",
                            learner)
                learner = "serial"
        dist_active = learner not in ("serial", "") and num_shards > 1
        if dist_active and learner == "data2d":
            from ..parallel.learners import (factor_mesh_shape,
                                             parse_mesh_shape)
            if mesh is not None:
                mesh_shape2d = tuple(int(s) for s in mesh.devices.shape)
            elif getattr(config, "mesh_shape", ""):
                mesh_shape2d = parse_mesh_shape(config.mesh_shape)
                # an explicit shape wins over the device count: the
                # builder raises when the host cannot satisfy it
                num_shards = mesh_shape2d[0] * mesh_shape2d[1]
            else:
                mesh_shape2d = factor_mesh_shape(num_shards)
        self._mesh_shape2d = mesh_shape2d

        from ..parallel.learners import pad_features_for, pad_rows_for
        row_block = rpb if use_pallas else 1
        kind = learner if dist_active else "serial"
        # per-AXIS shard counts: the 2-D learner pads rows to its row
        # axis and features to its feature axis; 1-D learners key both
        # off the flat width (the pad helpers ignore the irrelevant one)
        row_shards = mesh_shape2d[0] if mesh_shape2d else num_shards
        feat_shards = mesh_shape2d[1] if mesh_shape2d else num_shards
        self._n_pad = pad_rows_for(kind, row_shards, n, row_block)
        self._F_pad = pad_features_for(kind, feat_shards, F)

        monotone, penalty = self._constraint_tuples(config, train_set, F)
        forced = self._forced_splits(config, train_set, dist_active)

        # EFB bundling (FindGroups/FastFeatureBundling,
        # dataset.cpp:38-180): serial learner only; bundles capped at
        # the histogram bin budget so the device tensors keep shape
        self._bundles = None
        self._bundle_maps = None
        if config.enable_bundle and not dist_active and F > 1:
            from ..io.binning import BIN_CATEGORICAL as _CAT
            from ..io.bundle import find_bundles
            db = np.asarray(
                [0 if mappers[j].bin_type == _CAT
                 else mappers[j].default_bin for j in range(F)], np.int32)
            nb_arr = np.asarray([m.num_bin for m in mappers], np.int32)
            bundles = find_bundles(
                train_set.binned, nb_arr, db,
                max_conflict_rate=config.max_conflict_rate,
                bin_budget=min(config.max_bin, 255),
                seed=config.data_random_seed)
            # cost model for the one-hot-matmul histogram: work is
            # columns x KERNEL-padded bin width (the kernel pads bins
            # to a multiple of 8, so 2-bin one-hot indicator columns
            # still stream 8 one-hot rows each — comparing unpadded
            # widths wrongly rejected bundling exactly on the one-hot
            # datasets EFB exists for)
            B_bun = int(bundles.group_num_bins.max())
            # the committed device width is max(max_bin, B_bun): cost
            # the bundled pass at exactly that width
            cost_bundled = bundles.num_groups * _pad_bins(
                max(self.max_bin, B_bun))
            cost_plain = F * _pad_bins(self.max_bin)
            if bundles.num_groups < F and cost_bundled < 0.95 * cost_plain:
                self._bundles = bundles
                # commit the width that was costed: the kernel pads to
                # a multiple of 8 itself, so rounding max_bin up to a
                # power of two here would stream more one-hot rows than
                # the acceptance decision accounted for
                self.max_bin = max(self.max_bin, B_bun)
                B = self.max_bin
                fix = np.zeros((F, B), np.float32)
                for f in range(F):
                    if not bundles.is_singleton[bundles.group_id[f]]:
                        fix[f, db[f]] = 1.0
                self._bundle_maps = (
                    jnp.asarray(bundles.group_id),
                    jnp.asarray(bundles.to_bundle_map(B, nb_arr)),
                    jnp.asarray(bundles.from_bundle_map(B, nb_arr)),
                    jnp.asarray(fix))
                Log.info("EFB: bundled %d features into %d groups",
                         F, bundles.num_groups)

        # HistogramPool memory policy: the (L, G, B, 3) pool enables
        # the subtraction trick; when it exceeds histogram_pool_size
        # (or a 4 GB default), children are recomputed fresh instead
        G_cols = self._bundles.num_groups if self._bundles else self._F_pad
        pool_bytes = (config.num_leaves * G_cols * self.max_bin * 3 * 4)
        cap = config.histogram_pool_size * 1e6 \
            if config.histogram_pool_size > 0 else 4e9
        use_pool = pool_bytes <= cap
        if not use_pool and forced:
            Log.warning("forced splits require the histogram pool; "
                        "keeping the pool despite histogram_pool_size")
            use_pool = True
        if not use_pool:
            Log.info("histogram pool (%.0f MB) exceeds budget; "
                     "recomputing child histograms", pool_bytes / 1e6)

        any_cat = bool(any(m.bin_type == BIN_CATEGORICAL
                           for m in mappers))
        any_missing = bool(any(m.missing_type != 0 for m in mappers))
        # the growth tier and its kernels, decided once with their
        # reasons (models/tier.py): the one GrowParams and the tier
        # record (utils/telemetry.py: why a run landed on its tier,
        # readable without a profiler)
        plan = plan_tier(config, TierFacts(
            use_pallas=use_pallas, learner=learner, num_shards=num_shards,
            mesh_shape2d=mesh_shape2d, features=F, g_cols=G_cols,
            max_bin=self.max_bin, any_cat=any_cat,
            any_missing=any_missing,
            efb_groups=(int(self._bundles.num_groups)
                        if self._bundles is not None else 0),
            forced=forced, use_pool=use_pool, rows_per_block=rpb,
            monotone=monotone, penalty=penalty,
            objective_rows=(
                objective.shard_refusal() if objective is not None
                else "custom objective: the host hands over gradients "
                     "of the whole job's rows"),
            rank_layout=objective.layout if objective is not None
            else None))
        self.grow_params = plan.grow_params
        self.tier_decision = plan.record
        self._counts_proxy = plan.grow_params.two_col
        # per-row state (score carry, objective's row tensors,
        # gradients, leaf index) on the shard's rows only: decided by
        # the plan's row_state ladder, nothing here re-derives it
        self._rows_on_shard = plan.record["row_state"] == "shard"

        # ---- device-block pager (io/pager.py, docs/Streaming.md
        # "Out-of-core on device"): decide whether the binned matrix
        # trains RESIDENT or PAGED.  Auto triggers when ONE device's
        # matrix block would exceed hbm_budget_mb; "on" forces paging
        # and fails loudly on a paged-ineligible config instead of
        # silently training resident over budget ----
        old_pager = getattr(self, "_pager", None)
        if old_pager is not None:       # remesh re-runs __init__
            old_pager.abort()
            old_pager.close()
        self._pager = None
        self._pager_view = None
        self._pager_last = None
        paged_req = str(getattr(config, "paged_training", "auto")
                        or "auto").lower()
        hbm_budget = float(getattr(config, "hbm_budget_mb", 0.0) or 0.0)
        pg_out_cols = self._bundles.num_groups \
            if self._bundles is not None else self._F_pad
        pg_kind = learner if dist_active else "serial"
        pg_row_shards = (mesh_shape2d[0] if pg_kind == "data2d" else
                         num_shards if pg_kind in ("data", "voting")
                         else 1)
        pg_feat_shards = (mesh_shape2d[1] if pg_kind == "data2d" else
                          num_shards if pg_kind == "feature" else 1)
        if self._bundles is not None:
            pg_dtype = self._bundles.bundle_matrix(
                np.asarray(train_set.binned[:1])).dtype
        else:
            pg_dtype = train_set.binned.dtype
        pg_f_loc = pg_out_cols // max(pg_feat_shards, 1)
        pg_n_loc = self._n_pad // max(pg_row_shards, 1)
        per_dev_bytes = pg_f_loc * pg_n_loc * np.dtype(pg_dtype).itemsize
        want_paged = paged_req == "on" or (
            paged_req == "auto" and hbm_budget > 0 and
            per_dev_bytes > hbm_budget * (1 << 20))
        if want_paged:
            gp = self.grow_params
            if gp.hist_impl != "segsum":
                pg_gate = ("hist_impl=pallas — the on-chip histogram "
                           "tiers read the resident matrix")
            elif gp.wave or gp.speculate > 1:
                pg_gate = ("wave/speculative growth batches "
                           "multi-leaf passes over the resident matrix")
            elif gp.split_kernel == "pallas":
                pg_gate = "split_kernel=pallas reads resident tiles"
            else:
                pg_gate = None
            if pg_gate is not None:
                if paged_req == "on":
                    raise ValueError(
                        f"paged_training=on, but this config is "
                        f"paged-ineligible: {pg_gate}.  Paged "
                        f"training runs the baseline segsum+xla lane "
                        f"(docs/Streaming.md)")
                Log.warning("paged_training=auto: %s; training "
                            "resident", pg_gate)
                want_paged = False
        if want_paged:
            from ..io.pager import PageStore, plan_pages
            pg_plan = plan_pages(
                pg_n_loc, pg_f_loc, np.dtype(pg_dtype).itemsize,
                hbm_budget_mb=hbm_budget,
                page_rows=int(getattr(config, "paged_page_rows", 0)
                              or 0))
            self._pager = PageStore(
                train_set.binned, n_rows=n, n_pad=self._n_pad,
                out_cols=pg_out_cols, plan=pg_plan,
                row_shards=pg_row_shards, feat_shards=pg_feat_shards,
                transform=(self._bundles.bundle_matrix
                           if self._bundles is not None else None),
                dtype=pg_dtype,
                prefetch=bool(getattr(config, "stream_prefetch",
                                      True)))
            Log.info("paged training: %d pages x %d rows per device "
                     "block (%.1f MB resident vs %.1f MB paged "
                     "double-buffer)", pg_plan.n_pages,
                     pg_plan.page_rows, per_dev_bytes / 1e6,
                     2 * pg_plan.page_bytes *
                     np.dtype(pg_dtype).itemsize / 1e6)

        # parallel tree learner over the device mesh
        # (tree_learner={data,feature,voting}, tree_learner.cpp:9-33)
        self._dist = None
        if dist_active:
            from ..parallel import DistributedBuilder
            self._dist = DistributedBuilder(
                learner, self.grow_params, num_shards, mesh,
                mesh_shape=mesh_shape2d, pager=self._pager)
            if self._pager is not None:
                self._pager_view = self._dist.pager_view
            if learner == "data2d":
                Log.info("tree_learner=data2d over a %dx%d "
                         "(data x feature) device mesh",
                         self._dist.row_shards, self._dist.feat_shards)
            else:
                Log.info("tree_learner=%s over a %d-way device mesh",
                         learner, num_shards)
        self._stream_upload = None
        stream_info = getattr(train_set, "stream", None)
        if self._pager is not None:
            # paged lane: the binned matrix NEVER materializes on
            # device.  Dispatch signatures keep a replicated dummy
            # operand in the xt slot (shapes/specs stay uniform) and
            # the traced programs read pages through the PagedXt
            # view — the streamed cache mmap and the in-memory binned
            # array are served by the same PageStore, so no upload
            # window or host-side transpose happens at all
            self._xt = jnp.zeros((1, 8), dtype=pg_dtype)
            if self._pager_view is None:
                self._pager_view = self._pager.view("serial")
        elif stream_info is not None:
            # streamed dataset (io/stream.py): the binned matrix is a
            # read-only mmap over the crash-safe cache — upload it in
            # budgeted double-buffered windows instead of
            # materializing the full (F_pad, n_pad) transpose on the
            # host.  The resulting device array is value-identical to
            # the in-memory path's, so everything downstream (fused
            # scans, sharded placement, checkpoint replay) is shared.
            from ..io.stream import BlockFetcher
            out_cols = self._bundles.num_groups \
                if self._bundles is not None else self._F_pad
            fetcher = BlockFetcher(
                train_set.binned, n_rows=n, n_pad=self._n_pad,
                out_cols=out_cols,
                window_rows=stream_info.window_rows,
                transform=(self._bundles.bundle_matrix
                           if self._bundles is not None else None),
                prefetch=stream_info.prefetch,
                read_retries=int(getattr(config, "stream_read_retries",
                                         3)),
                backoff_base_s=float(getattr(config,
                                             "stream_backoff_base_s",
                                             0.1)))
            # windows land directly in the learner's layout (data2d:
            # the P("feature", "data") tiles) — no single-device
            # staging copy, no re-shard afterwards
            self._xt = fetcher.upload(
                sharding=(self._dist.shardings()["xt"]
                          if self._dist is not None else None))
            self._stream_upload = fetcher.stats()
        else:
            # the host's part of the upload: transpose, pad and the
            # enqueue of the copy (which lands later, under whichever
            # phase first waits for the device)
            from ..utils.profiling import timed
            with timed("dataset/xt_host_prep"):
                if self._bundles is not None:
                    xt = self._bundles.bundle_matrix(
                        train_set.binned).T  # (G, N)
                else:
                    xt = train_set.binned.T  # (F, N) narrow uint8/16
                col_pad = 0 if self._bundles is not None \
                    else self._F_pad - F
                xt = np.pad(xt, ((0, col_pad), (0, self._n_pad - n)))
                # NARROW dtype end to end: the host->device copy AND
                # device residency (uint8 = 295 MB at bench shape vs
                # 1.18 GB int32); the pallas kernels and routing
                # selects widen per tile.  Under a mesh the host array
                # goes to its devices below, each its own block: the
                # whole matrix never sits on one of them
                self._xt = xt if self._dist is not None \
                    else jnp.asarray(xt)
        base_mask = np.pad(np.ones(n, np.float32), (0, self._n_pad - n))
        self._base_mask = base_mask if self._dist is not None \
            else jnp.asarray(base_mask)
        if self._F_pad != F:
            # padded features are trivial: one bin, never splittable
            self._num_bins = jnp.concatenate(
                [self._num_bins, jnp.ones(self._F_pad - F, jnp.int32)])
            self._missing_type = jnp.concatenate(
                [self._missing_type, jnp.zeros(self._F_pad - F, jnp.int32)])
            self._is_cat = jnp.concatenate(
                [self._is_cat, jnp.zeros(self._F_pad - F, bool)])
        if self._dist is not None:
            # mesh-resident training state: place every persistent
            # tensor with the learner's NamedSharding ONCE, so neither
            # the per-tree dispatch nor the fused super-step re-shards
            # host-placed global arrays on every call (the per-shard
            # dispatch overhead behind the WEAKSCALE degradation)
            from ..utils.profiling import timed
            shd = self._dist.shardings()
            with timed("dataset/shard_upload"):
                if self._pager is None and stream_info is None:
                    # streamed uploads were already placed window-by-
                    # window
                    self._xt = jax.device_put(self._xt, shd["xt"])
                self._base_mask = jax.device_put(self._base_mask,
                                                 shd["row"])
                self._num_bins = jax.device_put(self._num_bins,
                                                shd["feat"])
                self._missing_type = jax.device_put(self._missing_type,
                                                    shd["feat"])
                self._is_cat = jax.device_put(self._is_cat, shd["feat"])
        self._build_tree = build_tree if self._dist is None else self._dist
        if self._pager is not None and self._dist is None:
            # serial paged per-tree dispatch: the jitted builder closes
            # over the PagedXt view (a trace-time object, not a pytree
            # leaf) and ignores the dummy xt operand — same signature
            # as build_tree, so the dispatch sites stay untouched
            import functools as _ft
            from ..ops.grow import build_tree_impl as _bt_impl
            view = self._pager_view

            def _paged_build(xt, grad, hess, mask, fmask, nb, mt, cat,
                             params, bundle_maps=None, quant_key=None):
                return _bt_impl(view, grad, hess, mask, fmask, nb, mt,
                                cat, params, bundle_maps=bundle_maps,
                                quant_key=quant_key)

            self._build_tree = _ft.partial(
                jax.jit, static_argnames=("params",))(_paged_build)

        # scores: (num_tree_per_iteration, N) device
        k = self.num_tree_per_iteration
        score = np.zeros((k, n), dtype=np.float32)
        if train_set.metadata.init_score is not None:
            init = np.asarray(train_set.metadata.init_score,
                              np.float64).reshape(-1)
            score += init.reshape(k, n) if init.size == k * n else init
        self._score = self._place_score(score)
        self._rng_feature = np.random.RandomState(
            config.feature_fraction_seed & 0x7FFFFFFF)
        self._rec_layout = None  # lazy: packed split-record fetch plan
        # sampling-mask randomness lives ON DEVICE (bagging/GOSS/MVS
        # masks are computed in jitted ops; a host mask would ship
        # 4N bytes to the device every iteration)
        self._bag_key = jax.random.PRNGKey(config.bagging_seed &
                                           0x7FFFFFFF)
        self._label_pos = None  # lazy device label>0 (pos/neg bagging)
        self._quant_key = (jax.random.PRNGKey(
            config.data_random_seed & 0x7FFFFFFF)
            if self.grow_params.quantize else None)
        if objective is not None and not self._rows_on_shard:
            objective.init(train_set.metadata, n)
        elif objective is not None:
            # the objective's row tensors go to the mesh as the score
            # did, each device its own rows: made on the host's
            # backend, so that none sits whole on a device of the mesh
            from ..utils.profiling import timed
            shd = self._dist.shardings()
            with _host_default_device():
                objective.init(train_set.metadata, n)
            with timed("dataset/shard_upload"):
                objective.place_rows(
                    self._n_pad, lambda a: jax.device_put(
                        a, shd["row" if a.ndim == 1 else "rows2d"]))

        # ---- observability -------------------------------------------
        from ..utils import telemetry as _tele_mod
        row_state = [self._score, self._base_mask] + (
            list(objective.rows().values()) if objective is not None
            else [])
        # what one device holds of the per-row state (a gauge: the
        # row-sharded state keeps it x devices constant)
        self.row_state_bytes_per_chip = int(sum(
            a.addressable_shards[0].data.nbytes for a in row_state))
        _tele_mod.counters.set("row_state_bytes_per_chip",
                               self.row_state_bytes_per_chip)
        if self._dist is not None:
            # the built mesh's shape, which only the builder knows
            self.tier_decision["mesh_shape"] = [
                int(s) for s in self._dist.mesh.devices.shape]
        self._collective_per_pass = 0
        self._collective_ops_per_pass = 0
        self._collective_per_axis = {}
        self._collective_plan = {}
        self._collective_last = (0, 0)  # (bytes, ops) of the last commit
        if dist_active and self._dist is not None:
            from ..ops.grow import (collective_bytes_per_pass,
                                    wave_collective_plan)
            # the wave data learner's collectives are counted from the
            # trees' own passes (_count_growth); the other learners
            # keep the static estimate.  The builder's params carry
            # the real DistConfig (the booster-level grow_params keeps
            # the serial default)
            self._collective_plan = wave_collective_plan(
                self._dist.params, self._F_pad)
            if not self._collective_plan:
                est = collective_bytes_per_pass(
                    self._dist.params, self._F_pad, self._n_pad)
                self._collective_per_pass = est["total"]
                self._collective_ops_per_pass = est["ops"]
                self._collective_per_axis = est.get("per_axis", {})
        self._telemetry = None
        self._tele_counters_last: Dict[str, float] = {}
        if getattr(config, "telemetry_file", ""):
            self.attach_telemetry(config.telemetry_file)
        else:
            # a process-default recorder (set by the continual daemon /
            # CLI via telemetry.set_recorder) adopts every booster it
            # outlives: one JSONL stream for a whole ingest->train->
            # publish loop instead of one file handle per batch
            if _tele_mod.get_recorder() is not None:
                self.attach_telemetry(_tele_mod.get_recorder())
        if self._stream_upload:
            # the streamed construction finished before the recorder
            # attached: publish the upload's prefetch-overlap stats
            # now (the ingest/prefetch record obs/rules.py watches)
            rec = self._telemetry or _tele_mod.get_recorder()
            if rec is not None:
                rec.emit("ingest", event="prefetch",
                         **self._stream_upload)

    # ------------------------------------------------------------------
    def _place_score(self, score_kn: np.ndarray):
        """The (k, rows) score carry from a host array, where the
        learner keeps it: on the one device; replicated on the mesh
        (the fused super-step donates it in place and it never leaves
        the mesh between blocks); or, where the row state is the
        shard's, padded to ``n_pad`` rows and each device its own."""
        import jax
        import jax.numpy as jnp
        score_kn = np.asarray(score_kn, np.float32)
        if self._dist is None:
            return jnp.asarray(score_kn)
        shd = self._dist.shardings()
        if not self._rows_on_shard:
            return jax.device_put(score_kn, shd["rep"])
        from ..utils.profiling import timed
        pad = self._n_pad - score_kn.shape[-1]
        with timed("dataset/shard_upload"):
            return jax.device_put(np.pad(score_kn, ((0, 0), (0, pad))),
                                  shd["rows2d"])

    def _gradient_fn(self):
        """``score -> (grad, hess)`` of the objective at the score
        carry's width: the objective's jitted wrapper, or, where the
        row tensors live on the shard, the one that takes them as
        arguments (no constant of the data set in the program, and the
        results stay each device's own rows)."""
        obj = self.objective
        if self._rows_on_shard:
            fn, rows = obj.gradient_fn_rows(), obj.rows()
            return lambda score: fn(score, rows)
        return obj.gradient_fn() or obj.get_gradients

    def _tables_ride(self) -> bool:
        """The objective has device tables, which the fused super-step
        takes as its last argument."""
        return self.objective is not None and \
            bool(self.objective.table_names)

    def _score_rows(self, rows):
        """A per-row vector of ``n_pad`` rows at the score carry's
        width: as it is where the carry is padded too (the row state is
        the shard's), else without the padding rows."""
        width = self._score.shape[-1]
        return rows if rows.shape[-1] == width else rows[..., :width]

    def _constraint_tuples(self, config: Config, train_set: TpuDataset,
                           F: int):
        """Static per-feature (monotone, penalty) tuples padded to the
        device feature width.  Config lists are indexed by ORIGINAL
        column (config.h:357 monotone_constraints, feature_contri);
        remap through used_features and pad with neutral values."""
        pad = self._F_pad
        mono = ()
        if config.monotone_constraints:
            mc = list(config.monotone_constraints)
            vals = [int(mc[i]) if i < len(mc) else 0
                    for i in train_set.used_features]
            if any(vals):
                mono = tuple(vals + [0] * (pad - F))
        pen = ()
        if config.feature_contri:
            fc = list(config.feature_contri)
            vals = [float(fc[i]) if i < len(fc) else 1.0
                    for i in train_set.used_features]
            if any(v != 1.0 for v in vals):
                pen = tuple(vals + [1.0] * (pad - F))
        return mono, pen

    def _forced_splits(self, config: Config, train_set: TpuDataset,
                       dist_active: bool):
        """BFS-flattened forced splits from ``forcedsplits_filename``
        (``ForceSplits``, serial_tree_learner.cpp:544): JSON nodes
        {feature, threshold, left, right} become (leaf_id,
        inner_feature, threshold_bin) triples in the order the growth
        loop will apply them (left child keeps the parent's leaf id,
        right child gets id t+1 at iteration t)."""
        fname = config.forcedsplits_filename
        if not fname:
            return ()
        if dist_active:
            Log.warning("forced splits are not supported by parallel "
                        "tree learners; ignoring %s", fname)
            return ()
        import json as _json
        with open(fname) as f:
            root = _json.load(f)
        out = []
        queue = [(root, 0)]
        t = 0
        while queue and t < config.num_leaves - 1:
            node, leaf = queue.pop(0)
            real_f = int(node["feature"])
            inner = train_set.inner_feature_index(real_f)
            if inner is None or inner < 0:
                Log.warning("forced split on unused feature %d; "
                            "stopping forced splits", real_f)
                break
            mapper = train_set.mappers[real_f]
            thr_bin = int(np.asarray(mapper.value_to_bin(
                np.asarray([float(node["threshold"])]))).reshape(-1)[0])
            out.append((leaf, inner, thr_bin))
            if node.get("left"):
                queue.append((node["left"], leaf))
            if node.get("right"):
                queue.append((node["right"], t + 1))
            t += 1
        return tuple(out)

    # ------------------------------------------------------------------
    def attach_telemetry(self, target):
        """Attach a run recorder (``utils/telemetry.py``): a JSONL path
        or an existing :class:`RunRecorder`.  Idempotent — the first
        attachment wins.  Works on loaded (predict-only) boosters too.
        """
        from ..utils import telemetry
        if getattr(self, "_telemetry", None) is not None:
            return self._telemetry
        if isinstance(target, telemetry.RunRecorder):
            rec = target
            rec.emit("run_start", **self._run_info())
        else:
            rec = telemetry.RunRecorder(str(target),
                                        run_info=self._run_info())
        self._telemetry = rec
        self._tele_counters_last = telemetry.counters_snapshot()
        return rec

    def telemetry_summary(self):
        rec = getattr(self, "_telemetry", None)
        return rec.summary() if rec is not None else None

    def _run_info(self):
        """Backend identity + config subset for the run_start record."""
        import jax
        cfg = self.config
        dev = jax.local_devices()[0]
        info = {
            "backend": jax.default_backend(),
            "device_kind": dev.device_kind,
            "tier": getattr(self, "tier_decision", None),
            "params": {
                "objective": cfg.objective,
                "num_leaves": cfg.num_leaves,
                "max_bin": cfg.max_bin,
                "num_class": cfg.num_class,
                "tree_learner": cfg.tree_learner,
                "use_quantized_grad": cfg.use_quantized_grad,
                "wave_splits": cfg.wave_splits,
                "hist_refinement": cfg.hist_refinement,
                "min_data_in_leaf": cfg.min_data_in_leaf,
            },
        }
        if self.train_set is not None:
            info["rows"] = int(self.num_data)
            info["features"] = int(self.num_features)
        stats = dev.memory_stats()      # the CPU backend reports None
        if stats:
            info["device_memory"] = {
                k: int(stats[k]) for k in
                ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                if k in stats}
        return info

    # ------------------------------------------------------------------
    def add_valid(self, name: str, raw: np.ndarray, metadata: Metadata,
                  binned: Optional[TpuDataset] = None):
        """Register a validation set.  When its aligned binned matrix is
        provided, per-iteration scoring runs on device by replaying the
        fresh tree's split records (:func:`~lightgbm_tpu.ops.grow.
        route_rows`) instead of a host tree traversal — O(1) host work
        per iteration."""
        import jax.numpy as jnp

        vs = ValidSet(name, raw, metadata)
        vs.score = np.zeros((self.num_tree_per_iteration, raw.shape[0]),
                            dtype=np.float64)
        if metadata.init_score is not None:
            vs.score += np.asarray(metadata.init_score).reshape(
                vs.score.shape[0], -1)
        # replay existing model (continue-train case)
        dt_leaf = np.uint8 if self.config.num_leaves <= 256 else np.uint16
        for i, tree in enumerate(self.models):
            if self._track_train_leaf:
                la = tree.predict_leaf_index(raw).astype(dt_leaf)
                vs.leaf_idx_per_tree.append(la)
                vs.score[i % self.num_tree_per_iteration] += \
                    tree.leaf_value[la.astype(np.int32)]
            else:
                vs.score[i % self.num_tree_per_iteration] += \
                    tree.predict(raw)
        if binned is not None and self.num_features > 0:
            if self._bundles is not None:
                xtv = self._bundles.bundle_matrix(binned.binned).T
            else:
                xtv = binned.binned.T  # (F, rows) narrow dtype
                xtv = np.pad(xtv,
                             ((0, self._F_pad - xtv.shape[0]), (0, 0)))
            vs.xt = jnp.asarray(xtv)  # narrow dtype on device
        self.valid_sets.append(vs)

    # ------------------------------------------------------------------
    def _feature_fraction_mask(self):
        import jax.numpy as jnp
        F = self.num_features
        frac = self.config.feature_fraction
        mask = np.zeros(self._F_pad, bool)
        if frac >= 1.0:
            mask[:F] = True
        else:
            k = max(1, int(frac * F))
            mask[self._rng_feature.choice(F, size=k, replace=False)] = True
        return jnp.asarray(mask)

    def _bagging_active(self) -> bool:
        cfg = self.config
        pos_neg = (cfg.pos_bagging_fraction < 1.0 or
                   cfg.neg_bagging_fraction < 1.0)
        return cfg.bagging_freq > 0 and (cfg.bagging_fraction < 1.0 or
                                         pos_neg)

    def _draw_bag_mask(self, it):
        """Pure device draw of the bernoulli/stratified bagging mask
        for (global) iteration ``it`` — ``it`` may be a host int or a
        traced scalar (the fused super-step folds it inside the scan).
        Keying the PRNG by the GLOBAL iteration — and running ONE
        jitted program from both the sequential and the scan-inlined
        call sites — makes the fused and sequential paths
        bit-identical."""
        import jax
        if getattr(self, "_trace_raw", False):
            # battery trace: ``self._bag_key`` is a per-model tracer,
            # so the draw must inline into the enclosing trace instead
            # of caching a jitted wrapper around it.  jit called under
            # a trace inlines to the same program as the raw call, so
            # this is program-identical to the solo path.
            self._ensure_label_pos()
            return self._draw_bag_mask_impl(it)
        if getattr(self, "_bag_draw_jit", None) is None:
            self._ensure_label_pos()
            self._bag_draw_jit = jax.jit(self._draw_bag_mask_impl)
        return self._bag_draw_jit(it)

    def _ensure_label_pos(self) -> None:
        """Materialize the label-sign vector for stratified bagging
        OUTSIDE any trace (a lazily-built device array created during
        tracing would cache a tracer on self)."""
        import jax.numpy as jnp
        cfg = self.config
        pos_neg = (cfg.pos_bagging_fraction < 1.0 or
                   cfg.neg_bagging_fraction < 1.0)
        if pos_neg and self._label_pos is None:
            self._label_pos = jnp.asarray(np.asarray(
                self.train_set.metadata.label)[:self.num_data] > 0)

    def _draw_bag_mask_impl(self, it):
        import jax
        import jax.numpy as jnp
        cfg = self.config
        pos_neg = (cfg.pos_bagging_fraction < 1.0 or
                   cfg.neg_bagging_fraction < 1.0)
        key = jax.random.fold_in(self._bag_key, it)
        u = jax.random.uniform(key, (self.num_data,))
        if pos_neg:
            # class-stratified bagging: positives/negatives sampled
            # at their own fractions
            return jnp.where(self._label_pos,
                             u < cfg.pos_bagging_fraction,
                             u < cfg.neg_bagging_fraction
                             ).astype(jnp.float32)
        return (u < cfg.bagging_fraction).astype(jnp.float32)

    def _bagging_mask(self, grad=None, hess=None):
        """Per-row sample weights for this iteration (0 = out of bag;
        non-0/1 weights rescale grad/hess, counts stay presence-based).
        Base class: bernoulli bagging every ``bagging_freq`` iterations
        (``GBDT::Bagging``, ``gbdt.cpp:182``); GOSS/MVS override using
        the gradient magnitudes.  Returns a DEVICE (N,) f32 vector —
        mask generation is jitted device work (a host mask means a 4N-
        byte upload per iteration)."""
        cfg = self.config
        if not self._bagging_active():
            return None
        if self.iter % cfg.bagging_freq == 0:
            self._cached_bag = self._draw_bag_mask(self.iter)
        return getattr(self, "_cached_bag", None)

    def _fused_mask_fn(self):
        """The sampling mask as a scan-capturable pure function
        ``(iter, prev_mask, grad, hess) -> mask`` for the fused
        super-step, or None when no sampling applies.  Base class:
        bernoulli/stratified bagging — redraw on ``bagging_freq``
        boundaries, carry the previous mask otherwise (exactly
        :meth:`_bagging_mask`'s cache semantics, with the cache as the
        scan carry).  GOSS/MVS override (models/boosting.py): their
        masks are pure functions of the iteration's gradients."""
        import jax
        if not self._bagging_active():
            return None
        self._ensure_label_pos()
        freq = self.config.bagging_freq

        def fn(it, prev, grad, hess):
            return jax.lax.cond(it % freq == 0, self._draw_bag_mask,
                                lambda _: prev, it)
        return fn

    # ------------------------------------------------------------------
    def _pipeline_ok(self) -> bool:
        """Pipelined boosting applies when nothing needs the host tree
        within the iteration: single tree per iteration, no validation
        scoring, no per-tree leaf tracking (DART) and no objective leaf
        renewal hook — then the newest tree's record fetch can hide
        behind the NEXT tree's device build."""
        return (self._pipeline_enabled and
                self.num_tree_per_iteration == 1 and
                not self.valid_sets and not self._track_train_leaf and
                self.objective is not None and self.num_features > 0 and
                type(self.objective).renew_tree_output
                is Objective.renew_tree_output)

    # ---- fused boosting super-steps ----------------------------------
    # One jitted ``lax.scan`` runs K = config.fused_iters boosting
    # iterations entirely on device — objective gradients, the
    # bagging/GOSS/MVS mask draw (PRNG key folded by GLOBAL iteration
    # inside the scan), ``build_tree`` and the score update — with the
    # (score, bagging-mask) carry donated.  The stacked (K, ...) split
    # records come back in ONE packed device->host transfer and are
    # materialized into K Trees up front; train_one_iter then serves
    # them one per call, so the external one-iteration-per-update
    # contract (engine loop, callbacks, num_boost_round counting) is
    # unchanged while Python dispatches and host syncs drop from
    # O(iterations) to O(iterations / K).  Both GPU-GBDT systems we
    # track keep the iteration resident on the accelerator the same
    # way (arXiv:1806.11248; arXiv:1706.08359).  Bit-exact with the
    # sequential (pipelined) path: same ops in the same order, the
    # same PRNG folds, and the same host-RNG feature-fraction draws
    # (pre-drawn per block in sequential order).

    def _fused_ok(self) -> bool:
        """Super-step eligibility.  Anything that needs the host tree,
        per-iteration scores, or per-iteration host randomness beyond
        the pre-drawn feature masks falls back to the per-iteration
        path: custom objectives (grad is checked at the call site),
        leaf-renewal objectives, multi-model-per-iteration objectives,
        DART/RF (``_superstep_enabled``), attached validation sets and
        training metrics (their eval cadence — including early
        stopping — reads scores every iteration).  Distributed
        learners (data/feature/voting) FUSE: the same K-iteration scan
        runs SPMD under ``shard_map`` over the learner's mesh, with
        the strategy collectives inside the one compiled program
        (:meth:`_build_superstep_fn`)."""
        cfg = self.config
        return (self._superstep_enabled and cfg.fused_iters > 1 and
                self.num_tree_per_iteration == 1 and
                not self.valid_sets and not self._track_train_leaf and
                self.objective is not None and
                self.num_features > 0 and
                not cfg.is_provide_training_metric and
                type(self.objective).renew_tree_output
                is Objective.renew_tree_output and
                self.objective.gradient_fn() is not None)

    def _fused_bias_pending(self) -> bool:
        """True when the NEXT iteration is the boost_from_average
        iteration 0 — it mutates the score from host state and the
        first tree absorbs the bias, so it runs unfused (the pipelined
        path); fusion engages from iteration 1."""
        return (self.iter == 0 and self.config.boost_from_average and
                not self._models and self._pending is None and
                self.train_set.metadata.init_score is None)

    def _superstep_core(self, batched: bool = False):
        """The raw (unjitted, unsharded) K-iteration scan body, shared
        by the solo fused path (:meth:`_build_superstep_fn`) and the
        many-model battery trainer (``models/battery.py``).

        With ``batched=True`` the returned callable grows two trailing
        per-model arguments — ``wvec``, a per-row gradient/hessian
        weight (the battery's CV fold masks ride here, multiplying
        exactly where solo weighted training multiplies metadata
        weights), and ``bag_key``, the bagging/GOSS/MVS PRNG key that
        replaces the closure-captured ``self._bag_key`` — so the whole
        scan can be lifted over a leading model axis with ``jax.vmap``.
        Per-model values arrive TRACED while every structural knob
        stays static (the bit-exactness anchor: a traced operand of
        equal value yields the same elementwise ops as a constant, but
        a static knob becoming traced would change the expression
        tree).  The tracer swap happens at trace time only, and
        ``_trace_raw`` routes the mask draws to their raw impls so no
        jitted wrapper captures a tracer in its closure."""
        import jax
        import jax.numpy as jnp
        from ..ops.grow import build_tree_impl
        from ..ops.lookup import take_small

        dist = self._dist
        p = self.grow_params if dist is None else dist.params
        n, n_pad = self.num_data, self._n_pad
        obj = self.objective
        grad_fn = self.objective.gradient_fn()
        mask_fn = self._fused_mask_fn()
        self._fused_has_bagging = mask_fn is not None
        bundle_maps = self._bundle_maps
        quantize = bool(p.quantize)
        li_dt = jnp.uint8 if self.config.num_leaves <= 255 else jnp.uint16
        # keys the host never reads stay on device (leaf_idx is kept
        # separately, narrow, for the exact rewind/rollback replay)
        drop = ("leaf_idx", "leaf_values", "leaf_values_final",
                "leaf_stats")
        rows_sharded = dist is not None and dist.kind in ("data",
                                                          "voting",
                                                          "data2d")
        # the row state on the shard (the plan's row_state ladder):
        # score carry, objective's row tensors, gradients, leaf index
        # and score update are each device's own rows; no array of the
        # whole job's rows enters, leaves or is a constant of the
        # per-device program
        own_rows = self._rows_on_shard
        if rows_sharded:
            # data2d shards rows over the ROW axis only (R of the R*F
            # devices); the 1-D learners' row axis is the whole mesh
            ax = dist.params.dist.axis
            n_loc = n_pad // dist.row_shards

        pager_view = getattr(self, "_pager_view", None)
        # the objective's device tables (lambdarank's query layout)
        # ride as the last argument and are swapped in while the scan
        # traces, so no table is a constant of the program; the
        # battery's lifted scan keeps them closed over
        tabled = not batched and self._tables_ride()

        def superstep(score, bag0, lr, quant_key, xt, base_mask,
                      num_bins, missing_type, is_cat, iters, fmasks,
                      tree_ids, *extras):
            tables = {}
            if tabled:
                *extras, tables = extras
            if pager_view is not None:
                # paged lane: the xt operand is a replicated dummy —
                # the scan reads the matrix through page callbacks
                # (trace-time swap; the scan body is otherwise
                # IDENTICAL to the resident one, which is what makes
                # paged-vs-resident byte-parity structural)
                xt = pager_view
            if batched:
                wvec, bag_key = extras
                saved_key = self._bag_key
                saved_raw = getattr(self, "_trace_raw", False)
                self._bag_key = bag_key
                self._trace_raw = True
            elif own_rows:
                # the shard's rows of the objective's row tensors, and
                # which of the shard's rows are the job's (not padding)
                (rows,) = extras
                live = base_mask > 0

            def own_rows_step(carry, xs):
                """One iteration on the shard's own rows.  Every row's
                gradient, rounding bits (hashed from its index in the
                job, ops/grow.py) and score update are those of the
                serial scan; the sums the tree needs cross the row
                axis inside ``build_tree_impl`` (each pass's histogram
                psum, the scales' pmax, the statistics)."""
                sc, bag_prev = carry
                it, fmask, tid = xs
                with obj.rows_as(rows):
                    grad, hess = obj.get_gradients(sc)
                # the padding rows' gradients are whatever the
                # objective makes of a zero label: they count for
                # nothing, and their score stays where it started
                gp_b = jnp.where(live, jnp.atleast_2d(grad)[0].astype(
                    jnp.float32), 0.0)
                hp_b = jnp.where(live, jnp.atleast_2d(hess)[0].astype(
                    jnp.float32), 0.0)
                kw = {}
                if quantize:
                    kw["quant_key"] = jax.random.fold_in(quant_key, tid)
                rec = build_tree_impl(xt, gp_b, hp_b, base_mask, fmask,
                                      num_bins, missing_type, is_cat, p,
                                      **kw)
                vals = rec["leaf_values_final"] * lr
                li = rec["leaf_idx"]
                new_sc = sc.at[0].add(
                    jnp.where(live, take_small(vals, li), 0.0))
                host_rec = {k: v for k, v in rec.items()
                            if k not in drop}
                # the health flag (see the replicated step) is the
                # job's: each shard's own rows, then one scalar pmax
                bad = jnp.logical_not(
                    jnp.all(jnp.isfinite(gp_b)) &
                    jnp.all(jnp.isfinite(vals)) &
                    jnp.all(jnp.isfinite(new_sc)))
                host_rec["nonfinite"] = jax.lax.pmax(
                    bad.astype(jnp.int32), ax) > 0
                return (new_sc, bag_prev), \
                    (host_rec, li.astype(li_dt), vals)

            def step(carry, xs):
                sc, bag_prev = carry
                it, fmask, tid = xs
                if batched:
                    # per-model fold/sample weights multiply inside
                    # the objective exactly where solo weighted
                    # training multiplies metadata weights
                    # (objectives.py ``_w``/``_jitted_gradients``) —
                    # the loop-of-solo CV reference's op order
                    with obj.weight_override(wvec):
                        grad, hess = obj.get_gradients(sc)
                else:
                    grad, hess = grad_fn(sc)
                grad = jnp.atleast_2d(grad)
                hess = jnp.atleast_2d(hess)
                bag = mask_fn(it, bag_prev, grad, hess) \
                    if mask_fn is not None else None
                gp = jnp.pad(grad[0].astype(jnp.float32), (0, n_pad - n))
                hp = jnp.pad(hess[0].astype(jnp.float32), (0, n_pad - n))
                w = None
                if bag is not None:
                    w = jnp.pad(jnp.asarray(bag, jnp.float32).reshape(-1),
                                (0, n_pad - n))
                    gp = gp * w
                    hp = hp * w
                if rows_sharded:
                    # replicated row state: the full-N weighted
                    # gradients are computed on every device
                    # (bit-identical to the serial scan), then each
                    # shard slices ITS contiguous row block for the
                    # local histogram pass; base_mask arrives already
                    # local via its in_spec
                    off = jax.lax.axis_index(ax) * n_loc
                    gp_b = jax.lax.dynamic_slice_in_dim(gp, off, n_loc)
                    hp_b = jax.lax.dynamic_slice_in_dim(hp, off, n_loc)
                    mask_b = base_mask
                    if w is not None:
                        mask_b = mask_b * (jax.lax.dynamic_slice_in_dim(
                            w, off, n_loc) > 0)
                else:
                    gp_b, hp_b = gp, hp
                    mask_b = base_mask if w is None \
                        else base_mask * (w > 0)
                kw = {}
                if quantize:
                    kw["quant_key"] = jax.random.fold_in(quant_key, tid)
                if bundle_maps is not None:
                    kw["bundle_maps"] = bundle_maps
                rec = build_tree_impl(xt, gp_b, hp_b, mask_b, fmask,
                                      num_bins, missing_type, is_cat, p,
                                      **kw)
                vals = rec["leaf_values_final"] * lr
                li = rec["leaf_idx"]
                if rows_sharded:
                    # the replicated row state (voting, data2d, and the
                    # data learner where the row_state ladder refused
                    # the shard: GOSS, MVS, bagging): the score delta
                    # is looked up on the shard's own rows and ONE
                    # tiled all-gather rebuilds the whole job's (N,)
                    # update for the replicated carry, in contiguous
                    # row order, so the adds land per row as in the
                    # serial scan.  own_rows_step has no such gather
                    upd = jax.lax.all_gather(take_small(vals, li), ax,
                                             tiled=True)[:n]
                else:
                    li = li[:n]
                    upd = take_small(vals, li)
                new_sc = sc.at[0].add(upd)
                host_rec = {k: v for k, v in rec.items()
                            if k not in drop}
                # numerical-health flag: non-finite gradients, leaf
                # values or scores ride the existing packed block
                # fetch (zero extra device calls).  Gradients must be
                # checked too — NaN gradients kill every split gain
                # and masquerade as a legitimate "no splittable leaf"
                # stop, which would end training silently instead of
                # loudly (utils/health.py)
                host_rec["nonfinite"] = jnp.logical_not(
                    jnp.all(jnp.isfinite(grad[0])) &
                    jnp.all(jnp.isfinite(vals)) &
                    jnp.all(jnp.isfinite(new_sc)))
                new_bag = bag if bag is not None else bag_prev
                return (new_sc, new_bag), \
                    (host_rec, li.astype(li_dt), vals)

            try:
                with obj.tables_as(tables):
                    (final_sc, final_bag), (recs, leaf_idx_k, vals_k) = \
                        jax.lax.scan(own_rows_step if own_rows else step,
                                     (score, bag0),
                                     (iters, fmasks, tree_ids))
            finally:
                if batched:
                    # the key/raw swap is trace-time state only —
                    # restore it even when the trace aborts (e.g. a
                    # kernel without a batching rule under vmap)
                    self._bag_key = saved_key
                    self._trace_raw = saved_raw
            # returning the donated inputs forces XLA to copy the
            # block-start score AND bagging mask out — the
            # rewind/rollback anchor at no extra dispatch, and (under
            # async pipelining) the un-donated value the PREVIOUS
            # block's commit reads after ITS outputs were donated to
            # this dispatch
            return (score, bag0, final_sc, final_bag, recs, leaf_idx_k,
                    vals_k)

        return superstep

    def _build_superstep_fn(self):
        """Build the jitted K-iteration scan.  K is carried by the xs
        shapes, so one jitted callable serves every block size (the
        shorter tail block recompiles once).  Big device residents
        (the binned matrix, masks, descriptors) ride as ARGUMENTS —
        closure capture would embed them in the remote-compile
        payload.  The objective's row tensors are arguments too where
        the row state is the shard's; elsewhere they stay
        closure-captured because ``gradient_fn`` owns them.  The
        objective's tables (:meth:`Objective.tables`) are always
        arguments, the last one.

        With a distributed learner the scan runs SPMD: the whole
        K-iteration program is wrapped in ``shard_map`` over the
        learner's mesh, the binned matrix arrives as the local shard
        (rows for data/voting, features for feature-parallel), and the
        per-strategy histogram/merge collectives inside
        ``build_tree_impl`` ride within the one compiled program — K
        iterations of sharded build+update cost ONE dispatch, not 5K
        per-shard dispatches.

        Where per-row state lives is the plan's ``row_state``
        (models/tier.py).  ``shard`` (the data learner, a pointwise
        objective, no sampling over the whole job): the score carry,
        the objective's row tensors, gradients, leaf index and score
        update are each device's own rows (``own_rows_step``); what
        crosses devices is each pass's histogram psum, the
        quantization scales' pmax and scalars, and no array of the
        whole job's rows is an operand, a constant or a result of the
        per-device program.  ``replicated`` (every other case):
        gradients, mask draws and the score update run on every device
        for all rows (identical math on every shard — the
        bit-exactness anchor against the serial scan), and the
        row-sharded learners all-gather the score delta once per
        iteration for the replicated carry."""
        import jax

        superstep = self._superstep_core()
        dist = self._dist
        rows_sharded = dist is not None and dist.kind in ("data",
                                                          "voting",
                                                          "data2d")
        if dist is not None:
            from jax.sharding import PartitionSpec as P
            ax_name = dist.params.dist.axis
            R = P()
            if dist.kind == "feature":
                # features sharded: xt + descriptors + the stacked
                # per-iteration feature masks split over the feature
                # axis; rows (and the score carry) replicated
                in_specs = (R, R, R, R, P(ax_name, None), R,
                            P(ax_name), P(ax_name), P(ax_name), R,
                            P(None, ax_name), R)
            elif dist.kind == "data2d":
                # 2-D: rows down the data axis (base_mask local),
                # feature tiles + descriptors + the stacked feature
                # masks across the feature axis; the score carry and
                # gradients stay replicated
                fax = dist.feat_axis
                in_specs = (R, R, R, R, P(fax, ax_name), P(ax_name),
                            P(fax), P(fax), P(fax), R,
                            P(None, fax), R)
            else:   # data | voting: rows sharded, features whole
                in_specs = (R, R, R, R, P(None, ax_name), P(ax_name),
                            R, R, R, R, R, R)
            # outputs are replicated by construction — split records/
            # merges are strategy-replicated, the score delta is
            # re-gathered in-step — EXCEPT the stacked per-iteration
            # leaf assignment of the row-sharded learners: each shard
            # emits its local (K, n_loc) block and the out_spec
            # stitches the global (K, n_pad) table with no collective
            # (the host-side rewind replay is its only reader)
            li_spec = P(None, ax_name) if rows_sharded else R
            sc_spec = R
            if self._rows_on_shard:
                # the score carry (in, its block-start copy and out)
                # and the objective's row tensors: rows over the axis,
                # whatever leads whole.  The bagging carry is the
                # one-element sentinel (no sampling on this path)
                sc_spec = P(None, ax_name)
                rows_spec = {
                    k: P(*([None] * (v.ndim - 1)), ax_name)
                    for k, v in self.objective.rows().items()}
                in_specs = (sc_spec,) + in_specs[1:] + (rows_spec,)
            if self._tables_ride():
                # the objective's tables, whole on every device
                in_specs = in_specs + (R,)
            if self._pager is not None:
                # paged: the xt slot carries a replicated dummy; each
                # program instance pages its OWN (f_loc, n_loc) block
                # via axis-indexed callbacks instead of receiving a
                # sharded operand
                in_specs = in_specs[:4] + (R,) + in_specs[5:]
            superstep = jax.shard_map(superstep, mesh=dist.mesh,
                                      check_vma=False,
                                      in_specs=in_specs,
                                      out_specs=(sc_spec, R, sc_spec, R,
                                                 R, li_spec, R))

        # carry donation frees both N-sized buffers for in-place reuse
        # on device; CPU XLA has no donation and would warn per call
        donate = (0, 1) if jax.default_backend() not in ("cpu",) else ()
        return jax.jit(superstep, donate_argnums=donate)

    def _pipeline_depth(self) -> int:
        """Extra fused blocks kept in flight beyond the one being
        landed (``superstep_pipeline_depth``); 0 = dispatch-then-fetch
        (the pre-pipelining behavior)."""
        return max(int(getattr(self.config, "superstep_pipeline_depth",
                               0) or 0), 0)

    def _next_dispatch_iter(self) -> int:
        """First iteration of the next block to dispatch: the frontier
        of the in-flight queue, or the served boundary when nothing is
        outstanding."""
        if self._sq:
            last = self._sq[-1]
            return last["i0"] + last["k"]
        return self.iter

    def _dispatch_superstep_block(self, elastic_alive,
                                  required: bool) -> bool:
        """Dispatch ONE fused block at the queue frontier and append
        it to the in-flight queue (dispatched, unfetched).  Returns
        False without dispatching when the frontier is at/past the
        ``num_iterations`` horizon and the block is speculative
        (``required=False``) — the pipeline never wastes device work
        past the end of training."""
        import time as _time

        import jax
        import jax.numpy as jnp
        from ..utils import telemetry as _telemetry
        from ..utils.profiling import timed

        cfg = self.config
        i0 = self._next_dispatch_iter()
        K = int(cfg.fused_iters)
        remaining = cfg.num_iterations - i0
        if remaining <= 0 and not required:
            return False
        if 0 < remaining < K:
            # auto-size the tail block down to the num_iterations
            # boundary (shorter scan -> one extra XLA compile there,
            # which triage_run treats as per-shape warmup)
            K = remaining
        # elastic dispatch fence: the ONLY host state a fused dispatch
        # consumes before its fetch lands is the feature-fraction RNG
        # stream and the quantization-stream position — when the
        # dispatch is abandoned (hung collective) or dies (shard
        # loss), abort_inflight_dispatch restores exactly these
        # (parallel/elastic.py recovery path).  With blocks in flight
        # the LIVE fence is always the OLDEST outstanding dispatch's
        # pre-state: restoring it rewinds across EVERY queued block's
        # RNG/quantization-stream consumption in one step.
        fence = {"rng_state": self._rng_feature.get_state(),
                 "tid": self._trees_dispatched}
        if self.__dict__.get("_dispatch_fence") is None:
            self._dispatch_fence = fence
        with timed("superstep/dispatch", iter=i0, k=K):
            # host feature-fraction draws consumed in sequential order
            fmasks = jnp.stack([self._feature_fraction_mask()
                                for _ in range(K)])
            iters = jnp.arange(i0, i0 + K, dtype=jnp.int32)
            tree_ids = jnp.arange(self._trees_dispatched,
                                  self._trees_dispatched + K,
                                  dtype=jnp.int32)
            self._trees_dispatched += K
            if self._superstep_jit is None:
                self._superstep_jit = self._build_superstep_fn()
            if self._sq:
                # chain on the in-flight predecessor's device futures:
                # the score/bag carries never touch the host between
                # blocks, and this dispatch goes out BEFORE the
                # predecessor's fetch
                prev = self._sq[-1]["outs"]
                score0, bag0 = prev[2], prev[3]
            else:
                score0 = self._score
                bag0 = getattr(self, "_cached_bag", None)
                if bag0 is None:
                    # ALL-ONES sentinel: with no cached mask the
                    # sequential path trains UNBAGGED until the next
                    # bagging_freq boundary (continue-training starts
                    # mid-cycle), and a unit weight vector is
                    # bit-identical to "no mask" (x*1.0 == x); a zeros
                    # sentinel would silently zero every gradient
                    # until the first in-block draw
                    bag0 = jnp.ones(1 if self._rows_on_shard
                                    else self.num_data, jnp.float32)
            qk = self._quant_key if self._quant_key is not None \
                else jax.random.PRNGKey(0)
            _telemetry.counters.incr("superstep_dispatches")
            if self._dist is not None:
                from ..utils import faults as _faults
                # fired once per fused-block dispatch: the injected
                # stand-in for a shard dying or wedging inside the
                # block's collectives (tools/chaos_elastic.py)
                fault_mode = _faults.fire("mesh.collective")
                if fault_mode:
                    self._mesh_collective_fault(fault_mode,
                                                elastic_alive)
            outs = self._superstep_jit(
                score0, bag0, jnp.float32(self.shrinkage_rate), qk,
                self._xt, self._base_mask, self._num_bins,
                self._missing_type, self._is_cat, iters, fmasks,
                tree_ids, *((self.objective.rows(),)
                            if self._rows_on_shard else ()),
                *((self.objective.tables(),)
                  if self._tables_ride() else ()))
        # an abandoned attempt (elastic stall watchdog moved on and a
        # re-mesh owns ``self`` now) must not commit ANY state — the
        # checks bracket every device interaction
        self._abandoned_check(elastic_alive)
        self._sq.append({"outs": outs, "i0": i0, "k": K,
                         "fence": fence, "lr": self.shrinkage_rate,
                         "t_dispatch": _time.perf_counter()})
        return True

    def _discard_queue(self) -> None:
        """Drop every dispatched-but-unfetched block and restore the
        host state their dispatches consumed (feature-fraction RNG
        draws, quantization-stream positions) — the pipelined half of
        the dispatch-fence contract.  The drain points are exactly
        the boundaries that already force one: the no-split stop, a
        learning-rate change, eligibility drift, rollback/rewind,
        a numerical-health trip, elastic abort/re-mesh."""
        if not self._sq:
            return
        first = self._sq[0]
        self._sq = []
        self._rng_feature.set_state(first["fence"]["rng_state"])
        self._trees_dispatched = int(first["fence"]["tid"])
        self.__dict__.pop("_dispatch_fence", None)

    def _recompute_bag_cache(self) -> None:
        """Rebuild the bernoulli/stratified bagging-mask cache from
        its defining PRNG fold at the CURRENT iteration — the one
        recipe shared by the fused-rewind restore and the pipeline
        drain (a drained queue may have donated the cached device
        buffer to an abandoned dispatch)."""
        cfg = self.config
        if not (self._fused_has_bagging and
                type(self)._bagging_mask is GBDT._bagging_mask):
            return
        it = self.iter
        if it > 0:
            last_draw = (it - 1) // cfg.bagging_freq * cfg.bagging_freq
            self._cached_bag = self._draw_bag_mask(last_draw)
        else:
            self.__dict__.pop("_cached_bag", None)

    def _train_superstep(self) -> bool:
        """One fused-super-step update: top up the in-flight dispatch
        queue (block K+1 goes out BEFORE block K's stacked records are
        fetched, so the one device->host round-trip per block hides
        behind the next block's device compute), then land the oldest
        block and serve its first tree.  The healthy-path device-call
        budget stays 2 per K-block at any pipeline depth — pipelining
        reorders the same dispatch+fetch pair, it never adds calls, and
        the fetch's ``block_until_ready`` (``fetch/wait``) waits on the
        pack its transfer waits on, so it adds none either."""
        self._flush_pending()
        if self._stop_flag:
            return True
        # THIS attempt's generation token, captured before any device
        # work: a later retry overwrites the attribute with its own
        # token, and an abandoned zombie checking the shared attribute
        # instead of its captured one would see the RETRY's (alive)
        # token and commit phantom state
        elastic_alive = getattr(self, "_elastic_alive", None)
        self._elastic_beat()
        if self._sq and self._sq[0]["lr"] != self.shrinkage_rate:
            # a learning_rates schedule changed the shrinkage since
            # the queued blocks were dispatched: they were built at
            # the old rate — drain and redispatch at the new one
            # (BEFORE topping up, so no fresh block chains onto a
            # stale carry)
            self._discard_queue()
        target = 1 + self._pipeline_depth()
        while len(self._sq) < target:
            if not self._dispatch_superstep_block(
                    elastic_alive, required=not self._sq):
                break
        return self._land_superstep_block(elastic_alive)

    def _land_superstep_block(self, elastic_alive) -> bool:
        """Fetch + materialize the OLDEST in-flight block (the K'
        trees materialize from a single stacked fetch) and serve its
        first tree."""
        import time as _time

        from ..utils import telemetry as _telemetry
        from ..utils.profiling import timed

        entry = self._sq.pop(0)
        K = entry["k"]
        i0 = entry["i0"]
        rng_state = entry["fence"]["rng_state"]
        start_tid = int(entry["fence"]["tid"])
        t_fetch0 = _time.perf_counter()
        with timed("superstep/fetch", iter=i0, k=K):
            # the block's ONE device->host transfer (packed f32)
            _telemetry.counters.incr("superstep_fetches")
            host = self._fetch_records(entry["outs"][4], iter=i0, k=K)
        self._abandoned_check(elastic_alive)
        # the live fence moves to the next outstanding dispatch (or
        # clears): this block is fetched, its state commits below
        if self._sq:
            self._dispatch_fence = self._sq[0]["fence"]
        else:
            self.__dict__.pop("_dispatch_fence", None)
        # per-block heartbeat: rides the block bookkeeping the
        # superstep telemetry record is assembled from — zero extra
        # device calls (parallel/elastic.py)
        self._elastic_beat(block=True)
        (start_score, _start_bag, final_score, final_bag, _recs,
         leaf_idx_k, vals_k) = entry["outs"]
        bad = np.asarray(host.pop("nonfinite", np.zeros(K)), bool)
        if np.any(bad):
            # the per-iteration health flag tripped: rewind to the
            # served boundary (nothing from this block — or the
            # queued blocks chained on it — was served or applied to
            # the score; only dispatch bookkeeping moved) and fail
            # loudly instead of serving a NaN model.  A finite stop
            # tree BEFORE the first bad iteration wins: post-stop
            # scan iterations are phantom state the replay discards
            # anyway.
            j = int(np.argmax(bad))
            stops = np.nonzero(np.asarray(host["n_leaves"])[:K] <= 1)[0]
            if stops.size == 0 or j <= int(stops[0]):
                self._sq = []
                self.__dict__.pop("_dispatch_fence", None)
                self._trees_dispatched = start_tid
                self._rng_feature.set_state(rng_state)
                from ..utils.health import abort_nonfinite
                abort_nonfinite(getattr(self, "_telemetry", None),
                                i0 + j, "superstep",
                                f"fused block of {K} starting at "
                                f"iteration {i0}")
        with timed("superstep/to_tree", iter=i0, k=K):
            n_leaves_k = host["n_leaves"]
            trees, stop_idx = [], None
            for t in range(K):
                if int(n_leaves_k[t]) <= 1:
                    # constant stop tree; its init bias is always 0
                    # here (iteration 0 runs unfused) and its score
                    # contribution inside the scan was gated to 0
                    trees.append(Tree(2))
                    stop_idx = t
                    break
                rec_t = {k: v[t] for k, v in host.items()}
                tree = self._records_to_tree(rec_t)
                tree.apply_shrinkage(entry["lr"])
                trees.append(tree)
        hist_passes = self._count_growth(
            host, len(trees), scan_flags=K if self._rows_on_shard else 0)
        self._fused_block = {
            "start_score": start_score, "start_iter": i0,
            "start_tid": start_tid, "rng_state": rng_state,
            "trees": trees, "stop_idx": stop_idx,
            "leaf_idx": leaf_idx_k, "vals": vals_k, "served": 0,
            # the shrinkage the block's trees were built with: a
            # learning_rates schedule (reset_parameter callback)
            # changing it mid-block invalidates the unserved trees
            "lr": entry["lr"],
        }
        if stop_idx is None:
            if self._sq:
                # this block's own final score/bag buffers were
                # DONATED to the next queued dispatch; commit the
                # bit-identical copies that dispatch returned of its
                # inputs instead
                self._score = self._sq[0]["outs"][0]
                if self._fused_has_bagging:
                    self._cached_bag = self._sq[0]["outs"][1]
            else:
                self._score = final_score
                if self._fused_has_bagging:
                    self._cached_bag = final_bag
        else:
            # the scan has no early exit: iterations AFTER the stop
            # tree still ran, and under bagging their fresh draws can
            # even split — those phantom contributions (and the
            # post-stop bagging mask) must not leak into the
            # model-consistent state.  Queued successor blocks are
            # phantom state wholesale: discard them (restoring their
            # consumed RNG draws), then replay the pre-stop prefix
            # (the stop tree itself contributes 0).
            self._discard_queue()
            self._score, _ = self._fused_replay_score(stop_idx)
        # superstep telemetry marker (consumed by train_one_iter).
        # fetch_overlap_s: wall between this block's dispatch and its
        # fetch — the window its device compute overlapped host work
        # (serving the previous block, materializing its trees,
        # dispatching the successor).  ~0 at depth 0 by construction.
        self._tele_superstep = {
            "k": K, "hist_passes": hist_passes,
            "pipeline_depth": self._pipeline_depth(),
            "fetch_overlap_s": round(
                max(t_fetch0 - entry["t_dispatch"], 0.0), 6),
        }
        if self._dist is not None:
            # per-block collective accounting for the sharded scan:
            # static per-pass estimate x passes in the block, plus the
            # once-per-iteration leaf-assignment all-gather of the
            # row-sharded learners
            hp = hist_passes if hist_passes is not None \
                else K * max(self.config.num_leaves, 1)
            extra_b = extra_o = 0
            if self._dist.kind in ("data", "voting", "data2d"):
                # per-SHARD send payload of the tiled leaf-assignment
                # all-gather — n_loc*4 bytes, O(1) in mesh size at
                # fixed rows/shard (collective_bytes_per_pass is a
                # per-shard estimate; mixing in the gathered GLOBAL
                # width would make the telemetry read as if wire cost
                # grew with the mesh)
                n_loc = self._n_pad // self._dist.row_shards
                extra_b, extra_o = K * n_loc * 4, K
            # per-AXIS attribution (obs/rules.py keys its weak-scaling
            # anomaly on these): 1-D learners put everything on their
            # single axis; data2d splits histogram traffic (row axis)
            # from merge+routing (feature axis).  The leaf-assignment
            # gather rides the row axis.
            if self._collective_plan:
                # the wave data learner: the count _count_growth made
                # of the block's own passes (the counters' figure),
                # plus the score delta's gather where the carry is
                # replicated; all of it on the one axis
                if self._rows_on_shard:
                    extra_b = extra_o = 0
                total_b = self._collective_last[0] + extra_b
                total_o = self._collective_last[1] + extra_o
                per_ax_b = {self._dist.axis: total_b}
                per_ax_o = {self._dist.axis: total_o}
            else:
                per_ax_b, per_ax_o = {}, {}
                for axn, v in self._collective_per_axis.items():
                    per_ax_b[axn] = int(v["bytes"] * hp)
                    per_ax_o[axn] = int(v["ops"] * hp)
                if extra_b and per_ax_b:
                    axn = self._dist.axis
                    per_ax_b[axn] = per_ax_b.get(axn, 0) + extra_b
                    per_ax_o[axn] = per_ax_o.get(axn, 0) + extra_o
                total_b = int(self._collective_per_pass * hp + extra_b)
                total_o = int(self._collective_ops_per_pass * hp
                              + extra_o)
            self._tele_superstep.update({
                "learner": self._dist.kind,
                "num_shards": int(self._dist.num_shards),
                "mesh_shape": [int(s) for s in
                               self._dist.mesh.devices.shape],
                "collective_bytes": total_b,
                "collective_ops": total_o,
                "collective_bytes_axis": per_ax_b,
                "collective_ops_axis": per_ax_o,
            })
        return self._serve_fused()

    def _serve_fused(self) -> bool:
        """Append the next materialized tree of the in-flight block —
        one boosting iteration from the caller's point of view."""
        blk = self._fused_block
        t = blk["served"]
        blk["served"] = t + 1
        self._models.append(blk["trees"][t])
        self._tele_serving = True
        if blk["stop_idx"] is not None and t == blk["stop_idx"]:
            self._stop_flag = True
            Log.warning("Stopped training because there are no more "
                        "leaves that meet the split requirements")
            return True
        self.iter += 1
        return False

    def _fused_replay_score(self, pos: int):
        """(score, prev_score) after replaying ``pos`` block
        iterations from the stacked (leaf values, leaf assignment)
        pairs the scan returned — the same take_small + f32 add the
        scan performed, so the replayed score is bit-identical to the
        in-scan partial state.  The ONE implementation behind the
        stop path, the rewind/rollback restore and the mid-block
        ``train_score`` reader (they must never drift apart)."""
        import jax.numpy as jnp
        from ..ops.lookup import take_small
        blk = self._fused_block
        score, prev = blk["start_score"], None
        # row-sharded learners stitch the stacked leaf table at the
        # PADDED width (each shard emits its local block); the serial
        # scan stores it pre-sliced — normalize to the real row count
        for t in range(pos):
            prev = score
            score = score.at[0].add(take_small(
                blk["vals"][t],
                self._score_rows(blk["leaf_idx"][t]).astype(jnp.int32)))
        return score, prev

    def _fused_restore(self, pos: int) -> None:
        """Restore the exact sequential state at block-start + ``pos``
        iterations: partial score replay, host-RNG rewind with the
        block's consumed draws re-drawn, and the bagging-mask cache
        recomputed from its defining PRNG fold."""
        blk = self._fused_block
        self._score, self._prev_score = self._fused_replay_score(pos)
        self.iter = blk["start_iter"] + pos
        self._trees_dispatched = blk["start_tid"] + pos
        self._rng_feature.set_state(blk["rng_state"])
        for _ in range(pos):
            self._feature_fraction_mask()
        self._recompute_bag_cache()

    def _fused_rewind(self) -> None:
        """Discard the block's unserved trees (and every queued
        in-flight successor) and land on the served boundary — the
        escape hatch when eligibility drifts mid-block (a validation
        set attached, a custom-gradient call)."""
        self._discard_queue()
        blk = self._fused_block
        if blk is None:
            return
        self._fused_restore(blk["served"])
        self._fused_block = None

    def _fused_rollback(self) -> None:
        """Undo the last served iteration of the in-flight block."""
        self._discard_queue()
        blk = self._fused_block
        self._stop_flag = False
        self._invalidate_predictor()
        self._models.pop()
        served = blk["served"]
        stopped = blk["stop_idx"] is not None and \
            served > blk["stop_idx"]
        if stopped:
            # the stop serve never advanced ``iter``: score rolls to
            # after the last REAL iteration, the counter steps back
            # (mirroring the sequential rollback-after-stop behavior)
            self._fused_restore(served - 1)
            self.iter -= 1
        else:
            self._fused_restore(served - 1)
        self._fused_block = None

    # ---- elastic mesh recovery (parallel/elastic.py) -----------------
    def _elastic_beat(self, block: bool = False) -> None:
        """Beat the elastic heartbeat (dispatch start / block landed).
        The ``mesh.heartbeat:suppress`` fault drops beats — the
        injected stand-in for a shard that stops reporting progress
        without dying, driving the stall watchdog distinctly from a
        hung collective."""
        hb = getattr(self, "_elastic_heartbeat", None)
        if hb is None:
            return
        from ..utils import faults as _faults
        if _faults.fire("mesh.heartbeat") == "suppress":
            return
        hb.beat(block=block)

    def _abandoned_check(self, alive) -> None:
        """Raise out of an abandoned dispatch attempt BEFORE it
        commits state: once the elastic stall watchdog moved on, a
        re-mesh owns ``self`` and a late-returning zombie thread must
        not race its restored bookkeeping.  ``alive`` is THIS
        attempt's captured generation token — never the live
        attribute, which a retry overwrites with its own."""
        if alive is not None and not alive():
            from ..parallel.elastic import ElasticAbandoned
            raise ElasticAbandoned("fused dispatch abandoned by the "
                                   "elastic supervisor")

    def _mesh_collective_fault(self, mode: str, alive) -> None:
        """Consume one armed ``mesh.collective`` fault: ``error``
        raises the way XLA surfaces a dead peer, ``hang`` blocks the
        way a lost shard stalls the collective rendezvous (forever
        when unsupervised — faithful to the real failure), and
        ``sleep_<ms>`` delays the dispatch (drives the watchdog when
        heartbeats are suppressed)."""
        import time as _time
        from ..utils.faults import InjectedFault
        if mode == "error":
            raise InjectedFault(
                "injected collective failure (mesh.collective:error): "
                "simulated shard loss inside the fused block")
        if mode == "hang":
            while alive is None or alive():
                _time.sleep(0.02)
            from ..parallel.elastic import ElasticAbandoned
            raise ElasticAbandoned("hung collective abandoned by the "
                                   "elastic supervisor")
        if mode.startswith("sleep_"):
            _time.sleep(float(mode[len("sleep_"):]) / 1e3)

    def abort_inflight_dispatch(self) -> bool:
        """Restore the pre-block host state the in-flight fused
        dispatches consumed when they will never land (hung or failed
        collective): the feature-fraction RNG stream and the
        quantization-stream position are the only mutations between
        dispatch and fetch.  Under async pipelining MORE THAN ONE
        block can be outstanding; the live fence is the OLDEST
        dispatch's pre-state, so one restore rewinds across BOTH (all)
        blocks' RNG/quantization-stream consumption, and every queued
        block dies with it.  Returns True when a fence was armed."""
        # the abort fence extends to in-flight host->device STREAM
        # copies (io/stream.py BlockFetcher): a re-mesh rebuilding
        # construction must never race a stale upload window
        from ..io.stream import abort_active_fetchers
        abort_active_fetchers()
        fence = self.__dict__.pop("_dispatch_fence", None)
        self._sq = []
        if fence is None:
            return False
        self._rng_feature.set_state(fence["rng_state"])
        self._trees_dispatched = int(fence["tid"])
        return True

    def next_update_is_local(self) -> bool:
        """True when the next ``train_one_iter`` only serves an
        already-materialized tree from the in-flight fused block —
        pure host work, no device dispatch — so the elastic
        supervisor runs it inline instead of on a watched thread."""
        blk = self._fused_block
        return (blk is not None and blk["served"] < len(blk["trees"])
                and blk.get("lr") == self.shrinkage_rate and
                self._fused_ok())

    def stream_identity(self) -> Optional[Dict]:
        """The streamed-ingest cache identity this booster trains
        from, or None (in-memory dataset).  Checkpoint manifests
        record it so resume can verify the cache was REUSED instead
        of silently re-binned (docs/Streaming.md resume contract)."""
        info = getattr(self.train_set, "stream", None)
        if info is None:
            return None
        return {"cache_key": info.cache_key,
                "cache_dir": info.cache_dir,
                "chunk_rows": int(info.chunk_rows)}

    def pager_identity(self) -> Optional[Dict]:
        """The device-block pager geometry this booster trains under,
        or None (fully resident).  Checkpoint manifests record it so a
        resume knows the run was out-of-core; paged results are
        byte-identical to resident, so a geometry CHANGE on resume
        (different budget, different mesh) is legal — the record is
        provenance, not a constraint (docs/Streaming.md)."""
        if self._pager is None:
            return None
        ident = dict(self._pager.plan.identity())
        ident["mode"] = str(getattr(self.config, "paged_training",
                                    "auto")).lower()
        ident["hbm_budget_mb"] = float(
            getattr(self.config, "hbm_budget_mb", 0.0))
        return ident

    def mesh_identity(self) -> Dict:
        """The live mesh topology — recorded in checkpoint manifests
        (``ckpt/manager.py``) so resume can validate it against the
        restoring booster and re-shard across widths."""
        if self._dist is None:
            return {"learner": "serial", "num_shards": 1,
                    "mesh_shape": [1]}
        return {"learner": self._dist.kind,
                "num_shards": int(self._dist.num_shards),
                "mesh_shape": [int(s) for s in
                               self._dist.mesh.devices.shape]}

    def remesh(self, num_shards: Optional[int] = None, mesh=None,
               raw=None, snapshot: Optional[Dict] = None,
               mesh_shape=None) -> int:
        """Re-mesh entry point: rebuild the device mesh (narrower
        after shard loss, any explicit 1-D mesh, or — via
        ``mesh_shape=(R, F)`` — a 2-D data x feature mesh for the
        data2d learner) and continue BIT-exactly from the last served
        boundary.

        Lands on the served boundary first (dispatch-fence restore +
        the PR 3 exact rewind), captures the PR 5 bit-exact training
        snapshot, re-runs construction against the new mesh — every
        mesh-dependent decision (row/feature paddings, NamedShardings,
        tier gates, EFB when the survivor set collapses to serial) is
        re-derived exactly as a fresh booster would derive it — and
        restores the snapshot; the mesh-resident tensors land under
        the new ``DistributedBuilder.shardings()`` and the fused scan
        rebuilds lazily, keyed by the new mesh shape.
        ``num_shards=1`` falls back to the serial learner.  Returns
        the new shard count.

        ``snapshot``: a pre-captured :meth:`training_snapshot` to
        restore instead of capturing one here — the elastic
        supervisor's degrade-retry loop passes the snapshot it took
        BEFORE the first attempt, so a remesh that failed after its
        internal re-construction (leaving this booster blank) cannot
        make the retry restore blank state."""
        import jax
        self.abort_inflight_dispatch()
        if snapshot is None:
            self._fused_rewind()
            self._flush_pending()
            snapshot = self.training_snapshot()
        rec = getattr(self, "_telemetry", None)
        valid_sets = self.valid_sets
        cfg = self.config
        if mesh is None:
            if mesh_shape is not None:
                r, f = (int(s) for s in mesh_shape)
                num_shards = r * f
                if num_shards > 1:
                    from ..parallel.learners import make_mesh_2d
                    mesh = make_mesh_2d((r, f))
            if num_shards is None:
                raise ValueError("remesh needs num_shards, mesh_shape "
                                 "or an explicit mesh")
            if mesh is None:
                from ..parallel.learners import AXIS_NAME, make_mesh_for
                if int(num_shards) > 1:
                    mesh = make_mesh_for(int(num_shards))
                else:
                    # 1-device mesh: resolve_num_shards reads 1 and the
                    # construction falls back to the serial learner
                    mesh = jax.sharding.Mesh(
                        np.asarray(jax.devices()[:1]), (AXIS_NAME,))
        # the SAME recorder must survive the re-construction: blank
        # the file param so __init__ cannot open a second handle on
        # the same JSONL
        tf = cfg.telemetry_file
        cfg.telemetry_file = ""
        try:
            self.__init__(cfg, self.train_set, self.objective,
                          self.metrics, mesh=mesh)
        finally:
            cfg.telemetry_file = tf
        self.valid_sets = valid_sets
        if rec is not None and getattr(self, "_telemetry", None) is not rec:
            # re-adopt THIS booster's recorder even when __init__
            # already adopted the process-default one (telemetry_file
            # was blanked, so a live global recorder wins that race)
            # — the run's own stream must keep receiving records.
            # Re-adoption emits a fresh run_start, which resets
            # triage_run's superstep-warmup tracking: the post-re-mesh
            # recompile is per-shape warmup, not a storm
            self._telemetry = None
            self.attach_telemetry(rec)
        self.restore_training_snapshot(snapshot, raw=raw)
        return int(self._dist.num_shards) if self._dist is not None \
            else 1

    # ------------------------------------------------------------------
    def _dispatch_build(self, grad_k, hess_k, bag):
        """Pad + bag-weight one class's gradients, draw the feature
        mask and dispatch the jitted tree build.  Returns (device
        record dict, sample mask) — shared by the classic and
        pipelined iteration paths."""
        import jax
        import jax.numpy as jnp
        from ..utils.profiling import timed

        n, n_pad = self.num_data, self._n_pad
        with timed("tree/prep", iter=self.iter):
            gp = grad_k.astype(jnp.float32)
            hp = hess_k.astype(jnp.float32)
            mask = self._base_mask
            if gp.shape[0] == n_pad and n_pad != n:
                # gradients at the padded width (the row state is the
                # shard's): the padding rows' values are whatever the
                # objective makes of a zero label, and count for nothing
                gp = jnp.where(mask > 0, gp, 0.0)
                hp = jnp.where(mask > 0, hp, 0.0)
            else:
                gp = jnp.pad(gp, (0, n_pad - gp.shape[0]))
                hp = jnp.pad(hp, (0, n_pad - hp.shape[0]))
            if bag is not None:
                # weights scale grad/hess (GOSS/MVS upweighting); the
                # count channel stays presence-based like the
                # reference's subsets
                w = jnp.pad(jnp.asarray(bag, jnp.float32).reshape(-1),
                            (0, n_pad - n))
                gp = gp * w
                hp = hp * w
                mask = mask * (w > 0)
            fmask = self._feature_fraction_mask()
        kw = {}
        if self.grow_params.quantize:
            # fresh stochastic-rounding randomness per tree
            kw["quant_key"] = jax.random.fold_in(
                self._quant_key, self._trees_dispatched)
        self._trees_dispatched += 1
        with timed("tree/dispatch", iter=self.iter):
            if self._bundle_maps is not None:
                rec = self._build_tree(
                    self._xt, gp, hp, mask, fmask, self._num_bins,
                    self._missing_type, self._is_cat, self.grow_params,
                    bundle_maps=self._bundle_maps, **kw)
            else:
                rec = self._build_tree(
                    self._xt, gp, hp, mask, fmask, self._num_bins,
                    self._missing_type, self._is_cat, self.grow_params,
                    **kw)
        return rec, mask

    def _materialize_pending(self) -> bool:
        """Fetch + host-materialize the in-flight tree; returns True
        when it could not split (the stop signal).

        The caller times this as ``tree/fetch`` — at steady state that
        time is overwhelmingly the WAIT for the in-flight build to
        finish on device, not transfer: the host dispatched tree t's
        build before fetching t-1's records, so the fetch blocks on
        t-1's remaining device compute while the ~one-RTT transfer and
        t's build overlap it.  Set LTPU_SPLIT_FETCH_TIMER=1 to split
        the phase into ``tree/device_wait`` (``block_until_ready``) and
        the residual transfer (diagnosis-only: it adds a host sync per
        tree)."""
        pending, self._pending = self._pending, None
        rec = pending["rec"]
        if os.environ.get("LTPU_SPLIT_FETCH_TIMER"):
            import jax
            from ..utils.profiling import timed
            with timed("tree/device_wait", iter=self.iter):
                jax.block_until_ready(rec["n_leaves"])
        recs = self._fetch_records(rec, iter=self.iter)
        self._count_growth(recs)
        n_leaves = int(recs["n_leaves"])
        if n_leaves <= 1:
            # non-finite gradients produce NaN gains everywhere and
            # masquerade as this legitimate stop (the unsplit tree's
            # returned record is all finite zeros, so the record
            # cannot tell the two apart).  Probe the gradients the
            # stop tree was dispatched with, plus the score — scalar
            # fetches on the at-most-once stop path only
            # (utils/health.py)
            import jax.numpy as jnp
            gh = pending.get("gh")
            ok = bool(jnp.all(jnp.isfinite(self._score)))
            if ok and gh is not None:
                ok = bool(jnp.all(jnp.isfinite(gh[0])) &
                          jnp.all(jnp.isfinite(gh[1])))
            if not ok:
                from ..utils.health import abort_nonfinite
                abort_nonfinite(getattr(self, "_telemetry", None),
                                max(self.iter - 1, 0), "pipelined",
                                "non-finite gradients/score at the "
                                "stop boundary")
            tree = Tree(2)
            tree.leaf_value[0] = pending["init_score"]
            if abs(pending["init_score"]) > _KEPS:
                self._score = self._score.at[0].add(
                    pending["init_score"])
            self._models.append(tree)
            return True
        tree = self._records_to_tree(recs)
        self._check_tree_health(tree, max(self.iter - 1, 0), "pipelined")
        tree.apply_shrinkage(pending["lr"])
        if abs(pending["init_score"]) > _KEPS:
            tree.add_bias(pending["init_score"])
        self._models.append(tree)
        return False

    def _flush_pending(self) -> None:
        if self._pending is not None:
            if self._materialize_pending():
                self._stop_flag = True

    # ---- numerical health (utils/health.py) --------------------------
    def _check_tree_health(self, tree, iteration: int,
                           phase: str) -> None:
        """Scan a just-materialized tree's leaf values (already
        host-side — zero extra device calls) for non-finite outputs;
        fail loudly instead of training on to a silent NaN model."""
        vals = tree.leaf_value[:max(tree.num_leaves, 1)]
        if not np.all(np.isfinite(vals)):
            from ..utils.health import abort_nonfinite
            n_bad = int((~np.isfinite(np.asarray(vals))).sum())
            abort_nonfinite(getattr(self, "_telemetry", None),
                            iteration, phase,
                            f"{n_bad} non-finite leaf value(s)")

    def _check_stop_health(self, grad, hess, iteration: int,
                           phase: str) -> None:
        """Non-finite gradients make every split gain NaN and
        masquerade as a legitimate "no splittable leaf" stop.  A stop
        happens at most once per training, so one scalar device fetch
        here costs nothing at steady state."""
        import jax.numpy as jnp
        ok = bool(jnp.all(jnp.isfinite(grad)) &
                  jnp.all(jnp.isfinite(hess)))
        if not ok:
            from ..utils.health import abort_nonfinite
            abort_nonfinite(getattr(self, "_telemetry", None),
                            iteration, phase,
                            "non-finite gradients at the stop "
                            "boundary (bad labels/scores, not an "
                            "exhausted tree)")

    def _train_one_iter_pipelined(self) -> bool:
        """Pipelined iteration: device work for tree t is dispatched
        (build + score update from the build's own final leaf values)
        BEFORE tree t-1's records are fetched, so the ~one-RTT fetch
        rides under device compute.  The materialized model trails the
        device state by one tree inside the loop; the ``models``
        property flushes, so every external reader sees the full list.
        Stop detection trails by one iteration (the stopping run gains
        one constant tree)."""
        import jax
        import jax.numpy as jnp
        from ..ops.lookup import take_small
        from ..utils.profiling import timed

        if self._stop_flag:
            return True
        self._prev_score = self._score
        self._prev_valid_scores = []
        init_score = 0.0
        if (self.iter == 0 and self.config.boost_from_average and
                not self._models and self._pending is None and
                self.train_set.metadata.init_score is None):
            init = self.objective.boost_from_score(0)
            if abs(init) > _KEPS:
                init_score = init
                self._score = self._score.at[0].add(init)
                Log.info("Start training from score %f", init)
        with timed("boosting/gradients", iter=self.iter):
            # the jitted wrapper, not the eager chain: one fused pass,
            # and the same compiled math the fused super-step inlines
            # (bit-parity between the two paths requires it).  An
            # objective that opted out of the pure contract
            # (gradient_fn -> None) keeps its eager get_gradients.
            grad, hess = self._gradient_fn()(self._score)
        grad = jnp.atleast_2d(grad)
        hess = jnp.atleast_2d(hess)
        bag = self._bagging_mask(grad, hess)
        rec, _ = self._dispatch_build(grad[0], hess[0], bag)
        with timed("tree/score_update", iter=self.iter):
            vals = rec["leaf_values_final"] * \
                jnp.float32(self.shrinkage_rate)
            self._score = self._score.at[0].add(
                self._score_rows(take_small(vals, rec["leaf_idx"])))
        prev_stop = False
        if self._pending is not None:
            with timed("tree/fetch", iter=self.iter):
                prev_stop = self._materialize_pending()
        self._pending = {"rec": rec, "init_score": init_score,
                         "lr": self.shrinkage_rate,
                         # kept for the stop-path health probe: a
                         # no-split stop must be distinguishable from
                         # NaN gradients killing every gain
                         "gh": (grad[0], hess[0])}
        self.iter += 1
        if prev_stop:
            self._check_stop_health(grad, hess, max(self.iter - 2, 0),
                                    "pipelined")
            self._stop_flag = True
            self._flush_pending()
            Log.warning("Stopped training because there are no more "
                        "leaves that meet the split requirements")
            return True
        return False

    def train_one_iter(self, grad: Optional[np.ndarray] = None,
                       hess: Optional[np.ndarray] = None) -> bool:
        """One boosting iteration; returns True when training should stop
        (no splittable leaf).  With a telemetry recorder attached, every
        iteration emits a structured record (phase deltas, compile/
        retrace counters, tier, histogram passes, collective bytes)."""
        rec = getattr(self, "_telemetry", None)
        if rec is None:
            stop = self._train_one_iter_impl(grad, hess)
            if self._pager is not None:
                self._pager.raise_if_poisoned()
            # clear the superstep markers: a recorder attached later
            # must not mis-emit a stale block
            self.__dict__.pop("_tele_superstep", None)
            self.__dict__.pop("_tele_serving", None)
            return stop
        import time as _time
        from ..utils import profiling
        it = self.iter
        ph0 = profiling.snapshot()
        t0 = _time.perf_counter()
        stop = self._train_one_iter_impl(grad, hess)
        if self._pager is not None:
            self._pager.raise_if_poisoned()
        dur_ms = (_time.perf_counter() - t0) * 1e3
        ss = self.__dict__.pop("_tele_superstep", None)
        if ss is not None:
            # fused super-step: ONE record per K iterations carrying
            # the block's amortized phase deltas and compile counters;
            # the K-1 serve calls that follow emit nothing (their cost
            # is microseconds of host list work)
            self._tele_serving = False
            cdelta, self._tele_counters_last = rec.counters_delta(
                self._tele_counters_last)
            fields = {
                "iter": it,
                "k": int(ss["k"]),
                "duration_ms": round(dur_ms, 3),
                "phases_ms": profiling.delta_ms(ph0),
                "counters": cdelta,
                "tier": self.tier_decision["tier"],
                "trees_per_iter": self.num_tree_per_iteration,
                "n_trees": len(self._models),
                "stopped": bool(stop),
            }
            if ss.get("hist_passes") is not None:
                fields["hist_passes"] = int(ss["hist_passes"])
            # async pipelining observability: the configured in-flight
            # depth and the wall this block's device compute ran
            # overlapped with host work (dispatch -> fetch window).
            # triage_run.py flags depth > 0 with ~zero overlap as
            # "pipelining silently disabled"
            fields["pipeline_depth"] = int(ss.get("pipeline_depth", 0))
            fields["fetch_overlap_s"] = float(
                ss.get("fetch_overlap_s", 0.0))
            # best-split engine per block: which scan ran and, when it
            # fell back to XLA, the gate that rejected the Pallas tier
            # (triage_run.py flags xla-on-a-TPU-backend as MED)
            fields["split_kernel"] = self.tier_decision.get(
                "split_kernel", "xla")
            sf = self.tier_decision.get("gates", {}).get("split")
            if sf:
                fields["split_fallback"] = sf
            # sharded super-step: per-block collective accounting +
            # mesh identity (the weak-scaling triage reads these —
            # per-iteration time growing with num_shards at constant
            # collective bytes is the dispatch-overhead signature the
            # single-program refactor exists to kill)
            for key in ("learner", "num_shards", "mesh_shape",
                        "collective_bytes", "collective_ops",
                        "collective_bytes_axis", "collective_ops_axis"):
                if key in ss:
                    fields[key] = ss[key]
            rec.emit("superstep", **fields)
            self._emit_pager_flush(rec, it)
            return stop
        if self.__dict__.pop("_tele_serving", False):
            # serving a tree from an already-recorded super-step block
            return stop
        cdelta, self._tele_counters_last = rec.counters_delta(
            self._tele_counters_last)
        fields = {
            "iter": it,
            "duration_ms": round(dur_ms, 3),
            "phases_ms": profiling.delta_ms(ph0),
            "counters": cdelta,
            "tier": self.tier_decision["tier"],
            "trees_per_iter": self.num_tree_per_iteration,
            # raw list length: the models property would flush the
            # pipelined in-flight tree and kill the fetch overlap
            "n_trees": len(self._models) +
            (1 if self._pending is not None else 0),
            "stopped": bool(stop),
        }
        passes = getattr(self, "last_arm_passes", None)
        if passes is not None:
            hp = (int(passes) + 1) * self.num_tree_per_iteration
            fields["hist_passes"] = hp
            # pool hit rate: fraction of the 2S child histograms a tree
            # needed that came from the pool (subtraction trick / armed
            # cache) instead of a fresh device pass.  Uses the last
            # MATERIALIZED tree's split count (the pipelined path trails
            # by one tree; the rate is a per-booster steady-state stat)
            if self._models and self._models[-1].num_leaves > 1:
                S = self._models[-1].num_leaves - 1
                fields["pool_hit_rate"] = round(
                    max(0.0, 1.0 - hp / float(2 * S)), 4)
        if self._collective_plan or self._collective_per_pass:
            if self._collective_plan:
                # the wave data learner: _count_growth's own count
                fields["collective_bytes"], fields["collective_ops"] = \
                    self._collective_last
            else:
                # passes this iteration: measured for speculative
                # builds; otherwise ~one fresh smaller-child pass per
                # split plus the root (subtraction covers the sibling)
                hp = fields.get("hist_passes")
                if hp is None:
                    n_leaves = (self._models[-1].num_leaves
                                if self._models
                                else self.config.num_leaves)
                    hp = max(n_leaves, 1) * self.num_tree_per_iteration
                fields["collective_bytes"] = int(
                    self._collective_per_pass * hp)
                fields["collective_ops"] = int(
                    self._collective_ops_per_pass * hp)
            if self._dist is not None:
                fields["learner"] = self._dist.kind
                fields["num_shards"] = int(self._dist.num_shards)
                fields["mesh_shape"] = [
                    int(s) for s in self._dist.mesh.devices.shape]
                if self._collective_per_axis:
                    fields["collective_bytes_axis"] = {
                        k: int(v["bytes"] * hp)
                        for k, v in self._collective_per_axis.items()}
        rec.emit("iteration", **fields)
        self._emit_pager_flush(rec, it)
        return stop

    def _emit_pager_flush(self, rec, it: int) -> None:
        """One pager record per telemetry-visible training step: the
        DELTA of the PageStore's cumulative stats since the last
        flush (pages/bytes/overlap_s/stalls — the series the
        pager_no_overlap rule reads)."""
        if self._pager is None or rec is None:
            return
        delta = self._pager.stats_delta(self._pager_last or {})
        self._pager_last = self._pager.stats()
        if delta.get("pages", 0) or delta.get("columns", 0):
            rec.emit("pager", event="flush", iter=int(it), **delta)

    def _train_one_iter_impl(self, grad: Optional[np.ndarray] = None,
                             hess: Optional[np.ndarray] = None) -> bool:
        import jax.numpy as jnp

        fused = grad is None and self._fused_ok()
        blk = self._fused_block
        if blk is not None:
            in_flight = blk["served"] < len(blk["trees"])
            # a learning_rates schedule changed the shrinkage since
            # dispatch: the unserved trees were built with the old
            # rate — rewind and redispatch at the new one
            lr_drift = blk.get("lr") != self.shrinkage_rate
            if fused and in_flight and not lr_drift:
                return self._serve_fused()
            if in_flight:
                # eligibility drifted mid-block (custom gradients, a
                # freshly attached valid set, a shrinkage change):
                # rewind to the served boundary, then fall through
                self._fused_rewind()
            elif not fused:
                self._fused_block = None  # rollback window closed
                if self._sq:
                    # fused mode just disengaged with blocks still in
                    # flight: drain them (restoring their consumed RNG
                    # draws) and rebuild the bagging cache the drained
                    # chain may have donated away
                    self._discard_queue()
                    self._recompute_bag_cache()
        if fused and not self._fused_bias_pending():
            return self._train_superstep()
        if grad is None and self._pipeline_ok():
            return self._train_one_iter_pipelined()
        self._flush_pending()
        if self._stop_flag:
            return True
        self._prev_score = self._score  # snapshot for rollback (immutable)
        # valid scores are NOT snapshotted per iteration: rollback
        # restores them by subtracting the popped trees' predictions
        # (``GBDT::RollbackOneIter`` does the same via Shrinkage(-1) +
        # AddScore) — a full f64 copy per valid set per iteration was
        # dead weight on the hot loop whenever nobody rolls back
        init_scores = [0.0] * self.num_tree_per_iteration
        custom = grad is not None
        if not custom:
            if (self.iter == 0 and self.config.boost_from_average and
                    not self.models and
                    self.train_set.metadata.init_score is None and
                    self.objective is not None and
                    self.num_features > 0):
                for k in range(self.num_tree_per_iteration):
                    init = self.objective.boost_from_score(k)
                    if abs(init) > _KEPS:
                        init_scores[k] = init
                        self._score = self._score.at[k].add(init)
                        for vs in self.valid_sets:
                            vs.score[k] += init
                        Log.info("Start training from score %f", init)
            from ..utils.profiling import timed
            with timed("boosting/gradients", iter=self.iter):
                grad, hess = self._gradient_fn()(self._score)
            grad = jnp.atleast_2d(grad)
            hess = jnp.atleast_2d(hess)
        else:
            grad = jnp.asarray(np.atleast_2d(np.asarray(grad, np.float32)))
            hess = jnp.asarray(np.atleast_2d(np.asarray(hess, np.float32)))

        from ..utils.profiling import timed
        bag = self._bagging_mask(grad, hess)
        should_stop = True
        for k in range(self.num_tree_per_iteration):
            with timed("tree/build", iter=self.iter):
                tree = self._train_one_tree(grad[k], hess[k], bag,
                                            init_scores[k])
            if tree.num_leaves > 1:
                should_stop = False
            self.models.append(tree)
        if should_stop:
            self._check_stop_health(grad, hess, self.iter, "tree")
            Log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            return True
        self.iter += 1
        return False

    def _train_one_tree(self, grad, hess, bag, init_score: float) -> Tree:
        import jax.numpy as jnp
        from ..utils.profiling import timed

        n = self.num_data
        recs = None
        if self.num_features == 0:
            rec = None
            n_leaves = 1
            mask = self._base_mask
        else:
            rec, mask = self._dispatch_build(grad, hess, bag)
            with timed("tree/fetch", iter=self.iter):
                # one packed device->host transfer per tree; doubles as
                # the device sync
                recs = self._fetch_records(rec, iter=self.iter)
            n_leaves = int(recs["n_leaves"])
            self._count_growth(recs)

        if n_leaves <= 1:
            # constant tree holding the init score (gbdt.cpp:380-397)
            tree = Tree(2)
            out = init_score
            tree.leaf_value[0] = out
            if abs(out) > _KEPS:
                tree_idx = len(self.models) % self.num_tree_per_iteration
                self._score = self._score.at[tree_idx].add(out)
                for vs in self.valid_sets:
                    vs.score[tree_idx] += out
            if self._track_train_leaf:
                self._train_leaf_idx.append(None)
                for vs in self.valid_sets:
                    vs.leaf_idx_per_tree.append(None)
            return tree

        with timed("tree/to_tree", iter=self.iter):
            tree = self._records_to_tree(recs)
        self._check_tree_health(tree, self.iter, "tree")
        if self._track_train_leaf:
            # compact dtype ON DEVICE: leaf ids fit uint8/16 and the
            # device->host link is slow, so never ship int32
            dt = jnp.uint8 if self.config.num_leaves <= 256 else jnp.uint16
            self._train_leaf_idx.append(
                np.asarray(rec["leaf_idx"][:n].astype(dt)))
        # leaf renewal hook (RenewTreeOutput) — objective-specific
        if self.objective is not None:
            with timed("tree/renew", iter=self.iter):
                self.objective.renew_tree_output(
                    tree, self._score, rec["leaf_idx"][:n], mask)
        tree.apply_shrinkage(self.shrinkage_rate)
        with timed("tree/score_update", iter=self.iter):
            # train-score update via the leaf assignment from the build;
            # the (N,) table lookup runs as the select-chain kernel (an
            # XLA gather here costs ~150 ms per iteration at bench
            # shape — ops/lookup.py)
            from ..ops.lookup import take_small
            vals = jnp.asarray(tree.leaf_value[:self.config.num_leaves],
                               jnp.float32)
            vals = jnp.pad(
                vals, (0, max(0, self.config.num_leaves - vals.shape[0])))
            tree_idx = len(self.models) % self.num_tree_per_iteration
            self._score = self._score.at[tree_idx].add(
                self._score_rows(take_small(vals, rec["leaf_idx"])))
        # valid scores: device split-record replay when the binned
        # matrix is resident, host traversal fallback otherwise
        from ..ops.grow import route_rows
        dt_leaf = np.uint8 if self.config.num_leaves <= 256 else np.uint16
        with timed("tree/valid", iter=self.iter):
            for vs in self.valid_sets:
                if vs.xt is not None:
                    li = route_rows(vs.xt, rec["leaf"], rec["feature"],
                                    rec["left_mask"], rec["valid"],
                                    self.config.num_leaves,
                                    bundle_maps=self._bundle_maps)
                    if self._track_train_leaf:
                        # DART drops/renormalizations replay per-tree
                        # valid contributions from this table instead
                        # of host tree traversals
                        la = np.asarray(li.astype(dt_leaf))
                        vs.leaf_idx_per_tree.append(la)
                        vs.score[tree_idx] += tree.leaf_value[
                            la.astype(np.int32)]
                    else:
                        vs.score[tree_idx] += np.asarray(
                            take_small(vals, li), np.float64)
                else:
                    if self._track_train_leaf:
                        la = tree.predict_leaf_index(vs.raw).astype(
                            dt_leaf)
                        vs.leaf_idx_per_tree.append(la)
                        vs.score[tree_idx] += tree.leaf_value[
                            la.astype(np.int32)]
                    else:
                        vs.score[tree_idx] += tree.predict(vs.raw)
        if abs(init_score) > _KEPS:
            tree.add_bias(init_score)
        return tree

    # ------------------------------------------------------------------
    def _count_growth(self, recs, n_trees: int = 1, scan_flags: int = 0):
        """Add the growth loop's own counts (``ops/grow.py``
        ``GROW_COUNTERS``) of the ``n_trees`` trees just fetched to the
        process counters: ``trees_grown``, ``hist_passes_coarse`` (one
        routing pass a wave), ``hist_passes_refine`` (the rest: c2f's
        windowed passes), ``grow_waves``, ``route_gather_waves`` (the
        waves routed by the routing step, where the tier record's
        ``route`` is ``gather``) and, on the wave tiers, where
        every lane a wave fills is one split, ``grow_lanes_live`` (the
        trees' splits) and ``grow_lanes_offered`` (waves x the wave's
        lanes); under the wave data learner also ``collective_bytes``
        and ``collective_ops`` (kept for the telemetry record in
        ``_collective_last``).  Returns the trees' histogram passes,
        each tree's root pass included (a record's ``hist_passes``), or None on a tier
        whose loop does not count (no batched passes).  A pairwise
        objective's iterations (one tree each) add ``rank_pair_slots``
        (the slots its layout computes) and ``rank_pairs`` (its
        queries' sum of L^2), on every tier."""
        from ..utils.telemetry import counters
        obj = self.objective
        if getattr(obj, "pair_slots", None) is not None:
            counters.incr("rank_pair_slots", n_trees * obj.pair_slots)
            counters.incr("rank_pairs", n_trees * obj.pairs)
        if "n_arm_passes" not in recs:
            return None
        arm = np.atleast_1d(recs["n_arm_passes"])[:n_trees]
        waves = int(np.sum(np.atleast_1d(recs["n_waves"])[:n_trees]))
        self.last_arm_passes = int(arm[-1])
        arm = int(np.sum(arm))
        plan = self._collective_plan
        if plan:
            # what these trees' passes handed to the row axis'
            # collectives (ops/grow.py wave_collective_plan: the
            # passes' own bins and lanes): a wave is one coarse pass,
            # the other batched passes are refine ones; off c2f every
            # pass is a full one.  ``scan_flags``: the fused own-rows
            # scan's health flag, one scalar pmax an iteration
            pb = plan["passes"]
            moved = (waves * pb["coarse"] + (arm - waves) * pb["refine"]
                     if "coarse" in pb else arm * pb["full"])
            moved += n_trees * plan["tree_bytes"] + 4 * scan_flags
            ops = arm + n_trees * plan["tree_ops"] + scan_flags
            counters.incr("collective_bytes", moved)
            counters.incr("collective_ops", ops)
            self._collective_last = (int(moved), int(ops))
        counters.incr("trees_grown", n_trees)
        counters.incr("hist_passes_coarse", waves)
        counters.incr("hist_passes_refine", arm - waves)
        counters.incr("grow_waves", waves)
        if self.tier_decision.get("route") == "gather":
            # the waves whose rows the routing step routed
            # (ops/histogram.py histogram_pallas_route): every wave,
            # on a shape whose features chunk
            counters.incr("route_gather_waves", waves)
        p = self.grow_params
        if p.wave:
            leaves = np.atleast_1d(recs["n_leaves"])[:n_trees]
            counters.incr("grow_lanes_live",
                          int(np.sum(np.maximum(leaves, 1) - 1)))
            counters.incr("grow_lanes_offered",
                          waves * min(p.speculate, p.num_leaves))
        return arm + n_trees

    def _fetch_records(self, rec, **ids):
        """ONE device->host transfer per tree: every split record except
        the (N,) leaf assignment (which stays on device for the score
        update), concatenated into a single f32 buffer on device —
        ``device_get`` on a dict pays one transfer PER array, and the
        records hold ~15.  All record values (leaf ids,
        bins, gains, stats, flag bits) are exactly representable in f32.

        Two child phases split the caller's fetch phase: ``fetch/wait``,
        the pack's dispatch up to ``block_until_ready`` on its result
        (the wait for the device), and ``fetch/copy``, the transfer to
        the host and the unpack.  The ``block_until_ready`` waits on
        the pack the transfer waits on anyway: it adds no device call.
        """
        import jax
        import jax.numpy as jnp

        from ..utils.profiling import timed

        keys = [k for k in sorted(rec) if k != "leaf_idx"]
        layout = [(k, tuple(rec[k].shape), np.dtype(rec[k].dtype))
                  for k in keys]
        if self._rec_layout != layout:
            # keyed on SHAPES too: the fused super-step fetches stacked
            # (K, ...) records through the same pack, and the tail
            # block's K differs
            self._rec_layout = layout
            self._rec_pack = jax.jit(lambda r: jnp.concatenate(
                [r[k].astype(jnp.float32).reshape(-1) for k in keys]))
        with timed("fetch/wait", **ids):
            packed = jax.block_until_ready(
                self._rec_pack({k: rec[k] for k in keys}))
        with timed("fetch/copy", **ids):
            flat = np.asarray(packed)
            out, off = {}, 0
            for k, shp, dt in self._rec_layout:
                size = int(np.prod(shp)) if shp else 1
                out[k] = flat[off:off + size].reshape(shp).astype(dt)
                off += size
        return out

    # ------------------------------------------------------------------
    def _records_to_tree(self, rec) -> Tree:
        return records_to_tree(rec, self.config, self.train_set,
                               counts_proxy=getattr(self, "_counts_proxy",
                                                    False))

    # ---- checkpoint/resume (lightgbm_tpu/ckpt/) ----------------------
    def completed_iterations(self) -> int:
        """Iterations fully materialized on the host — mid-fused-block
        this is the SERVED boundary, not the block-end state the
        device score holds."""
        blk = getattr(self, "_fused_block", None)
        if blk is not None and blk["served"] < len(blk["trees"]):
            return blk["start_iter"] + blk["served"]
        return self.iter

    def training_snapshot(self) -> Dict:
        """Model-consistent training state at the last COMPLETED
        iteration, as host arrays — the capture side of the checkpoint
        subsystem.  Mid-fused-block, the state is aligned to the
        served boundary exactly the way :meth:`_fused_restore` would
        land there (partial score replay, host-RNG re-advance), but
        WITHOUT disturbing the in-flight block: training continues
        serving from it after the save."""
        blk = getattr(self, "_fused_block", None)
        if blk is not None and blk["served"] < len(blk["trees"]):
            served = blk["served"]
            score, _ = self._fused_replay_score(served)
            it = blk["start_iter"] + served
            tid = blk["start_tid"] + served
            cur = self._rng_feature.get_state()
            self._rng_feature.set_state(blk["rng_state"])
            for _ in range(served):
                self._feature_fraction_mask()
            rng_state = self._rng_feature.get_state()
            self._rng_feature.set_state(cur)
        else:
            _ = self.models            # flush any pipelined tree
            score = self._score
            it = self.iter
            if self._sq:
                # block boundary with successor blocks dispatched but
                # unfetched: the LIVE stream positions include their
                # consumed feature-fraction draws and quantization
                # tids — model-consistent state is the OLDEST queued
                # dispatch's pre-state (exactly the fence an abort
                # would restore; the resumed run redispatches those
                # blocks itself)
                tid = int(self._sq[0]["fence"]["tid"])
                rng_state = self._sq[0]["fence"]["rng_state"]
            else:
                tid = self._trees_dispatched
                rng_state = self._rng_feature.get_state()
        return {
            "iter": int(it),
            "trees_dispatched": int(tid),
            "shrinkage_rate": float(self.shrinkage_rate),
            "stopped": bool(self._stop_flag),
            "score": np.asarray(score)[:, :self.num_data],
            "rng_feature": rng_state,
            "models": list(self._models),
            "valid_scores": {vs.name: np.asarray(vs.score)
                             for vs in self.valid_sets},
            "extra": self._extra_ckpt_state(),
        }

    def _extra_ckpt_state(self) -> Dict:
        """Subclass hook: boosting-mode state beyond the base carry
        (DART's drop RNG/weights, models/boosting.py)."""
        return {}

    def _restore_extra_ckpt_state(self, extra: Dict, raw) -> None:
        pass

    def restore_training_snapshot(self, snap: Dict, raw=None) -> None:
        """Install a :meth:`training_snapshot` into this (freshly
        constructed) booster so the next ``train_one_iter`` continues
        bit-identically to the run the snapshot was taken from: exact
        device score carry, host-RNG stream position, quantization
        stream position, and the bagging-cycle cache recomputed from
        its defining PRNG fold.  Valid sets must already be
        registered; their accumulated scores (path-dependent under
        DART renormalization) are overwritten from the snapshot."""
        self._fused_block = None
        self._sq = []
        self.__dict__.pop("_dispatch_fence", None)
        self._pending = None
        self._stop_flag = bool(snap.get("stopped", False))
        self.models = list(snap["models"])   # setter bumps the predictor
        self.iter = int(snap["iter"])
        self._trees_dispatched = int(snap["trees_dispatched"])
        self.shrinkage_rate = float(snap["shrinkage_rate"])
        # mesh-resident contract: the restored carry goes back where
        # construction placed the fresh one — a host-placed carry
        # would compile a second executable for its input sharding on
        # the first block
        self._score = self._place_score(snap["score"])
        self._prev_score = None
        self._prev_valid_scores = []
        self._rng_feature.set_state(snap["rng_feature"])
        cfg = self.config
        if (self._bagging_active() and self.iter > 0 and
                type(self)._bagging_mask is GBDT._bagging_mask):
            # the bernoulli/stratified cache is a pure function of the
            # last bagging_freq boundary (same recompute as
            # _fused_restore); GOSS/MVS masks are functions of the
            # iteration's gradients and need no cache
            last_draw = (self.iter - 1) // cfg.bagging_freq * \
                cfg.bagging_freq
            self._cached_bag = self._draw_bag_mask(last_draw)
        vsc = snap.get("valid_scores") or {}
        k = max(self.num_tree_per_iteration, 1)
        for vs in self.valid_sets:
            if vs.name in vsc:
                arr = np.asarray(vsc[vs.name], np.float64)
                if arr.size != vs.score.size:
                    Log.fatal("checkpointed valid set %r has %d scores, "
                              "the registered one needs %d — resume "
                              "requires the same validation data",
                              vs.name, arr.size, vs.score.size)
                vs.score = arr.reshape(vs.score.shape)
            else:
                # registered at resume but absent from the checkpoint:
                # add_valid replayed ZERO trees (it ran before this
                # restore installed them), so replay the model now —
                # the same continue-training semantics add_valid gives
                # an init_model (scores from this point on accumulate
                # incrementally like any fresh registration)
                Log.warning("valid set %r was not registered when the "
                            "checkpoint was taken; replaying the "
                            "restored model into its score", vs.name)
                for i, tree in enumerate(self._models):
                    vs.score[i % k] += tree.predict(vs.raw)
        if self._track_train_leaf:
            # per-tree leaf assignments are discrete and recomputable
            # exactly from the restored trees (init_from_model does
            # the same); constant trees keep their None sentinel
            dt = np.uint8 if cfg.num_leaves <= 256 else np.uint16
            if raw is not None:
                self._train_leaf_idx = [
                    None if t.num_leaves <= 1 else
                    t.predict_leaf_index(raw).astype(dt)
                    for t in self._models]
            else:
                # streamed dataset: replay chunk-by-chunk off the raw
                # source (docs/Streaming.md), like init_from_model
                src = getattr(self.train_set, "raw_source", None)
                sinfo = getattr(self.train_set, "stream", None)
                if src is None or sinfo is None:
                    Log.fatal("resuming %s requires the training "
                              "set's raw matrix (free_raw_data="
                              "False)", type(self).__name__)
                from ..io.cache import chunk_grid
                parts: List[List[np.ndarray]] = \
                    [[] for _ in self._models]
                for start, stop in chunk_grid(self.num_data,
                                              sinfo.chunk_rows):
                    blk = src.read_rows(start, stop)
                    for i, t in enumerate(self._models):
                        if t.num_leaves > 1:
                            parts[i].append(
                                t.predict_leaf_index(blk).astype(dt))
                self._train_leaf_idx = [
                    None if t.num_leaves <= 1 else
                    np.concatenate(parts[i])
                    for i, t in enumerate(self._models)]
            for vs in self.valid_sets:
                vs.leaf_idx_per_tree = [
                    None if t.num_leaves <= 1 else
                    t.predict_leaf_index(vs.raw).astype(dt)
                    for t in self._models]
        self._restore_extra_ckpt_state(dict(snap.get("extra") or {}),
                                       raw)

    # ------------------------------------------------------------------
    @property
    def train_score(self) -> np.ndarray:
        blk = getattr(self, "_fused_block", None)
        if blk is not None and blk["served"] < len(blk["trees"]):
            # mid-block the device score is ahead of the model (it
            # holds the end-of-block state); replay the served prefix
            # non-destructively so readers see the model-consistent
            # score — fusion eligibility already excludes every
            # per-iteration reader (metrics, custom fobj)
            score, _ = self._fused_replay_score(blk["served"])
            return np.asarray(score)[:, :self.num_data]
        return np.asarray(self._score)[:, :self.num_data]

    def _eval_one_set(self, name: str, score_kn: np.ndarray,
                      meta: Metadata) -> List[Tuple[str, str, float, bool]]:
        """Run every metric on one dataset.  ``score_kn`` is the raw
        (num_tree_per_iteration, rows) score block; multiclass metrics
        receive the full (rows, K) matrix, single-output objectives the
        1-D vector.  Rank metrics report one entry per eval_at position
        (the reference's ndcg@1..ndcg@5 rows)."""
        if self.num_tree_per_iteration > 1:
            score = np.asarray(score_kn, np.float64).T  # (rows, K)
        else:
            score = np.asarray(score_kn[0], np.float64)
        if self.objective is not None:
            score = self.objective.convert_output(score)
        label = np.asarray(meta.label, np.float64)
        out = []
        for m in self.metrics:
            if hasattr(m, "eval_all"):
                for mname, val in m.eval_all(label, score, meta.weight,
                                             meta.query_boundaries):
                    out.append((name, mname, val, m.higher_better))
            else:
                out.append((name, m.name,
                            m.eval(label, score, meta.weight,
                                   meta.query_boundaries), m.higher_better))
        return out

    def eval_set(self) -> List[Tuple[str, str, float, bool]]:
        """Evaluate all metrics on train (optional) + valid sets.
        Returns (dataset_name, metric_name, value, higher_better)."""
        out = []
        if self.config.is_provide_training_metric and self.objective:
            out.extend(self._eval_one_set("training", self.train_score,
                                          self.train_set.metadata))
        for vs in self.valid_sets:
            out.extend(self._eval_one_set(vs.name, vs.score, vs.metadata))
        return out

    # ------------------------------------------------------------------
    def _use_predict_engine(self, override=None) -> bool:
        from ..ops.predict import engine_enabled
        if not engine_enabled():
            return False
        if override is not None:
            return bool(override)
        return bool(getattr(self.config, "predict_engine", True))

    def _engine(self):
        """The process-wide engine, with this booster's LRU capacity
        preference applied (``predict_cache_slots``; last booster to
        predict wins — the cache is shared by design)."""
        from ..ops.predict import get_engine
        eng = get_engine()
        slots = int(getattr(self.config, "predict_cache_slots", 0) or 0)
        if slots > 0 and slots != eng.cache_size:
            eng.set_cache_size(slots)
        return eng

    def _flat_forest(self):
        """Flattened SoA forest tables (ops/predict.py), cached until
        the model mutates — appends/pops change the tree count in the
        key, in-place tree mutations bump ``_model_version`` via
        :meth:`_invalidate_predictor`.

        Same-process train->predict takes the DEVICE-HANDOFF path
        (``predict_device_handoff``, default on): per-tree flat rows
        are extracted once as trees materialize from the training
        fetch and only the delta since the last handoff is walked —
        zero full-forest host repacks at the train->serve seam
        (``flatten_full_repacks`` telemetry counter stays 0;
        byte-identical to :func:`~..ops.predict.flatten_forest`,
        pinned by tests/test_pipeline.py).  Cold loads (model file,
        handoff disabled) keep the numpy full-repack path."""
        from ..ops.predict import flatten_forest, flatten_forest_device
        models = self.models            # flushes any pending tree
        key = (self._model_version, len(models))
        if self._flat_cache is None or self._flat_cache[0] != key:
            if (bool(getattr(self.config, "predict_device_handoff",
                             True)) and self.train_set is not None):
                flat = flatten_forest_device(
                    models, self.num_tree_per_iteration,
                    self._tree_flats)
            else:
                flat = flatten_forest(models,
                                      self.num_tree_per_iteration)
            self._flat_cache = (key, flat)
        return self._flat_cache[1]

    def predict_raw(self, X: np.ndarray, num_iteration: int = -1,
                    early_stop: bool = False, early_stop_freq: int = 10,
                    early_stop_margin: float = 10.0,
                    predict_engine=None,
                    predict_chunk_rows=None) -> np.ndarray:
        """Raw scores (rows,) or (rows, num_class).

        Served by the flattened jitted engine (``ops/predict.py``);
        ``LTPU_PREDICT_ENGINE=0`` or ``predict_engine=false`` falls
        back to the per-tree host loop (the oracle path).  The
        ``predict_engine``/``predict_chunk_rows`` arguments are
        per-call overrides of the config values (the C-API passes them
        from the parameters string without mutating shared state).

        ``early_stop``: per-row prediction early stopping
        (``prediction_early_stop.cpp``): every ``early_stop_freq``
        iterations, rows whose margin (|score| for binary, top1-top2
        for multiclass) exceeds ``early_stop_margin`` stop accumulating
        further trees."""
        import time as _time
        t0 = _time.perf_counter()
        X = np.ascontiguousarray(np.asarray(X, np.float64))
        k = self.num_tree_per_iteration
        n_trees = len(self.models)
        if num_iteration is not None and num_iteration > 0:
            n_trees = min(n_trees, num_iteration * k)
        use_es = early_stop and k >= 1 and not self.average_output
        used_engine = n_trees > 0 and X.shape[0] > 0 and \
            self._use_predict_engine(predict_engine)
        if used_engine:
            out = self._engine().predict_raw(
                self._flat_forest(), X, n_trees, early_stop=use_es,
                early_stop_freq=early_stop_freq,
                early_stop_margin=early_stop_margin,
                chunk_rows=predict_chunk_rows or
                getattr(self.config, "predict_chunk_rows", 0))
        else:
            out = self._predict_raw_loop(X, n_trees, k, use_es,
                                         early_stop_freq,
                                         early_stop_margin)
        if self.average_output and n_trees:
            out = out / max(n_trees // k, 1)
        self._record_predict("raw", X.shape[0], n_trees, used_engine, t0)
        return out[0] if k == 1 else out.T

    def _predict_raw_loop(self, X: np.ndarray, n_trees: int, k: int,
                          use_es: bool, early_stop_freq: int,
                          early_stop_margin: float) -> np.ndarray:
        """Per-tree host traversal — the engine's bit-level oracle."""
        n = X.shape[0]
        out = np.zeros((k, n), dtype=np.float64)
        active = np.ones(n, dtype=bool)
        for i in range(n_trees):
            if use_es and not np.all(active):
                idx = np.nonzero(active)[0]
                if len(idx) == 0:
                    break
                out[i % k, idx] += self.models[i].predict(X[idx])
            else:
                out[i % k] += self.models[i].predict(X)
            if use_es and (i + 1) % (early_stop_freq * k) == 0:
                if k == 1:
                    # binary margin = 2|raw| (prediction_early_stop.cpp)
                    margin = 2.0 * np.abs(out[0])
                else:
                    top2 = np.partition(out, k - 2, axis=0)[-2:]
                    margin = top2[1] - top2[0]
                active &= margin < early_stop_margin
        return out

    def predict(self, X: np.ndarray, num_iteration: int = -1,
                **engine_kw) -> np.ndarray:
        raw = self.predict_raw(X, num_iteration, **engine_kw)
        if self.objective is not None:
            return self.objective.convert_output(raw)
        return raw

    def _shap_forest(self):
        """Flattened SHAP path-descriptor tables (ops/shap.py), cached
        until the model mutates — same invalidation rules as
        :meth:`_flat_forest`."""
        from ..ops.shap import flatten_forest_shap
        models = self.models            # flushes any pending tree
        key = (self._model_version, len(models))
        cache = getattr(self, "_shap_cache", None)
        if cache is None or cache[0] != key:
            cache = (key, flatten_forest_shap(
                models, self.num_tree_per_iteration))
            self._shap_cache = cache
        return cache[1]

    def predict_contrib(self, X: np.ndarray, num_iteration: int = -1,
                        predict_engine=None,
                        predict_chunk_rows=None) -> np.ndarray:
        """Per-row SHAP contributions (``PredictContrib`` layout:
        (rows, nf+1), multiclass flattened to (rows, k*(nf+1)) with
        per-class bias columns).  Served by the flattened explanation
        engine (``ops/shap.py``); the per-tree host recursion stays
        the oracle path behind the same ``predict_engine`` /
        ``LTPU_PREDICT_ENGINE`` gates as :meth:`predict_raw`."""
        import time as _time
        t0 = _time.perf_counter()
        X = np.ascontiguousarray(np.asarray(X, np.float64))
        k = self.num_tree_per_iteration
        n_trees = len(self.models)
        if num_iteration is not None and num_iteration > 0:
            n_trees = min(n_trees, num_iteration * k)
        rows, nf = X.shape
        used_engine = n_trees > 0 and rows > 0 and \
            self._use_predict_engine(predict_engine)
        if used_engine:
            from ..ops.shap import get_shap_engine
            sf = self._shap_forest()
            raw = get_shap_engine().predict_contrib(
                sf, X, n_trees,
                chunk_rows=predict_chunk_rows or
                getattr(self.config, "predict_chunk_rows", 0))
            F = sf.num_features
            out = np.zeros((rows, k, nf + 1), dtype=np.float64)
            c = min(F, nf)
            out[:, :, :c] = np.moveaxis(raw[:, :c, :], 2, 0)
            out[:, :, -1] = raw[:, F, :].T
            out = out[:, 0, :] if k == 1 else \
                out.reshape(rows, k * (nf + 1))
        else:
            from ..ops.shap import predict_contrib as _host_contrib
            out = _host_contrib(self.models, X, num_iteration, k)
        self._record_predict("contrib", rows, n_trees, used_engine, t0)
        return out

    def predict_leaf_index(self, X: np.ndarray, num_iteration: int = -1,
                           predict_engine=None,
                           predict_chunk_rows=None) -> np.ndarray:
        import time as _time
        t0 = _time.perf_counter()
        X = np.ascontiguousarray(np.asarray(X, np.float64))
        n_trees = len(self.models)
        if num_iteration is not None and num_iteration > 0:
            n_trees = min(n_trees, num_iteration * self.num_tree_per_iteration)
        used_engine = n_trees > 0 and X.shape[0] > 0 and \
            self._use_predict_engine(predict_engine)
        if used_engine:
            out = self._engine().predict_leaf_index(
                self._flat_forest(), X, n_trees,
                chunk_rows=predict_chunk_rows or
                getattr(self.config, "predict_chunk_rows", 0))
        else:
            out = np.stack([self.models[i].predict_leaf_index(X)
                            for i in range(n_trees)], axis=1)
        self._record_predict("leaf", X.shape[0], n_trees, used_engine, t0)
        return out

    def _record_predict(self, kind: str, rows: int, n_trees: int,
                        used_engine: bool, t0: float) -> None:
        """One ``predict`` telemetry record per call.  Cache counters
        are reported CUMULATIVE from the process-wide engine — the
        merge-safe form under concurrent predicts (utils/telemetry.py
        aggregates by keeping the latest value)."""
        rec = getattr(self, "_telemetry", None)
        if rec is None:
            return
        import time as _time
        fields = {"kind": kind, "rows": int(rows), "n_trees": int(n_trees),
                  "engine": bool(used_engine),
                  "duration_ms": round((_time.perf_counter() - t0) * 1e3,
                                       3)}
        try:
            if kind == "contrib":
                from ..ops.shap import get_shap_engine
                fields["cache"] = get_shap_engine().cache_info()
            else:
                from ..ops.predict import get_engine
                fields["cache"] = get_engine().cache_info()
        except Exception:
            pass
        rec.emit("predict", **fields)

    def init_from_model(self, models: List[Tree],
                        raw: Optional[np.ndarray]) -> None:
        """Continue-training: seed this booster with an existing model's
        trees (``engine.py`` init_model / ``application.cpp:90-93``) and
        replay them into the training score.  ``raw`` is the training
        set's raw feature matrix (the init model may have been trained
        with different bin boundaries, so replay must use real values).
        """
        if len(models) % max(self.num_tree_per_iteration, 1):
            Log.fatal("init model has %d trees, not a multiple of "
                      "num_tree_per_iteration=%d", len(models),
                      self.num_tree_per_iteration)
        import copy
        # deep-copy: later in-place mutations (DART renormalization,
        # refit) must not corrupt the donor booster's trees
        self.models = [copy.deepcopy(t) for t in models]
        self.iter = len(models) // max(self.num_tree_per_iteration, 1)
        self._trees_dispatched = len(models)
        k = self.num_tree_per_iteration
        dt = np.uint8 if self.config.num_leaves <= 256 else np.uint16
        add = np.zeros((k, self.num_data), np.float32)
        leaf_idx: List[Optional[np.ndarray]] = []
        if raw is not None:
            for i, tree in enumerate(self.models):
                add[i % k] += tree.predict(raw)
            if self._track_train_leaf:
                leaf_idx = [t.predict_leaf_index(raw).astype(dt)
                            for t in self.models]
        else:
            # streamed dataset (docs/Streaming.md): the raw matrix is
            # out-of-core by design — replay the seed trees CHUNK by
            # chunk off the raw source (tree predict is row-wise, so
            # the chunked replay is exact)
            src = getattr(self.train_set, "raw_source", None)
            info = getattr(self.train_set, "stream", None)
            if src is None or info is None:
                Log.fatal("continue-training requires the training "
                          "set's raw matrix (free_raw_data=False)")
            from ..io.cache import chunk_grid
            parts: List[List[np.ndarray]] = [[] for _ in self.models] \
                if self._track_train_leaf else []
            for start, stop in chunk_grid(self.num_data,
                                          info.chunk_rows):
                blk = src.read_rows(start, stop)
                for i, tree in enumerate(self.models):
                    add[i % k, start:stop] += tree.predict(blk)
                    if self._track_train_leaf:
                        parts[i].append(
                            tree.predict_leaf_index(blk).astype(dt))
            if self._track_train_leaf:
                leaf_idx = [np.concatenate(p) for p in parts]
        self._score = self._score + self._place_score(add)
        if self._track_train_leaf:
            # DART needs per-tree train-leaf assignments to drop and
            # renormalize the seeded trees
            self._train_leaf_idx = leaf_idx

    def refit(self, X: np.ndarray, y: np.ndarray, weight=None,
              decay_rate: float = 0.9) -> None:
        """Refit the existing trees' leaf values to new data
        (``GBDT::RefitTree``, ``gbdt.cpp:265``;
        ``SerialTreeLearner::FitByExistingTree``,
        ``serial_tree_learner.cpp:223-252``): keep every tree's
        structure, recompute each leaf's output from the new data's
        gradient statistics at that leaf, and blend
        ``decay_rate*old + (1-decay_rate)*new``."""
        if self.objective is None:
            Log.fatal("refit requires a built-in objective")
        X = np.ascontiguousarray(np.asarray(X, np.float64))
        n = X.shape[0]
        meta = Metadata(n)
        meta.set_label(np.asarray(y, np.float64).reshape(-1))
        if weight is not None:
            meta.set_weight(weight)
        # a FRESH objective bound to the refit data — the training
        # objective must stay bound to the train set (the reference's
        # RefitTree reuses the training gradients buffer, but its
        # objective is naturally re-pointed via leaf_pred; ours is
        # stateful over Metadata)
        objective = create_objective(self.config.objective, self.config)
        objective.init(meta, n)
        # per-tree leaf assignment of the new data (rows, n_trees)
        leaf_pred = np.stack([t.predict_leaf_index(X)
                              for t in self.models], axis=1)
        self._refit_core(leaf_pred, objective, n, decay_rate)

    def refit_leaf_preds(self, leaf_pred: np.ndarray,
                         decay_rate: float = 0.9) -> None:
        """C-API refit (``LGBM_BoosterRefit``, ``c_api.h:446``): leaf
        assignments are supplied by the caller and the gradients come
        from the TRAINING set's objective (``GBDT::RefitTree``)."""
        if self.objective is None:
            Log.fatal("refit requires a built-in objective")
        if self.train_set is None:
            Log.fatal("refit by leaf predictions needs the training set")
        n = self.num_data
        leaf_pred = np.asarray(leaf_pred, np.int32).reshape(n, -1)
        if leaf_pred.shape[1] != len(self.models):
            Log.fatal("leaf_preds has %d columns but the model has %d "
                      "trees", leaf_pred.shape[1], len(self.models))
        objective = create_objective(self.config.objective, self.config)
        objective.init(self.train_set.metadata, n)
        self._refit_core(leaf_pred, objective, n, decay_rate)

    def _refit_core(self, leaf_pred: np.ndarray, objective, n: int,
                    decay_rate: float) -> None:
        from ..ops.split import EPS
        import jax.numpy as jnp
        self._invalidate_predictor()    # leaf values mutate in place
        k = max(self.num_tree_per_iteration, 1)
        score = jnp.zeros((k, n), jnp.float32)
        cfg = self.config
        n_iters = len(self.models) // k
        for it in range(n_iters):
            g, h = objective.get_gradients(score)
            g = np.atleast_2d(np.asarray(g))
            h = np.atleast_2d(np.asarray(h))
            for tree_id in range(k):
                mi = it * k + tree_id
                tree = self.models[mi]
                lp = leaf_pred[:, mi]
                nl = tree.num_leaves
                sg = np.bincount(lp, weights=g[tree_id], minlength=nl)
                sh = np.bincount(lp, weights=h[tree_id],
                                 minlength=nl) + EPS
                out = -_threshold_l1(sg, cfg.lambda_l1) / \
                    (sh + cfg.lambda_l2)
                if cfg.max_delta_step > 0:
                    out = np.clip(out, -cfg.max_delta_step,
                                  cfg.max_delta_step)
                new_out = out * tree.shrinkage
                tree.leaf_value[:nl] = (decay_rate * tree.leaf_value[:nl]
                                        + (1.0 - decay_rate) * new_out)
                score = score.at[tree_id].add(
                    jnp.asarray(tree.leaf_value[lp], jnp.float32))

    def merge_from(self, other: "GBDT") -> None:
        """Merge another booster's trees in FRONT of this one's
        (``GBDT::MergeFrom``, ``src/boosting/gbdt.h:54``) — the parallel
        model-merge workflow's primitive.  Scores become stale relative
        to the merged ensemble, matching the reference (which also only
        splices the model list)."""
        import copy
        if other.num_tree_per_iteration != self.num_tree_per_iteration:
            Log.fatal("cannot merge boosters with different "
                      "num_tree_per_iteration")
        self.models = [copy.deepcopy(t) for t in other.models] + self.models
        self.iter = len(self.models) // max(self.num_tree_per_iteration, 1)

    def shuffle_models(self, start_iter: int = 0,
                       end_iter: int = -1) -> None:
        """Permute whole iterations in [start_iter, end_iter)
        (``GBDT::ShuffleModels``, ``src/boosting/gbdt.h:73``; fixed seed
        17 like the reference's ``Random tmp_rand(17)``)."""
        k = max(self.num_tree_per_iteration, 1)
        total_iter = len(self.models) // k
        start_iter = max(0, start_iter)
        end_iter = total_iter if end_iter <= 0 else min(total_iter,
                                                        end_iter)
        idx = np.arange(total_iter)
        rng = np.random.RandomState(17)
        span = idx[start_iter:end_iter]
        rng.shuffle(span)
        idx[start_iter:end_iter] = span
        self.models = [self.models[i * k + j] for i in idx
                       for j in range(k)]

    def rollback_one_iter(self) -> None:
        """Undo the last iteration (``GBDT::RollbackOneIter``): train
        score from the pre-iteration snapshot; valid scores by
        SUBTRACTING the popped trees' predictions (the reference's
        ``Shrinkage(-1)`` + ``AddScore``) — per-iteration valid-score
        copies were dropped from the hot loop.  A subclass that still
        snapshots (RF's multiplicative averaging) restores from
        ``_prev_valid_scores`` instead."""
        blk = getattr(self, "_fused_block", None)
        if blk is not None and blk["served"] > 0:
            self._fused_rollback()
            return
        if self.iter <= 0 or self._prev_score is None:
            return
        # materialize any in-flight tree FIRST: its flush mutates score
        # (init-score bias) and may set the stop flag — both must land
        # before the rollback restores/clears them
        self._flush_pending()
        self._stop_flag = False  # the popped tree may have set it
        # pop-then-retrain restores the tree COUNT, so the count-keyed
        # flattened-predictor cache must be version-bumped explicitly
        self._invalidate_predictor()
        self._score = self._prev_score
        if self._prev_valid_scores:
            for vs, snap in zip(self.valid_sets, self._prev_valid_scores):
                vs.score = snap
        elif self.valid_sets:
            # subtract the iteration's trees: tree.predict includes any
            # absorbed init bias, which the forward path added to the
            # valid score separately (bias + raw contribution = the
            # biased prediction), so one subtraction undoes both
            k = max(self.num_tree_per_iteration, 1)
            models = self.models  # flushed above; property is safe
            for j in range(k):
                tree = models[-1 - j]
                tree_idx = (len(models) - 1 - j) % k
                for vs in self.valid_sets:
                    vs.score[tree_idx] -= tree.predict(vs.raw)
        self._prev_score = None
        for _ in range(self.num_tree_per_iteration):
            self.models.pop()
            if self._track_train_leaf:
                for vs in self.valid_sets:
                    if vs.leaf_idx_per_tree:
                        vs.leaf_idx_per_tree.pop()
        self.iter -= 1
