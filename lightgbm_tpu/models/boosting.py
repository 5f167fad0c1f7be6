"""Boosting-mode variants and the boosting factory.

Capability parity with ``src/boosting/``: GOSS (``goss.hpp:26``), MVS —
the fork's signature addition (``mvs.hpp:28``), DART (``dart.hpp:17``)
and RF (``rf.hpp:18``), dispatched by ``config.boosting`` like
``Boosting::CreateBoosting`` (``boosting.cpp:33-58``).

TPU-first: sampling modes produce per-row WEIGHT vectors (0 = dropped,
>1 = upweighted) consumed by the device growth loop's masked histogram
pass, instead of the reference's index-buffer compaction — the binned
matrix never moves, only the (N,) gradient/hessian/mask vectors change.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from ..config import Config
from ..io.dataset import Metadata, TpuDataset
from ..objectives import Objective
from ..metrics import Metric
from ..utils.log import Log
from .gbdt import GBDT, _KEPS
from .tree import Tree


class GOSS(GBDT):
    """Gradient-based one-side sampling (``goss.hpp:26``): keep the
    ``top_rate`` rows by |g*h|, sample ``other_rate`` of the rest and
    upweight their grad/hess by (n - top_k) / other_k
    (``goss.hpp:99-128``)."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        cfg = self.config
        if cfg.top_rate + cfg.other_rate > 1.0:
            Log.fatal("GOSS requires top_rate + other_rate <= 1")
        if cfg.top_rate <= 0 or cfg.other_rate <= 0:
            Log.fatal("GOSS requires top_rate > 0 and other_rate > 0")
        if cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0:
            Log.fatal("Cannot use bagging in GOSS")
        Log.info("Using GOSS")

    def _bagging_mask(self, grad=None, hess=None):
        if grad is None:
            return None
        return self._goss_mask(self.iter, grad, hess)

    def _fused_mask_fn(self):
        """GOSS inside the fused super-step: the mask is a pure device
        function of the iteration's gradients and the PRNG fold of the
        GLOBAL iteration index — bit-identical to the sequential
        draw."""
        return lambda it, prev, grad, hess: self._goss_mask(it, grad,
                                                            hess)

    def _goss_mask(self, it, grad, hess):
        """Device GOSS mask: the top set is everything above the
        ``top_rate``-quantile of |g*h| (one device sort, no host
        round-trip), the rest is a Bernoulli sample at ``other_rate``'s
        expected size — same expected composition and upweighting as
        the reference's exact argsort + without-replacement choice, in
        O(sort) device work instead of a full-N host argsort per
        iteration.  ``it`` may be a host int or a traced scalar; one
        jitted program serves the sequential and scan-inlined call
        sites (fused-path bit-parity)."""
        import jax
        if getattr(self, "_trace_raw", False):
            # battery trace: ``self._bag_key`` is a per-model tracer —
            # inline the raw impl (jit under a trace compiles to the
            # same program, so solo/battery stay bit-identical)
            return self._goss_mask_impl(it, grad, hess)
        if getattr(self, "_goss_mask_jit", None) is None:
            self._goss_mask_jit = jax.jit(self._goss_mask_impl)
        return self._goss_mask_jit(it, grad, hess)

    def _goss_mask_impl(self, it, grad, hess):
        import jax
        import jax.numpy as jnp
        cfg = self.config
        n = self.num_data
        gh = jnp.sum(jnp.abs(grad * hess), axis=0)[:n]
        top_k = max(int(n * cfg.top_rate), 1)
        other_k = int(n * cfg.other_rate)
        thr = -jnp.sort(-gh)[top_k - 1]
        key = jax.random.fold_in(self._bag_key, it)
        ku, kt = jax.random.split(key)
        # tie-safe top set: strictly-greater rows always kept, rows AT
        # the threshold admitted at the rate that fills top_k in
        # expectation — a plain gh >= thr would keep EVERY tied row
        # (e.g. the whole dataset when >top_rate of |g*h| is 0)
        gt = gh > thr
        tie = gh == thr
        n_gt = jnp.sum(gt)
        n_tie = jnp.maximum(jnp.sum(tie), 1)
        p_tie = jnp.clip((top_k - n_gt) / n_tie, 0.0, 1.0)
        topm = gt | (tie & (jax.random.uniform(kt, (n,)) < p_tie))
        u = jax.random.uniform(ku, (n,))
        n_rest = max(n - top_k, 1)
        pick = (~topm) & (u < other_k / n_rest)
        amp = (n - top_k) / float(max(other_k, 1))
        return jnp.where(topm, 1.0,
                         jnp.where(pick, amp, 0.0)).astype(jnp.float32)


class MVS(GBDT):
    """Minimal-variance sampling — the fork's addition (``mvs.hpp:28``):
    per-row score sqrt((sum_k |g*h|)^2 + var_weight), adaptive threshold
    mu solving  sum_i min(1, s_i/mu) = bagging_fraction * n
    (``CalculateThreshold``, ``mvs.hpp:91``); rows below mu are kept
    with probability s/mu and importance-weighted by mu/s."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        Log.info("Using MVS")

    @staticmethod
    def _threshold_device(s, target: float):
        """Smallest mu with sum(min(1, s/mu)) <= target (expected
        sample size).  Closed form over the descending order statistic
        (equivalent to the reference's recursive partition), as device
        ops: one sort + one cumsum."""
        import jax.numpy as jnp
        n = s.shape[0]
        s_desc = -jnp.sort(-s)
        suffix = jnp.cumsum(s_desc[::-1])[::-1]  # suffix[i] = sum(s[i:])
        idx = jnp.arange(n, dtype=jnp.float32)
        est = idx + suffix / jnp.maximum(s_desc, 1e-35)
        # est is nondecreasing; first position whose estimate exceeds
        # the target brackets the threshold
        over = est > target
        i = jnp.argmax(over)
        mu_in = suffix[i] / jnp.maximum(target - i.astype(jnp.float32),
                                        1e-10)
        return jnp.where(jnp.any(over), mu_in, s_desc[-1])

    def _bagging_mask(self, grad=None, hess=None):
        if grad is None or self.config.bagging_fraction >= 1.0:
            return None
        return self._mvs_mask(self.iter, grad, hess)

    def _fused_mask_fn(self):
        """MVS inside the fused super-step: pure function of the
        iteration's gradients + the global-iteration PRNG fold."""
        if self.config.bagging_fraction >= 1.0:
            return None
        return lambda it, prev, grad, hess: self._mvs_mask(it, grad,
                                                           hess)

    def _mvs_mask(self, it, grad, hess):
        """One jitted program from both call sites — see
        :meth:`GOSS._goss_mask`."""
        import jax
        if getattr(self, "_trace_raw", False):
            # battery trace: see GOSS._goss_mask
            return self._mvs_mask_impl(it, grad, hess)
        if getattr(self, "_mvs_mask_jit", None) is None:
            self._mvs_mask_jit = jax.jit(self._mvs_mask_impl)
        return self._mvs_mask_jit(it, grad, hess)

    def _mvs_mask_impl(self, it, grad, hess):
        import jax
        import jax.numpy as jnp
        cfg = self.config
        n = self.num_data
        gh = jnp.sum(jnp.abs(grad * hess), axis=0)[:n]
        s = jnp.sqrt(gh * gh + jnp.float32(cfg.var_weight))
        mu = self._threshold_device(s, cfg.bagging_fraction * n)
        key = jax.random.fold_in(self._bag_key, it)
        prob = jnp.minimum(s / jnp.maximum(mu, 1e-35), 1.0)
        keep = jax.random.uniform(key, (n,)) < prob
        return jnp.where(keep, 1.0 / jnp.maximum(prob, 1e-35),
                         0.0).astype(jnp.float32)


class DART(GBDT):
    """Dropouts meet MART (``dart.hpp:17``): per iteration, drop a
    random subset of past trees from the training score, fit the new
    tree against the reduced score, then renormalize the new and
    dropped trees by k/(k+1) (``DroppingTrees:91``, ``Normalize:59``;
    xgboost mode uses k/(k+lr))."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._track_train_leaf = True
        self._pipeline_enabled = False  # drops need the host tree
        self._superstep_enabled = False  # per-iter drops/renormalize
        self._rng_drop = np.random.RandomState(
            self.config.drop_seed & 0x7FFFFFFF)
        self.tree_weight: List[float] = []
        self.sum_weight = 0.0
        self._drop_index: List[int] = []
        Log.info("Using DART")

    def init_from_model(self, models, raw) -> None:
        super().init_from_model(models, raw)
        # seed per-iteration drop weights: each seeded tree's stored
        # cumulative shrinkage is the best available estimate of its
        # normalized DART weight
        K = self.num_tree_per_iteration
        self.tree_weight = [float(self.models[i * K].shrinkage)
                            for i in range(self.iter)]
        self.sum_weight = float(sum(self.tree_weight))

    # -- checkpoint/resume: drop RNG + per-tree weight state ----------
    def _extra_ckpt_state(self):
        return {"rng_drop": self._rng_drop.get_state(),
                "tree_weight": list(self.tree_weight),
                "sum_weight": float(self.sum_weight)}

    def _restore_extra_ckpt_state(self, extra, raw) -> None:
        if "rng_drop" in extra:
            self._rng_drop.set_state(extra["rng_drop"])
        self.tree_weight = [float(w)
                            for w in extra.get("tree_weight", [])]
        self.sum_weight = float(extra.get("sum_weight", 0.0))
        self._drop_index = []
        self._dart_undo = None

    # -- per-tree train contribution from the stored leaf assignment --
    def _train_contrib(self, model_idx: int):
        import jax.numpy as jnp
        from ..ops.lookup import take_small
        tree = self.models[model_idx]
        la = self._train_leaf_idx[model_idx]
        if la is None:
            return jnp.float32(tree.leaf_value[0])
        # pad the table to a STABLE shape — the lookup kernel's
        # unrolled select-chain compiles per table length; seeded trees
        # from a donor model may exceed the current num_leaves
        L = max(self.config.num_leaves, tree.num_leaves)
        vals = np.zeros(L, np.float32)
        vals[:tree.num_leaves] = tree.leaf_value[:tree.num_leaves]
        return take_small(jnp.asarray(vals), jnp.asarray(la, jnp.int32))

    def _select_drops(self) -> None:
        cfg = self.config
        self._drop_index = []
        if self._rng_drop.random_sample() < cfg.skip_drop or self.iter == 0:
            pass
        elif cfg.uniform_drop:
            rate = cfg.drop_rate
            if cfg.max_drop > 0:
                rate = min(rate, cfg.max_drop / float(self.iter))
            for i in range(self.iter):
                if self._rng_drop.random_sample() < rate:
                    self._drop_index.append(i)
                    if len(self._drop_index) >= cfg.max_drop > 0:
                        break
        else:
            inv_avg = len(self.tree_weight) / max(self.sum_weight, _KEPS)
            rate = cfg.drop_rate
            if cfg.max_drop > 0:
                rate = min(rate, cfg.max_drop * inv_avg /
                           max(self.sum_weight, _KEPS))
            for i in range(self.iter):
                if self._rng_drop.random_sample() < \
                        rate * self.tree_weight[i] * inv_avg:
                    self._drop_index.append(i)
                    if len(self._drop_index) >= cfg.max_drop > 0:
                        break
        k = float(len(self._drop_index))
        lr = self.config.learning_rate
        if not cfg.xgboost_dart_mode:
            self.shrinkage_rate = lr / (1.0 + k)
        else:
            self.shrinkage_rate = lr if not self._drop_index else \
                lr / (lr + k)

    def train_one_iter(self, grad=None, hess=None) -> bool:
        # snapshot BEFORE drops so rollback restores a consistent state
        pre_score = self._score
        pre_valid = [vs.score.copy() for vs in self.valid_sets]
        pre_weights = (list(self.tree_weight), self.sum_weight)
        self._select_drops()
        K = self.num_tree_per_iteration
        # remove dropped trees from the training score so gradients see
        # the reduced ensemble
        for i in self._drop_index:
            for k in range(K):
                self._score = self._score.at[k].add(
                    -self._train_contrib(i * K + k))
        stop = super().train_one_iter(grad, hess)
        if stop:
            # no tree was added: restore the dropped contributions so
            # the score matches the (unchanged) model, and invalidate
            # the undo snapshot (it describes an older iteration)
            for i in self._drop_index:
                for k in range(K):
                    self._score = self._score.at[k].add(
                        self._train_contrib(i * K + k))
            self._drop_index = []
            self._dart_undo = None
            return stop
        scale = self._normalize()
        if not self.config.uniform_drop:
            self.tree_weight.append(self.shrinkage_rate)
            self.sum_weight += self.shrinkage_rate
        self._dart_undo = (pre_score, pre_valid, pre_weights,
                           list(self._drop_index), scale)
        return False

    def rollback_one_iter(self) -> None:
        """Undo the last DART iteration: restore pre-drop scores, unscale
        the renormalized dropped trees, and pop the new tree."""
        if self.iter <= 0 or getattr(self, "_dart_undo", None) is None:
            return
        pre_score, pre_valid, (tw, sw), dropped, scale = self._dart_undo
        K = self.num_tree_per_iteration
        # pop-then-retrain aliases the count-keyed flattened-predictor
        # cache (and non-empty drops additionally unscale in place)
        self._invalidate_predictor()
        for i in dropped:
            for k in range(K):
                self.models[i * K + k].apply_shrinkage(1.0 / scale)
        self._score = pre_score
        for vs, snap in zip(self.valid_sets, pre_valid):
            vs.score = snap
        self.tree_weight, self.sum_weight = tw, sw
        for _ in range(K):
            self.models.pop()
            if self._train_leaf_idx:
                self._train_leaf_idx.pop()
            for vs in self.valid_sets:
                if vs.leaf_idx_per_tree:
                    vs.leaf_idx_per_tree.pop()
        self.iter -= 1
        self._dart_undo = None

    def _normalize(self) -> float:
        k = float(len(self._drop_index))
        if k == 0:
            return 1.0
        # renormalization rescales EXISTING trees' leaf values in
        # place — the flattened inference tables must be rebuilt
        self._invalidate_predictor()
        cfg = self.config
        lr = cfg.learning_rate
        scale = k / (k + 1.0) if not cfg.xgboost_dart_mode else \
            k / (k + lr)
        K = self.num_tree_per_iteration
        for i in self._drop_index:
            for kk in range(K):
                mi = i * K + kk
                tree = self.models[mi]
                tree.apply_shrinkage(scale)
                # train score: net change is -(1-scale) x original
                self._score = self._score.at[kk].add(
                    self._train_contrib(mi))
                # valid scores: subtract the same (1-scale) slice via
                # the stored per-tree leaf tables (a numpy lookup, not
                # an O(rows x depth) host tree walk per drop)
                if self.valid_sets:
                    factor = (1.0 - scale) / scale
                    for vs in self.valid_sets:
                        la = vs.leaf_idx_per_tree[mi] \
                            if mi < len(vs.leaf_idx_per_tree) else None
                        if la is None:
                            contrib = tree.leaf_value[0] \
                                if tree.num_leaves <= 1 else \
                                tree.predict(vs.raw)
                        else:
                            contrib = tree.leaf_value[
                                la.astype(np.int32)]
                        vs.score[kk] -= contrib * factor
            if not cfg.uniform_drop:
                unit = (k + 1.0) if not cfg.xgboost_dart_mode else (k + lr)
                self.sum_weight -= self.tree_weight[i] / unit
                self.tree_weight[i] *= scale
        return scale


class RF(GBDT):
    """Random forest (``rf.hpp:18``): unit shrinkage, mandatory
    bagging, gradients computed ONCE from the constant init score, and
    the model score maintained as the AVERAGE of tree outputs."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        cfg = self.config
        if not (cfg.bagging_freq > 0 and 0 < cfg.bagging_fraction < 1):
            Log.fatal("random forest requires bagging "
                      "(bagging_freq > 0, 0 < bagging_fraction < 1)")
        self.average_output = True
        self._pipeline_enabled = False  # averaged-score updates
        self._superstep_enabled = False  # averaged-score updates
        self.shrinkage_rate = 1.0
        if self.objective is None:
            Log.fatal("rf does not support a custom objective")
        if self.train_set.metadata.init_score is not None:
            # rf.hpp:38 — the averaged-score update is incompatible
            # with a per-row initial score
            Log.fatal("cannot use initial score for random forest")
        Log.info("Using RF")
        K = self.num_tree_per_iteration
        self._init_scores = [0.0] * K
        if self.config.boost_from_average and self.objective is not None:
            for k in range(K):
                self._init_scores[k] = self.objective.boost_from_score(k)
        # fixed gradients from the constant init score (RF::Boosting)
        import jax.numpy as jnp
        base = jnp.asarray(
            np.repeat(np.asarray(self._init_scores, np.float32)[:, None],
                      self.num_data, axis=1))
        g, h = self.objective.get_gradients(base)
        self._rf_grad = jnp.atleast_2d(g)
        self._rf_hess = jnp.atleast_2d(h)

    def _train_one_iter_impl(self, grad=None, hess=None) -> bool:
        # overriding the IMPL keeps the base train_one_iter's telemetry
        # wrapper (per-iteration run records) around RF iterations too
        import jax.numpy as jnp
        if grad is not None:
            Log.fatal("rf does not support a custom objective")
        self._prev_score = self._score
        self._prev_valid_scores = [vs.score.copy() for vs in self.valid_sets]
        bag = self._bagging_mask()
        K = self.num_tree_per_iteration
        m = float(self.iter)
        for k in range(K):
            # average-maintaining update: score <- (score*m + tree)/(m+1)
            self._score = self._score.at[k].multiply(m)
            for vs in self.valid_sets:
                vs.score[k] *= m
            tree = self._train_one_tree(self._rf_grad[k], self._rf_hess[k],
                                        bag, self._init_scores[k])
            # the per-tree bias is inside the tree but excluded from the
            # incremental score update; add it so the average is exact
            if abs(self._init_scores[k]) > _KEPS and tree.num_leaves > 1:
                self._score = self._score.at[k].add(self._init_scores[k])
                for vs in self.valid_sets:
                    vs.score[k] += self._init_scores[k]
            self._score = self._score.at[k].multiply(1.0 / (m + 1.0))
            for vs in self.valid_sets:
                vs.score[k] /= (m + 1.0)
            self.models.append(tree)
        self.iter += 1
        return False


_BOOSTING_TYPES = {
    "gbdt": GBDT, "gbrt": GBDT,
    "dart": DART,
    "goss": GOSS,
    "rf": RF, "random_forest": RF,
    "mvs": MVS,
}


def create_boosting(config: Config, train_set: TpuDataset,
                    objective: Optional[Objective],
                    metrics: Sequence[Metric] = (), mesh=None) -> GBDT:
    """``Boosting::CreateBoosting`` (``boosting.cpp:33-58``)."""
    cls = _BOOSTING_TYPES.get(config.boosting)
    if cls is None:
        Log.fatal("unknown boosting type %s", config.boosting)
    from ..utils.profiling import timed
    with timed("boost/init"):
        return cls(config, train_set, objective, metrics, mesh=mesh)
