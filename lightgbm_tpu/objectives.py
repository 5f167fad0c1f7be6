"""Objective functions: gradients/hessians on device.

Capability parity with ``src/objective/`` (factory at
``objective_function.cpp:10-47``).  Each objective implements
``get_gradients(score) -> (grad, hess)`` over ``(num_data,)`` (or
``(num_class, num_data)`` for multiclass) device arrays, plus
``boost_from_score`` (initial score), ``convert_output`` (raw score →
prediction), optional per-leaf output renewal
(``RenewTreeOutput``, ``objective_function.h:38-47``) and constant-hessian
detection.

TPU-first: all math is vectorized jnp (fused by XLA into a single
elementwise pass over the score array); per-query ranking loops become
segment-id masked ops.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np

from .utils.log import Log

_REGISTRY: Dict[str, Type["Objective"]] = {}


def register(*names):
    def deco(cls):
        for n in names:
            _REGISTRY[n] = cls
        cls.name = names[0]
        return cls
    return deco


def create_objective(name: str, config) -> "Objective":
    """Factory (``ObjectiveFunction::CreateObjectiveFunction``)."""
    if name not in _REGISTRY:
        Log.fatal("unknown objective %s", name)
    return _REGISTRY[name](config)


_EMPTY_F32 = None


def _empty_f32():
    """Cached 0-length weight sentinel (a fresh jnp.zeros per call is
    an extra eager dispatch on the hot path).  Created under
    ``ensure_compile_time_eval``: the first call may now happen inside
    a jit trace (``gradient_fn``), and caching a tracer in a global
    would leak it into every later trace."""
    global _EMPTY_F32
    if _EMPTY_F32 is None:
        with jax.ensure_compile_time_eval():
            _EMPTY_F32 = jnp.zeros((0,), jnp.float32)
    return _EMPTY_F32


class Objective:
    name = "base"
    is_constant_hessian = False
    num_model_per_iteration = 1
    # the per-row device tensors ``get_gradients`` reads, where a row's
    # gradient is a function of that row's score and tensors alone (a
    # pointwise objective): the row-sharded boosting loop hands each
    # device its own rows of them as arguments (:meth:`rows`).  None
    # where a row's gradient reads other rows (lambdarank's queries)
    row_tensors: Optional[Tuple[str, ...]] = ("label", "weight")
    # the device tables ``get_gradients`` reads beyond the row tensors,
    # by attribute name (lambdarank's query layout): arguments of the
    # programs that compute gradients (:meth:`gradient_fn`, the fused
    # super-step), never their constants
    table_names: Tuple[str, ...] = ()
    # how the pairs of a listwise objective are laid out, for the tier
    # record (``models/tier.py``); None for a pointwise objective
    layout: Optional[str] = None

    # transform applied to raw score at predict time
    def __init__(self, config):
        self.config = config
        self.label: Optional[jax.Array] = None
        self.weight: Optional[jax.Array] = None

    def init(self, metadata, num_data: int) -> None:
        self.num_data = num_data
        self.label = jnp.asarray(metadata.label, jnp.float32)
        self.weight = (jnp.asarray(metadata.weight, jnp.float32)
                       if metadata.weight is not None else None)

    # ---- per-row tensors as arguments (models/gbdt.py, row_state) -----
    def shard_refusal(self) -> Optional[str]:
        """Why this objective's row tensors cannot live on the shard
        of a row-sharded learner (``models/tier.py`` records it), or
        None."""
        if self.row_tensors is None:
            return (f"objective={self.name}: a row's gradient reads "
                    f"other rows' scores")
        if self.num_model_per_iteration > 1:
            return (f"objective={self.name} grows "
                    f"{self.num_model_per_iteration} trees an iteration "
                    f"(not fused)")
        if type(self).renew_tree_output is not \
                Objective.renew_tree_output:
            return (f"objective={self.name} renews leaf outputs on the "
                    f"host from the whole job's residuals")
        return None

    def rows(self) -> Dict[str, jax.Array]:
        """The row tensors that are set, by attribute name."""
        return {k: v for k in self.row_tensors or ()
                if (v := getattr(self, k, None)) is not None}

    @contextlib.contextmanager
    def rows_as(self, rows: Dict[str, jax.Array]):
        """Swap the row tensors for ``rows`` while a program traces
        (the shard's own rows inside ``shard_map``), as
        :meth:`weight_override` swaps the weight."""
        saved = {k: getattr(self, k) for k in rows}
        self.__dict__.update(rows)
        try:
            yield
        finally:
            self.__dict__.update(saved)

    def tables(self) -> Dict[str, object]:
        """The objective's device tables, by attribute name (empty for
        a pointwise objective)."""
        return {k: getattr(self, k) for k in self.table_names}

    # the tables swap as the row tensors do, while a program traces
    tables_as = rows_as

    def place_rows(self, width: int, place) -> None:
        """Pad every row tensor to ``width`` rows and hand it to
        ``place(host array) -> device array``: after this the tensors
        are the mesh's, each device holding its rows, and host readers
        slice ``[:num_data]`` (:meth:`_host_rows`)."""
        for k, v in self.rows().items():
            a = np.asarray(v)
            pad = [(0, 0)] * (a.ndim - 1) + [(0, width - a.shape[-1])]
            setattr(self, k, place(np.pad(a, pad)))

    def _host_rows(self, a) -> np.ndarray:
        """A row tensor on the host, float64, without padding rows."""
        return np.asarray(a, np.float64)[..., :self.num_data]

    def gradient_fn_rows(self):
        """:meth:`gradient_fn` with the row tensors as arguments:
        ``(score, rows) -> (grad, hess)``, jitted.  Nothing of the data
        set is a constant of the program, so placed tensors keep their
        placement and the compiled program does not depend on them."""
        if getattr(self, "_gradient_fn_rows_jit", None) is None:
            def fn(score, rows):
                with self.rows_as(rows):
                    return self.get_gradients(score)
            self._gradient_fn_rows_jit = jax.jit(fn)
        return self._gradient_fn_rows_jit

    def _w(self, grad, hess):
        if self.weight is not None:
            return grad * self.weight, hess * self.weight
        return grad, hess

    # Battery training (models/battery.py): objectives whose weight
    # handling is a pure gradient-time multiply can accept a per-trace
    # weight override (per-model CV fold masks riding as a traced
    # vector).  MAPE opts out — it bakes weights into its label
    # weighting at init, so an override would be silently ignored.
    supports_weight_override = True

    @contextlib.contextmanager
    def weight_override(self, weight):
        """Swap ``self.weight`` for the duration of a trace.  The
        override multiplies gradients/hessians at exactly the point
        solo weighted training multiplies metadata weights, so a fold
        mask entering here reproduces the solo weighted op order
        bit-for-bit."""
        saved = self.weight
        self.weight = weight
        try:
            yield
        finally:
            self.weight = saved

    def _jitted_gradients(self, impl, args, **statics):
        """Dispatch ``impl(*args, weight, *, weighted=..., **statics)``
        as ONE jitted program.  Eagerly, a gradient chain dispatches
        each (N,)-scale op as its own HBM round-trip; fused it runs as
        one pass.  ``weight`` rides as an argument (a closure over a
        big device array would embed it in the remote-compile payload);
        unweighted calls share a cached 0-length sentinel."""
        if getattr(self, "_grad_fn", None) is None:
            self._grad_fn = jax.jit(
                impl,
                static_argnames=tuple(statics) + ("weighted",))
        w = self.weight if self.weight is not None else _empty_f32()
        return self._grad_fn(*args, w, weighted=self.weight is not None,
                             **statics)

    def get_gradients(self, score: jax.Array) -> Tuple[jax.Array, jax.Array]:
        raise NotImplementedError

    def gradient_fn(self):
        """A pure JITTED ``score -> (grad, hess)`` device function,
        capturable inside a larger jitted program (the fused training
        super-step traces it inside a ``lax.scan`` body,
        ``models/gbdt.py``).

        The contract: the returned callable reads only ``score`` and
        device arrays fixed at ``init`` time (labels, weights, query
        layouts) — no host work, no Python state mutation beyond
        first-call jit caching.  Every built-in objective's
        ``get_gradients`` satisfies this (the label/weight tensors are
        device residents and the math is jnp), so the base
        implementation jits it; an objective whose gradients need
        per-iteration host work must override this to return ``None``,
        which excludes it from super-step fusion.

        The jit wrapper is ALSO what the sequential training loop
        calls: XLA's fused elementwise loops are not bit-identical to
        the same chain dispatched eagerly (measured on the CPU
        backend: a fused ``sqrt(x*x+c)`` differs in the last ulp), so
        routing both paths through one compiled function is what makes
        the fused super-step bit-exact against the per-iteration path
        — and it is the faster form anyway (one pass over the score
        array instead of one HBM round-trip per op).

        An objective with :meth:`tables` hands them to the program as
        an argument: the callable passes whatever :meth:`tables` holds
        when it is called (a tracer's inside a program that swapped
        them in, :meth:`tables_as`), so the program is the same for
        every data set whose tables have the same shapes."""
        if getattr(self, "_gradient_fn_jit", None) is None:
            if not self.table_names:
                self._gradient_fn_jit = jax.jit(self.get_gradients)
            else:
                def get_gradients(score, tables):
                    with self.tables_as(tables):
                        return self.get_gradients(score)
                fn = jax.jit(get_gradients)
                self._gradient_fn_jit = \
                    lambda score: fn(score, self.tables())
        return self._gradient_fn_jit

    def boost_from_score(self, class_id: int = 0) -> float:
        return 0.0

    def convert_output(self, raw: np.ndarray) -> np.ndarray:
        return raw

    def renew_tree_output(self, tree, score, leaf_idx, mask) -> None:
        """Optional per-leaf refit (L1/quantile/MAPE families)."""
        return None

    def _weighted_mean_label(self) -> float:
        lab = self._host_rows(self.label)
        if self.weight is not None:
            w = self._host_rows(self.weight)
            return float(np.sum(lab * w) / np.sum(w))
        return float(np.mean(lab))


@register("regression", "regression_l2", "l2", "mean_squared_error", "mse",
          "l2_root", "root_mean_squared_error", "rmse")
class RegressionL2(Objective):
    """L2 loss (``regression_objective.hpp`` RegressionL2loss).

    ``reg_sqrt`` fits sqrt(|label|) like the reference.
    """
    is_constant_hessian = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if self.config.reg_sqrt:
            lab = jnp.sign(self.label) * jnp.sqrt(jnp.abs(self.label))
            self.label = lab
        if self.weight is not None:
            self.is_constant_hessian = False

    def get_gradients(self, score):
        return self._w(score - self.label, jnp.ones_like(score))

    def boost_from_score(self, class_id=0):
        return self._weighted_mean_label()

    def convert_output(self, raw):
        if self.config.reg_sqrt:
            return np.sign(raw) * raw * raw
        return raw


def _weighted_percentile(values: np.ndarray, weights: Optional[np.ndarray],
                         alpha: float) -> float:
    """PercentileFun / WeightedPercentileFun (regression_objective.hpp)."""
    if len(values) == 0:
        return 0.0
    order = np.argsort(values)
    v = values[order]
    if weights is None:
        pos = alpha * (len(v) - 1)
        lo = int(np.floor(pos))
        hi = min(lo + 1, len(v) - 1)
        return float(v[lo] + (pos - lo) * (v[hi] - v[lo]))
    w = weights[order]
    cum = np.cumsum(w)
    threshold = alpha * cum[-1]
    idx = int(np.searchsorted(cum, threshold, side="left"))
    return float(v[min(idx, len(v) - 1)])


class _RenewableRegression(Objective):
    """Base for objectives whose leaf outputs are refit as per-leaf
    percentiles of the residuals (``RenewTreeOutput``,
    ``regression_objective.hpp``)."""
    renew_alpha = 0.5

    def renew_tree_output(self, tree, score, leaf_idx, mask) -> None:
        score = np.asarray(score)[0] if np.ndim(score) > 1 else \
            np.asarray(score)
        leaf_idx = np.asarray(leaf_idx)
        mask = np.asarray(mask)[:len(leaf_idx)]
        label = np.asarray(self.label, np.float64)
        weight = None if self.weight is None else np.asarray(self.weight)
        residual = label - score[:len(label)]
        in_bag = mask > 0
        for leaf in range(tree.num_leaves):
            rows = in_bag & (leaf_idx[:len(label)] == leaf)
            if not np.any(rows):
                continue
            tree.leaf_value[leaf] = self._renew_value(
                residual[rows], None if weight is None else weight[rows])

    def _renew_value(self, residuals, weights):
        return _weighted_percentile(residuals, weights, self.renew_alpha)


@register("regression_l1", "l1", "mean_absolute_error", "mae")
class RegressionL1(_RenewableRegression):
    """L1 loss: constant gradients with per-leaf median refit."""
    is_constant_hessian = True

    def get_gradients(self, score):
        return self._w(jnp.sign(score - self.label), jnp.ones_like(score))

    def boost_from_score(self, class_id=0):
        return _weighted_percentile(
            np.asarray(self.label, np.float64),
            None if self.weight is None else np.asarray(self.weight), 0.5)


@register("quantile")
class Quantile(_RenewableRegression):
    """Pinball loss at ``alpha`` with per-leaf quantile refit."""
    is_constant_hessian = True

    def __init__(self, config):
        super().__init__(config)
        self.alpha = float(config.alpha)
        self.renew_alpha = self.alpha

    def get_gradients(self, score):
        grad = jnp.where(self.label > score, -self.alpha, 1.0 - self.alpha)
        return self._w(grad, jnp.ones_like(score))

    def boost_from_score(self, class_id=0):
        return _weighted_percentile(
            np.asarray(self.label, np.float64),
            None if self.weight is None else np.asarray(self.weight),
            self.alpha)


@register("huber")
class Huber(Objective):
    """Huber loss with transition at ``alpha``."""
    is_constant_hessian = True

    def __init__(self, config):
        super().__init__(config)
        self.alpha = float(config.alpha)

    def get_gradients(self, score):
        d = score - self.label
        grad = jnp.clip(d, -self.alpha, self.alpha)
        return self._w(grad, jnp.ones_like(score))

    def boost_from_score(self, class_id=0):
        return self._weighted_mean_label()


@register("fair")
class Fair(Objective):
    """Fair loss: c*d/(|d|+c) gradient (regression_objective.hpp)."""

    def __init__(self, config):
        super().__init__(config)
        self.c = float(config.fair_c)

    def get_gradients(self, score):
        d = score - self.label
        denom = jnp.abs(d) + self.c
        grad = self.c * d / denom
        hess = self.c * self.c / (denom * denom)
        return self._w(grad, hess)


@register("poisson")
class Poisson(Objective):
    """Poisson regression with log link."""

    def __init__(self, config):
        super().__init__(config)
        self.max_delta = float(config.poisson_max_delta_step)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if np.any(np.asarray(metadata.label) < 0):
            Log.fatal("poisson objective requires non-negative labels")

    def get_gradients(self, score):
        grad = jnp.exp(score) - self.label
        hess = jnp.exp(score + self.max_delta)
        return self._w(grad, hess)

    def boost_from_score(self, class_id=0):
        return float(np.log(max(self._weighted_mean_label(), 1e-12)))

    def convert_output(self, raw):
        return np.exp(raw)


@register("mape")
class MAPE(_RenewableRegression):
    """Mean absolute percentage error: L1 with 1/|label| row weights and
    weighted-median leaf refit."""
    is_constant_hessian = True
    supports_weight_override = False  # weights baked into _label_weight

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lab = np.asarray(metadata.label, np.float64)
        w = 1.0 / np.maximum(1.0, np.abs(lab))
        if metadata.weight is not None:
            w = w * np.asarray(metadata.weight, np.float64)
        w = w / np.sum(w) * num_data
        self._label_weight = jnp.asarray(w, jnp.float32)
        self.weight = None  # folded into _label_weight

    def get_gradients(self, score):
        grad = jnp.sign(score - self.label) * self._label_weight
        return grad, self._label_weight

    def _renew_value(self, residuals, weights):
        return _weighted_percentile(residuals, weights, 0.5)

    def renew_tree_output(self, tree, score, leaf_idx, mask):
        self.weight = self._label_weight  # residual weighting for refit
        super().renew_tree_output(tree, score, leaf_idx, mask)
        self.weight = None

    def boost_from_score(self, class_id=0):
        return _weighted_percentile(np.asarray(self.label, np.float64),
                                    np.asarray(self._label_weight), 0.5)


@register("gamma")
class Gamma(Objective):
    """Gamma regression with log link."""

    def get_gradients(self, score):
        e = jnp.exp(-score)
        grad = 1.0 - self.label * e
        hess = self.label * e
        return self._w(grad, hess)

    def boost_from_score(self, class_id=0):
        return float(np.log(max(self._weighted_mean_label(), 1e-12)))

    def convert_output(self, raw):
        return np.exp(raw)


@register("tweedie")
class Tweedie(Objective):
    """Tweedie deviance with variance power rho in [1, 2)."""

    def __init__(self, config):
        super().__init__(config)
        self.rho = float(config.tweedie_variance_power)

    def get_gradients(self, score):
        a = jnp.exp((1.0 - self.rho) * score)
        b = jnp.exp((2.0 - self.rho) * score)
        grad = -self.label * a + b
        hess = (-self.label * (1.0 - self.rho) * a +
                (2.0 - self.rho) * b)
        return self._w(grad, hess)

    def boost_from_score(self, class_id=0):
        return float(np.log(max(self._weighted_mean_label(), 1e-12)))

    def convert_output(self, raw):
        return np.exp(raw)


@register("binary")
class Binary(Objective):
    """Log loss (``binary_objective.hpp``): labels {0,1} mapped to ±1,
    sigmoid scaling, ``scale_pos_weight`` / ``is_unbalance`` class
    weights, initial score log(p/(1-p))/sigmoid."""

    def __init__(self, config):
        super().__init__(config)
        # config-derived fields must exist for predictor-only use
        # (model loaded from file; init() never runs)
        self.sigmoid = float(config.sigmoid)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lab = np.asarray(metadata.label)
        vals = np.unique(lab)
        if not np.all(np.isin(vals, [0.0, 1.0])):
            Log.fatal("binary objective requires 0/1 labels, got %s",
                      vals[:5])
        self.sigmoid = float(self.config.sigmoid)
        cnt_pos = float(np.sum(lab == 1))
        cnt_neg = float(np.sum(lab == 0))
        # minority class upweighting + multiplicative scale_pos_weight
        # (binary_objective.hpp:82-91)
        w_neg, w_pos = 1.0, 1.0
        if self.config.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            if cnt_pos > cnt_neg:
                w_neg = cnt_pos / cnt_neg
            else:
                w_pos = cnt_neg / cnt_pos
        w_pos *= float(self.config.scale_pos_weight)
        self.label_weights = (w_neg, w_pos)
        # initial probability from per-row weights x class weights
        # (BinaryLogloss::BoostFromScore accumulates weighted sums)
        if metadata.weight is not None:
            sw = np.asarray(metadata.weight, np.float64)
            sum_pos = float(np.sum(sw * (lab == 1)))
            sum_neg = float(np.sum(sw * (lab == 0)))
        else:
            sum_pos, sum_neg = cnt_pos, cnt_neg
        self._p_mean = (sum_pos * self.label_weights[1]) / max(
            sum_pos * self.label_weights[1] +
            sum_neg * self.label_weights[0], 1e-12)
        self.sign_label = jnp.asarray(np.where(lab == 1, 1.0, -1.0),
                                      jnp.float32)
        self.cls_weight = jnp.asarray(
            np.where(lab == 1, self.label_weights[1], self.label_weights[0]),
            jnp.float32)

    row_tensors = ("label", "weight", "sign_label", "cls_weight")

    def get_gradients(self, score):
        return self._jitted_gradients(
            self._grads_impl, (score, self.sign_label, self.cls_weight),
            sigmoid=self.sigmoid)

    @staticmethod
    def _grads_impl(score, sign_label, cls_weight, weight, *, sigmoid,
                    weighted):
        # response = -yl*sigma / (1 + exp(yl*sigma*score))
        t = sign_label * sigmoid
        response = -t / (1.0 + jnp.exp(t * score))
        absr = jnp.abs(response)
        grad = response * cls_weight
        hess = absr * (sigmoid - absr) * cls_weight
        if weighted:
            grad = grad * weight
            hess = hess * weight
        return grad, hess

    def boost_from_score(self, class_id=0):
        p = min(max(self._p_mean, 1e-12), 1 - 1e-12)
        init = float(np.log(p / (1 - p)) / self.sigmoid)
        return init

    def convert_output(self, raw):
        return 1.0 / (1.0 + np.exp(-self.sigmoid * raw))


@register("multiclass", "softmax")
class MulticlassSoftmax(Objective):
    """Softmax multiclass (``multiclass_objective.hpp``): one tree per
    class per iteration; grad = p - 1{y=k}, hess = 2 p (1-p)."""

    def __init__(self, config):
        super().__init__(config)
        self.num_class = int(config.num_class)
        if self.num_class < 2:
            Log.fatal("multiclass objective requires num_class >= 2")
        self.num_model_per_iteration = self.num_class

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lab = np.asarray(metadata.label).astype(np.int32)
        if lab.min() < 0 or lab.max() >= self.num_class:
            Log.fatal("multiclass label out of range [0, %d)",
                      self.num_class)
        self._onehot = jnp.asarray(
            np.eye(self.num_class, dtype=np.float32)[lab].T)  # (K, N)
        counts = np.bincount(lab, minlength=self.num_class).astype(np.float64)
        self._class_init = np.log(np.maximum(counts / counts.sum(), 1e-10))

    def get_gradients(self, score):
        return self._jitted_gradients(self._grads_impl,
                                      (score, self._onehot))

    @staticmethod
    def _grads_impl(score, onehot, weight, *, weighted):
        # score (K, N)
        p = jax.nn.softmax(score, axis=0)
        grad = p - onehot
        hess = 2.0 * p * (1.0 - p)
        if weighted:
            grad = grad * weight[None, :]
            hess = hess * weight[None, :]
        return grad, hess

    def boost_from_score(self, class_id=0):
        return float(self._class_init[class_id])

    def convert_output(self, raw):
        # raw (rows, K)
        e = np.exp(raw - raw.max(axis=-1, keepdims=True))
        return e / e.sum(axis=-1, keepdims=True)


@register("multiclassova", "multiclass_ova", "ova", "ovr")
class MulticlassOVA(Objective):
    """One-vs-all multiclass: K independent binary objectives."""

    def __init__(self, config):
        super().__init__(config)
        self.num_class = int(config.num_class)
        if self.num_class < 2:
            Log.fatal("multiclassova requires num_class >= 2")
        self.num_model_per_iteration = self.num_class
        self.sigmoid = float(config.sigmoid)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lab = np.asarray(metadata.label).astype(np.int32)
        self._sign = jnp.asarray(np.where(
            np.eye(self.num_class, dtype=bool)[lab].T, 1.0, -1.0
        ).astype(np.float32))  # (K, N)
        counts = np.bincount(lab, minlength=self.num_class).astype(np.float64)
        p = np.clip(counts / counts.sum(), 1e-12, 1 - 1e-12)
        self._class_init = np.log(p / (1 - p)) / self.sigmoid

    def get_gradients(self, score):
        t = self._sign * self.sigmoid
        response = -t / (1.0 + jnp.exp(t * score))
        absr = jnp.abs(response)
        grad = response
        hess = absr * (self.sigmoid - absr)
        if self.weight is not None:
            grad = grad * self.weight[None, :]
            hess = hess * self.weight[None, :]
        return grad, hess

    def boost_from_score(self, class_id=0):
        return float(self._class_init[class_id])

    def convert_output(self, raw):
        return 1.0 / (1.0 + np.exp(-self.sigmoid * raw))


@register("cross_entropy", "xentropy")
class CrossEntropy(Objective):
    """Cross-entropy for probabilistic labels in [0, 1]
    (``xentropy_objective.hpp:71``)."""

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lab = np.asarray(metadata.label)
        if lab.min() < 0 or lab.max() > 1:
            Log.fatal("cross_entropy labels must be in [0, 1]")

    def get_gradients(self, score):
        z = jax.nn.sigmoid(score)
        return self._w(z - self.label, z * (1.0 - z))

    def boost_from_score(self, class_id=0):
        p = np.clip(self._weighted_mean_label(), 1e-12, 1 - 1e-12)
        return float(np.log(p / (1 - p)))

    def convert_output(self, raw):
        return 1.0 / (1.0 + np.exp(-raw))


@register("cross_entropy_lambda", "xentlambda")
class CrossEntropyLambda(Objective):
    """Alternative-parameterization cross-entropy
    (``xentropy_objective.hpp:181``)."""

    def get_gradients(self, score):
        if self.weight is None:
            z = jax.nn.sigmoid(score)
            return z - self.label, z * (1.0 - z)
        w = self.weight
        y = self.label
        epf = jnp.exp(score)
        hhat = jnp.log1p(epf)
        z = 1.0 - jnp.exp(-w * hhat)
        enf = 1.0 / epf
        grad = (1.0 - y / z) * w / (1.0 + enf)
        c = 1.0 / (1.0 - z)
        d = 1.0 + epf
        a = w * epf / (d * d)
        d = c - 1.0
        b = (c / (d * d)) * (1.0 + w * epf - c)
        hess = a * (1.0 + y * b)
        return grad, hess

    def boost_from_score(self, class_id=0):
        p = np.clip(self._weighted_mean_label(), 1e-12, 1 - 1e-12)
        return float(np.log(np.expm1(-np.log1p(-p))))  # log(exp(hhat)-1)

    def convert_output(self, raw):
        return np.log1p(np.exp(raw))


def default_label_gain(n: int = 31) -> np.ndarray:
    """label_gain = 2^i - 1 (``dcg_calculator.cpp:30``)."""
    return np.concatenate([[0.0], (2.0 ** np.arange(1, n).astype(np.float64)
                                   - 1.0)])


# tiles of the pair tensors: a query's documents are padded to a
# multiple of 8 up to 128, then of 128 (a lane-wide row)
_SUBLANES, _LANES = 8, 128
# slots (queries x L x L) of one chunk of a bucket's pair tensor
_PAIR_SLOTS = int(2e7)
# at most this many buckets: each is a loop of its own in the programs
_MAX_BUCKETS = 8


def _tile_length(lengths: np.ndarray) -> np.ndarray:
    """Each query's length padded to the chip's tiles."""
    lengths = np.asarray(lengths, np.int64)
    return np.where(lengths <= _LANES, -(-lengths // _SUBLANES) * _SUBLANES,
                    -(-lengths // _LANES) * _LANES)


def bucket_lengths(lengths: np.ndarray,
                   max_buckets: int = _MAX_BUCKETS) -> np.ndarray:
    """The padded lengths of the buckets, ascending: at most
    ``max_buckets`` of the tile lengths the queries take, the longest
    always among them, chosen so that the pair slots
    sum_q L_b(q)^2 are fewest (a query goes to the shortest bucket that
    holds it; dynamic programming over the distinct tile lengths)."""
    tops, counts = np.unique(_tile_length(lengths), return_counts=True)
    k = len(tops)
    below = np.concatenate([[0], np.cumsum(counts)])
    sq = tops.astype(np.float64) ** 2
    # cost[b, j]: least slots of the queries up to tops[j] in b + 1
    # buckets, the last one tops[j]; prev[b, j]: that bucket's floor
    cost = np.full((max_buckets, k), np.inf)
    prev = np.full((max_buckets, k), -1)
    cost[0] = below[1:] * sq
    for b in range(1, max_buckets):
        for j in range(1, k):
            c = cost[b - 1, :j] + (below[j + 1] - below[1:j + 1]) * sq[j]
            i = int(np.argmin(c))
            cost[b, j], prev[b, j] = c[i], i
    b, j = int(np.argmin(cost[:, -1])), k - 1
    out = []
    while j >= 0:
        out.append(int(tops[j]))
        j, b = int(prev[b, j]), b - 1
    return np.asarray(out[::-1], np.int64)


def bucket_plan(lengths: np.ndarray, max_buckets: int = _MAX_BUCKETS):
    """``[(L, queries, chunks, cq)]``, a bucket each: its padded
    length, the indices of its queries (a query goes to the shortest
    bucket that holds it) and its chunks of ``cq`` queries, as even as
    ``cq * L * L <= _PAIR_SLOTS`` allows, and at least two: XLA inlines
    a loop of one trip, and the table-only part of its body is then
    hoisted out of the fused super-step's scan, which the
    per-iteration program has no loop to do; the two would fuse, and
    round, differently."""
    tops = bucket_lengths(lengths, max_buckets)
    which = np.searchsorted(tops, _tile_length(lengths))
    plan = []
    for b, L in enumerate(int(t) for t in tops):
        qs = np.flatnonzero(which == b)
        chunks = max(2, -(-len(qs) // max(1, _PAIR_SLOTS // (L * L))))
        plan.append((L, qs, chunks, -(-len(qs) // chunks)))
    return plan


def pair_slots(plan) -> int:
    """The pair slots a :func:`bucket_plan` computes an iteration."""
    return int(sum(chunks * cq * L * L for L, _, chunks, cq in plan))


@register("lambdarank", "rank")
class LambdaRank(Objective):
    """LambdaRank with NDCG gains (``rank_objective.hpp:19``).

    TPU-first: the reference's per-query pairwise loops become padded
    tensors over buckets of queries of like length (``layout``): each
    bucket a ``(queries, L)`` table of document indices, labels and
    gains, its pairs an all-pairs (q, i, j) lambda tensor chunked over
    queries to bound memory, with a per-query sort and positional
    discounts.  The tables are the programs' arguments
    (:meth:`Objective.tables`), not their constants.  Sigmoid uses the
    same 2/(1+exp(2*sigma*d)) shape the reference tabulates
    (``rank_objective.hpp:194``).
    """

    row_tensors = None      # a document's lambda reads its query
    table_names = ("_rank_tables",)
    layout = "buckets"

    def __init__(self, config):
        super().__init__(config)
        self.sigmoid = float(config.sigmoid)
        self.norm = bool(config.lambdamart_norm)
        self.max_position = int(config.max_position)
        gains = config.label_gain
        self.label_gain = (np.asarray(gains, np.float64) if gains
                           else default_label_gain())

    def init(self, metadata, num_data):
        from .utils.profiling import timed
        from .utils.telemetry import counters
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            Log.fatal("lambdarank requires query information (set group)")
        lab = np.asarray(metadata.label).astype(np.int64)
        if lab.max() >= len(self.label_gain):
            Log.fatal("label %d exceeds label_gain table size %d",
                      int(lab.max()), len(self.label_gain))
        with timed("rank/layout"):
            self._rank_tables = self._layout(
                np.asarray(metadata.query_boundaries, np.int64), lab,
                num_data)
        counters.set("rank_buckets", len(self._rank_tables["buckets"]))

    def _inv_max_dcg(self, qb: np.ndarray, gains: np.ndarray) -> np.ndarray:
        """1 / the best DCG of each query, truncated at
        ``max_position`` (0 where it is 0)."""
        nq = len(qb) - 1
        qid = np.repeat(np.arange(nq), np.diff(qb))
        order = np.lexsort((-gains, qid))       # by query, gain down
        pos = np.arange(len(qid)) - qb[qid]
        top = pos < self.max_position
        dcg = np.bincount(qid[top], weights=(
            gains[order] / np.log2(pos + 2.0))[top], minlength=nq)
        return np.where(dcg > 0, 1.0 / np.where(dcg > 0, dcg, 1.0), 0.0)

    def _layout(self, qb: np.ndarray, lab: np.ndarray, n: int,
                max_buckets: int = _MAX_BUCKETS) -> Dict:
        """The query tables of :func:`bucket_plan`'s buckets.  A bucket
        of length L: ``idx`` ``(chunks, cq, L)`` (the documents' rows,
        ``n`` on padding), ``lbl`` and ``gain`` beside it (-1 and 0 on
        padding) and ``inv_max`` ``(chunks, cq)``.  ``pos`` ``(n,)`` is
        each row's slot in the buckets' slots laid end to end.  Sets
        ``pair_slots`` (what an iteration computes: sum over buckets of
        chunks x cq x L^2) and ``pairs`` (sum_q L_q^2)."""
        cnts = np.diff(qb)
        gains = self.label_gain[lab]
        inv_max = self._inv_max_dcg(qb, gains)
        lab_pad = np.concatenate([lab, [-1]]).astype(np.int32)
        gains_pad = np.concatenate([gains, [0.0]]).astype(np.float32)
        pos = np.empty(n, np.int32)
        buckets, base = [], 0
        plan = bucket_plan(cnts, max_buckets)
        for L, qs, chunks, cq in plan:
            col = np.arange(L)
            live = col < cnts[qs][:, None]
            idx = np.full((chunks * cq, L), n, np.int32)
            idx[:len(qs)] = np.where(live, qb[qs][:, None] + col, n)
            im = np.zeros(chunks * cq, np.float32)
            im[:len(qs)] = inv_max[qs]
            flat = np.flatnonzero(live.reshape(-1))
            pos[idx[:len(qs)].reshape(-1)[flat]] = base + flat
            buckets.append({
                "idx": jnp.asarray(idx.reshape(chunks, cq, L)),
                "lbl": jnp.asarray(lab_pad[idx].reshape(chunks, cq, L)),
                "gain": jnp.asarray(gains_pad[idx].reshape(chunks, cq, L)),
                "inv_max": jnp.asarray(im.reshape(chunks, cq))})
            base += idx.size
        self.pair_slots = pair_slots(plan)
        self.pairs = int(np.sum(cnts.astype(np.int64) ** 2))
        return {"pos": jnp.asarray(pos), "buckets": tuple(buckets)}

    def get_gradients(self, score):
        # the whole pairwise computation runs as ONE jitted program:
        # eagerly, every (cq, L, L) intermediate of the lambda chain
        # materializes to HBM (tens of GB per iteration at this chip's
        # ~26 GB/s) — fused under jit it stays in registers/VMEM
        return self._jitted_gradients(
            self._grads_impl, (score, self._rank_tables),
            n=int(score.reshape(-1).shape[0]), norm=self.norm,
            sigmoid=self.sigmoid)

    @staticmethod
    def _pairs(sc_pad, n, norm, sigmoid, args):
        """One chunk of a bucket: each slot's lambda and hessian sums
        over its query's pairs."""
        doc_idx, inv_max, lbl, gain = args
        valid = doc_idx < n
        s = sc_pad[doc_idx]                          # (cq, L)
        order = jnp.argsort(-jnp.where(valid, s, -jnp.inf), axis=1,
                            stable=True)
        rank = jnp.argsort(order, axis=1)            # row -> position
        disc = 1.0 / jnp.log2(2.0 + rank.astype(jnp.float32))
        # pairwise (cq, L, L): i = high candidate, j = low
        li = lbl[:, :, None]
        lj = lbl[:, None, :]
        pair_ok = (li > lj) & valid[:, :, None] & valid[:, None, :]
        ds = s[:, :, None] - s[:, None, :]
        dg = gain[:, :, None] - gain[:, None, :]
        dd = jnp.abs(disc[:, :, None] - disc[:, None, :])
        delta = dg * dd * inv_max[:, None, None]
        if norm:
            smax = jnp.max(jnp.where(valid, s, -jnp.inf), axis=1)
            smin = jnp.min(jnp.where(valid, s, jnp.inf), axis=1)
            nz = (smax != smin)[:, None, None]
            delta = jnp.where(nz, delta / (0.01 + jnp.abs(ds)), delta)
        p = 2.0 / (1.0 + jnp.exp(jnp.clip(2.0 * sigmoid * ds, -60.0, 60.0)))
        lam = jnp.where(pair_ok, -delta * p, 0.0)
        hes = jnp.where(pair_ok, 2.0 * delta * p * (2.0 - p), 0.0)
        g_doc = jnp.sum(lam, axis=2) - jnp.sum(lam, axis=1)
        h_doc = jnp.sum(hes, axis=2) + jnp.sum(hes, axis=1)
        return g_doc, h_doc

    @staticmethod
    def _grads_impl(score, tables, weight, *, n, norm, sigmoid, weighted):
        score = score.reshape(-1)
        sc_pad = jnp.concatenate([score, jnp.array([-jnp.inf],
                                                   score.dtype)])
        pairs = functools.partial(LambdaRank._pairs, sc_pad, n, norm,
                                  sigmoid)
        gs, hs = [], []
        for b in tables["buckets"]:
            g, h = jax.lax.map(pairs, (b["idx"], b["inv_max"], b["lbl"],
                                       b["gain"]))
            gs.append(g.reshape(-1))
            hs.append(h.reshape(-1))
        # a document sits in one slot: its row's sums are one gather
        grad = jnp.concatenate(gs)[tables["pos"]]
        hess = jnp.concatenate(hs)[tables["pos"]]
        if weighted:
            grad = grad * weight
            hess = hess * weight
        return grad, hess
