"""Best-split search over per-leaf histograms.

Reference: ``FeatureHistogram::FindBestThreshold`` and helpers
(``src/treelearner/feature_histogram.hpp:84-520``): numerical threshold
scan with missing-value default-direction handling (two scans), L1/L2
regularization (``ThresholdL1:440``), ``max_delta_step`` clipping,
min_data / min_sum_hessian constraints, categorical one-vs-other and
sorted many-vs-many splits.

TPU-first: the per-feature sequential bin scans become vectorized
cumulative sums over the whole (F, B, 3) histogram tensor; the winning
split is materialized as a (B,) boolean "goes-left" mask over bin ids so
row routing is a single gather regardless of split kind.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from .histogram import _compiler_params

EPS = 1e-15
NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class SplitParams:
    """Static (trace-time) split-finding parameters.

    ``monotone``/``penalty`` are per-feature tuples (padded to the
    device feature count); empty means no constraints / all ones.
    Carried here (static) so the common unconstrained case traces with
    zero extra work.
    """
    max_bin: int
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    max_delta_step: float = 0.0
    max_cat_to_onehot: int = 4
    max_cat_threshold: int = 32
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    min_data_per_group: int = 100
    monotone: Tuple[int, ...] = ()   # -1/0/+1 per feature (config.h:357)
    penalty: Tuple[float, ...] = ()  # feature_contri gain multipliers
    # static dataset facts that let the scan drop whole branches at
    # trace time: no categorical feature -> no per-leaf bin sorts, no
    # missing values anywhere -> single-direction threshold scan.
    # Defaults are the conservative "might have them".
    any_cat: bool = True
    any_missing: bool = True
    # the histogram count channel is a HESS COPY, not a real count
    # (two-column quantized passes).  Only legal when
    # min_data_in_leaf <= 1 and min_sum_hessian_in_leaf > 0: a side
    # with hess_sum >= msh > 0 necessarily holds >= 1 row, so the
    # count constraint is implied and never read.
    counts_proxy: bool = False

    @property
    def has_monotone(self) -> bool:
        return bool(self.monotone) and any(self.monotone)

    @property
    def has_penalty(self) -> bool:
        return bool(self.penalty) and any(x != 1.0 for x in self.penalty)


def threshold_l1(s, l1):
    """ThresholdL1 (feature_histogram.hpp:440)."""
    if l1 == 0.0:
        return s
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def leaf_output(g, h, l1, l2, max_delta_step):
    """CalculateSplittedLeafOutput (feature_histogram.hpp:445)."""
    out = -threshold_l1(g, l1) / (h + l2 + EPS)
    if max_delta_step > 0.0:
        out = jnp.clip(out, -max_delta_step, max_delta_step)
    return out


def _gain_given_output(g, h, out, l1, l2):
    """GetLeafSplitGainGivenOutput (feature_histogram.hpp:498)."""
    sg = threshold_l1(g, l1)
    return -(2.0 * sg * out + (h + l2) * out * out)


def leaf_gain(g, h, l1, l2, max_delta_step):
    """GetLeafSplitGain (feature_histogram.hpp:493)."""
    return _gain_given_output(g, h, leaf_output(g, h, l1, l2, max_delta_step),
                              l1, l2)


def _split_gain(gl, hl, gr, hr, l1, l2, mds, mn=None, mx=None, mono=None):
    """GetSplitGains (feature_histogram.hpp:456-465): child outputs are
    clamped to the leaf's inherited [mn, mx] value constraint, and a
    candidate violating the per-feature monotone direction (left output
    above/below right) is discarded."""
    lo = leaf_output(gl, hl, l1, l2, mds)
    ro = leaf_output(gr, hr, l1, l2, mds)
    if mn is not None:
        lo = jnp.clip(lo, mn, mx)
        ro = jnp.clip(ro, mn, mx)
    g = (_gain_given_output(gl, hl, lo, l1, l2) +
         _gain_given_output(gr, hr, ro, l1, l2))
    if mono is not None:
        viol = ((mono > 0) & (lo > ro)) | ((mono < 0) & (lo < ro))
        g = jnp.where(viol, NEG_INF, g)
    return g


def _constraints(L, R, p: SplitParams, min_data_override=None):
    """min_data / min_sum_hessian feasibility of a candidate."""
    if p.counts_proxy:
        # counts channel is a hess copy (see SplitParams.counts_proxy);
        # the gate guarantees the count constraint is implied by the
        # hessian one
        msh = max(p.min_sum_hessian_in_leaf, EPS)
        return (L[..., 1] >= msh) & (R[..., 1] >= msh)
    min_data = p.min_data_in_leaf if min_data_override is None \
        else min_data_override
    return ((L[..., 2] >= max(min_data, 1)) &
            (R[..., 2] >= max(min_data, 1)) &
            (L[..., 1] >= p.min_sum_hessian_in_leaf) &
            (R[..., 1] >= p.min_sum_hessian_in_leaf))


@functools.partial(jax.jit, static_argnames=("params",))
def find_best_split(hist: jax.Array, parent: jax.Array,
                    num_bins: jax.Array, missing_type: jax.Array,
                    is_cat: jax.Array, feature_mask: jax.Array,
                    params: SplitParams, monotone=None, penalty=None,
                    min_output=None, max_output=None):
    """Find the best split for one leaf.

    hist: (F, B, 3) [sum_grad, sum_hess, count]; parent: (3,);
    num_bins/missing_type: (F,) int32; is_cat/feature_mask: (F,) bool.
    monotone: optional (F,) int32 per-feature direction; penalty:
    optional (F,) f32 gain multipliers; min_output/max_output: optional
    scalar leaf-value bounds inherited from monotone ancestors.

    Returns dict(gain, feature, threshold, default_left, is_cat,
    left_mask(B,), left_stats(3,)) — gain is net (minus parent gain and
    min_gain_to_split); <= 0 means "do not split".
    """
    p = params
    F, B, _ = hist.shape
    l1, l2, mds = p.lambda_l1, p.lambda_l2, p.max_delta_step
    mn, mx = min_output, max_output
    parent_gain = leaf_gain(parent[0], parent[1], l1, l2, mds)
    gain_shift = parent_gain + p.min_gain_to_split

    jidx = jnp.arange(B, dtype=jnp.int32)
    if p.any_missing:
        has_missing = missing_type != 0
        nv = num_bins - has_missing.astype(jnp.int32)  # value bins
    else:
        has_missing = jnp.zeros_like(missing_type, dtype=bool)
        nv = num_bins
    in_value = jidx[None, :] < nv[:, None]
    hv = hist * in_value[..., None]
    # missing-bin stats (last bin when feature has a missing bin)
    if p.any_missing:
        miss = jnp.take_along_axis(
            hist, (num_bins - 1)[:, None, None].astype(jnp.int32), axis=1
        )[:, 0, :] * has_missing[:, None]  # (F, 3)
    else:
        miss = jnp.zeros((F, 3), hist.dtype)

    # ---------------- numerical: prefix thresholds, two directions ----
    cum = jnp.cumsum(hv, axis=1)  # (F, B, 3): left side for thr=j
    cand_ok = jidx[None, :] <= nv[:, None] - 2
    if p.any_cat:
        cand_ok = cand_ok & ~is_cat[:, None]

    mono_col = None if monotone is None else monotone[:, None]

    def scan_dir(default_left: bool):
        L = cum + (miss[:, None, :] if default_left else 0.0)
        R = parent[None, None, :] - L
        g = (_split_gain(L[..., 0], L[..., 1] + EPS,
                         R[..., 0], R[..., 1] + EPS, l1, l2, mds,
                         mn, mx, mono_col)
             - gain_shift)
        ok = cand_ok & _constraints(L, R, p)
        return jnp.where(ok, g, NEG_INF), L

    g_r, L_r = scan_dir(False)
    if p.any_missing:
        g_l, L_l = scan_dir(True)
        # when the feature has no missing data both scans coincide;
        # prefer default-right (use_na_as_missing=false) like the
        # reference
        no_miss = miss[:, 2] <= 0
        g_l = jnp.where(no_miss[:, None], NEG_INF, g_l)
        num_gain = jnp.maximum(g_r, g_l)  # (F, B)
        num_dir_left = g_l > g_r
    else:
        L_l = L_r
        num_gain = g_r
        num_dir_left = jnp.zeros_like(g_r, dtype=bool)

    # ---------------- categorical one-vs-other -----------------------
    # bin 0 is the other/unseen catch-all (no real category id) — it can
    # never be in the left set, so train-time routing matches the
    # category-bitset model semantics where unseen goes right
    if not p.any_cat:
        # no categorical features: the numerical scan is the answer
        all_gain = num_gain
        if penalty is not None:
            all_gain = jnp.where(all_gain > 0.5 * NEG_INF,
                                 all_gain * penalty[:, None], all_gain)
        all_gain = jnp.where(feature_mask[:, None], all_gain, NEG_INF)
        best_per_f = jnp.max(all_gain, axis=1)
        best_j = jnp.argmax(all_gain, axis=1).astype(jnp.int32)
        f_star = jnp.argmax(best_per_f).astype(jnp.int32)
        j_star = best_j[f_star]
        dir_left = num_dir_left[f_star, j_star]
        left_stats = jnp.where(dir_left, L_l[f_star, j_star],
                               L_r[f_star, j_star])
        nb_f = num_bins[f_star]
        nv_f = nv[f_star]
        left_mask = (jidx <= j_star) & (jidx < nv_f)
        if p.any_missing:
            left_mask = left_mask | \
                (dir_left & has_missing[f_star] & (jidx == nb_f - 1))
        return {
            "gain": best_per_f[f_star],
            "feature": f_star,
            "threshold": j_star,
            "default_left": dir_left,
            "is_cat": jnp.asarray(False),
            "left_mask": left_mask,
            "left_stats": left_stats,
            "per_feature_gain": best_per_f,
        }

    not_other = jidx[None, :] > 0
    onehot_ok = is_cat[:, None] & (nv <= p.max_cat_to_onehot)[:, None] & \
        in_value & not_other
    Lc = hv  # singleton {k}
    Rc = parent[None, None, :] - Lc
    # categorical splits clamp outputs but carry no monotone direction
    # (feature_histogram.hpp:148 passes monotone 0)
    g_c = (_split_gain(Lc[..., 0], Lc[..., 1] + EPS,
                       Rc[..., 0], Rc[..., 1] + EPS, l1, l2 + p.cat_l2, mds,
                       mn, mx)
           - gain_shift)
    cat1_gain = jnp.where(onehot_ok & _constraints(Lc, Rc, p), g_c, NEG_INF)

    # ---------------- categorical sorted many-vs-many ----------------
    # sort value bins by sum_grad / (sum_hess + cat_smooth); scan prefixes
    # from both ends capped at max_cat_threshold
    # (FindBestThresholdCategorical, feature_histogram.hpp:112)
    cnt_ok = (hv[..., 2] > 0) & not_other
    ratio = jnp.where(cnt_ok & in_value,
                      hv[..., 0] / (hv[..., 1] + p.cat_smooth), jnp.inf)
    order = jnp.argsort(ratio, axis=1)  # invalid bins (inf) sink to end
    sorted_h = jnp.take_along_axis(hv * (cnt_ok & in_value)[..., None],
                                   order[..., None], axis=1)
    n_valid = jnp.sum(cnt_ok & in_value, axis=1)  # (F,)
    cum_s = jnp.cumsum(sorted_h, axis=1)
    many_ok = is_cat[:, None] & (nv > p.max_cat_to_onehot)[:, None]
    rank = jnp.argsort(order, axis=1)  # bin -> position

    def cat_scan(from_low: bool):
        if from_low:
            Ls = cum_s
        else:
            total_s = cum_s[:, -1:, :]
            Ls = total_s - cum_s  # suffix after position j
        if from_low:
            ok = (jidx[None, :] + 1 <= jnp.minimum(
                n_valid - 1, p.max_cat_threshold)[:, None])
        else:
            size = n_valid[:, None] - (jidx[None, :] + 1)
            ok = (size >= 1) & (size <= p.max_cat_threshold) & \
                (jidx[None, :] + 1 < n_valid[:, None])
        Rs = parent[None, None, :] - Ls
        g = (_split_gain(Ls[..., 0], Ls[..., 1] + EPS,
                         Rs[..., 0], Rs[..., 1] + EPS, l1, l2 + p.cat_l2, mds,
                         mn, mx)
             - gain_shift)
        ok = ok & many_ok & _constraints(Ls, Rs, p) & \
            (Ls[..., 2] >= p.min_data_per_group) & \
            (Rs[..., 2] >= p.min_data_per_group)
        return jnp.where(ok, g, NEG_INF), Ls

    gm_lo, L_lo = cat_scan(True)
    gm_hi, L_hi = cat_scan(False)
    many_gain = jnp.maximum(gm_lo, gm_hi)
    many_from_low = gm_lo >= gm_hi

    cat_gain = jnp.maximum(cat1_gain, many_gain)
    cat_is_onehot = cat1_gain >= many_gain

    # ---------------- combine --------------------------------------
    all_gain = jnp.where(is_cat[:, None], cat_gain, num_gain)  # (F, B)
    if penalty is not None:
        # feature_contri: net gain scaled per feature
        # (feature_histogram.hpp:81 ``output->gain *= meta_->penalty``)
        all_gain = jnp.where(all_gain > 0.5 * NEG_INF,
                             all_gain * penalty[:, None], all_gain)
    all_gain = jnp.where(feature_mask[:, None], all_gain, NEG_INF)
    best_per_f = jnp.max(all_gain, axis=1)
    best_j = jnp.argmax(all_gain, axis=1).astype(jnp.int32)
    f_star = jnp.argmax(best_per_f).astype(jnp.int32)
    j_star = best_j[f_star]
    gain = best_per_f[f_star]

    fcat = is_cat[f_star]
    f_onehot = cat_is_onehot[f_star, j_star]
    f_from_low = many_from_low[f_star, j_star]
    dir_left = num_dir_left[f_star, j_star] & ~fcat

    # left stats of the winner
    L_num = jnp.where(dir_left, L_l[f_star, j_star], L_r[f_star, j_star])
    L_cat = jnp.where(f_onehot, hv[f_star, j_star],
                      jnp.where(f_from_low, L_lo[f_star, j_star],
                                L_hi[f_star, j_star]))
    left_stats = jnp.where(fcat, L_cat, L_num)

    # goes-left mask over bin ids
    nb_f = num_bins[f_star]
    miss_bin_mask = has_missing[f_star] & (jidx == nb_f - 1)
    nv_f = nv[f_star]
    num_mask = (jidx <= j_star) & (jidx < nv_f)
    num_mask = num_mask | (dir_left & miss_bin_mask)
    rank_f = rank[f_star]
    many_mask = jnp.where(f_from_low, rank_f <= j_star, rank_f > j_star) & \
        (jidx < nv_f) & cnt_ok[f_star]
    cat_mask = jnp.where(f_onehot, jidx == j_star, many_mask)
    left_mask = jnp.where(fcat, cat_mask, num_mask)

    return {
        "gain": gain,
        "feature": f_star,
        "threshold": j_star,
        "default_left": dir_left,
        "is_cat": fcat,
        "left_mask": left_mask,
        "left_stats": left_stats,
        # per-feature best gains — the voting-parallel learner's ballot
        # (VotingParallelTreeLearner, parallel_tree_learner.h:100-180)
        "per_feature_gain": best_per_f,
    }


# ---- coarse-to-fine split search -----------------------------------
#
# The histogram pass cost is ∝ padded-bin-count (see ops/histogram.py),
# so the split search can run on (a) a COARSE histogram (fine bins
# collapsed 2^shift-to-1) plus (b) a narrow fine WINDOW of r_bins
# around the most promising coarse boundary.  Candidate thresholds are
# the coarse boundaries (exact: a coarse boundary IS a fine threshold)
# plus every fine threshold inside the window (exact: coarse prefix at
# the window start + fine prefix within).  The search is exact whenever
# the best fine threshold falls inside the chosen window; the window
# heuristic (2 coarse bins straddling the best coarse boundary) is
# validated empirically in tests/test_c2f.py and by the bench AUC
# anchor.  Numerical (non-categorical) features only — the plan
# gates it (models/tier.py).  Missing values are supported: the
# per-feature missing bin rides a RESERVED last coarse slot
# (:func:`_c2f_miss`) and both default directions are scanned.


def _c2f_miss(coarse: jax.Array, missing_type: jax.Array,
              params: SplitParams):
    """Missing-bin stats on the c2f path.  With ``params.any_missing``
    the LAST coarse slot is RESERVED for the per-feature missing bin
    (the histogram kernels map ``x == num_bins-1`` there when the
    feature has one); value bins occupy slots [0, Bc-1).  Returns
    (value_slots (F, Bcv, 3), miss (F, 3), no_miss (F,))."""
    if not params.any_missing:
        F = coarse.shape[0]
        return coarse, jnp.zeros((F, 3), coarse.dtype), None
    has = (missing_type != 0)
    miss = coarse[:, -1, :] * has[:, None]
    # "no missing data in this leaf": with counts_proxy the count
    # channel is a hess copy — the same proxy the constraint checks use
    no_miss = miss[:, 2] <= 0
    return coarse[:, :-1, :], miss, no_miss


def _c2f_coarse_scan(coarse: jax.Array, parent: jax.Array,
                     num_bins: jax.Array, params: SplitParams,
                     shift: int, monotone=None, min_output=None,
                     max_output=None, missing_type=None):
    """Gains at the coarse boundaries.  coarse (F, Bc, 3) dequantized
    (last slot = reserved missing bin when ``params.any_missing``);
    returns (gains (F, Bcv), L (F, Bcv, 3), thr_fine (Bcv,),
    dir_left (F, Bcv))."""
    p = params
    l1, l2, mds = p.lambda_l1, p.lambda_l2, p.max_delta_step
    parent_gain = leaf_gain(parent[0], parent[1], l1, l2, mds)
    gain_shift = parent_gain + p.min_gain_to_split
    vals, miss, no_miss = _c2f_miss(coarse, missing_type, p)
    F, Bcv, _ = vals.shape
    cum = jnp.cumsum(vals, axis=1)                    # (F, Bcv, 3)
    thr_fine = ((jnp.arange(Bcv, dtype=jnp.int32) + 1) << shift) - 1
    if p.any_missing:
        nv = num_bins - (missing_type != 0).astype(jnp.int32)
    else:
        nv = num_bins
    ok = thr_fine[None, :] <= nv[:, None] - 2
    mono_col = None if monotone is None else monotone[:, None]

    def scan_dir(default_left: bool):
        L = cum + (miss[:, None, :] if default_left else 0.0)
        R = parent[None, None, :] - L
        g = (_split_gain(L[..., 0], L[..., 1] + EPS,
                         R[..., 0], R[..., 1] + EPS, l1, l2, mds,
                         min_output, max_output, mono_col) - gain_shift)
        return jnp.where(ok & _constraints(L, R, p), g, NEG_INF), L

    g_r, L_r = scan_dir(False)
    if p.any_missing:
        g_l, L_l = scan_dir(True)
        g_l = jnp.where(no_miss[:, None], NEG_INF, g_l)
        g = jnp.maximum(g_r, g_l)
        dir_left = g_l > g_r
        L = jnp.where(dir_left[..., None], L_l, L_r)
    else:
        g, L = g_r, L_r
        dir_left = jnp.zeros_like(g, dtype=bool)
    return g, L, thr_fine, dir_left


def choose_window(coarse: jax.Array, parent: jax.Array,
                  num_bins: jax.Array, params: SplitParams, shift: int,
                  monotone=None, min_output=None, max_output=None,
                  missing_type=None) -> jax.Array:
    """Pick the per-feature refine window start (fine-bin id, coarse-
    aligned): the 2 coarse bins straddling the best coarse boundary."""
    g, _, _, _ = _c2f_coarse_scan(coarse, parent, num_bins, params,
                                  shift, monotone, min_output,
                                  max_output, missing_type)
    Bcv = g.shape[1]
    c_star = jnp.argmax(g, axis=1).astype(jnp.int32)        # (F,)
    win_c = jnp.clip(c_star, 0, max(Bcv - 2, 0))
    return win_c << shift


def _read_at(x, hit, axes):
    """Sum of ``x`` (..., 3) over ``axes`` where ``hit`` (``x``'s
    leading dims) is True: with one True on those axes, the value there
    (with none, -0.0).  Every other term is -0.0, which adds nothing to
    any float, so the read is exact.  It reads ``x`` in the layout its
    producer gave it; a gather at a per-child index makes XLA lay the
    whole wave's ``x`` out again under vmap, the 3 channels minor."""
    return jax.lax.reduce(jnp.where(hit[..., None], x, -0.0),
                          jnp.array(-0.0, x.dtype), jax.lax.add, axes)


@functools.partial(jax.jit, static_argnames=("params", "shift"))
def find_best_split_c2f(coarse: jax.Array, win: jax.Array,
                        win_lo: jax.Array, parent: jax.Array,
                        num_bins: jax.Array, feature_mask: jax.Array,
                        params: SplitParams, shift: int, monotone=None,
                        penalty=None, min_output=None, max_output=None,
                        missing_type=None):
    """Best split from a coarse histogram + fine refine window.

    coarse (F, Bc, 3); win (F, R, 3) fine bins at positions
    [win_lo, win_lo + R); win_lo (F,) int32 coarse-aligned; parent (3,).
    Same record contract as :func:`find_best_split`; numerical splits
    only.  With ``params.any_missing`` the last coarse slot is the
    reserved missing bin (see :func:`_c2f_miss`), the windowed stats
    exclude missing rows, and both default directions are scanned.

    The coarse and the window slots are scanned apart and never joined
    into one (F, Bcv + R) slot axis: the winner comes from the gains
    (a coarse slot wins a tie, as the first of one joined argmax), and
    its threshold, direction and left stats are read at that one slot
    (:func:`_read_at`).  Under a wave's vmap a joined (2W, F, Bcv + R,
    3) left-stats tensor is 147 MB at 2,000 features, laid out again at
    30 GB/s (minor dimension 3) for three floats a child: 9.8 ms a
    wave on the chip, the scan 15.4 ms a wave against 2.95 without it.
    """
    p = params
    F = coarse.shape[0]
    R_w = win.shape[1]
    B = p.max_bin
    l1, l2, mds = p.lambda_l1, p.lambda_l2, p.max_delta_step
    mn, mx = min_output, max_output
    g_c, L_c, thr_c, dirl_c = _c2f_coarse_scan(
        coarse, parent, num_bins, p, shift, monotone, mn, mx,
        missing_type)
    parent_gain = leaf_gain(parent[0], parent[1], l1, l2, mds)
    gain_shift = parent_gain + p.min_gain_to_split
    vals_c, miss, no_miss = _c2f_miss(coarse, missing_type, p)
    if p.any_missing:
        has_missing = missing_type != 0
        nv = num_bins - has_missing.astype(jnp.int32)
    else:
        has_missing = jnp.zeros((F,), bool)
        nv = num_bins

    # fine candidates: exact prefix = coarse prefix before the window
    # (win_lo is coarse-aligned) + fine prefix within the window
    cum_c = jnp.cumsum(vals_c, axis=1)
    win_c0 = (win_lo >> shift).astype(jnp.int32)[:, None]
    before = jnp.arange(cum_c.shape[1]) == win_c0 - 1    # (F, Bcv)
    base = jnp.where(win_c0 > 0, _read_at(cum_c, before, (1,)),
                     0.0)[:, None, :]                    # (F, 1, 3)
    Lf_base = base + jnp.cumsum(win, axis=1)             # (F, R, 3)
    thr_f = win_lo[:, None] + jnp.arange(R_w, dtype=jnp.int32)[None, :]
    ok_f = thr_f <= nv[:, None] - 2
    mono_col = None if monotone is None else monotone[:, None]

    def fine_dir(default_left: bool):
        L_f = Lf_base + (miss[:, None, :] if default_left else 0.0)
        R_side = parent[None, None, :] - L_f
        g = (_split_gain(L_f[..., 0], L_f[..., 1] + EPS,
                         R_side[..., 0], R_side[..., 1] + EPS, l1, l2,
                         mds, mn, mx, mono_col) - gain_shift)
        return jnp.where(ok_f & _constraints(L_f, R_side, p), g,
                         NEG_INF), L_f

    gf_r, Lf_r = fine_dir(False)
    if p.any_missing:
        gf_l, Lf_l = fine_dir(True)
        gf_l = jnp.where(no_miss[:, None], NEG_INF, gf_l)
        g_f = jnp.maximum(gf_r, gf_l)
        dirl_f = gf_l > gf_r
        L_f = jnp.where(dirl_f[..., None], Lf_l, Lf_r)
    else:
        g_f, L_f = gf_r, Lf_r
        dirl_f = jnp.zeros_like(g_f, dtype=bool)

    def masked(g):
        if penalty is not None:
            g = jnp.where(g > 0.5 * NEG_INF, g * penalty[:, None], g)
        return jnp.where(feature_mask[:, None], g, NEG_INF)

    g_c, g_f = masked(g_c), masked(g_f)
    best_c, best_f = jnp.max(g_c, axis=1), jnp.max(g_f, axis=1)
    use_f = best_f > best_c
    best_per_f = jnp.where(use_f, best_f, best_c)
    f_star = jnp.argmax(best_per_f).astype(jnp.int32)
    in_f = use_f[f_star]
    k_c = jnp.argmax(g_c, axis=1).astype(jnp.int32)[f_star]
    k_f = jnp.argmax(g_f, axis=1).astype(jnp.int32)[f_star]
    j_star = jnp.where(in_f, win_lo[f_star] + k_f, thr_c[k_c])
    on_f = jnp.arange(F, dtype=jnp.int32)[:, None] == f_star
    hit_c = on_f & ~in_f & (jnp.arange(g_c.shape[1]) == k_c)
    hit_f = on_f & in_f & (jnp.arange(R_w) == k_f)
    dir_left = jnp.any(hit_c & dirl_c) | jnp.any(hit_f & dirl_f)
    left_stats = (_read_at(L_c, hit_c, (0, 1)) +
                  _read_at(L_f, hit_f, (0, 1)))
    jidx = jnp.arange(B, dtype=jnp.int32)
    nv_f = nv[f_star]
    left_mask = (jidx <= j_star) & (jidx < nv_f)
    if p.any_missing:
        left_mask = left_mask | \
            (dir_left & has_missing[f_star] &
             (jidx == num_bins[f_star] - 1))
    return {
        "gain": best_per_f[f_star],
        "feature": f_star,
        "threshold": j_star,
        "default_left": dir_left,
        "is_cat": jnp.asarray(False),
        "left_mask": left_mask,
        "left_stats": left_stats,
        "per_feature_gain": best_per_f,
    }


# ---- Pallas best-split kernel ---------------------------------------
#
# The XLA split scan above reads the full (leaves x F x B x 3)
# histogram back from HBM after the histogram pass wrote it — a pure
# producer/consumer round-trip (the same memory-bound pairing the GPU
# boosting systems fuse, arXiv:1706.08359 §4, arXiv:1806.11248 §3).
# ``find_best_split_pallas`` runs the NUMERICAL threshold scan on-chip:
# a standalone per-(leaf, feature-tile) kernel over an
# already-materialized histogram (every child of a wave, the root, the
# exact/speculative tiers): grid (leaf-lane, feature-tile), each step
# cumsums its (FC, B) tile in VMEM, evaluates both default directions
# + constraints, and reduces to ONE 16-lane partial row; a tiny
# second-stage argmax over tiles (XLA, O(tiles) work) picks the global
# winner.
#
# Parity contract: numerical features only (the plan gates
# categorical/EFB/c2f/forced to the XLA scan and records why —
# models/tier.py); on identical inputs the same (feature,
# bin, default_left) choice as :func:`find_best_split` with first-max
# tie order (lowest bin within a feature, lowest feature globally),
# and gains within 1e-4 relative (measured worst 5.9e-5).  Never
# bit-equal: the kernel takes its prefix sums as a matmul
# (:func:`_prefix_sum`), each compiler fuses the gain expression its
# own way, and a prefix sum of mixed-sign gradients cancels, so its
# drift is an ulp of the summands.  tests/test_split_kernel.py
# holds the pin in the interpret lane (``pl.pallas_call(...,
# interpret=True)`` on a CPU backend, utils/env.pallas_interpret);
# tools/check_tpu_integration.py holds the choice (same trees as the
# segsum + XLA twin) under Mosaic.

_PART_LANES = 16  # partial-row width: [gain, f_loc, j, dir, Lg, Lh, Lc, pad]


def _prefix_sum(x):
    """Inclusive prefix sum over the last (bin) axis, as a contraction
    with an upper-triangular ones matrix.  Mosaic has no lowering for
    ``cumsum`` (jax 0.9.0: "Unimplemented primitive in Pallas TPU
    lowering for KernelType.TC: cumsum"), and the MXU is idle in the
    scan.  The weights are 0/1 and ``HIGHEST`` splits each f32 term
    into exact bf16 pieces, so every product is exact; only the order
    of the f32 additions differs from a sequential scan — last-ulp
    class, covered by the parity contract above."""
    B = x.shape[-1]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (B, B), 0) <=
           jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
           ).astype(x.dtype)
    out = jax.lax.dot_general(
        x.reshape(-1, B), tri, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=x.dtype)
    return out.reshape(x.shape)


def _scan_tile(g, h, c, nb, mt, fm, mono, pen, pg, ph, pc, gshift,
               mn, mx, p: SplitParams):
    """Shared numerical scan over one feature tile — the expression
    tree of :func:`find_best_split`'s numeric section except for the
    prefix sums (see the parity contract above).

    g/h/c: (..., FC, B) per-channel histograms (dequantized);
    nb/mt: (..., FC, 1) int32; fm: (..., FC, 1) bool; mono: (..., FC,
    1) int32 or None; pen: (..., FC, 1) f32 or None;
    pg/ph/pc/gshift/mn/mx: (..., 1, 1) per-lane scalars (mn/mx None =
    unconstrained).  mono/pen/mn/mx None-ness must mirror the XLA
    call exactly: a neutral-VALUE operand (zeros / ones / ±inf) is
    value-identical but compiles a different expression tree, and the
    extra clip/select ops fuse differently — more drift against
    :func:`find_best_split` for work the static gating saves.
    Returns (masked gain, dir_left, winner-side Lg/Lh/Lc), all
    (..., FC, B).
    """
    l1, l2, mds = p.lambda_l1, p.lambda_l2, p.max_delta_step
    jidx = jax.lax.broadcasted_iota(jnp.int32, g.shape, g.ndim - 1)
    if p.any_missing:
        has_missing = mt != 0
        nv = nb - has_missing.astype(jnp.int32)
    else:
        nv = nb
    in_value = jidx < nv
    gv, hv, cv = g * in_value, h * in_value, c * in_value
    if p.any_missing:
        # miss stats via one-hot contraction (single nonzero term —
        # exact), not a per-feature gather
        moh = ((jidx == nb - 1) & has_missing).astype(g.dtype)
        mg = jnp.sum(g * moh, axis=-1, keepdims=True)
        mh = jnp.sum(h * moh, axis=-1, keepdims=True)
        mc = jnp.sum(c * moh, axis=-1, keepdims=True)
    cum_g, cum_h, cum_c = (_prefix_sum(v) for v in (gv, hv, cv))
    cand_ok = jidx <= nv - 2

    def scan_dir(default_left: bool):
        Lg = cum_g + mg if default_left else cum_g
        Lh = cum_h + mh if default_left else cum_h
        Lc = cum_c + mc if default_left else cum_c
        Rg, Rh, Rc = pg - Lg, ph - Lh, pc - Lc
        gg = _split_gain(Lg, Lh + EPS, Rg, Rh + EPS, l1, l2, mds,
                         mn, mx, mono) - gshift
        if p.counts_proxy:
            msh = max(p.min_sum_hessian_in_leaf, EPS)
            ok = (Lh >= msh) & (Rh >= msh)
        else:
            md = max(p.min_data_in_leaf, 1)
            ok = ((Lc >= md) & (Rc >= md) &
                  (Lh >= p.min_sum_hessian_in_leaf) &
                  (Rh >= p.min_sum_hessian_in_leaf))
        return jnp.where(cand_ok & ok, gg, NEG_INF), Lg, Lh, Lc

    g_r, Lg_r, Lh_r, Lc_r = scan_dir(False)
    if p.any_missing:
        g_l, Lg_l, Lh_l, Lc_l = scan_dir(True)
        no_miss = mc <= 0
        g_l = jnp.where(no_miss, NEG_INF, g_l)
        gain = jnp.maximum(g_r, g_l)
        dirl = g_l > g_r
        Lg_s = jnp.where(dirl, Lg_l, Lg_r)
        Lh_s = jnp.where(dirl, Lh_l, Lh_r)
        Lc_s = jnp.where(dirl, Lc_l, Lc_r)
    else:
        gain, dirl = g_r, jnp.zeros(g_r.shape, bool)
        Lg_s, Lh_s, Lc_s = Lg_r, Lh_r, Lc_r
    if pen is not None:
        gain = jnp.where(gain > 0.5 * NEG_INF, gain * pen, gain)
    gain = jnp.where(fm, gain, NEG_INF)
    return gain, dirl, Lg_s, Lh_s, Lc_s


def _tile_best(gain, dirl, Lg, Lh, Lc):
    """Tile-stage reduction: (..., FC, B) masked gains -> ((..., 16)
    partial row, (..., FC, 1) per-feature bests).  Ties resolve to
    the lowest bin within a feature and the lowest feature in the
    tile — the first-max order of ``jnp.argmax`` in
    :func:`find_best_split` — expressed as where/min reductions
    (Mosaic-friendly; no argmax primitive needed in-kernel)."""
    FC, B = gain.shape[-2:]
    f32 = jnp.float32
    jl = jax.lax.broadcasted_iota(jnp.int32, gain.shape, gain.ndim - 1)
    fio = jax.lax.broadcasted_iota(jnp.int32, gain.shape[:-1] + (1,),
                                   gain.ndim - 2)
    best_pf = jnp.max(gain, axis=-1, keepdims=True)        # (...,FC,1)
    best_j = jnp.min(jnp.where(gain == best_pf, jl, B), axis=-1,
                     keepdims=True)                        # (...,FC,1)
    gmax = jnp.max(best_pf, axis=-2, keepdims=True)        # (...,1,1)
    f_loc = jnp.min(jnp.where(best_pf == gmax, fio, FC), axis=-2,
                    keepdims=True)                         # (...,1,1)
    f_oh = (fio == f_loc).astype(f32)                      # (...,FC,1)
    j_star = jnp.sum(best_j.astype(f32) * f_oh, axis=-2,
                     keepdims=True)                        # (...,1,1)
    win = f_oh * (jl.astype(f32) == j_star)                # (...,FC,B)

    def pick(x):
        # winner extraction by one-hot sum: a single nonzero term, so
        # the reduction is exact for any float value
        s = jnp.sum(x.astype(f32) * win, axis=-1, keepdims=True)
        return jnp.sum(s, axis=-2, keepdims=True)[..., 0]  # (...,1)

    lead = gain.shape[:-2]
    row = jnp.concatenate([
        gmax[..., 0], f_loc.astype(f32)[..., 0], j_star[..., 0],
        pick(dirl), pick(Lg), pick(Lh), pick(Lc),
        jnp.zeros(lead + (_PART_LANES - 7,), f32)], axis=-1)
    return row, best_pf


def split_lane_scalars(parent, params: SplitParams, min_output=None,
                       max_output=None) -> jax.Array:
    """(W, 8) f32 per-lane scalar operand for the split-scan kernel:
    [parent_g, parent_h, parent_c, gain_shift, min_out, max_out, 0, 0].
    Neutral ±inf bounds reproduce the unconstrained XLA scan exactly
    (clip against ±inf is the identity on the finite leaf outputs)."""
    p = params
    parent = jnp.asarray(parent, jnp.float32)
    if parent.ndim == 1:
        parent = parent[None]
    W = parent.shape[0]
    pgain = leaf_gain(parent[:, 0], parent[:, 1], p.lambda_l1,
                      p.lambda_l2, p.max_delta_step)
    gshift = (pgain + p.min_gain_to_split).astype(jnp.float32)
    BIG = jnp.float32(jnp.inf)
    mn = (jnp.full((W,), -BIG, jnp.float32) if min_output is None else
          jnp.broadcast_to(jnp.asarray(min_output, jnp.float32), (W,)))
    mx = (jnp.full((W,), BIG, jnp.float32) if max_output is None else
          jnp.broadcast_to(jnp.asarray(max_output, jnp.float32), (W,)))
    z = jnp.zeros((W,), jnp.float32)
    return jnp.stack([parent[:, 0], parent[:, 1], parent[:, 2],
                      gshift, mn, mx, z, z], axis=-1)


def split_scan_descriptors(num_bins, missing_type, feature_mask,
                           monotone, penalty, f_pad: int):
    """Per-feature descriptor operands padded to the kernel feature
    width, (f_pad, 1) each.  Padded features get nb=1 / fmask=0 so
    they can never win a tile."""
    F = num_bins.shape[0]
    padf = f_pad - F
    nb = jnp.pad(num_bins.astype(jnp.int32), (0, padf),
                 constant_values=1)[:, None]
    mt = jnp.pad(missing_type.astype(jnp.int32), (0, padf))[:, None]
    fm = jnp.pad(feature_mask.astype(jnp.int32), (0, padf))[:, None]
    mono = (jnp.zeros((f_pad, 1), jnp.int32) if monotone is None else
            jnp.pad(monotone.astype(jnp.int32), (0, padf))[:, None])
    pen = (jnp.ones((f_pad, 1), jnp.float32) if penalty is None else
           jnp.pad(penalty.astype(jnp.float32), (0, padf),
                   constant_values=1.0)[:, None])
    return nb, mt, fm, mono, pen


def finish_split_partials(part, fc: int, num_bins, missing_type,
                          params: SplitParams, max_bin: int):
    """Global stage of the two-stage reduction: (W, T, 16) per-tile
    partial rows -> per-lane split records.  O(W*T) XLA work —
    the only part of the scan that is not in-kernel.  First-max
    over tiles preserves the feature-major tie order (tiles are
    contiguous feature ranges)."""
    p = params
    W = part.shape[0]
    ti = jnp.argmax(part[..., 0], axis=1)               # (W,) first max
    row = jnp.take_along_axis(part, ti[:, None, None], axis=1)[:, 0]
    f_star = (ti * fc).astype(jnp.int32) + row[:, 1].astype(jnp.int32)
    j_star = row[:, 2].astype(jnp.int32)
    dir_left = row[:, 3] > 0.5
    jidx = jnp.arange(max_bin, dtype=jnp.int32)
    nb_f = num_bins[f_star]
    if p.any_missing:
        has_m = missing_type[f_star] != 0
        nv_f = nb_f - has_m.astype(jnp.int32)
    else:
        has_m = jnp.zeros((W,), bool)
        nv_f = nb_f
    left_mask = (jidx[None, :] <= j_star[:, None]) & \
        (jidx[None, :] < nv_f[:, None])
    if p.any_missing:
        left_mask = left_mask | \
            (dir_left[:, None] & has_m[:, None] &
             (jidx[None, :] == nb_f[:, None] - 1))
    return {
        "gain": row[:, 0],
        "feature": f_star,
        "threshold": j_star,
        "default_left": dir_left,
        "is_cat": jnp.zeros((W,), bool),
        "left_mask": left_mask,
        "left_stats": row[:, 4:7],
    }


def _split_tile(f: int) -> Tuple[int, int]:
    """(padded feature count, features per kernel tile).  Small
    feature sets run one tile; wide ones chunk at 256 (8-sublane
    aligned) so each grid step's VMEM working set stays bounded and
    the tile partials feed the global reduction."""
    f8 = (f + 7) // 8 * 8
    if f8 <= 256:
        return f8, f8
    return (f + 255) // 256 * 256, 256


def _split_scan_kernel(g_ref, h_ref, c_ref, nb_ref, mt_ref, fm_ref,
                       *rest, params: SplitParams, has_mono: bool,
                       has_pen: bool, has_bounds: bool,
                       with_pfg: bool):
    """One (leaf-lane, feature-tile) grid step of the standalone
    best-split kernel: scan the tile, reduce to one partial row.
    mono/pen operands ride along only when present (the static flags
    keep the traced expression tree identical to the XLA scan's —
    see :func:`_scan_tile`); the per-feature-gain output exists only
    when requested (a pallas output cannot be DCE'd, so an always-on
    (W, F) store would tax every hot-path scan for a value only the
    voting ballots and the parity tests read)."""
    rest = list(rest)
    mono = rest.pop(0)[...][None] if has_mono else None  # (1, FC, 1)
    pen = rest.pop(0)[...][None].astype(jnp.float32) if has_pen \
        else None
    if with_pfg:
        lane_ref, part_ref, pfg_ref = rest
    else:
        lane_ref, part_ref = rest
    g = g_ref[...]                               # (1, FC, B)
    h = h_ref[...]
    c = c_ref[...]
    nb = nb_ref[...][None]                       # (1, FC, 1)
    mt = mt_ref[...][None]
    fm = fm_ref[...][None] > 0
    lane = lane_ref[0]                           # (1, 8)
    pg = lane[:, 0:1][..., None]                 # (1, 1, 1)
    ph = lane[:, 1:2][..., None]
    pc = lane[:, 2:3][..., None]
    gs = lane[:, 3:4][..., None]
    mn = lane[:, 4:5][..., None] if has_bounds else None
    mx = lane[:, 5:6][..., None] if has_bounds else None
    gain, dirl, Lg, Lh, Lc = _scan_tile(g, h, c, nb, mt, fm, mono, pen,
                                        pg, ph, pc, gs, mn, mx, params)
    row, best_pf = _tile_best(gain, dirl, Lg, Lh, Lc)
    part_ref[...] = row[:, None, None, :]        # (1, 1, 1, 16)
    if with_pfg:
        pfg_ref[...] = best_pf                   # (1, FC, 1)


@functools.partial(jax.jit, static_argnames=("params",
                                             "with_per_feature_gain"))
def find_best_split_pallas(hist: jax.Array, parent: jax.Array,
                           num_bins: jax.Array, missing_type: jax.Array,
                           feature_mask: jax.Array, params: SplitParams,
                           monotone=None, penalty=None, min_output=None,
                           max_output=None,
                           with_per_feature_gain: bool = False):
    """Pallas best-split search — the standalone tier of the kernel
    family (see the section comment above).

    hist: (F, B, 3) for one leaf or (W, F, B, 3) for a lane batch
    (the kernel grid runs lanes natively — no vmap); parent: (3,) or
    (W, 3); min_output/max_output: scalar or (W,).  Numerical
    features only (``params.any_cat`` must be False).  Returns the
    :func:`find_best_split` record dict (batched with a leading W dim
    when the input is batched); ``is_cat`` is always False, and
    ``per_feature_gain`` is present only when
    ``with_per_feature_gain`` asks for it (the extra kernel output
    cannot be dead-code-eliminated like the XLA scan's).
    """
    import jax.experimental.pallas as pl
    from ..utils.env import pallas_interpret

    p = params
    assert not p.any_cat, \
        "find_best_split_pallas is numerical-only (driver-gated)"
    batched = hist.ndim == 4
    if not batched:
        hist = hist[None]
        parent = jnp.asarray(parent)[None]
        if min_output is not None:
            min_output = jnp.asarray(min_output)[None]
            max_output = jnp.asarray(max_output)[None]
    W, F, B, _ = hist.shape
    f_pad, fc = _split_tile(F)
    nt = f_pad // fc
    hp = hist.astype(jnp.float32)
    if f_pad != F:
        hp = jnp.pad(hp, ((0, 0), (0, f_pad - F), (0, 0), (0, 0)))
    nb, mt, fm, mono, pen = split_scan_descriptors(
        num_bins, missing_type, feature_mask, monotone, penalty, f_pad)
    lane = split_lane_scalars(parent, p, min_output, max_output)
    has_mono = monotone is not None
    has_pen = penalty is not None
    has_bounds = min_output is not None

    chan_spec = pl.BlockSpec((1, fc, B), lambda w, j: (w, j, 0))
    desc_spec = pl.BlockSpec((fc, 1), lambda w, j: (j, 0))
    in_specs = [chan_spec] * 3 + [desc_spec] * 3
    operands = [hp[..., 0], hp[..., 1], hp[..., 2], nb, mt, fm]
    if has_mono:
        in_specs.append(desc_spec)
        operands.append(mono)
    if has_pen:
        in_specs.append(desc_spec)
        operands.append(pen)
    # per-lane and per-tile rows ride with a unit second-minor dim:
    # Mosaic wants a block's last two dims on the (8, 128) grid or
    # equal to the array's, and a (1, 8) block of a (W, 8) array is
    # neither once W > 1
    in_specs.append(pl.BlockSpec((1, 1, 8), lambda w, j: (w, 0, 0)))
    operands.append(lane[:, None, :])

    out_specs = [pl.BlockSpec((1, 1, 1, _PART_LANES),
                              lambda w, j: (w, j, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((W, nt, 1, _PART_LANES),
                                      jnp.float32)]
    if with_per_feature_gain:
        out_specs.append(pl.BlockSpec((1, fc, 1),
                                      lambda w, j: (w, j, 0)))
        out_shape.append(jax.ShapeDtypeStruct((W, f_pad, 1),
                                              jnp.float32))
    res = pl.pallas_call(
        functools.partial(_split_scan_kernel, params=p,
                          has_mono=has_mono, has_pen=has_pen,
                          has_bounds=has_bounds,
                          with_pfg=with_per_feature_gain),
        grid=(W, nt),                    # (leaf lanes, feature tiles)
        in_specs=in_specs,
        out_specs=out_specs if with_per_feature_gain else out_specs[0],
        out_shape=out_shape if with_per_feature_gain else out_shape[0],
        compiler_params=_compiler_params(),
        interpret=pallas_interpret(),
    )(*operands)

    part = (res[0] if with_per_feature_gain else res)[:, :, 0]
    rec = finish_split_partials(part, fc, num_bins, missing_type, p, B)
    if with_per_feature_gain:
        rec["per_feature_gain"] = res[1][:, :F, 0]
    if not batched:
        rec = {k: v[0] for k, v in rec.items()}
    return rec


def eval_forced_split(hist: jax.Array, parent: jax.Array, feat, thr,
                      num_bins: jax.Array, missing_type: jax.Array,
                      params: SplitParams, monotone=None,
                      min_output=None, max_output=None):
    """Evaluate a NUMERICAL split at a fixed (feature, threshold-bin).

    The forced-splits path (``SerialTreeLearner::ForceSplits``,
    ``serial_tree_learner.cpp:544``; per-threshold stats gathered by
    ``FeatureHistogram::GatherInfoForThreshold``): instead of scanning
    all candidates, gather left/right stats at bin ``thr`` of feature
    ``feat``, choosing the better missing default direction.  Returns
    the same record dict as :func:`find_best_split` plus ``feasible``
    (both children populated and net gain >= 0 — a forced split below
    that aborts forcing, matching the reference's gain<0 erase).
    """
    p = params
    F, B, _ = hist.shape
    l1, l2, mds = p.lambda_l1, p.lambda_l2, p.max_delta_step
    mn, mx = min_output, max_output
    parent_gain = leaf_gain(parent[0], parent[1], l1, l2, mds)
    gain_shift = parent_gain + p.min_gain_to_split

    col = jax.lax.dynamic_index_in_dim(hist, feat, axis=0, keepdims=False)
    nb_f = jax.lax.dynamic_index_in_dim(num_bins, feat, keepdims=False)
    has_miss = jax.lax.dynamic_index_in_dim(
        missing_type, feat, keepdims=False) != 0
    nv_f = nb_f - has_miss.astype(jnp.int32)
    jidx = jnp.arange(B, dtype=jnp.int32)
    in_value = jidx < nv_f
    colv = col * in_value[:, None]
    thr = jnp.clip(thr, 0, B - 1)
    cum = jnp.cumsum(colv, axis=0)
    L_base = cum[thr]
    miss = col[nb_f - 1] * has_miss
    mono_f = None if monotone is None else \
        jax.lax.dynamic_index_in_dim(monotone, feat, keepdims=False)

    def one_dir(default_left: bool):
        L = L_base + (miss if default_left else 0.0)
        R = parent - L
        g = (_split_gain(L[0], L[1] + EPS, R[0], R[1] + EPS,
                         l1, l2, mds, mn, mx, mono_f) - gain_shift)
        ok = (L[2] >= 1) & (R[2] >= 1) & (thr <= nv_f - 2)
        return jnp.where(ok, g, NEG_INF), L

    g_r, L_r = one_dir(False)
    g_l, L_l = one_dir(True)
    no_miss = miss[2] <= 0
    g_l = jnp.where(no_miss, NEG_INF, g_l)
    dir_left = g_l > g_r
    gain = jnp.maximum(g_r, g_l)
    left_stats = jnp.where(dir_left, L_l, L_r)
    miss_bin_mask = has_miss & (jidx == nb_f - 1)
    left_mask = ((jidx <= thr) & (jidx < nv_f)) | (dir_left & miss_bin_mask)
    return {
        "gain": gain,
        "feature": feat,
        "threshold": thr,
        "default_left": dir_left,
        "is_cat": jnp.asarray(False),
        "left_mask": left_mask,
        "left_stats": left_stats,
        "feasible": gain >= 0,
    }
