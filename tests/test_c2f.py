"""Coarse-to-fine histogram refinement (hist_refinement).

The c2f wave replaces each full-resolution histogram pass with a
coarse pass + a narrow windowed refine pass (ops/histogram.py), and the
split search scans coarse boundaries + in-window fine thresholds
(ops/split.py:find_best_split_c2f).  Tests pin:

- the windowed segsum oracle against a brute-force histogram,
- the c2f search against the full-resolution search (never better,
  exact whenever the best threshold falls in the window, and always at
  least the best coarse boundary),
- end-to-end tree self-consistency and quality vs the full-resolution
  wave.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.grow import GrowParams, build_tree
from lightgbm_tpu.ops.histogram import (histogram_segsum_multi,
                                        histogram_segsum_multi_win)
from lightgbm_tpu.ops.split import (choose_window, find_best_split,
                                    find_best_split_c2f, SplitParams)


def test_windowed_segsum_oracle():
    rng = np.random.RandomState(0)
    F, N, W, R = 4, 512, 3, 8
    bins = rng.randint(0, 29, size=(F, N)).astype(np.int32)
    vals = rng.randn(N, 3).astype(np.float32)
    sel = rng.randint(-1, W, size=N).astype(np.int32)
    lo = rng.randint(0, 22, size=(W, F)).astype(np.int32)
    out = np.asarray(histogram_segsum_multi_win(
        jnp.asarray(bins), jnp.asarray(vals), jnp.asarray(sel),
        jnp.asarray(lo), R, W))
    ref = np.zeros((W, F, R, 3), np.float32)
    for n in range(N):
        if sel[n] < 0:
            continue
        for f in range(F):
            r = bins[f, n] - lo[sel[n], f]
            if 0 <= r < R:
                ref[sel[n], f, r] += vals[n]
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)


def test_coarse_shift_segsum():
    rng = np.random.RandomState(1)
    F, N, W = 3, 256, 2
    bins = rng.randint(0, 63, size=(F, N)).astype(np.int32)
    vals = rng.randn(N, 3).astype(np.float32)
    sel = rng.randint(-1, W, size=N).astype(np.int32)
    out = np.asarray(histogram_segsum_multi(
        jnp.asarray(bins), jnp.asarray(vals), jnp.asarray(sel), 8, W,
        shift=3))
    ref = np.asarray(histogram_segsum_multi(
        jnp.asarray(bins >> 3), jnp.asarray(vals), jnp.asarray(sel),
        8, W))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)


def _leaf_case(seed, B=63, F=6, N=4096):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, B, size=(F, N)).astype(np.int32)
    # a planted signal so gains aren't pure noise
    y = (bins[0] > rng.randint(10, 50)).astype(np.float32) + \
        0.2 * rng.randn(N).astype(np.float32)
    grad = (0.5 - y).astype(np.float32)
    hess = np.ones(N, np.float32)
    vals = np.stack([grad, hess, np.ones(N, np.float32)], -1)
    return bins, vals


@pytest.mark.parametrize("seed", range(8))
def test_c2f_vs_full_single_leaf(seed):
    B, F, shift = 63, 6, 3
    R = 2 << shift
    bins, vals = _leaf_case(seed, B=B, F=F)
    sp = SplitParams(max_bin=B, min_data_in_leaf=5, any_cat=False,
                     any_missing=False)
    nb = jnp.full(F, B, jnp.int32)
    fm = jnp.ones(F, bool)
    hist = histogram_segsum_multi(jnp.asarray(bins), jnp.asarray(vals),
                                  jnp.zeros(bins.shape[1], jnp.int32),
                                  B, 1)[0]
    parent = jnp.sum(hist[0], axis=0)
    full = find_best_split(hist, parent, nb,
                           jnp.zeros(F, jnp.int32), jnp.zeros(F, bool),
                           fm, sp)
    coarse = histogram_segsum_multi(
        jnp.asarray(bins), jnp.asarray(vals),
        jnp.zeros(bins.shape[1], jnp.int32), ((B - 1) >> shift) + 1, 1,
        shift=shift)[0]
    lo = choose_window(coarse, parent, nb, sp, shift)
    win = histogram_segsum_multi_win(
        jnp.asarray(bins), jnp.asarray(vals),
        jnp.zeros(bins.shape[1], jnp.int32), lo[None, :], R, 1)[0]
    c2f = find_best_split_c2f(coarse, win, lo, parent, nb, fm, sp, shift)
    g_full, g_c2f = float(full["gain"]), float(c2f["gain"])
    # c2f scans a subset of candidates: never better than full
    assert g_c2f <= g_full + 1e-3 * abs(g_full) + 1e-4
    thr_full = int(full["threshold"])
    f_full = int(full["feature"])
    in_win = int(lo[f_full]) <= thr_full < int(lo[f_full]) + R
    on_boundary = (thr_full + 1) % (1 << shift) == 0
    if in_win or on_boundary:
        # the best fine threshold was scanned -> exact agreement
        assert g_c2f >= g_full - 1e-3 * abs(g_full) - 1e-4
        assert int(c2f["threshold"]) == thr_full
        assert int(c2f["feature"]) == f_full
        np.testing.assert_allclose(np.asarray(c2f["left_stats"]),
                                   np.asarray(full["left_stats"]),
                                   rtol=1e-4, atol=1e-3)


def _tree_data(seed=3, N=8192, F=6, B=63):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, B, size=(F, N)).astype(np.int32)
    logit = (bins[0] / B - 0.5) + 0.7 * (bins[1] > 40) - \
        0.4 * (bins[2] < 9)
    y = (rng.random_sample(N) < 1 / (1 + np.exp(-3 * logit))
         ).astype(np.float32)
    p0 = y.mean()
    grad = (p0 - y).astype(np.float32)
    hess = np.full(N, p0 * (1 - p0), np.float32)
    return bins, grad, hess


@pytest.mark.parametrize("L,W", [(16, 8), (31, 20)])
def test_c2f_tree_self_consistent(L, W):
    bins, grad, hess = _tree_data()
    F, N = bins.shape
    B = 63
    p = GrowParams(split=SplitParams(max_bin=B, min_data_in_leaf=5,
                                     any_cat=False, any_missing=False),
                   num_leaves=L, hist_impl="segsum", wave=True,
                   speculate=W, refine_shift=3)
    rec = build_tree(jnp.asarray(bins), jnp.asarray(grad),
                     jnp.asarray(hess), jnp.ones(N, jnp.float32),
                     jnp.ones(F, bool), jnp.full(F, B, jnp.int32),
                     jnp.zeros(F, jnp.int32), jnp.zeros(F, bool), p)
    li = np.asarray(rec["leaf_idx"])
    ls = np.asarray(rec["leaf_stats"])
    nl = int(rec["n_leaves"])
    assert nl > L // 2
    for leaf in range(nl):
        rows = li == leaf
        assert abs(rows.sum() - ls[leaf, 2]) < 0.5, leaf
        assert abs(grad[rows].sum() - ls[leaf, 0]) < 1e-2, leaf
    valid = np.asarray(rec["valid"])
    k = valid.sum()
    assert valid[:k].all() and not valid[k:].any()


def test_c2f_tree_quality_close_to_full_wave():
    bins, grad, hess = _tree_data(seed=7, N=16384)
    F, N = bins.shape
    B = 63
    out = {}
    for name, shift in (("full", 0), ("c2f", 3)):
        p = GrowParams(split=SplitParams(max_bin=B, min_data_in_leaf=5,
                                         any_cat=False,
                                         any_missing=False),
                       num_leaves=31, hist_impl="segsum", wave=True,
                       speculate=16, refine_shift=shift)
        rec = build_tree(jnp.asarray(bins), jnp.asarray(grad),
                         jnp.asarray(hess), jnp.ones(N, jnp.float32),
                         jnp.ones(F, bool), jnp.full(F, B, jnp.int32),
                         jnp.zeros(F, jnp.int32), jnp.zeros(F, bool), p)
        li = np.asarray(rec["leaf_idx"])
        lv = np.asarray(rec["leaf_values"])
        # squared-error reduction of the fitted tree on grad
        pred = lv[li]
        out[name] = float(np.sum(grad * pred))
    # c2f must realize most of the full-resolution wave's gradient fit
    assert out["c2f"] <= 0
    assert out["full"] <= 0
    assert out["c2f"] <= 0.97 * out["full"], out


@pytest.mark.slow
def test_c2f_engine_auc():
    """End-to-end through the public API with hist_refinement on/off."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(11)
    # F=28: the stream-size gate needs F * padded(max_bin) >= 7000
    N, F = 20000, 28
    X = rng.randn(N, F)
    logit = X[:, 0] + 0.6 * X[:, 1] * X[:, 1] - 0.8 * (X[:, 2] > 0.3)
    y = (rng.random_sample(N) < 1 / (1 + np.exp(-logit))).astype(int)
    Xtr, ytr, Xva, yva = X[:16000], y[:16000], X[16000:], y[16000:]
    aucs = {}
    for ref in (True, False):
        # the stream-size gate needs F * padded(max_bin) >= 7000
        params = {"objective": "binary", "metric": "auc",
                  "num_leaves": 31, "learning_rate": 0.1,
                  "max_bin": 255, "wave_splits": True,
                  "use_quantized_grad": True, "min_data_in_leaf": 1,
                  "hist_refinement": ref, "verbose": -1}
        ds = lgb.Dataset(Xtr, label=ytr)
        vs = ds.create_valid(Xva, label=yva)
        res = {}
        bst = lgb.train(params, ds, num_boost_round=20,
                        valid_sets=[vs], valid_names=["va"],
                        callbacks=[lgb.record_evaluation(res)],
                        verbose_eval=False)
        aucs[ref] = res["va"]["auc"][-1]
    assert aucs[True] > 0.5
    assert abs(aucs[True] - aucs[False]) < 0.01, aucs


# ---- missing-value c2f -------------------------------------------------

def _missing_leaf_case(seed, B=64, F=6, N=4096, miss_frac=0.15):
    """Binned data where each feature's LAST bin is the missing bin."""
    rng = np.random.RandomState(seed)
    nv = B - 1                      # value bins 0..B-2, missing = B-1
    bins = rng.randint(0, nv, size=(F, N)).astype(np.int32)
    miss = rng.random_sample((F, N)) < miss_frac
    bins[miss] = B - 1
    y = (bins[0] > rng.randint(10, 50)).astype(np.float32) + \
        0.3 * miss[0] + 0.2 * rng.randn(N).astype(np.float32)
    grad = (0.5 - y).astype(np.float32)
    hess = np.ones(N, np.float32)
    vals = np.stack([grad, hess, np.ones(N, np.float32)], -1)
    return bins, vals


@pytest.mark.parametrize("seed", range(6))
def test_c2f_missing_vs_full_single_leaf(seed):
    """c2f with the reserved missing coarse slot must agree with the
    full-resolution scan (threshold, direction, stats) whenever the
    best fine threshold lands in the window or on a boundary."""
    B, F, shift = 64, 6, 3
    R = 2 << shift
    bins, vals = _missing_leaf_case(seed, B=B, F=F)
    sp = SplitParams(max_bin=B, min_data_in_leaf=5, any_cat=False,
                     any_missing=True)
    nb = jnp.full(F, B, jnp.int32)
    mt = jnp.full(F, 1, jnp.int32)          # MissingType NaN
    mb = nb - 1
    fm = jnp.ones(F, bool)
    zsel = jnp.zeros(bins.shape[1], jnp.int32)
    hist = histogram_segsum_multi(jnp.asarray(bins), jnp.asarray(vals),
                                  zsel, B, 1)[0]
    parent = jnp.sum(hist[0], axis=0)
    full = find_best_split(hist, parent, nb, mt,
                           jnp.zeros(F, bool), fm, sp)
    Bc = ((B - 1) >> shift) + 2             # +1 reserved missing slot
    coarse = histogram_segsum_multi(
        jnp.asarray(bins), jnp.asarray(vals), zsel, Bc, 1,
        shift=shift, miss_bin=mb)[0]
    # reserved slot must hold exactly the missing-bin stats
    np.testing.assert_allclose(np.asarray(coarse[:, -1]),
                               np.asarray(hist[:, B - 1]),
                               rtol=1e-5, atol=1e-4)
    lo = choose_window(coarse, parent, nb, sp, shift, missing_type=mt)
    win = histogram_segsum_multi_win(
        jnp.asarray(bins), jnp.asarray(vals), zsel, lo[None, :], R, 1,
        miss_bin=mb)[0]
    c2f = find_best_split_c2f(coarse, win, lo, parent, nb, fm, sp,
                              shift, missing_type=mt)
    g_full, g_c2f = float(full["gain"]), float(c2f["gain"])
    assert g_c2f <= g_full + 1e-3 * abs(g_full) + 1e-4
    thr_full = int(full["threshold"])
    f_full = int(full["feature"])
    in_win = int(lo[f_full]) <= thr_full < int(lo[f_full]) + R
    on_boundary = (thr_full + 1) % (1 << shift) == 0
    if in_win or on_boundary:
        assert g_c2f >= g_full - 1e-3 * abs(g_full) - 1e-4
        assert int(c2f["threshold"]) == thr_full
        assert int(c2f["feature"]) == f_full
        assert bool(c2f["default_left"]) == bool(full["default_left"])
        np.testing.assert_allclose(np.asarray(c2f["left_stats"]),
                                   np.asarray(full["left_stats"]),
                                   rtol=1e-4, atol=1e-3)
        np.testing.assert_array_equal(np.asarray(c2f["left_mask"]),
                                      np.asarray(full["left_mask"]))


@pytest.mark.slow
def test_c2f_engine_auc_with_missing():
    """End-to-end: NaN-laden data runs the wave + quantized + c2f fast
    tiers (no exact-tier fallback) at quality parity with the
    full-resolution exact scan."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(23)
    N, F = 20000, 28
    X = rng.randn(N, F)
    logit = X[:, 0] + 0.6 * X[:, 1] * X[:, 1] - 0.8 * (X[:, 2] > 0.3)
    y = (rng.random_sample(N) < 1 / (1 + np.exp(-logit))).astype(int)
    X[rng.random_sample((N, F)) < 0.1] = np.nan     # 10% missing
    Xtr, ytr, Xva, yva = X[:16000], y[:16000], X[16000:], y[16000:]
    aucs = {}
    for fast in (True, False):
        params = {"objective": "binary", "metric": "auc",
                  "num_leaves": 31, "learning_rate": 0.1,
                  "max_bin": 255, "wave_splits": fast,
                  "use_quantized_grad": fast, "min_data_in_leaf": 1,
                  "hist_refinement": fast, "verbose": -1}
        ds = lgb.Dataset(Xtr, label=ytr)
        vs = ds.create_valid(Xva, label=yva)
        res = {}
        bst = lgb.train(params, ds, num_boost_round=20,
                        valid_sets=[vs], valid_names=["va"],
                        callbacks=[lgb.record_evaluation(res)],
                        verbose_eval=False)
        aucs[fast] = res["va"]["auc"][-1]
        if fast:
            gp = bst._gbdt.grow_params
            assert gp.wave and gp.quantize > 0
            assert gp.refine_shift > 0, \
                "c2f must stay ON with missing values"
            assert gp.two_col, "two_col must stay ON with missing"
            assert gp.split.any_missing
    assert aucs[True] > 0.5
    assert abs(aucs[True] - aucs[False]) < 0.015, aucs


# ---- the scan's winner read at one slot (ISSUE 36) ---------------------

def _c2f_oracle(coarse, win, win_lo, parent, num_bins, feature_mask,
                params, shift, monotone=None, penalty=None,
                min_output=None, max_output=None, missing_type=None):
    """The scan as it stood before PR 36: coarse and window slots
    joined into one (F, Bcv + R) axis, the winner gathered from it.
    Also returns the joined gains (``_all_gain``) for the tie checks."""
    from lightgbm_tpu.ops.split import (EPS, NEG_INF, _c2f_coarse_scan,
                                        _c2f_miss, _constraints,
                                        _split_gain, leaf_gain)
    p = params
    F = coarse.shape[0]
    R_w = win.shape[1]
    B = p.max_bin
    l1, l2, mds = p.lambda_l1, p.lambda_l2, p.max_delta_step
    mn, mx = min_output, max_output
    g_c, L_c, thr_c, dirl_c = _c2f_coarse_scan(
        coarse, parent, num_bins, p, shift, monotone, mn, mx,
        missing_type)
    Bcv = g_c.shape[1]
    parent_gain = leaf_gain(parent[0], parent[1], l1, l2, mds)
    gain_shift = parent_gain + p.min_gain_to_split
    vals_c, miss, no_miss = _c2f_miss(coarse, missing_type, p)
    if p.any_missing:
        has_missing = missing_type != 0
        nv = num_bins - has_missing.astype(jnp.int32)
    else:
        has_missing = jnp.zeros((F,), bool)
        nv = num_bins
    cum_c = jnp.cumsum(vals_c, axis=1)
    cpad = jnp.concatenate([jnp.zeros((F, 1, 3), coarse.dtype), cum_c],
                           axis=1)
    win_c0 = (win_lo >> shift).astype(jnp.int32)
    base = jnp.take_along_axis(cpad, win_c0[:, None, None], axis=1)
    Lf_base = base + jnp.cumsum(win, axis=1)
    thr_f = win_lo[:, None] + jnp.arange(R_w, dtype=jnp.int32)[None, :]
    ok_f = thr_f <= nv[:, None] - 2
    mono_col = None if monotone is None else monotone[:, None]

    def fine_dir(default_left):
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
    all_gain = jnp.concatenate([g_c, g_f], axis=1)
    all_thr = jnp.concatenate(
        [jnp.broadcast_to(thr_c[None, :], (F, Bcv)), thr_f], axis=1)
    all_L = jnp.concatenate([L_c, L_f], axis=1)
    all_dirl = jnp.concatenate([dirl_c, dirl_f], axis=1)
    if penalty is not None:
        all_gain = jnp.where(all_gain > 0.5 * NEG_INF,
                             all_gain * penalty[:, None], all_gain)
    all_gain = jnp.where(feature_mask[:, None], all_gain, NEG_INF)
    best_per_f = jnp.max(all_gain, axis=1)
    best_k = jnp.argmax(all_gain, axis=1).astype(jnp.int32)
    f_star = jnp.argmax(best_per_f).astype(jnp.int32)
    k_star = best_k[f_star]
    j_star = all_thr[f_star, k_star]
    dir_left = all_dirl[f_star, k_star]
    jidx = jnp.arange(B, dtype=jnp.int32)
    left_mask = (jidx <= j_star) & (jidx < nv[f_star])
    if p.any_missing:
        left_mask = left_mask | \
            (dir_left & has_missing[f_star] &
             (jidx == num_bins[f_star] - 1))
    return {"gain": best_per_f[f_star], "feature": f_star,
            "threshold": j_star, "default_left": dir_left,
            "is_cat": jnp.asarray(False), "left_mask": left_mask,
            "left_stats": all_L[f_star, k_star],
            "per_feature_gain": best_per_f, "_all_gain": all_gain}


_SCAN_KEYS = ("gain", "feature", "threshold", "default_left", "is_cat",
              "left_mask", "left_stats", "per_feature_gain")


def _children_case(case, W=8, F=7, B=64, shift=3, N=6000, seed=11):
    """Histograms of W children: (coarse, win, win_lo, parent) stacked
    over the children, plus the scan's per-feature arguments."""
    import jax
    rng = np.random.RandomState(seed)
    missing = case == "missing"
    nv = B - 1 if missing else B
    bins = rng.randint(0, nv, size=(F, N)).astype(np.int32)
    if missing:
        # features 0..3 hold NaNs (the last bin); the rest none at all
        bins[:4][rng.random_sample((4, N)) < 0.15] = B - 1
    child = rng.randint(0, W, size=N).astype(np.int32)
    # each child its own signal: a feature and a threshold of its own
    c = np.arange(W)
    f_c, t_c = c % 3, 8 + 5 * c
    if case in ("tie_coarse_window", "tie_features"):
        # integer gradients: every prefix sum is exact, so a coarse
        # boundary inside the window and the window threshold at the
        # same bin give bit-equal gains; the signal sits on a boundary
        if case == "tie_features":
            bins[3] = bins[0]                 # an identical feature
            f_c = 0 * c
        t_c = ((2 + c % 5) << shift) - 1
    x = bins[f_c[child], np.arange(N)]
    step = np.where(x > t_c[child], 1.0, 0.0)
    if case in ("tie_coarse_window", "tie_features"):
        grad = 4.0 * step - 2.0 + rng.randint(-1, 2, N)
        hess = np.ones(N)
    else:
        y = step + 0.5 * (bins[3] < 20) + 0.3 * rng.randn(N)
        if missing:                           # NaNs lean by the child
            y += np.where(x == B - 1, np.where(child % 2, 0.9, -0.9), 0)
        grad, hess = 0.5 - y, 0.25 + 0.5 * rng.random_sample(N)
    vals = np.stack([grad, hess, np.ones(N)], -1).astype(np.float32)
    sp = SplitParams(max_bin=B, min_data_in_leaf=5, any_cat=False,
                     any_missing=missing)
    nb = jnp.full(F, B, jnp.int32)
    mt = jnp.asarray([1] * 4 + [0] * (F - 4), jnp.int32) if missing \
        else None
    mb = jnp.where(mt != 0, nb - 1, -1) if missing else None
    Bc = ((B - 1) >> shift) + (2 if missing else 1)
    R = 2 << shift
    coarse = histogram_segsum_multi(jnp.asarray(bins), jnp.asarray(vals),
                                    jnp.asarray(child), Bc, W,
                                    shift=shift, miss_bin=mb)
    parent = jnp.sum(histogram_segsum_multi(
        jnp.asarray(bins), jnp.asarray(vals), jnp.asarray(child), B, W,
    )[:, 0], axis=1)                                    # (W, 3)
    kw = dict(missing_type=mt)
    fm = jnp.ones(F, bool)
    mn = mx = None
    if case == "monotone":
        kw["monotone"] = jnp.asarray([1, -1, 0, 1, 0, -1, 0][:F],
                                     jnp.int32)
        mn = jnp.linspace(-2.0, -0.1, W).astype(jnp.float32)
        mx = jnp.linspace(0.3, 2.0, W).astype(jnp.float32)
    if case == "penalty":
        kw["penalty"] = jnp.asarray(rng.uniform(0.3, 1.5, F), jnp.float32)
    if case == "feature_mask":
        fm = jnp.asarray([False, True, True, False, True, True, False][:F])
    if mn is None:
        lo = jax.vmap(lambda c, s: choose_window(
            c, s, nb, sp, shift, missing_type=mt))(coarse, parent)
    else:
        lo = jax.vmap(lambda c, s, a, b: choose_window(
            c, s, nb, sp, shift, kw["monotone"], a, b,
            missing_type=mt))(coarse, parent, mn, mx)
    win = histogram_segsum_multi_win(jnp.asarray(bins), jnp.asarray(vals),
                                     jnp.asarray(child), lo, R, W,
                                     miss_bin=mb)
    return (coarse, win, lo, parent, mn, mx), (nb, fm, sp, shift, kw)


@pytest.mark.parametrize("case", ["plain", "missing", "monotone",
                                  "penalty", "feature_mask",
                                  "tie_coarse_window", "tie_features"])
def test_c2f_scan_equals_joined_slot_oracle(case):
    """The scan that picks its winner from the gains and reads the
    stats at one slot gives today's record bit for bit (CPU), under the
    wave's vmap over children, the planted ties included.  Op by op:
    under one jit XLA:CPU contracts the gains' multiply-adds as each
    program's fusions fall, so two programs with the same gain
    expression can round a gain apart (a coarse boundary and the window
    threshold at the same bin, 0.66856194 against 0.66856146 here)."""
    import jax
    (coarse, win, lo, parent, mn, mx), (nb, fm, sp, shift, kw) = \
        _children_case(case)

    def run(fn):
        def one(c, wh, l, s, a, b):
            return fn(c, wh, l, s, nb, fm, sp, shift, min_output=a,
                      max_output=b, **kw)
        with jax.disable_jit():
            if mn is None:
                return jax.vmap(
                    lambda c, wh, l, s: one(c, wh, l, s, None, None))(
                        coarse, win, lo, parent)
            return jax.vmap(one)(coarse, win, lo, parent, mn, mx)

    got, want = run(find_best_split_c2f), run(_c2f_oracle)
    for k in _SCAN_KEYS:
        g, w = np.asarray(got[k]), np.asarray(want[k])
        assert g.dtype == w.dtype and g.shape == w.shape, k
        assert g.tobytes() == w.tobytes(), (k, g, w)
    assert (np.asarray(want["gain"]) > 0).sum() >= 6   # real splits
    ag = np.asarray(want["_all_gain"])                  # (W, F, Bcv + R)
    f = np.asarray(want["feature"])
    Bcv = ag.shape[2] - win.shape[2]
    at_f = ag[np.arange(len(f)), f]                     # (W, Bcv + R)
    if case == "tie_coarse_window":
        # the winner is a coarse slot that a window slot ties exactly
        coarse_best, win_best = at_f[:, :Bcv].max(1), at_f[:, Bcv:].max(1)
        assert (coarse_best == win_best).all()
        assert ((np.asarray(want["threshold"]) + 1) % (1 << shift)
                == 0).all()
    if case == "tie_features":
        assert (ag[:, 0] == ag[:, 3]).all() and (f == 0).all()


@pytest.mark.parametrize("scan,joined", [("find_best_split_c2f", False),
                                         ("oracle", True)])
def test_c2f_scan_builds_no_joined_slot_axis(scan, joined):
    """Compiled under a wave's vmap at (2W=128, F=200, Bc=16, R=32), the
    scan holds no array with a Bc + R = 48 slot axis: not the joined
    left stats (147 MB a wave at 2,000 features, a 9.8 ms layout copy
    on the chip), thresholds or gains.  The oracle, which joins them,
    shows the check can see one."""
    import re
    import jax
    W2, F, Bc, R, B, shift = 128, 200, 16, 32, 255, 4
    fn = find_best_split_c2f if scan == "find_best_split_c2f" \
        else _c2f_oracle
    sp = SplitParams(max_bin=B, min_data_in_leaf=1,
                     min_sum_hessian_in_leaf=100.0, any_cat=False,
                     any_missing=False, counts_proxy=True)
    nb = jnp.full(F, B, jnp.int32)
    fm = jnp.ones(F, bool)
    f32 = jnp.float32
    hlo = jax.jit(jax.vmap(
        lambda c, wh, lo, s: fn(c, wh, lo, s, nb, fm, sp, shift))).lower(
        jax.ShapeDtypeStruct((W2, F, Bc, 3), f32),
        jax.ShapeDtypeStruct((W2, F, R, 3), f32),
        jax.ShapeDtypeStruct((W2, F), jnp.int32),
        jax.ShapeDtypeStruct((W2, 3), f32)).compile().as_text()
    shapes = re.findall(r"\b[a-z]+\d*\[([\d,]+)\]", hlo)
    assert shapes
    with_joined = [s for s in shapes
                   if str(Bc + R) in s.split(",")]
    assert bool(with_joined) == joined, with_joined[:5]
