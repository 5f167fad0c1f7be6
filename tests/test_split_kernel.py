"""split_kernel=pallas parity: the Pallas best-split kernel.

The kernel (``ops/split.py``: ``find_best_split_pallas``) must
select the SAME splits as the XLA scan ``find_best_split`` — same
(feature, bin, default_left) under first-max tie order, same
left_mask — with gains within ``GAIN_RTOL``.  The kernel takes its
prefix sums as a triangular matmul (Mosaic has no cumsum) and XLA
fuses the clip and the gain differently, so the f32 additions run in
another order, and a prefix sum of mixed-sign gradients cancels: its
last-ulp drift is an ulp of the summands, not of the sum, and the gain
squares it.  Measured on the kernel-level matrix below over 60 data
seeds a case: worst 5.3e-5 relative, no choice flipped (5.9e-5 seen
once under the per-process ``hash()`` seed this file used to draw; a
single seed shows ~6e-6, which is not the bound).  In a grown tree a
low-gain node also drifts by ulps of its TERMS, not of the gain
(measured: 7.1e-5 relative on a gain of 0.43 in a tree whose root
gain is 322), so the tree-level pin adds ``GAIN_ULPS`` ulps of the
tree's total gain, which bounds every node's terms.  Model parity is
stated by ``_assert_same_model``.  On the CPU backend these tests
force, every kernel runs under ``pl.pallas_call(...,
interpret=True)`` (utils/env.pallas_interpret); on the chip
``tools/check_tpu_integration.py`` holds the choice (same trees as
the segsum + XLA twin).
"""
import json
import zlib

import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.split import (SplitParams, find_best_split,
                                    find_best_split_pallas)

# written tolerances (the choice itself is always exact): see the
# module docstring for where the drift comes from and what was measured
GAIN_RTOL = 1e-4
GAIN_ULPS = 4        # of the tree's total gain, f32
LEAF_RTOL, LEAF_ATOL = 1e-4, 1e-6


def _rand_hist(rng, F, B, nb, n_rows=500):
    hist = np.zeros((F, B, 3), np.float32)
    for f in range(F):
        bins = rng.randint(0, nb[f], size=n_rows)
        g = rng.randn(n_rows).astype(np.float32)
        h = (np.abs(rng.randn(n_rows)) + 0.1).astype(np.float32)
        for b_, g_, h_ in zip(bins, g, h):
            hist[f, b_] += [g_, h_, 1.0]
    return hist


def _assert_same_record(a, b, ctx=""):
    for k in ("feature", "threshold", "default_left"):
        assert int(a[k]) == int(b[k]), (ctx, k, a[k], b[k])
    np.testing.assert_array_equal(np.asarray(a["left_mask"]),
                                  np.asarray(b["left_mask"]), ctx)
    np.testing.assert_allclose(float(a["gain"]), float(b["gain"]),
                               rtol=GAIN_RTOL, err_msg=ctx)
    np.testing.assert_allclose(np.asarray(a["left_stats"]),
                               np.asarray(b["left_stats"]),
                               rtol=1e-5, atol=1e-4, err_msg=ctx)


# ---- kernel-level parity matrix -------------------------------------
# {numerical, missing variants, monotone, min_data / min_hessian} — the
# ISSUE-12 satellite matrix; every case pins identical choice + mask.

CASES = [
    # (name, any_missing, miss_rate, monotone, min_data, min_hess, pen)
    ("numerical", False, 0.0, False, 1, 1e-3, False),
    ("missing", True, 0.1, False, 1, 1e-3, False),
    ("missing_dense", True, 0.45, False, 1, 1e-3, False),
    ("missing_none_present", True, 0.0, False, 1, 1e-3, False),
    ("monotone", True, 0.1, True, 1, 1e-3, False),
    ("monotone_nomiss", False, 0.0, True, 1, 1e-3, False),
    ("min_data", True, 0.1, False, 40, 1e-3, False),
    ("min_hessian", True, 0.1, False, 1, 2.0, False),
    ("penalty", False, 0.0, False, 1, 1e-3, True),
    ("kitchen_sink", True, 0.15, True, 25, 0.5, True),
]


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_kernel_parity_matrix(case):
    name, any_missing, miss_rate, mono_on, md, msh, pen_on = case
    # (not hash(name): str hashes are drawn per process)
    rng = np.random.RandomState(zlib.crc32(name.encode()) & 0xFFFF)
    F, B = 7, 16
    nb = rng.randint(6, B + 1, size=F).astype(np.int32)
    mt = (np.ones(F, np.int32) * 2 if any_missing
          else np.zeros(F, np.int32))
    hist = np.zeros((F, B, 3), np.float32)
    for f in range(F):
        n_rows = 400
        n_miss = int(n_rows * miss_rate)
        bins = rng.randint(0, nb[f] - (1 if any_missing else 0),
                           size=n_rows)
        if any_missing and n_miss:
            bins[:n_miss] = nb[f] - 1  # the reserved missing bin
        g = rng.randn(n_rows).astype(np.float32)
        h = (np.abs(rng.randn(n_rows)) + 0.1).astype(np.float32)
        for b_, g_, h_ in zip(bins, g, h):
            hist[f, b_] += [g_, h_, 1.0]
    parent = hist[0].sum(axis=0)
    mono_t = tuple(rng.randint(-1, 2, F).tolist()) if mono_on else ()
    pen_t = tuple((0.5 + rng.random_sample(F)).tolist()) if pen_on \
        else ()
    p = SplitParams(max_bin=B, min_data_in_leaf=md,
                    min_sum_hessian_in_leaf=msh, monotone=mono_t,
                    penalty=pen_t, any_cat=False,
                    any_missing=any_missing)
    mono = jnp.asarray(mono_t, jnp.int32) if mono_on else None
    pen = jnp.asarray(pen_t, jnp.float32) if pen_on else None
    mn = jnp.float32(-np.inf) if mono_on else None
    mx = jnp.float32(np.inf) if mono_on else None
    fm = jnp.ones(F, bool)
    a = find_best_split(jnp.asarray(hist), jnp.asarray(parent),
                        jnp.asarray(nb), jnp.asarray(mt),
                        jnp.zeros(F, bool), fm, p, monotone=mono,
                        penalty=pen, min_output=mn, max_output=mx)
    b = find_best_split_pallas(jnp.asarray(hist), jnp.asarray(parent),
                               jnp.asarray(nb), jnp.asarray(mt), fm, p,
                               monotone=mono, penalty=pen,
                               min_output=mn, max_output=mx,
                               with_per_feature_gain=True)
    _assert_same_record(a, b, name)
    np.testing.assert_allclose(np.asarray(a["per_feature_gain"]),
                               np.asarray(b["per_feature_gain"]),
                               rtol=GAIN_RTOL, err_msg=name)


def test_kernel_feature_mask_and_tile_chunking():
    """feature_fraction masks + a feature count that spans several
    kernel tiles (F > 256 chunks at 256) keep the first-max tie order
    of the XLA argmax."""
    rng = np.random.RandomState(7)
    F, B = 260, 8          # forces 2 feature tiles (256 + pad)
    nb = np.full(F, B, np.int32)
    mt = np.zeros(F, np.int32)
    # duplicate feature blocks -> guaranteed cross-tile gain TIES; the
    # winner must still be the lowest feature id (first-max order)
    base = _rand_hist(rng, 4, B, nb[:4])
    hist = np.tile(base, (65, 1, 1))[:F]
    parent = base[0].sum(axis=0)
    p = SplitParams(max_bin=B, min_data_in_leaf=1, any_cat=False,
                    any_missing=False)
    fmask = rng.random_sample(F) > 0.3
    fmask[:8] = True
    a = find_best_split(jnp.asarray(hist), jnp.asarray(parent),
                        jnp.asarray(nb), jnp.asarray(mt),
                        jnp.zeros(F, bool), jnp.asarray(fmask), p)
    b = find_best_split_pallas(jnp.asarray(hist), jnp.asarray(parent),
                               jnp.asarray(nb), jnp.asarray(mt),
                               jnp.asarray(fmask), p)
    _assert_same_record(a, b, "tiled")


def test_kernel_batched_lanes():
    """(W, F, B, 3) lane batches run natively on the kernel grid and
    match per-lane XLA scans."""
    rng = np.random.RandomState(11)
    F, B, W = 6, 16, 5
    nb = rng.randint(6, B + 1, size=F).astype(np.int32)
    mt = np.ones(F, np.int32) * 2
    hists, parents = [], []
    for w in range(W):
        h = _rand_hist(rng, F, B, nb)
        hists.append(h)
        parents.append(h[0].sum(axis=0))
    hists, parents = np.stack(hists), np.stack(parents)
    # lane 3: a dead lane (all-zero histogram, zero parent) — gains
    # must come back NEG_INF-masked, not NaN
    hists[3] = 0.0
    parents[3] = 0.0
    p = SplitParams(max_bin=B, min_data_in_leaf=5, any_cat=False,
                    any_missing=True)
    fm = jnp.ones(F, bool)
    batch = find_best_split_pallas(jnp.asarray(hists),
                                   jnp.asarray(parents),
                                   jnp.asarray(nb), jnp.asarray(mt),
                                   fm, p)
    for w in range(W):
        a = find_best_split(jnp.asarray(hists[w]),
                            jnp.asarray(parents[w]), jnp.asarray(nb),
                            jnp.asarray(mt), jnp.zeros(F, bool), fm, p)
        one = {k: v[w] for k, v in batch.items()}
        _assert_same_record(a, one, f"lane{w}")
    assert float(batch["gain"][3]) < 0  # dead lane never splits
    assert np.isfinite(np.asarray(batch["left_stats"])).all()


# ---- build_tree wave parity (standalone kernel for every child) ----

@pytest.mark.parametrize("hist_impl", ["segsum", "pallas"])
@pytest.mark.parametrize("with_missing", [False, True])
def test_build_tree_wave_parity(hist_impl, with_missing):
    """Wave growth with split_kernel=pallas (the standalone kernel for
    all children, on either histogram tier) grows the same tree as the
    XLA scan, leaf values within LEAF_RTOL."""
    from lightgbm_tpu.ops.grow import GrowParams, build_tree
    rng = np.random.RandomState(1)
    N, F = 2048, 6
    bins = rng.randint(0, 13, size=(F, N)).astype(np.uint8)
    nbins = np.full(F, 14, np.int32)
    mt = np.zeros(F, np.int32)
    if with_missing:
        bins[rng.random_sample((F, N)) < 0.1] = 13
        mt[:] = 2
    grad = rng.randn(N).astype(np.float32)
    hess = np.ones(N, np.float32)
    args = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.ones(N, jnp.float32), jnp.ones(F, bool),
            jnp.asarray(nbins), jnp.asarray(mt), jnp.zeros(F, bool))
    sp = SplitParams(max_bin=16, min_data_in_leaf=5, any_cat=False,
                     any_missing=with_missing)
    recs = {}
    for sk in ("xla", "pallas"):
        p = GrowParams(split=sp, num_leaves=15, hist_impl=hist_impl,
                       rows_per_block=1024, wave=True, speculate=8,
                       split_kernel=sk)
        recs[sk] = {k: np.asarray(v) for k, v in
                    build_tree(*args, p).items()}
    a, b = recs["xla"], recs["pallas"]
    for k in ("leaf", "feature", "threshold", "default_left", "valid",
              "left_mask", "leaf_idx", "n_leaves"):
        np.testing.assert_array_equal(a[k], b[k], k)
    np.testing.assert_allclose(a["leaf_values"], b["leaf_values"],
                               rtol=LEAF_RTOL, atol=LEAF_ATOL)


def test_build_tree_exact_tier_parity():
    """The non-wave exact/speculative tier routes best_of through the
    standalone kernel."""
    from lightgbm_tpu.ops.grow import GrowParams, build_tree
    rng = np.random.RandomState(4)
    N, F = 2048, 5
    bins = rng.randint(0, 15, size=(F, N)).astype(np.uint8)
    nbins = np.full(F, 16, np.int32)
    grad = rng.randn(N).astype(np.float32)
    hess = np.ones(N, np.float32)
    args = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.ones(N, jnp.float32), jnp.ones(F, bool),
            jnp.asarray(nbins), jnp.zeros(F, jnp.int32),
            jnp.zeros(F, bool))
    sp = SplitParams(max_bin=16, min_data_in_leaf=5, any_cat=False,
                     any_missing=False)
    recs = {}
    for sk in ("xla", "pallas"):
        p = GrowParams(split=sp, num_leaves=8, hist_impl="segsum",
                       split_kernel=sk)
        recs[sk] = {k: np.asarray(v) for k, v in
                    build_tree(*args, p).items()}
    for k in ("leaf", "feature", "threshold", "default_left", "valid"):
        np.testing.assert_array_equal(recs["xla"][k], recs["pallas"][k])
    np.testing.assert_allclose(recs["xla"]["leaf_values"],
                               recs["pallas"]["leaf_values"],
                               rtol=LEAF_RTOL, atol=LEAF_ATOL)


# ---- end-to-end model parity + telemetry ----------------------------

def _gain_tol(gain, tree):
    n = tree.num_leaves - 1
    return GAIN_RTOL * np.abs(gain) + \
        GAIN_ULPS * np.finfo(np.float32).eps * tree.split_gain[:n].sum()


def _assert_same_tree(x, y, ctx=""):
    """Split-for-split equality, gains and leaf values within the
    written tolerances.  The one admitted difference is a NEAR-TIE:
    the first node (in split order) where the two trees differ must
    carry gains the tolerance cannot tell apart, so each scan took a
    candidate the other also rates as best.  Returns that node index,
    or None for an equal tree."""
    n = min(x.num_leaves, y.num_leaves) - 1
    differ = np.zeros(n, bool)
    for k in ("split_feature", "threshold_bin", "decision_type",
              "left_child", "right_child"):
        differ |= getattr(x, k)[:n] != getattr(y, k)[:n]
    tie = int(np.argmax(differ)) if differ.any() else None
    m = n if tie is None else tie + 1
    assert tie is not None or x.num_leaves == y.num_leaves, ctx
    assert (np.abs(x.split_gain[:m] - y.split_gain[:m])
            <= _gain_tol(x.split_gain[:m], x)).all(), \
        (ctx, tie, x.split_gain[:m], y.split_gain[:m])
    if tie is None:
        np.testing.assert_allclose(x.leaf_value[:n + 1],
                                   y.leaf_value[:n + 1], rtol=LEAF_RTOL,
                                   atol=LEAF_ATOL, err_msg=ctx)
    return tie


def _assert_same_model(a, b, X, ctx=""):
    """End-to-end model parity under the written tolerances: every
    tree split for split (``_assert_same_tree``).  A near-tie that
    resolved the other way gives the trees after it other gradients,
    so from that tree on the pair is held by what it predicts: the
    mean raw-score difference stays below a thousandth of the score
    spread.  Returns the near-tie as (tree, node), or None."""
    ta, tb = a._gbdt.models, b._gbdt.models
    assert len(ta) == len(tb), ctx
    tie = None
    for i, (x, y) in enumerate(zip(ta, tb)):
        node = _assert_same_tree(x, y, f"{ctx} tree{i}")
        if node is not None:
            tie = (i, node)
            break
    pa = a.predict(X, raw_score=True)
    pb = b.predict(X, raw_score=True)
    assert np.mean(np.abs(pa - pb)) <= 1e-3 * np.std(pa), ctx
    return tie


@pytest.mark.parametrize("fused_iters", [1, 4])
def test_e2e_model_parity(fused_iters, tmp_path):
    """Fused-superstep end-to-end: split_kernel=pallas trains the
    same model as split_kernel=xla at fused_iters {1,4}."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.randn(1200, 8)
    X[rng.random_sample((1200, 8)) < 0.05] = np.nan
    y = (np.nan_to_num(X[:, 0]) + 0.4 * rng.randn(1200) > 0
         ).astype(float)
    models = {}
    for sk in ("xla", "pallas"):
        p = {"objective": "binary", "num_leaves": 15, "verbose": -1,
             "metric": "None", "split_kernel": sk,
             "fused_iters": fused_iters}
        d = lgb.Dataset(X, label=y, params=p)
        d.construct()
        models[sk] = lgb.train(p, d, num_boost_round=7)
    _assert_same_model(models["xla"], models["pallas"], X)


@pytest.mark.slow
def test_e2e_monotone_min_data_parity():
    """Constraint matrix end to end: monotone + min_data/min_hessian
    configs pin the same models (on this data one near-tie flips:
    min_data_in_leaf=40, tree 5, thresholds 192/193 of feature 0 with
    gains 9.842697/9.842705)."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(3)
    X = rng.randn(1000, 6)
    y = X[:, 0] * 1.5 - X[:, 1] + 0.3 * rng.randn(1000)
    for extra in ({"monotone_constraints": [1, -1, 0, 0, 0, 0]},
                  {"min_data_in_leaf": 40},
                  {"min_sum_hessian_in_leaf": 5.0}):
        models = {}
        for sk in ("xla", "pallas"):
            p = {"objective": "regression", "num_leaves": 15,
                 "verbose": -1, "metric": "None", "split_kernel": sk,
                 "fused_iters": 4, **extra}
            d = lgb.Dataset(X, label=y, params=p)
            d.construct()
            models[sk] = lgb.train(p, d, num_boost_round=6)
        _assert_same_model(models["xla"], models["pallas"], X,
                           str(extra))


def test_telemetry_fields_and_fallback_gate(tmp_path):
    """superstep records carry split_kernel; an ineligible config
    (categorical features) records the fallback gate."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.randn(600, 5)
    X[:, 2] = rng.randint(0, 4, size=600)  # categorical column
    y = (X[:, 0] > 0).astype(float)
    tf = str(tmp_path / "t.jsonl")
    p = {"objective": "binary", "num_leaves": 7, "verbose": -1,
         "metric": "None", "split_kernel": "pallas", "fused_iters": 4,
         "categorical_feature": [2], "telemetry_file": tf}
    d = lgb.Dataset(X, label=y, params=p,
                    categorical_feature=[2])
    d.construct()
    bst = lgb.train(p, d, num_boost_round=5)
    bst._gbdt._telemetry.close()
    recs = [json.loads(l) for l in open(tf)]
    ss = [r for r in recs if r["type"] == "superstep"]
    assert ss and all(r["split_kernel"] == "xla" for r in ss)
    assert all("categorical" in r["split_fallback"] for r in ss)
    start = [r for r in recs if r["type"] == "run_start"][0]
    assert start["tier"]["split_kernel"] == "xla"
    assert "categorical" in start["tier"]["gates"]["split"]


def test_triage_flags_tpu_fallback():
    """The MED anomaly fires for an XLA fallback on a TPU backend,
    stays silent on CPU and for an explicit split_kernel=xla."""
    import sys
    sys.path.insert(0, "tools")
    from triage_run import scan_anomalies

    def recs(backend, sk, reason):
        ss = {"type": "superstep", "iter": 1, "k": 4,
              "duration_ms": 10.0, "split_kernel": sk}
        if reason:
            ss["split_fallback"] = reason
        return [{"type": "run_start", "backend": backend,
                 "tier": {"split_kernel": sk,
                          "gates": {"split": reason} if reason else {}}},
                ss]

    def has_split_anomaly(records):
        return any("split kernel fell back" in m
                   for _, m in scan_anomalies(records))

    assert has_split_anomaly(recs("tpu v5e", "xla",
                                  "categorical scans"))
    assert not has_split_anomaly(recs("cpu", "xla",
                                      "cpu backend"))
    assert not has_split_anomaly(recs("tpu v5e", "xla",
                                      "split_kernel=xla"))
    assert not has_split_anomaly(recs("tpu v5e", "pallas", None))
    # non-fused runs (no superstep records) triage from run_start
    start_only = recs("tpu v5e", "xla", "EFB bundles active")[:1]
    assert has_split_anomaly(start_only)


@pytest.mark.slow
@pytest.mark.parametrize("tier_params", [
    # quantized tier: exact int values, cols=3 lane extraction
    {"use_quantized_grad": True, "min_data_in_leaf": 5},
    # two-column tier: cols=2 + in-kernel count:=hess proxy
    {"use_quantized_grad": True, "min_data_in_leaf": 1,
     "min_sum_hessian_in_leaf": 1e-3},
], ids=["quantized", "two_col"])
def test_interpret_lane_quantized_tiers(monkeypatch, tier_params):
    """On the exact (cols=3) and two-column (cols=2, count := hess
    copy) quantized histograms the kernel matches the XLA scan."""
    import lightgbm_tpu as lgb
    monkeypatch.setenv("LTPU_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(1)
    X = rng.randn(600, 5)
    y = (X[:, 0] + 0.4 * rng.randn(600) > 0).astype(float)
    models, tiers = {}, {}
    for sk in ("xla", "pallas"):
        p = {"objective": "binary", "num_leaves": 7, "verbose": -1,
             "metric": "None", "split_kernel": sk, "fused_iters": 2,
             "wave_splits": True, "hist_refinement": False,
             "tpu_rows_per_block": 512, "max_bin": 15, **tier_params}
        d = lgb.Dataset(X, label=y, params=p)
        d.construct()
        bst = models[sk] = lgb.train(p, d, num_boost_round=4)
        tiers[sk] = bst._gbdt.tier_decision
    assert tiers["pallas"]["split_kernel"] == "pallas", tiers["pallas"]
    assert tiers["pallas"]["quantize"] > 0
    if tier_params.get("min_data_in_leaf") == 1:
        assert tiers["pallas"]["tier"] == "two_col", tiers["pallas"]
    _assert_same_model(models["xla"], models["pallas"], X)


@pytest.mark.slow
def test_interpret_lane_e2e(monkeypatch):
    """LTPU_PALLAS_INTERPRET=1: the whole kernel tier (pallas
    histograms + routed passes + the split kernel) runs
    interpreted on CPU, and split_kernel=pallas stays structurally
    identical to xla under the SAME histogram tier."""
    import lightgbm_tpu as lgb
    monkeypatch.setenv("LTPU_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(0)
    X = rng.randn(600, 5)
    y = (X[:, 0] + 0.4 * rng.randn(600) > 0).astype(float)
    models = {}
    for sk in ("xla", "pallas"):
        p = {"objective": "binary", "num_leaves": 7, "verbose": -1,
             "metric": "None", "split_kernel": sk, "fused_iters": 2,
             "wave_splits": True, "tpu_rows_per_block": 512,
             "max_bin": 15}
        d = lgb.Dataset(X, label=y, params=p)
        d.construct()
        bst = models[sk] = lgb.train(p, d, num_boost_round=4)
        assert bst._gbdt.tier_decision["hist_impl"] == "pallas"
        assert bst._gbdt.tier_decision["split_kernel"] == sk
    _assert_same_model(models["xla"], models["pallas"], X)
