"""LambdaRank's bucketed pair layout (``objectives.py``
``bucket_plan``, ``LambdaRank._layout``): the gradient against the
plain reference and against every query padded to the longest, the
slots the layout computes at MS LTR's population, the fused super-step
against the per-iteration loop, the query tables as the programs'
arguments (a second data set of the same shapes compiles nothing
fresh), and what the layout counts and records."""
import json
import math
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.objectives import (bucket_lengths, bucket_plan,
                                     create_objective, pair_slots)
from lightgbm_tpu.utils import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

# lengths over several buckets: one-document queries, short, tile-wide
# and past a lane row
GROUP = np.array([1, 3, 7, 12, 30, 1, 60, 150, 9, 40, 200, 5, 33, 130])


def pairwise_loop(score, y, group, norm, max_position=20):
    """``GetGradientsForOneQuery``'s loops in float64 Python scalars,
    with ``lambdamart_norm`` as a switch."""
    gains = [2.0 ** i - 1.0 for i in range(31)]
    g, h = np.zeros(len(y)), np.zeros(len(y))
    lo = 0
    for cnt in group:
        rows = list(range(lo, lo + int(cnt)))
        lo += int(cnt)
        srt = sorted(rows, key=lambda i: -score[i])
        disc = {i: 1.0 / math.log2(2.0 + p) for p, i in enumerate(srt)}
        top = sorted((int(y[i]) for i in rows), reverse=True)[:max_position]
        dcg = sum(gains[l] / math.log2(2.0 + p) for p, l in enumerate(top))
        if dcg <= 0.0:
            continue
        spread = score[srt[0]] != score[srt[-1]]
        for a in rows:
            for b in rows:
                if not int(y[a]) > int(y[b]):
                    continue
                ds = float(score[a]) - float(score[b])
                delta = (gains[int(y[a])] - gains[int(y[b])]) \
                    * abs(disc[a] - disc[b]) / dcg
                if norm and spread:
                    delta /= float(np.float32(0.01)) + abs(ds)
                p = 2.0 / (1.0 + math.exp(2.0 * ds))
                g[a] -= delta * p
                g[b] += delta * p
                h[a] += 2.0 * delta * p * (2.0 - p)
                h[b] += 2.0 * delta * p * (2.0 - p)
    return g, h


def _objective(y, group, norm=True, max_buckets=None):
    obj = create_objective("lambdarank", Config(
        {"objective": "lambdarank", "lambdamart_norm": norm}))
    qb = np.concatenate([[0], np.cumsum(group)])
    obj.init(types.SimpleNamespace(label=y, weight=None,
                                   query_boundaries=qb), len(y))
    if max_buckets is not None:
        obj._rank_tables = obj._layout(qb, y.astype(np.int64), len(y),
                                       max_buckets)
    return obj


def _case(scores):
    rng = np.random.default_rng(11)
    n = int(GROUP.sum())
    y = rng.integers(0, 5, n).astype(np.float32)
    y[11:23] = 2                # the query of 12: every label equal
    score = {"ties": np.round(rng.standard_normal(n), 1),
             "spread": rng.standard_normal(n) * 3.0,
             "equal": np.zeros(n)}[scores].astype(np.float32)
    score[23:53] = 0.25         # the query of 30: every score tied
    return y, score


@pytest.mark.parametrize("norm", [True, False])
@pytest.mark.parametrize("scores", ["ties", "spread", "equal"])
def test_bucketed_gradient(norm, scores):
    """Against the pairwise loop (float64) and, with the norm, the
    benchmark's reference; against one bucket padded to the longest
    query (the unbucketed layout).  The layout works in float32: a
    pair's term takes a handful of roundings of 2^-24 and a document
    sums its pairs' terms (under 200 here), which 16 float32 epsilons
    of the largest lambda bound; the two layouts sum the same terms in
    rows of other lengths, so they agree to a few epsilons as well."""
    import jax.numpy as jnp
    y, score = _case(scores)
    obj = _objective(y, GROUP, norm)
    assert len(obj.tables()["_rank_tables"]["buckets"]) >= 3
    g, h = (np.asarray(a, np.float64)
            for a in obj.get_gradients(jnp.asarray(score)))
    want_g, want_h = pairwise_loop(score, y, GROUP, norm)
    tol = 16 * np.finfo(np.float32).eps
    scale_g = max(np.abs(want_g).max(), 1e-30)
    scale_h = max(np.abs(want_h).max(), 1e-30)
    assert np.max(np.abs(g - want_g)) <= tol * scale_g
    assert np.max(np.abs(h - want_h)) <= tol * scale_h
    # a one-document query, and one whose labels are all equal, get 0
    assert g[0] == 0 and g[int(GROUP[:5].sum())] == 0
    assert np.all(g[11:23] == 0) and np.all(h[11:23] == 0)
    if norm:
        if BENCH not in sys.path:
            sys.path.insert(0, BENCH)
        from harness import cells
        ref_g, ref_h = cells.objective("lambdarank").gradients(
            score.astype(np.float64), y, GROUP, {})
        assert np.max(np.abs(g - ref_g)) <= tol * scale_g
        assert np.max(np.abs(h - ref_h)) <= tol * scale_h
    one = _objective(y, GROUP, norm, max_buckets=1)
    assert len(one.tables()["_rank_tables"]["buckets"]) == 1
    pg, ph = (np.asarray(a, np.float64)
              for a in one.get_gradients(jnp.asarray(score)))
    assert np.max(np.abs(g - pg)) <= tol * scale_g
    assert np.max(np.abs(h - ph)) <= tol * scale_h


def test_layout_tables():
    """Every row in one slot of its query's bucket; the padding slots
    hold the sentinel row, label -1 and gain 0; buckets ascend, each a
    length on the tiles (8 up to 128, then 128)."""
    y, _ = _case("ties")
    obj = _objective(y, GROUP)
    tab = obj.tables()["_rank_tables"]
    n = len(y)
    slots = np.concatenate([np.asarray(b["idx"]).reshape(-1)
                            for b in tab["buckets"]])
    lbl = np.concatenate([np.asarray(b["lbl"]).reshape(-1)
                          for b in tab["buckets"]])
    gain = np.concatenate([np.asarray(b["gain"]).reshape(-1)
                           for b in tab["buckets"]])
    pos = np.asarray(tab["pos"])
    assert np.array_equal(slots[pos], np.arange(n))
    assert np.sum(slots < n) == n
    assert np.all(lbl[slots == n] == -1) and np.all(gain[slots == n] == 0)
    assert np.array_equal(lbl[pos], y.astype(np.int32))
    lengths = [b["idx"].shape[-1] for b in tab["buckets"]]
    assert lengths == sorted(lengths)
    assert all(L % 8 == 0 and (L <= 128 or L % 128 == 0) for L in lengths)
    assert obj.pairs == int(np.sum(GROUP.astype(np.int64) ** 2))
    assert obj.pair_slots == sum(int(np.prod(b["idx"].shape)) *
                                 b["idx"].shape[-1]
                                 for b in tab["buckets"])


def test_pair_slots_at_ms_ltr_population():
    """At the cell's population (``benchmark/configs/mslr137.json``:
    11,350,740 rows, lengths of mean 120, log-spread 0.7, cap 1,251) the
    layout computes at most 2.5 slots a real pair, where padding every
    query to the longest computes some 66."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from harness import cells
    with open(os.path.join(BENCH, "configs", "mslr137.json")) as f:
        cfg = json.load(f)
    ql = cfg["data"]["query_length"]
    lengths = cells.generator("query_grouped").query_lengths(
        int(cfg["rows"]), float(ql["mean"]), float(ql["spread"]),
        int(ql["cap"]), int(cfg["data"]["model_seed"]))
    real = int(np.sum(lengths.astype(np.int64) ** 2))
    plan = bucket_plan(lengths)
    assert pair_slots(plan) <= 2.5 * real
    assert len(lengths) * int(lengths.max()) ** 2 > 60 * real
    assert sorted(np.concatenate([q for _, q, _, _ in plan])) == \
        list(range(len(lengths)))
    for L, qs, chunks, cq in plan:
        assert chunks >= 2 and chunks * cq >= len(qs) > (chunks - 1) * cq
        assert cq * L * L <= 2e7 or cq == 1


def test_bucket_lengths_fewest_slots():
    """The chosen lengths give the fewest slots any choice of as many
    tile lengths gives (checked by brute force), and one bucket is the
    longest query's tile."""
    import itertools
    rng = np.random.default_rng(3)
    lengths = np.clip(np.rint(rng.lognormal(3.5, 0.8, 400)), 1, 700)
    tops = np.unique(np.where(lengths <= 128, -(-lengths // 8) * 8,
                              -(-lengths // 128) * 128)).astype(int)

    def slots(chosen):
        t = np.where(lengths <= 128, -(-lengths // 8) * 8,
                     -(-lengths // 128) * 128)
        return sum(chosen[np.searchsorted(chosen, x)] ** 2 for x in t)

    assert list(bucket_lengths(lengths, 1)) == [tops[-1]]
    for k in (2, 3):
        got = bucket_lengths(lengths, k)
        best = min(slots(np.array(sorted(c) + [tops[-1]]))
                   for c in itertools.combinations(tops[:-1], k - 1))
        assert len(got) <= k and slots(got) == best


def _rank_data(seed, lengths=GROUP):
    rng = np.random.default_rng(seed)
    group = rng.permutation(lengths)
    n = int(group.sum())
    return (rng.standard_normal((n, 6)),
            rng.integers(0, 5, n).astype(np.float64), group)


def _train_rank(fused, data, rounds=9, **extra):
    X, y, group = data
    p = {"objective": "lambdarank", "num_leaves": 7, "max_bin": 31,
         "verbose": -1, "metric": "None", "fused_iters": fused,
         "min_data_in_leaf": 1, **extra}
    d = lgb.Dataset(X, label=y, group=group, params=p)
    return lgb.train(p, d, num_boost_round=rounds, verbose_eval=False)


@pytest.mark.parametrize("extra", [{}, {"wave_splits": True,
                                        "use_quantized_grad": True}])
def test_fused_superstep_bit_exact(extra):
    """Trees and training scores of the fused super-step (the tables an
    argument of the scan) equal to the bit those of the per-iteration
    loop (the tables an argument of ``jit(get_gradients)``)."""
    data = _rank_data(5)
    a = _train_rank(1, data, **extra)
    b = _train_rank(4, data, **extra)
    ga, gb = a._gbdt, b._gbdt
    assert len(ga.models) == len(gb.models) == 9
    for ta, tb in zip(ga.models, gb.models):
        np.testing.assert_array_equal(ta.leaf_value, tb.leaf_value)
        np.testing.assert_array_equal(ta.split_feature, tb.split_feature)
        np.testing.assert_array_equal(ta.threshold_bin, tb.threshold_bin)
    np.testing.assert_array_equal(ga.train_score, gb.train_score)


def test_counters_and_tier_record():
    """The layout's phase and gauge at construction; the pair counters
    once an iteration; ``rank_layout`` in the tier record of a
    lambdarank booster and of no other."""
    c0 = telemetry.counters_snapshot()
    bst = _train_rank(4, _rank_data(6), rounds=8)
    c = telemetry.counters_snapshot()

    def grown(key):
        return c[key] - c0.get(key, 0.0)

    obj = bst._gbdt.objective
    assert grown("phase_calls/rank/layout") == 1
    assert c["rank_buckets"] == len(obj.tables()["_rank_tables"]["buckets"])
    assert grown("rank_pair_slots") == 8 * obj.pair_slots
    assert grown("rank_pairs") == 8 * obj.pairs
    assert bst._gbdt.tier_decision["rank_layout"] == "buckets"
    X, y, _ = _rank_data(6)
    p = {"objective": "regression", "verbose": -1, "num_leaves": 7}
    reg = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                    num_boost_round=1, verbose_eval=False)
    assert "rank_layout" not in reg._gbdt.tier_decision
    assert reg._gbdt.objective.tables() == {}


# two data sets of the same rows and query lengths, in another order,
# trained one after the other; then a third whose lengths differ
SCRIPT = r"""
import json, sys
import jax
import numpy as np
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
import lightgbm_tpu as lgb
from lightgbm_tpu.utils import telemetry
telemetry.install_jax_hooks()
lengths = np.array([1, 3, 7, 12, 30, 60, 150, 9, 40, 200, 5, 33] * 3)

def train(seed, lengths):
    rng = np.random.default_rng(seed)
    group = rng.permutation(lengths)
    n = int(group.sum())
    X = rng.standard_normal((n, 6))
    y = rng.integers(0, 5, n).astype(float)
    p = {"objective": "lambdarank", "num_leaves": 7, "max_bin": 31,
         "verbose": -1, "metric": "None", "fused_iters": 4,
         "min_data_in_leaf": 1}
    lgb.train(p, lgb.Dataset(X, label=y, group=group, params=p),
              num_boost_round=9, verbose_eval=False)
    return telemetry.counters_snapshot()

snaps = [train(1, lengths), train(2, lengths)]
other = lengths.copy()
other[6], other[7] = 100, 50        # the same rows, other buckets
snaps.append(train(3, other))
print(json.dumps(snaps))
"""


@pytest.fixture(scope="module")
def compile_snaps(tmp_path_factory):
    cache = str(tmp_path_factory.mktemp("jax_cache"))
    # the entry points keep the cache where the variable says
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=cache)
    p = subprocess.run([sys.executable, "-c", SCRIPT, cache],
                       capture_output=True, text=True, env=env,
                       timeout=600, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("program", ["jit(get_gradients)",
                                     "jit(superstep)"])
def test_second_data_set_compiles_nothing_fresh(compile_snaps, program):
    """The query tables are arguments of the gradient program and of
    the fused super-step, not their constants: the second data set's
    requests for both are served by the persistent cache.  The third,
    whose buckets differ, compiles both fresh, so the counters see a
    fresh compile where there is one."""
    first, second, third = compile_snaps

    def grown(lo, hi, key):
        return hi.get(f"{key}/{program}", 0) - lo.get(f"{key}/{program}", 0)

    assert grown({}, first, "xla_fresh_compiles") >= 1
    assert grown(first, second, "xla_fresh_compiles") == 0
    assert grown(first, second, "xla_cache_loads") >= 1
    assert grown(second, third, "xla_fresh_compiles") >= 1


def test_rank_metrics_read_the_counters():
    """The three per-layer metric files of the ranking cell read what a
    run counts (``harness/readers.py``); a run whose objective lays out
    no pairs adds nothing to the counters (0 where an earlier run of the
    process made them, and no fill)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from harness import readers
    names = ("rank_pair_slots_per_iter", "rank_pair_fill", "rank_layout_s")
    metrics = []
    for name in names:
        with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
            metrics.append(json.load(f))
        assert metrics[-1]["workloads"] == ["mslr137.fast"]

    def read(train):
        c0 = telemetry.counters_snapshot()
        bst = train()
        c1 = telemetry.counters_snapshot()
        ctx = {"spans": {}, "counters": {"setup": (c0, c1),
                                         "window": (c0, c1)},
               "quantities": {"window_iterations": 9}}
        return bst, readers.read_all(metrics, ctx)

    bst, got = read(lambda: _train_rank(4, _rank_data(8)))
    obj = bst._gbdt.objective
    assert got["rank_pair_slots_per_iter"]["value"] == obj.pair_slots
    assert got["rank_pair_fill"]["value"] == pytest.approx(
        obj.pairs / obj.pair_slots, rel=1e-12)
    assert 0 < got["rank_pair_fill"]["value"] <= 1
    assert got["rank_layout_s"]["value"] > 0
    X, y, _ = _rank_data(8)
    p = {"objective": "regression", "verbose": -1, "num_leaves": 7,
         "fused_iters": 4}
    _, none = read(lambda: lgb.train(p, lgb.Dataset(X, label=y, params=p),
                                     num_boost_round=9, verbose_eval=False))
    assert "rank_pair_fill" not in none
    assert all(v["value"] == 0 for v in none.values())
