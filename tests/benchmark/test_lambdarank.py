"""The lambdarank reference (``harness/objectives/lambdarank.py``) and
the query-grouped generator (``harness/generators/query_grouped.py``):
the reference against a pairwise loop in plain Python written from
``rank_objective.hpp``'s own order of loops, and against the program's
``LambdaRank.get_gradients``; the generator's queries, levels and
population.  Nothing here touches a JAX backend while it is
imported."""
from __future__ import annotations

import math
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from harness import cells, datagen                         # noqa: E402

SPEC = {"generator": "query_grouped",
        "level_shares": [0.514, 0.325, 0.134, 0.019, 0.008],
        "query_length": {"mean": 40, "spread": 0.9, "cap": 300},
        "signal": 0.6, "query_effect": 0.4, "model_seed": 0}
SEED = 2 ** 31 + 403


def pairwise_loop(score, y, group, sigmoid=1.0, max_position=20):
    """``GetGradientsForOneQuery`` as the source loops: positions by a
    stable sort on the score, every (high, low) pair with high's label
    the larger, in float64 Python scalars."""
    gains = [2.0 ** i - 1.0 for i in range(31)]
    g, h = [0.0] * len(y), [0.0] * len(y)
    lo = 0
    for n in group:
        rows = list(range(lo, lo + int(n)))
        lo += int(n)
        srt = sorted(rows, key=lambda i: -score[i])
        disc = {i: 1.0 / math.log2(2.0 + p) for p, i in enumerate(srt)}
        top = sorted((int(y[i]) for i in rows), reverse=True)[:max_position]
        dcg = sum(gains[l] / math.log2(2.0 + p) for p, l in enumerate(top))
        if dcg <= 0.0:
            continue
        best, worst = score[srt[0]], score[srt[-1]]
        for a in srt:
            for b in srt:
                if not int(y[a]) > int(y[b]):
                    continue
                ds = score[a] - score[b]
                delta = (gains[int(y[a])] - gains[int(y[b])]) \
                    * abs(disc[a] - disc[b]) / dcg
                if best != worst:
                    delta /= float(np.float32(0.01)) + abs(ds)
                p = 2.0 / (1.0 + math.exp(2.0 * sigmoid * ds))
                g[a] -= delta * p
                g[b] += delta * p
                h[a] += 2.0 * delta * p * (2.0 - p)
                h[b] += 2.0 * delta * p * (2.0 - p)
    return np.array(g), np.array(h)


@pytest.fixture(scope="module")
def lambdarank():
    return cells.objective("lambdarank")


def test_objective_is_found_by_its_aliases(lambdarank):
    assert cells.objective("rank").__file__ == lambdarank.__file__
    assert lambdarank.init_score(np.ones(3), np.array([3]), {}) == 0.0
    assert not hasattr(lambdarank, "loss")


@pytest.mark.parametrize("scores", ["ties", "equal", "spread"])
def test_reference_against_the_pairwise_loop(lambdarank, scores):
    """A handful of queries: one of one document, one whose labels are
    all 0, one whose scores are all equal, tied labels and tied scores
    everywhere; sigmoid and max_position off their defaults too."""
    rng = np.random.default_rng(7)
    group = np.array([1, 5, 7, 12, 3, 30, 2, 25], np.int32)
    n = int(group.sum())
    y = rng.integers(0, 5, n).astype(np.float32)
    y[1:6] = 0
    score = {"ties": np.round(rng.standard_normal(n), 1),
             "equal": np.zeros(n),
             "spread": rng.standard_normal(n) * 20.0}[scores]
    score[6:13] = 0.5
    for params, kw in (({}, {}), ({"sigmoid": 2.5, "max_position": 3},
                                  {"sigmoid": 2.5, "max_position": 3})):
        want_g, want_h = pairwise_loop(list(score), y, group, **kw)
        g, h = lambdarank.gradients(score, y, group, params)
        assert np.max(np.abs(g - want_g)) <= 1e-12 * np.abs(want_g).max()
        assert np.max(np.abs(h - want_h)) <= 1e-12 * np.abs(want_h).max()
        assert np.all(g[1:6] == 0) and g[0] == 0 and np.all(h >= 0)


@pytest.mark.parametrize("scale", [0.0, 0.1, 1.0, 5.0])
def test_reference_against_the_program(lambdarank, scale):
    """The program's ``LambdaRank.get_gradients`` on the same scores
    (float32 values, so both read the same numbers).  It works in
    float32: each pair's term takes a handful of roundings of 2^-24 and
    a document sums its pairs' terms (under 300 here); 16 float32
    epsilons of the largest lambda (1.9e-6) bound that, and the two
    agree to about 2e-7 of it."""
    sys.path.insert(0, ROOT)
    import jax.numpy as jnp
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.objectives import create_objective
    x, y, group = datagen.make(6000, 6, SPEC, SEED)
    program = create_objective("lambdarank",
                               Config({"objective": "lambdarank"}))
    bounds = np.concatenate([[0], np.cumsum(group)])
    program.init(types.SimpleNamespace(label=y, weight=None,
                                       query_boundaries=bounds), len(y))
    score = (np.random.default_rng(5).standard_normal(len(y))
             * scale).astype(np.float32)
    g, h = lambdarank.gradients(score.astype(np.float64), y, group, {})
    pg, ph = (np.asarray(a, np.float64)
              for a in program.get_gradients(jnp.asarray(score)))
    tol = 16 * np.finfo(np.float32).eps
    assert np.max(np.abs(pg - g)) <= tol * np.abs(g).max()
    assert np.max(np.abs(ph - h)) <= tol * np.abs(h).max()


def test_generator_draws_one_population():
    """Every seed: queries that hold every row, each within the cap;
    the same multiset of query lengths, in another order; other rows;
    the levels in their shares over the rows."""
    a = datagen.make(60000, 6, SPEC, SEED)
    b = datagen.make(60000, 6, SPEC, SEED + 1)
    again = datagen.make(60000, 6, SPEC, SEED)
    for field in range(3):
        assert np.array_equal(a[field], again[field])
    assert a.group.dtype == np.int32 and int(a.group.sum()) == 60000
    assert a.group.min() >= 1 and a.group.max() <= 300
    assert np.array_equal(np.sort(a.group), np.sort(b.group))
    assert not np.array_equal(a.group, b.group)
    assert not np.array_equal(a.x, b.x)
    shares = np.bincount(a.y.astype(np.int64), minlength=5) / len(a.y)
    assert np.allclose(shares, SPEC["level_shares"], atol=0.02)
    assert np.isclose(a.group.mean(), 40, rtol=0.1)
    with pytest.raises(ValueError):     # the two-value form drops none
        datagen.make_data(600, 6, SPEC, SEED)


def test_generator_fills_blocks_of_whole_queries(monkeypatch):
    """Blocks of whole queries, each with a stream of its own: a block
    size that cuts the set elsewhere gives other rows, the same
    queries."""
    gen = cells.generator("query_grouped")
    x, y, group = gen.make(5000, 4, SPEC, SEED)
    monkeypatch.setattr(gen, "CHUNK", 700)
    x2, y2, group2 = gen.make(5000, 4, SPEC, SEED)
    assert np.array_equal(group, group2)
    bounds = np.cumsum(group)
    first = int(bounds[np.searchsorted(bounds, 700)])
    assert np.array_equal(x[:first], x2[:first])
    assert not np.array_equal(x[first:], x2[first:])
