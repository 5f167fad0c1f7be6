"""Query-grouped ranking data: dense float32 columns, graded relevance
labels and contiguous queries of web-ranking lengths.

The population comes from ``model_seed`` and the configuration alone:
the label model's weights and the multiset of query lengths (so that
every seed gives the objective the same pairs to work through); the
seed draws the rows, the queries' order and each query's own
difficulty.  The rows are filled by blocks of whole queries of about
1M rows, each block with a stream of its own, in a few threads."""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from statistics import NormalDist
from typing import Dict

import numpy as np

CHUNK = 1_000_000
THREADS = 4


def query_lengths(rows: int, mean: float, spread: float, cap: int,
                  model_seed: int) -> np.ndarray:
    """Log-normal lengths of the given mean and log-spread, each in
    [1, cap], drawn until they hold ``rows``; the last is cut to fit."""
    rng = np.random.default_rng([model_seed, 0x9A0E])
    mu = np.log(mean) - spread * spread / 2.0
    out, total = [], 0
    while total < rows:
        d = np.clip(np.rint(rng.lognormal(mu, spread, 4096)), 1, cap)
        out.append(d.astype(np.int64))
        total += int(d.sum())
    lengths = np.concatenate(out)
    ends = np.cumsum(lengths)
    last = int(np.searchsorted(ends, rows))
    lengths = lengths[:last + 1]
    lengths[last] = rows - (ends[last - 1] if last else 0)
    return lengths.astype(np.int32)


def make(rows: int, features: int, spec: Dict, seed: int):
    """``spec`` keys: ``level_shares`` (the share of each relevance
    level, 0 first), ``query_length`` (``mean``, ``spread``: the
    log-normal's sigma, ``cap``), ``signal`` and ``query_effect`` (the
    weights, in a unit-variance relevance, of the documents' linear
    score and of the query's difficulty; noise makes up the rest) and
    ``model_seed``.  A document's level is its relevance's quantile
    band, so the levels take their shares over the population."""
    model_seed = int(spec.get("model_seed", 0))
    shares = np.asarray(spec["level_shares"], np.float64)
    ql = spec["query_length"]
    a = float(spec["signal"])
    b = float(spec["query_effect"])
    c = float(np.sqrt(1.0 - a * a - b * b))
    cuts = np.array([NormalDist().inv_cdf(q)
                     for q in np.cumsum(shares / shares.sum())[:-1]],
                    np.float32)
    w = np.random.default_rng([model_seed, 0xDA7A]) \
        .standard_normal(features, dtype=np.float32)
    w *= np.float32(a / np.sqrt(float(w @ w)))
    group = np.random.default_rng([seed, 0x9A0E]).permutation(
        query_lengths(rows, float(ql["mean"]), float(ql["spread"]),
                      int(ql["cap"]), model_seed))
    bounds = np.concatenate([[0], np.cumsum(group, dtype=np.int64)])
    blocks, q = [], 0          # (first query, last query + 1)
    while q < len(group):
        end = int(np.searchsorted(bounds, bounds[q] + CHUNK))
        end = min(max(end, q + 1), len(group))
        blocks.append((q, end))
        q = end
    x = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)

    def fill(k: int) -> None:
        q0, q1 = blocks[k]
        lo, hi = int(bounds[q0]), int(bounds[q1])
        rng = np.random.default_rng([seed, 0x9A0E, k + 1])
        xb = x[lo:hi]
        rng.standard_normal(out=xb, dtype=np.float32)
        u = rng.standard_normal(q1 - q0, dtype=np.float32)
        rel = xb @ w + np.float32(b) * np.repeat(u, group[q0:q1]) \
            + np.float32(c) * rng.standard_normal(hi - lo, dtype=np.float32)
        y[lo:hi] = np.searchsorted(cuts, rel)

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, range(len(blocks))))
    return x, y, group
