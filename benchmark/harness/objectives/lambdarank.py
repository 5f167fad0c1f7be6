"""LambdaRank with NDCG gains, as LightGBM 2.2.3's
``src/objective/rank_objective.hpp`` (``LambdarankNDCG``) defines it,
written from that source alone: the positions and the best DCGs in
numpy, the pairs in float64 by ``ref_lambdarank`` (harness/ref_kernels.c).

In a query, the documents sorted by score (stable, highest first) take
the discount ``1 / log2(2 + position)``.  Every pair (high, low) whose
labels differ, high's the larger, adds

    delta  = (gain[high] - gain[low]) * |disc[high] - disc[low]|
             * inverse_max_dcg
    delta /= 0.01f + |s_high - s_low|     (unless every score is equal)
    p      = 2 / (1 + exp(2 * sigmoid * (s_high - s_low)))
    lambda[high] -= delta * p          lambda[low] += delta * p
    hess[high]   += 2 delta p (2 - p)  hess[low]   += 2 delta p (2 - p)

where ``gain[l]`` is ``label_gain[l]`` (2^l - 1 by default, l < 31) and
``inverse_max_dcg`` is one over the DCG of the query's labels sorted
from the highest, truncated at ``max_position`` (0 where that DCG is
0).  The init score is 0: the source's ``BoostFromScore`` default,
which lambdarank keeps.

Departures from the source:
- ``p`` is computed exactly; the source reads it from a table of 2^20
  entries over ``|2 sigmoid ds| <= 50``, rounded down to an entry (the
  end entries beyond that range).
- each document's lambda and hessian are summed in float64; the source
  adds a pair's terms for the low document in float32 (``score_t``).
- no document has the score ``kMinScore``, which the source skips: a
  score here is a finite sum of leaf values.
- no weights: the benchmark passes none to the trainer."""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from harness import native

ALIASES = ("lambdarank", "rank")

DEFAULT_LABEL_GAIN = 2.0 ** np.arange(31) - 1.0
NORM_FLOOR = float(np.float32(0.01))     # the source's 0.01f


def settings(params: Dict):
    """(label gains, sigmoid, max_position) as the trainer reads them."""
    gains = params.get("label_gain") or DEFAULT_LABEL_GAIN
    return (np.asarray(gains, np.float64), float(params.get("sigmoid", 1.0)),
            int(params.get("max_position", 20)))


def init_score(y: np.ndarray, group: Optional[np.ndarray],
               params: Dict) -> float:
    return 0.0


def gradients(score: np.ndarray, y: np.ndarray,
              group: Optional[np.ndarray], params: Dict):
    if group is None:
        raise ValueError("lambdarank needs query groups")
    gains, sigmoid, k = settings(params)
    labels = y.astype(np.int32)
    bounds = np.concatenate([[0], np.cumsum(group, dtype=np.int64)])
    query = np.repeat(np.arange(len(group)), group)
    start = bounds[query]
    # a row's position in its query by score, and by label for the best
    # DCG: np.lexsort is stable, so tied scores keep the rows' order
    pos = np.empty(len(y), np.int64)
    pos[np.lexsort((-np.asarray(score, np.float64), query))] = \
        np.arange(len(y)) - start
    by_label = np.lexsort((-labels, query))
    at = np.arange(len(y)) - start
    top = at < k
    dcg = np.bincount(query[top], gains[labels[by_label][top]]
                      / np.log2(at[top] + 2.0), minlength=len(group))
    with np.errstate(divide="ignore"):
        inv_max = np.where(dcg > 0, 1.0 / dcg, 0.0)
    return native.lambdarank(score, labels, 1.0 / np.log2(pos + 2.0),
                             bounds, inv_max, gains, sigmoid, NORM_FLOOR)
