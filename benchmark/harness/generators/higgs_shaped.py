"""Dense float32 columns and binary labels: a copy of
``bench.make_higgs_shaped``'s label model on ``numpy.random.Generator``
float32 draws, in chunks of 1M rows that each have a stream of their
own (so a few threads fill them and the result does not depend on
which finishes first).  No query groups."""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict

import numpy as np

CHUNK = 1_000_000
THREADS = 4


def make(rows: int, features: int, spec: Dict, seed: int):
    """``spec`` keys: ``abs_every`` (every k-th column is |x|: momentum
    like), ``integer_columns`` (the first k columns become counts,
    floor(exp(integer_scale * x)): many ties, fewer bins),
    ``logit_scale``, ``interaction`` ([i, j, weight]), ``bias`` and
    ``model_seed`` of the label model.  The label model's weights come
    from ``model_seed``, not from ``seed``: every seed draws fresh rows
    of the same population, so that the trees, and with them the work
    of an iteration, are alike from seed to seed."""
    abs_every = int(spec.get("abs_every", 3))
    n_int = int(spec.get("integer_columns", 0))
    int_scale = np.float32(spec.get("integer_scale", 1.5))
    i, j, w_ij = spec.get("interaction", [0, 1, 0.3])
    scale = np.float32(spec.get("logit_scale", 0.5))
    bias = np.float32(spec.get("bias", -0.1))
    w = np.random.default_rng([int(spec.get("model_seed", 0)), 0xDA7A]) \
        .standard_normal(features, dtype=np.float32)
    x = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)

    def fill(c: int) -> None:
        lo, hi = c * CHUNK, min((c + 1) * CHUNK, rows)
        rng = np.random.default_rng([seed, 0xDA7A, c + 1])
        xc = x[lo:hi]
        rng.standard_normal(out=xc, dtype=np.float32)
        if abs_every > 0:
            np.abs(xc[:, ::abs_every], out=xc[:, ::abs_every])
        logits = (xc @ w) * scale + np.float32(w_ij) * xc[:, i] * xc[:, j] \
            + bias
        if n_int:
            xc[:, :n_int] = np.floor(np.exp(int_scale * xc[:, :n_int]))
        p = 1.0 / (1.0 + np.exp(-logits))
        y[lo:hi] = rng.random(hi - lo, dtype=np.float32) < p

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, range(-(-rows // CHUNK))))
    return x, y, None
