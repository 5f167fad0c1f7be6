"""The work a window's trees required, counted from the trees alone so
that it reads the same whatever implements the pass, and the chip's
peaks it is held against."""
from __future__ import annotations

import json
import os
from typing import Dict

import numpy as np

from .reference import TreeArrays

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "..", "peaks.json")


def peaks_for(device_kind: str) -> Dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise SystemExit(f"benchmark: no peaks for device kind "
                         f"{device_kind!r} in peaks.json")
    return table[device_kind]


def rows_touched(tree: TreeArrays) -> int:
    """Rows a histogram builder with subtraction has to visit for this
    tree: every row once for the root, then the smaller child of every
    split."""
    n_internal = len(tree.feature)
    if n_internal == 0:
        return int(tree.leaf_count.sum())
    count = np.zeros(n_internal, np.int64)

    def child(c: int) -> int:
        return int(tree.leaf_count[~c]) if c < 0 else int(count[c])
    total = 0
    # children are always created after their parent: walk backwards
    for i in range(n_internal - 1, -1, -1):
        lc, rc = child(tree.left[i]), child(tree.right[i])
        count[i] = lc + rc
        total += min(lc, rc)
    return total + int(count[0])


def hist_bytes(trees, features: int, rows: int) -> float:
    """Bytes the histogram passes must read: one byte a bin, a row
    touched times the features.  Bandwidth bound on the v5e: an add a
    byte is nothing beside 197 TFLOP/s."""
    return float(sum(rows_touched(t) for t in trees)) * features


def iteration_bytes(trees, features: int, rows: int) -> float:
    """The whole iteration: the histogram bytes, 8 bytes of gradient
    and hessian a row touched, and 12 bytes a row of the data set for
    the gradient and score update."""
    touched = float(sum(rows_touched(t) for t in trees))
    return touched * (features + 8) + 12.0 * rows * len(trees)


COUNTS = {"hist_bytes": hist_bytes, "iteration_bytes": iteration_bytes}
