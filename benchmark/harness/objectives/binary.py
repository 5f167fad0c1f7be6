"""Binary logistic loss, as the reference follows it: the raw score is
a log-odds, labels are 0 or 1, and the first tree starts from the log
of the positives' odds (``boost_from_average``).  No query groups."""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

ALIASES = ("binary",)


def init_score(y: np.ndarray, group: Optional[np.ndarray],
               params: Dict) -> float:
    p = float(np.mean(y, dtype=np.float64))
    return float(np.log(p / (1.0 - p)))


def gradients(score: np.ndarray, y: np.ndarray,
              group: Optional[np.ndarray], params: Dict):
    p = 1.0 / (1.0 + np.exp(-score))
    return p - y, p * (1.0 - p)


def loss(score: np.ndarray, y: np.ndarray, group: Optional[np.ndarray],
         params: Dict) -> float:
    return float(np.mean(np.logaddexp(0.0, -(2.0 * y - 1.0) * score)))
