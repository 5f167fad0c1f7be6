"""Training data from ``--seed`` and a configuration's ``data`` block
(``make``), made by the generator that the block names: the file
``harness/generators/<generator>.py`` (found by ``cells.generator``),
whose ``make(rows, features, spec, seed)`` returns ``x`` (float32,
rows x features), ``y`` (float32) and ``group`` (int32 sizes of
contiguous queries that sum to ``rows``, or None).  What it returns is
checked here, so that neither the trainer nor the reference is handed
a malformed set."""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import numpy as np

from . import cells


class Data(NamedTuple):
    x: np.ndarray
    y: np.ndarray
    group: Optional[np.ndarray]


def make(rows: int, features: int, spec: Dict, seed: int,
         root: str = cells.ROOT) -> Data:
    name = spec.get("generator")
    x, y, group = cells.generator(name, root).make(rows, features, spec,
                                                   seed)
    if not (x.dtype == np.float32 and x.shape == (rows, features)
            and x.flags.c_contiguous
            and y.dtype == np.float32 and y.shape == (rows,)):
        raise ValueError(f"generator {name!r} made x {x.dtype} {x.shape}, "
                         f"y {y.dtype} {y.shape}, not float32 "
                         f"({rows}, {features}) and ({rows},)")
    if group is not None:
        group = np.asarray(group)
        if not (group.dtype == np.int32 and group.ndim == 1
                and group.size and int(group.min()) >= 1
                and int(group.sum(dtype=np.int64)) == rows):
            raise ValueError(f"generator {name!r} made query sizes that "
                             f"are not int32, at least 1 and summing to "
                             f"{rows}")
    return Data(x, y, group)


def make_data(rows: int, features: int, spec: Dict, seed: int,
              root: str = cells.ROOT):
    """``(x, y)`` of a generator that makes no query groups."""
    x, y, group = make(rows, features, spec, seed, root)
    if group is not None:
        raise ValueError(f"generator {spec.get('generator')!r} makes "
                         f"query groups: datagen.make returns them")
    return x, y
