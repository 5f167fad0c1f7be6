"""The record fetch split where the host waits (models/gbdt.py
``_fetch_records``): ``fetch/wait``, the pack's dispatch up to the
device's answer, and ``fetch/copy``, the transfer and the unpack, once
each a fetch, inside the caller's ``superstep/fetch`` or
``tree/fetch``; a device gap under a fetch is named by the child phase
under it."""
import glob
import os
import sys

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.utils import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from harness import xplane  # noqa: E402

PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
          "learning_rate": 0.1, "min_data_in_leaf": 1,
          "min_sum_hessian_in_leaf": 5.0, "verbose": -1, "metric": "None"}
FUSED = dict(PARAMS, wave_splits=True, use_quantized_grad=True,
             fused_iters=4)
ROUNDS = 9                      # iteration 0, then two blocks of 4


def _data(seed=0, rows=4000, features=6):
    rng = np.random.RandomState(seed)
    x = rng.randn(rows, features).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2] + 0.3 * rng.randn(rows) > 0)
    return x, y.astype(np.float32)


def _train(params):
    x, y = _data()
    before = telemetry.counters_snapshot()
    bst = lgb.train(params, lgb.Dataset(x, label=y, params=params),
                    num_boost_round=ROUNDS)
    assert bst.num_trees() == ROUNDS    # lands a tree still held back
    now = telemetry.counters_snapshot()
    return bst, {k: v - before.get(k, 0.0) for k, v in now.items()}


@pytest.mark.parametrize("params", [FUSED, PARAMS],
                         ids=["fused", "per_tree"])
def test_one_wait_and_one_copy_a_fetch(params):
    bst, g = _train(params)
    trees = bst.num_trees()
    blocks = g.get("superstep_fetches", 0.0)
    k = params.get("fused_iters", 1)
    # a block's trees come in one fetch, every other tree in its own
    fetches = blocks + (trees - k * blocks)
    if params is FUSED:
        assert blocks == 2 and fetches == 3
    assert g["phase_calls/fetch/wait"] == fetches
    assert g["phase_calls/fetch/copy"] == fetches
    assert g["phase_secs/fetch/wait"] > 0 and g["phase_secs/fetch/copy"] > 0


@pytest.mark.filterwarnings("ignore:builtin type event_stats")
def test_child_spans_lie_inside_the_block_fetch(tmp_path):
    """On the profiler's trace each ``ltpu.superstep.fetch`` holds one
    ``ltpu.fetch.wait`` and then one ``ltpu.fetch.copy``; a device gap
    that lies under the wait is named by it, not by the parent."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _train(FUSED)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb"))[-1]
    host = xplane.load(path)["host"]
    blocks = [h for h in host if h[0] == "ltpu.superstep.fetch"]
    assert len(blocks) == 2
    for _, s, e in blocks:
        inner = sorted((h for h in host if h[0].startswith("ltpu.fetch.")
                        and s <= h[1] and h[2] <= e), key=lambda h: h[1])
        assert [h[0] for h in inner] == ["ltpu.fetch.wait",
                                         "ltpu.fetch.copy"]
        wait = inner[0]
        assert wait[2] <= inner[1][1]
        mid = (wait[1] + wait[2]) / 2
        gap = (mid - (wait[2] - wait[1]) / 4, mid + (wait[2] - wait[1]) / 4)
        assert xplane.name_gap(*gap, host) == "ltpu.fetch.wait"
