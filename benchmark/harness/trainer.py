"""The system under test, and the only file of the benchmark that
imports it: a ``Booster`` built by the public constructor from a
``lgb.Dataset`` (the path ``lgb.train`` takes), driven one
``update()`` at a time."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
from typing import Dict, Optional

import numpy as np

from .reference import Produced, TreeArrays

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_program() -> None:
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)


def configure_jax(log) -> str:
    """The compile cache at JAX_COMPILATION_CACHE_DIR or
    <checkout>/.jax_cache, every program kept in it whatever it took to
    compile, and the compile counters hooked up."""
    use_program()
    import jax
    from lightgbm_tpu.utils.env import configure_compile_cache
    from lightgbm_tpu.utils.telemetry import install_jax_hooks
    cache_dir = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    install_jax_hooks()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"compile cache: {cache_dir} ({entries} entries at start)")
    return cache_dir


def counters() -> Dict[str, float]:
    from lightgbm_tpu.utils.telemetry import counters_snapshot
    return dict(counters_snapshot())


def build_native_binner(log) -> None:
    """cpp/libltpu_io.so, built once per checkout from the committed
    sources.  The Python binner is never taken in silence."""
    use_program()
    from lightgbm_tpu.io import native
    cpp = os.path.join(ROOT, "cpp")
    if not (shutil.which("make")
            and shutil.which(os.environ.get("CXX", "g++"))):
        raise SystemExit("benchmark: no make/g++ to build "
                         "cpp/libltpu_io.so; the Python binner is not "
                         "what the cells are sized for")
    subprocess.run(["make", "-C", cpp, "libltpu_io.so"], check=True,
                   stdout=subprocess.DEVNULL)
    if not native.available():
        raise SystemExit("benchmark: cpp/libltpu_io.so does not load")
    log("binning: native cpp/libltpu_io.so")


def _tree_arrays(tree) -> TreeArrays:
    n = int(tree.num_leaves)
    k = max(n - 1, 0)
    return TreeArrays(
        np.array(tree.split_feature[:k], np.int32),
        np.array(tree.threshold[:k], np.float64),
        np.array(tree.left_child[:k], np.int32),
        np.array(tree.right_child[:k], np.int32),
        np.array(tree.leaf_value[:n], np.float64),
        np.array(tree.leaf_weight[:n], np.float64),
        np.array(tree.leaf_count[:n], np.int64),
        float(tree.internal_weight[0] if k else tree.leaf_weight[0]))


class Trainer:
    def __init__(self, params: Dict, x: np.ndarray, y: np.ndarray,
                 telemetry_file: Optional[str] = None,
                 group: Optional[np.ndarray] = None):
        use_program()
        import lightgbm_tpu as lgb
        self.dataset = lgb.Dataset(x, label=y, group=group, params=params)
        self.dataset.construct()
        self.booster = lgb.Booster(params, self.dataset)
        self.gbdt = self.booster._gbdt
        if telemetry_file:
            self.gbdt.attach_telemetry(telemetry_file)

    def step(self) -> None:
        """One boosting iteration as the caller sees it."""
        self.booster.update()

    def trees_done(self) -> int:
        """Trees fetched from the device so far (reading the model
        lands the one the pipelined loop still holds back)."""
        return len(self.gbdt.models)

    def tier(self) -> Dict:
        return dict(self.gbdt.tier_decision)

    def hist_passes(self) -> Optional[float]:
        summ = self.gbdt.telemetry_summary()
        return None if summ is None else float(summ.get("hist_passes", 0))

    def produced(self) -> Produced:
        """The trees and the training score; reading the score waits
        for whatever block is still on the device."""
        trees = [_tree_arrays(t) for t in self.gbdt.models]
        score = np.asarray(self.gbdt.train_score, np.float32)
        return Produced(trees, score.reshape(-1), int(self.gbdt.num_data))

    def close(self) -> None:
        rec = getattr(self.gbdt, "_telemetry", None)
        if rec is not None:
            rec.close(log=False)
        self.booster = self.gbdt = self.dataset = None
