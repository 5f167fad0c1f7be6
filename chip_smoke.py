"""chip_smoke.py: the quickest proof that the training path still starts
on the chip.

One process on one TPU (it takes the chip; run nothing else beside it):

    python chip_smoke.py

It drives ``lgb.train`` -> ``GBDT`` -> ``build_tree`` -> the Pallas
histogram and split kernels -> ``Booster.predict`` at the full width of
the headline configuration (the Higgs shape of ``bench.py`` and
``BASELINE.md``: 28 features x 255 bins x 255 leaves; rows are cut to
1M, width is not), twice: once as a user who sets nothing gets it, once
on the headline tier.  It checks the tier records, the holdout AUCs, a
model round trip, a pallas-vs-segsum pair on the device, and an
in-process server, and with more than one device the parallel learners.
Any failed leg raises: there is no ``except`` in this file.

It refuses to run anywhere but on a TPU: no CPU fallback.  The last
line of stdout is one JSON object, ``{"ok": true, "device": {...}}``.
The wall times it prints are smoke timings (compilation included), not
benchmark numbers.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

N_FEATURES = 28
N_ROWS = 1_000_000       # uint8 residency, row tiling and 255 leaves real
N_HOLD = 100_000
N_PARITY_ROWS = 131_072  # the reduced pallas-vs-segsum pair
FUSED_K = 8
# iteration 0 absorbs the bias unfused, then two whole K-blocks: the
# second block must run the program the first one compiled
N_ITERS = 1 + 2 * FUSED_K
AUC_FLOOR = 0.85         # 0.880 at 17 iterations on this data (v5e)
AUC_TOL = 0.02           # tests/test_wave.py, iteration-matched

BASE = {
    "objective": "binary", "num_leaves": 255, "max_bin": 255,
    "learning_rate": 0.1, "min_sum_hessian_in_leaf": 100.0,
    "min_data_in_leaf": 0, "verbose": -1, "metric": "None",
}
HEADLINE = {"wave_splits": True, "use_quantized_grad": True,
            "fused_iters": FUSED_K}

# what the plan of models/tier.py gives this shape on one chip
EXPECT_DEFAULTS = {
    "hist_impl": "pallas", "tier": "speculative", "wave": False,
    "quantize": 0, "c2f": False, "routed": True,
    "split_kernel": "pallas",
    "learner": "serial", "num_shards": 1,
}
EXPECT_HEADLINE = {
    "hist_impl": "pallas", "tier": "two_col", "wave": True,
    "c2f": True, "refine_shift": 4, "routed": True,
    # c2f scans coarse + window in XLA; the gate is in the record
    "split_kernel": "xla",
    "learner": "serial", "num_shards": 1,
}


class SmokeFailure(Exception):
    """A leg of the smoke did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def acquire_chip():
    """Refuse anything but a TPU, with Pallas compiled, not interpreted
    (also the guard of tools/check_routed_kernels.py and
    tools/check_tpu_integration.py)."""
    import jax
    from lightgbm_tpu.utils.env import pallas_interpret
    devs = jax.devices()
    dev = devs[0]
    log(f"platform={dev.platform} device_kind={dev.device_kind} "
        f"device_count={len(devs)} jax={jax.__version__}")
    if dev.platform != "tpu":
        sys.exit(f"{os.path.basename(sys.argv[0])}: needs a TPU, JAX "
                 f"found platform {dev.platform!r}; there is no CPU "
                 f"fallback")
    check(not os.environ.get("LTPU_PALLAS_INTERPRET"),
          "LTPU_PALLAS_INTERPRET is set: kernels would run interpreted")
    check(not pallas_interpret(), "pallas_interpret() is true on a TPU")
    return dev, len(devs)


def build_native() -> None:
    """Build cpp/libltpu_io.so from the committed sources, never load
    one that happened to be on disk (the .so files are gitignored)."""
    from lightgbm_tpu.io import native
    cpp = os.path.join(ROOT, "cpp")
    if shutil.which("make") and shutil.which(os.environ.get("CXX", "g++")):
        subprocess.run(["make", "-B", "-C", cpp, "libltpu_io.so"],
                       check=True, stdout=sys.stderr)
        check(native.available(), "the freshly built libltpu_io.so "
                                  "does not load")
        log("binning: native cpp/libltpu_io.so, built here from the "
            "committed sources")
        return
    stale = os.path.join(cpp, "libltpu_io.so")
    if os.path.exists(stale):
        os.remove(stale)
    check(not native.available(), "a native lib loaded without a build")
    log("binning: the Python path ran (no make/g++ on this machine)")


def auc(y, pred) -> float:
    import numpy as np
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.metrics import AUCMetric
    return float(AUCMetric(Config()).eval(np.asarray(y, np.float64), pred))


def train_leg(name, extra, expect, ds, Xh, yh, tmp):
    """One lgb.train at full width; returns (booster, holdout AUC)."""
    import jax
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.telemetry import read_records
    tele = os.path.join(tmp, f"{name}.jsonl")
    t0 = time.time()
    bst = lgb.train(dict(BASE, **extra, telemetry_file=tele), ds,
                    num_boost_round=N_ITERS, verbose_eval=False)
    jax.block_until_ready(bst._gbdt._score)
    t_train = time.time() - t0
    summ = bst._gbdt.telemetry_summary()
    tier = bst._gbdt.tier_decision
    log(f"[{name}] tier record: {json.dumps(tier, sort_keys=True)}")
    check(summ["tier"] == tier["tier"] and summ["backend"] == "tpu",
          f"[{name}] the run's telemetry recorded tier {summ['tier']!r} "
          f"on backend {summ['backend']!r}")
    for key, want in expect.items():
        check(tier[key] == want,
              f"[{name}] tier record {key}={tier[key]!r}, the gates give "
              f"this shape {want!r}")
    check(bst.num_trees() == N_ITERS,
          f"[{name}] {bst.num_trees()} trees after {N_ITERS} iterations")
    leaves = [t.num_leaves for t in bst._gbdt.models]
    check(min(leaves) == BASE["num_leaves"],
          f"[{name}] trees stopped short of 255 leaves: {leaves}")
    t0 = time.time()
    pred = bst.predict(Xh)
    t_pred = time.time() - t0
    check(pred.shape == (len(yh),) and bool(np.isfinite(pred).all()),
          f"[{name}] holdout predictions not finite of shape "
          f"({len(yh)},)")
    a = auc(yh, pred)
    check(np.isfinite(a) and a > AUC_FLOOR,
          f"[{name}] holdout AUC {a:.4f} not above {AUC_FLOOR}")
    # round trip: model text -> a fresh Booster -> the same scores
    again = lgb.Booster(model_str=bst.model_to_string()).predict(Xh)
    check(float(np.abs(again - pred).max()) <= 1e-12,
          f"[{name}] reloaded model predicts differently "
          f"(max |diff| {float(np.abs(again - pred).max()):.3e})")
    bst._gbdt._telemetry.close(log=False)
    blocks = [r for r in read_records(tele) if r["type"] == "superstep"]
    if extra.get("fused_iters", 1) > 1:
        check([b["k"] for b in blocks] == [FUSED_K, FUSED_K],
              f"[{name}] expected two {FUSED_K}-iteration blocks, got "
              f"{[b['k'] for b in blocks]}")
        again_compiles = blocks[1]["counters"].get("xla_compiles", 0)
        check(again_compiles == 0,
              f"[{name}] the second K-block compiled {again_compiles} "
              f"programs; it must hit the jit cache")
    log(f"[{name}] {N_ITERS} iterations, holdout AUC {a:.4f}, "
        f"{summ.get('xla_compiles', 0):.0f} compiles "
        f"({summ.get('xla_compile_secs', 0.0):.1f} s), "
        f"{summ.get('hist_passes', 0):.0f} histogram passes; "
        f"smoke timing: train {t_train:.1f} s, predict {t_pred:.1f} s")
    return bst, a


def parity_leg(X, y) -> None:
    """The headline tier with the Pallas kernels and with their segsum
    twins (device_type=cpu keeps hist_impl=segsum on the same device,
    tools/check_tpu_integration.py): the same trees, split for split."""
    import numpy as np
    import lightgbm_tpu as lgb
    t0 = time.time()
    models = {}
    for dev in ("tpu", "cpu"):
        p = dict(BASE, **HEADLINE, num_leaves=31, device_type=dev)
        ds = lgb.Dataset(X[:N_PARITY_ROWS], label=y[:N_PARITY_ROWS],
                         params=p)
        models[dev] = lgb.train(p, ds, num_boost_round=1 + FUSED_K,
                                verbose_eval=False)
    impls = {d: m._gbdt.tier_decision["hist_impl"]
             for d, m in models.items()}
    check(impls == {"tpu": "pallas", "cpu": "segsum"},
          f"parity pair ran {impls}")
    check(models["tpu"]._gbdt.tier_decision["c2f"],
          "parity pair left the c2f tier")
    for i, (tp, ts) in enumerate(zip(models["tpu"]._gbdt.models,
                                     models["cpu"]._gbdt.models)):
        n = tp.num_leaves - 1
        check(tp.num_leaves == ts.num_leaves and
              np.array_equal(tp.split_feature[:n], ts.split_feature[:n])
              and np.array_equal(tp.threshold_bin[:n],
                                 ts.threshold_bin[:n]),
              f"pallas and segsum trees differ at tree {i}")
    log(f"parity: pallas == segsum structurally over {1 + FUSED_K} trees "
        f"({N_PARITY_ROWS} rows, headline tier); smoke timing "
        f"{time.time() - t0:.1f} s")


def serve_leg(bst, Xh) -> None:
    """An in-process serve.Server answers predict and explain requests,
    and says where its engines compute."""
    import numpy as np
    from lightgbm_tpu.serve import Server
    t0 = time.time()
    srv = Server(bst).start()
    try:
        t_start = time.time() - t0
        for lo, n in ((0, 1), (1, 7), (8, 300), (308, 700), (1008, 1500)):
            got = srv.predict(Xh[lo:lo + n])
            want = bst.predict(Xh[lo:lo + n])
            check(got.shape == want.shape and
                  float(np.abs(got - want).max()) <= 1e-9,
                  f"served predict of {n} rows differs from "
                  f"Booster.predict")
        for lo, n in ((0, 3), (3, 64)):
            contrib = srv.explain(Xh[lo:lo + n])
            raw = bst.predict(Xh[lo:lo + n], raw_score=True)
            check(bool(np.isfinite(contrib).all()) and
                  float(np.abs(contrib.sum(axis=1) - raw).max()) <= 1e-6,
                  f"served explain of {n} rows: contributions do not "
                  f"sum to the raw score")
        stats = srv.stats()
        tables = next(iter(srv.registry.current().flat._dev.values()))
        log(f"serve: engines compute on {stats['engine_device']}; the "
            f"forest tables live on {sorted(map(str, tables[0].devices()))}; "
            f"5 predict + 2 explain requests ok; smoke timing: start + "
            f"warm-up {t_start:.1f} s")
    finally:
        srv.stop()


def multichip_leg(X, y, n_dev: int) -> None:
    """The parallel learners on every chip of the host, at full width."""
    import jax
    import lightgbm_tpu as lgb
    for learner, extra, rounds in (("data", HEADLINE, 1 + FUSED_K),
                                   ("data2d", {}, 3)):
        p = dict(BASE, **extra, tree_learner=learner)
        t0 = time.time()
        bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                        num_boost_round=rounds, verbose_eval=False)
        g = bst._gbdt
        jax.block_until_ready(g._score)
        tier = g.tier_decision
        check(g._dist is not None and g._dist.num_shards == n_dev and
              tier["learner"] == learner and tier["num_shards"] == n_dev,
              f"tree_learner={learner}: {tier['learner']} over "
              f"{tier['num_shards']} shards on {n_dev} devices")
        check(tier["hist_impl"] == "pallas",
              f"tree_learner={learner}: hist_impl={tier['hist_impl']}")
        shards = g._xt.addressable_shards
        check(len({s.device for s in shards}) == n_dev and
              all(s.data.size * n_dev == g._xt.size for s in shards),
              f"tree_learner={learner}: the bin matrix is not spread "
              f"evenly over the {n_dev} devices")
        # devices past the first held nothing before this leg: each
        # must now hold at least its shard of the bin matrix
        in_use = [d.memory_stats()["bytes_in_use"] for d in jax.devices()]
        check(min(in_use[1:]) >= g._xt.nbytes // n_dev,
              f"tree_learner={learner}: bytes in use per device {in_use}: "
              f"the bin matrix sits on device 0")
        log(f"[{learner}] {rounds} iterations over mesh "
            f"{tier['mesh_shape']}, tier {tier['tier']}, bytes in use "
            f"per device {in_use}; smoke timing {time.time() - t0:.1f} s")
        del bst, g


def main() -> int:
    t_start = time.time()
    import lightgbm_tpu as lgb
    from bench import make_higgs_shaped
    from lightgbm_tpu.utils.env import configure_compile_cache
    from lightgbm_tpu.utils.telemetry import (counters_snapshot,
                                              install_jax_hooks)
    cache_dir = configure_compile_cache()
    install_jax_hooks()
    dev, n_dev = acquire_chip()
    log(f"compile cache: {cache_dir}")
    build_native()

    t0 = time.time()
    X, y = make_higgs_shaped(N_ROWS + N_HOLD, N_FEATURES, seed=0)
    Xh, yh = X[N_ROWS:], y[N_ROWS:]
    X, y = X[:N_ROWS], y[:N_ROWS]
    ds = lgb.Dataset(X, label=y, params=BASE)
    ds.construct()
    log(f"data: {N_ROWS} x {N_FEATURES} + {N_HOLD} holdout, binned; "
        f"smoke timing {time.time() - t0:.1f} s")

    with tempfile.TemporaryDirectory() as tmp:
        bst_d, auc_d = train_leg("defaults", {}, EXPECT_DEFAULTS, ds,
                                 Xh, yh, tmp)
        _, auc_h = train_leg("headline", HEADLINE, EXPECT_HEADLINE, ds,
                             Xh, yh, tmp)
    check(abs(auc_d - auc_h) < AUC_TOL,
          f"iteration-matched AUCs differ by more than {AUC_TOL}: "
          f"defaults {auc_d:.4f}, headline {auc_h:.4f}")
    parity_leg(X, y)
    serve_leg(bst_d, Xh)
    if n_dev > 1:
        multichip_leg(X, y, n_dev)

    c = counters_snapshot()
    log(f"compiles: {c.get('xla_compiles', 0):.0f} requests "
        f"({c.get('xla_compile_secs', 0.0):.1f} s); persistent cache "
        f"{cache_dir}: {c.get('jax_cache_hits', 0):.0f} hits, "
        f"{c.get('jax_cache_misses', 0):.0f} writes")
    mem = dev.memory_stats() or {}
    log(f"device memory: peak {mem.get('peak_bytes_in_use', 0) / 2**20:.0f}"
        f" MiB of {mem.get('bytes_limit', 0) / 2**20:.0f} MiB")
    log(f"smoke timing: {time.time() - t_start:.1f} s wall, compilation "
        f"included")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
