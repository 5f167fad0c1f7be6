"""Per-iteration phase profile at bench shape (VERDICT r2 weak#2).

Trains a few iterations of the bench config and prints:
  - per-iteration wall times (median/min),
  - the host-side phase breakdown from utils/profiling (prep, dispatch,
    device_wait, fetch, to_tree, renew, score_update),
  - arm-pass counts per tree (from the growth loop's n_arm_passes),
  - standalone single/multi histogram-pass kernel times on the same
    device matrix, interleaved, so device_wait decomposes into passes
    vs loop overhead.

Env:
  PROF_ROWS   (default 10_500_000)
  PROF_ITERS  (default 10 steady iterations)
  PROF_BINS   (default 63)
  PROF_TOL    speculative_tolerance (default 0.25)
  PROF_QUANT  use_quantized_grad 0/1 (default 1)
  PROF_WAVE   wave_splits 0/1 (default 0)
  PROF_KERNEL 0 to skip the standalone kernel timings
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def sync(x):
    import jax
    return jax.block_until_ready(x)


def main():
    rows = int(os.environ.get("PROF_ROWS", "10500000"))
    iters = int(os.environ.get("PROF_ITERS", "10"))
    bins = int(os.environ.get("PROF_BINS", "63"))
    tol = float(os.environ.get("PROF_TOL", "0.25"))
    quant = int(os.environ.get("PROF_QUANT", "1"))

    import jax
    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils import profiling

    from bench import make_higgs_shaped

    t0 = time.time()
    X, y = make_higgs_shaped(rows, 28)
    print(f"datagen {time.time() - t0:.1f}s", flush=True)

    params = {
        "objective": "binary",
        "num_leaves": 255,
        "max_bin": bins,
        "learning_rate": 0.1,
        "min_sum_hessian_in_leaf": 100.0,
        "min_data_in_leaf": 0,
        "verbose": -1,
        "metric": "None",
        "speculative_tolerance": tol,
        "use_quantized_grad": bool(quant),
        "wave_splits": os.environ.get("PROF_WAVE", "0") == "1",
    }
    t0 = time.time()
    train = lgb.Dataset(X, label=y, params=params)
    train.construct()
    print(f"binning {time.time() - t0:.1f}s", flush=True)

    booster = lgb.Booster(params=params, train_set=train)
    t0 = time.time()
    booster.update()
    booster.update()
    print(f"warmup(2 iters + compiles) {time.time() - t0:.1f}s", flush=True)

    profiling.reset()
    gb = booster._gbdt
    arm = []
    times = []
    for _ in range(iters):
        t1 = time.time()
        booster.update()
        times.append(time.time() - t1)
        arm.append(getattr(gb, "last_arm_passes", -1))
    times_s = sorted(times)
    print(f"\nsteady iters: median {times_s[len(times) // 2]:.3f}s  "
          f"min {times_s[0]:.3f}s  max {times_s[-1]:.3f}s")
    print("arm passes/tree:", arm)
    print("\nphase breakdown (host wall):")
    print(profiling.summary())

    if os.environ.get("PROF_KERNEL", "1") == "1":
        from lightgbm_tpu.ops.histogram import (histogram_pallas,
                                                histogram_pallas_multi)
        gp = gb.grow_params
        xt = gb._xt
        n_pad = xt.shape[1]
        vals = jnp.ones((n_pad, 3), jnp.float32)
        sel = jnp.zeros(n_pad, jnp.int32)
        B = gp.split.max_bin
        W = max(gp.speculate, 2)
        exact = gp.quantize > 0
        # compile both
        sync(histogram_pallas(xt, vals, B, gp.rows_per_block, exact=exact))
        sync(histogram_pallas_multi(xt, vals, sel, B, W,
                                    gp.rows_per_block, exact=exact))
        singles, multis = [], []
        for _ in range(8):
            t1 = time.time()
            sync(histogram_pallas(xt, vals, B, gp.rows_per_block,
                                  exact=exact))
            singles.append(time.time() - t1)
            t1 = time.time()
            sync(histogram_pallas_multi(xt, vals, sel, B, W,
                                        gp.rows_per_block, exact=exact))
            multis.append(time.time() - t1)
        print(f"\nkernel single-pass (B={B}, exact={exact}): "
              f"min {min(singles) * 1e3:.1f}ms median "
              f"{sorted(singles)[4] * 1e3:.1f}ms")
        print(f"kernel multi-pass (W={W}): min {min(multis) * 1e3:.1f}ms "
              f"median {sorted(multis)[4] * 1e3:.1f}ms")
        n_pass = [a + 2 for a in arm if a >= 0]  # root + final? ~a+1..a+2
        if n_pass:
            est = np.median(n_pass) * min(multis)
            print(f"=> est. histogram device time/iter ~{est:.2f}s of "
                  f"median {times_s[len(times) // 2]:.3f}s")

    print(json.dumps({"median_iter_s": times_s[len(times) // 2],
                      "min_iter_s": times_s[0], "arm_passes": arm}))


if __name__ == "__main__":
    main()
