"""Interleaved A/B of the int8 value operand at bench shape (TPU).

Trains two boosters on the same constructed dataset — vals_i8 on vs
off — alternating single iterations (an A/B is only valid interleaved
in one process), and checks the resulting models agree (int8 holds
the same exact ints as f32, so trees should be structurally
identical).

Env: AB_ROWS (default 10_500_000), AB_BINS (255), AB_ITERS (10 per
side), AB_MDIL (min_data_in_leaf, default 0).
"""
import dataclasses
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
    rows = int(os.environ.get("AB_ROWS", "10500000"))
    bins = int(os.environ.get("AB_BINS", "255"))
    iters = int(os.environ.get("AB_ITERS", "10"))
    mdil = int(os.environ.get("AB_MDIL", "0"))

    import lightgbm_tpu as lgb
    from bench import make_higgs_shaped

    X, y = make_higgs_shaped(rows, 28)
    params = {
        "objective": "binary", "num_leaves": 255, "max_bin": bins,
        "learning_rate": 0.1, "min_sum_hessian_in_leaf": 100.0,
        "min_data_in_leaf": mdil, "verbose": -1, "metric": "None",
        "wave_splits": True, "use_quantized_grad": True,
    }
    d = lgb.Dataset(X, label=y, params=params)
    d.construct()

    boosters = {}
    for name, flag in (("i8", True), ("f32", False)):
        b = lgb.Booster(params=params, train_set=d)
        g = b._gbdt
        g.grow_params = dataclasses.replace(g.grow_params, vals_i8=flag)
        boosters[name] = b

    # warmup/compile both
    for name, b in boosters.items():
        t0 = time.time()
        b.update(); b.update()
        print(f"{name}: warmup {time.time() - t0:.1f}s", flush=True)

    times = {"i8": [], "f32": []}
    for it in range(iters):
        for name in ("i8", "f32"):
            b = boosters[name]
            t0 = time.time()
            b.update()
            times[name].append(time.time() - t0)
        print(f"iter {it}: i8 {times['i8'][-1]:.3f} "
              f"f32 {times['f32'][-1]:.3f}", flush=True)

    out = {}
    for name, ts in times.items():
        ts = sorted(ts)
        out[f"{name}_median_s"] = round(ts[len(ts) // 2], 4)
        out[f"{name}_min_s"] = round(ts[0], 4)
    # structural agreement: same data, same noise stream -> identical
    # trees expected (int8 is exact)
    Xs = X[:100000]
    pa = boosters["i8"].predict(Xs, raw_score=True)
    pb = boosters["f32"].predict(Xs, raw_score=True)
    out["pred_max_abs_diff"] = float(np.max(np.abs(pa - pb)))
    out["gain_ms_per_iter"] = round(
        (out["f32_median_s"] - out["i8_median_s"]) * 1e3, 1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
