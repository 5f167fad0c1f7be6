"""TPU-side end-to-end kernel validation: pallas vs segsum models.

Trains small boosters on the REAL device twice — once with the Pallas
kernels (device_type=tpu) and once with the segsum reference ops
(device_type=cpu keeps hist_impl=segsum while still executing on the
TPU backend) — and requires structurally identical models for:

- the exact best-first tier (routed arming pass),
- the wave + quantized (+two_col) tier,
- wave + quantized with MISSING values (routed default-direction),
- wave + quantized + coarse-to-fine (reserved miss slot), and
- wave + quantized with CATEGORICAL features (mask-chain routing).

Run after touching ops/histogram.py or ops/grow.py (the CPU suite
pins the segsum half; this closes the kernel half end to end).  It
refuses to run anywhere but on a TPU with Pallas compiled
(``chip_smoke.acquire_chip``): on a CPU backend both twins resolve to
segsum and the comparison proves nothing.
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chip_smoke import acquire_chip  # noqa: E402

acquire_chip()

import lightgbm_tpu as lgb  # noqa: E402

N, F = 262144, 12
rng = np.random.RandomState(0)
X = rng.randn(N, F).astype(np.float32)
logit = X[:, 0] + 0.6 * X[:, 1] * X[:, 1] - 0.8 * (X[:, 2] > 0.3)
y = (rng.random_sample(N) < 1 / (1 + np.exp(-logit))).astype(np.float32)
Xm = X.copy()
Xm[rng.random_sample(Xm.shape) < 0.1] = np.nan
Xc = X.copy()
for c in range(3):
    Xc[:, c] = np.floor(np.abs(Xc[:, c]) * 4) % 11
# c2f engages from 7000 feature x bin units (models/gbdt.py): 28 x 256
Xw = np.hstack([Xm, Xm, Xm[:, :4]])

CASES = {
    "exact": (X, {}, {}),
    "wave": (X, {"wave_splits": True, "use_quantized_grad": True,
                 "min_data_in_leaf": 1, "hist_refinement": False}, {}),
    "wave_missing": (Xm, {"wave_splits": True, "use_quantized_grad": True,
                          "min_data_in_leaf": 1,
                          "hist_refinement": False}, {}),
    "wave_c2f_missing": (Xw, {"wave_splits": True,
                              "use_quantized_grad": True,
                              "min_data_in_leaf": 1, "max_bin": 255,
                              "hist_refinement": True}, {}),
    "wave_categorical": (Xc, {"wave_splits": True,
                              "use_quantized_grad": True,
                              "min_data_in_leaf": 1},
                         {"categorical_feature": [0, 1, 2]}),
}

def train_pair(Xd, extra, dkw):
    models = {}
    for dev in ("tpu", "cpu"):   # cpu => segsum ops on the same device
        p = {"objective": "binary", "num_leaves": 31, "verbose": -1,
             "learning_rate": 0.1, "max_bin": extra.get("max_bin", 63),
             "device_type": dev}
        p.update(extra)
        ds = lgb.Dataset(Xd, label=y, params=p, **dkw)
        models[dev] = lgb.train(p, ds, num_boost_round=5,
                                verbose_eval=False)
    return models


fail = 0
for name, (Xd, extra, dkw) in CASES.items():
    try:
        models = train_pair(Xd, extra, dkw)
    except Exception as exc:  # noqa: BLE001 - reported, run fails
        # a kernel Mosaic refuses fails its case and the run goes on,
        # so one call on the chip names every case that does not lower
        print(f"{name}: RAISED {type(exc).__name__}: "
              f"{str(exc).strip()[-1500:]}", flush=True)
        fail += 1
        continue
    tier = models["tpu"]._gbdt.tier_decision
    impls = {d: m._gbdt.tier_decision["hist_impl"]
             for d, m in models.items()}
    print(f"{name}: tier", tier["tier"], "hist_impl", impls,
          "split_kernel", tier["split_kernel"], "c2f", tier["c2f"],
          flush=True)
    # the case runs what it names, kernels against their segsum twins
    ok = tier["c2f"] == ("c2f" in name) and \
        impls == {"tpu": "pallas", "cpu": "segsum"}
    for tp, tc in zip(models["tpu"]._gbdt.models,
                      models["cpu"]._gbdt.models):
        n = tp.num_leaves - 1
        if tc.num_leaves != tp.num_leaves or \
                not np.array_equal(tp.split_feature[:n],
                                   tc.split_feature[:n]) or \
                not np.array_equal(tp.threshold_bin[:n],
                                   tc.threshold_bin[:n]):
            ok = False
            break
    pt = models["tpu"].predict(Xd[:5000])
    pc = models["cpu"].predict(Xd[:5000])
    pdiff = float(np.max(np.abs(pt - pc)))
    print(f"{name}: structure_equal={ok} pred_max_diff={pdiff:.2e}",
          flush=True)
    if not ok or pdiff > 1e-4:
        fail += 1
print("FAIL" if fail else "ALL TPU INTEGRATION CHECKS PASS")
sys.exit(1 if fail else 0)
