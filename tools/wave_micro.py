"""Microbench the wave-body components at bench shape (TPU).

Times, interleaved (shared-chip A/B rule): the multi histogram pass
(old vs new tiling via rows_per_block), the vectorized routing block,
the vmapped 2W-children split search, and a small-table take — to
attribute the per-wave overhead seen in prof_wave.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops.histogram import histogram_pallas_multi
from lightgbm_tpu.ops.split import SplitParams, find_best_split

N = int(os.environ.get("MB_ROWS", "10502144"))  # 16384-multiple
F = 32
B = 64
W = 42
L = 255


def sync(x):
    return jax.block_until_ready(x)


def timeit(fn, *args, reps=6):
    sync(fn(*args))  # compile
    ts = []
    for _ in range(reps):
        t0 = time.time()
        sync(fn(*args))
        ts.append(time.time() - t0)
    return min(ts), sorted(ts)[len(ts) // 2]


def main():
    rng = np.random.RandomState(0)
    xt = jnp.asarray(rng.randint(0, 63, size=(F, N), dtype=np.int32))
    vals = jnp.ones((N, 3), jnp.float32)
    sel = jnp.asarray(rng.randint(-1, W, size=N, dtype=np.int32))
    leaf_idx = jnp.asarray(rng.randint(0, L, size=N, dtype=np.int32))

    sp = SplitParams(max_bin=B, min_data_in_leaf=0,
                     min_sum_hessian_in_leaf=100.0)
    nb = jnp.full(F, 63, jnp.int32)
    mt = jnp.zeros(F, jnp.int32)
    cat = jnp.zeros(F, bool)
    fmask = jnp.ones(F, bool)

    # 1) multi pass, old (2048) vs new (16384) tiling
    for rpb in (2048, 16384):
        f = jax.jit(lambda x, v, s, r=rpb: histogram_pallas_multi(
            x, v, s, B, W, r, exact=True))
        mn, md = timeit(f, xt, vals, sel)
        print(f"multi pass rpb={rpb}: min {mn*1e3:.1f}ms median {md*1e3:.1f}ms",
              flush=True)

    # 2) routing block (select chain + table takes + bit test)
    ids = jnp.asarray(rng.choice(L, W, replace=False).astype(np.int32))
    feat_w = jnp.asarray(rng.randint(0, F, W, dtype=np.int32))
    mask_w = jnp.asarray(rng.random_sample((W, B)) < 0.5)

    @jax.jit
    def routing(leaf_idx, xt, ids, feat_w, mask_w):
        w_ar = jnp.arange(W, dtype=jnp.int32)
        leaf_to_w = jnp.full(L + 1, -1, jnp.int32).at[ids].set(w_ar)
        w_row = leaf_to_w[leaf_idx]
        in_wave = w_row >= 0
        w_safe = jnp.where(in_wave, w_row, 0)
        nw = (B + 31) // 32
        bits = jnp.pad(mask_w.astype(jnp.uint32), ((0, 0), (0, nw * 32 - B)))
        words = jnp.sum(bits.reshape(W, nw, 32) <<
                        jnp.arange(32, dtype=jnp.uint32)[None, None, :],
                        axis=2).reshape(-1)
        csel = feat_w[w_safe]
        col = jnp.zeros(N, jnp.int32)
        for g in range(F):
            col = jnp.where(csel == g, xt[g], col)
        wd = words[w_safe * nw + (col >> 5)]
        gl = in_wave & (((wd >> (col & 31).astype(jnp.uint32)) & 1) > 0)
        return jnp.where(in_wave & gl, w_row, jnp.int32(-1))

    mn, md = timeit(routing, leaf_idx, xt, ids, feat_w, mask_w)
    print(f"routing block: min {mn*1e3:.1f}ms median {md*1e3:.1f}ms",
          flush=True)

    # 3) vmapped children split search (2W leaves)
    ch_hist = jnp.asarray(rng.random_sample((2 * W, F, B, 3)).astype(
        np.float32))
    ch_stats = jnp.asarray(
        np.abs(rng.random_sample((2 * W, 3))).astype(np.float32) * 1000)

    @jax.jit
    def children(ch_hist, ch_stats):
        return jax.vmap(lambda h, s: find_best_split(
            h, s, nb, mt, cat, fmask, sp))(ch_hist, ch_stats)["gain"]

    mn, md = timeit(children, ch_hist, ch_stats)
    print(f"vmap children split: min {mn*1e3:.1f}ms median {md*1e3:.1f}ms",
          flush=True)

    # 4) small-table take + elementwise wheres (leaf update block)
    @jax.jit
    def leafupd(leaf_idx, sel, ids):
        w_ar = jnp.arange(W, dtype=jnp.int32)
        leaf_to_w = jnp.full(L + 1, -1, jnp.int32).at[ids].set(w_ar)
        w_row = leaf_to_w[leaf_idx]
        new_ids = jnp.arange(W, dtype=jnp.int32) + 100
        return jnp.where((w_row >= 0) & (sel < 0), new_ids[
            jnp.where(w_row >= 0, w_row, 0)], leaf_idx)

    mn, md = timeit(leafupd, leaf_idx, sel, ids)
    print(f"leaf update block: min {mn*1e3:.1f}ms median {md*1e3:.1f}ms",
          flush=True)


if __name__ == "__main__":
    main()
