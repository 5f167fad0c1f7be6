"""TPU-side oracle validation of the Pallas histogram kernels.

Run on a machine with a TPU (one process, it takes the chip):
    python tools/check_routed_kernels.py
Compares every Pallas histogram kernel against its independent segsum
oracle at 63 bins and at 255 bins (the primary shape: 28 features x
255 bins, coarse-to-fine shift 4 -> 16 coarse bins + a 32-bin window):
the routed kernel in all three modes (small / children /
children+shift) with and without missing-value routing, the int8 value
operand, the windowed and lane-routed windowed passes, and the
leaf-stats renewal kernel.  It does so at both benchmark widths, each
at its ``fast`` job's lanes: 28 features on the two-column tier (64
lanes) and 67 on the count-carrying one (42 lanes).  The bin matrix is
the kernels' operand as stored and the feature tail is made in VMEM
(``ops/histogram.BinTiling``): 28 features have a tail at 16 bins (32
rows) and none at 32; 67 have one everywhere (72 at 16 bins, 68 at
32), and at 8 coarse bins they run in five chunks of 16 whose last
block overhangs the matrix.  Every integer diff must be 0; the script
exits non-zero otherwise, and it refuses to run anywhere but on a TPU
with Pallas compiled (``chip_smoke.acquire_chip``): on a CPU backend
the kernels would run interpreted and prove nothing about Mosaic.  The
oracles themselves are pinned on the CPU by tests/test_routed.py and
this script closes the kernel half.

Last, the contraction's type (``ops/histogram._accumulate``): the four
batched kernels at both cells' shapes, rows and lanes (21M x 28 on 64
two-column lanes, 20M x 67 on 42), int8 values (the int8 x int8 ->
int32 contraction) against the same integers as float32 (the bf16
one); every diff must be 0, and the ms a pass of each is printed
(medians of 6) with the seconds Mosaic took to compile it, its one-hot
rows, the us a one-hot row the pass measures (the unrouted pass of its
kind at two feature counts, the difference over the rows added), the
stream (rows x us a row) and PASS LESS STREAM, the per-row prologue
that does not shrink with the feature rows: the kernel-alone table of
PERF.md, by one command.
"""
import math
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chip_smoke import acquire_chip  # noqa: E402
from lightgbm_tpu.ops.histogram import (  # noqa: E402
    histogram_pallas_multi, histogram_pallas_multi_routed,
    histogram_pallas_multi_win, histogram_pallas_multi_win_lanes,
    histogram_segsum_multi, histogram_segsum_multi_routed,
    histogram_segsum_multi_win, histogram_segsum_multi_win_lanes,
    leaf_stats_pallas, routed_chunk_ok)

N, RPB, L = 262144, 16384, 255
FAILED = []


def report(name, pairs, tol=0.0):
    """Run one check (``pairs()`` -> {label: (kernel, oracle)}), print
    its max-abs diffs; a diff above ``tol`` or a kernel that does not
    compile fails it.  The error is printed and the run goes on, so
    one call on the chip names every kernel Mosaic refuses."""
    try:
        vals = {k: float(np.abs(np.asarray(a, np.float64) -
                                np.asarray(b, np.float64)).max())
                for k, (a, b) in pairs().items()}
    except Exception as exc:  # noqa: BLE001 - reported, run fails
        print(name, "RAISED", type(exc).__name__,
              str(exc).strip()[:1500], flush=True)
        FAILED.append(name)
        return
    print(name, " ".join(f"{k}: {v:g}" for k, v in vals.items()),
          flush=True)
    if not all(v <= tol for v in vals.values()):
        FAILED.append(name)


def check_bins(F: int, W: int, two_col: bool, B: int, shift: int,
               rng) -> None:
    """All kernel-vs-oracle pairs over ``F`` features at ``B`` fine
    bins, ``W`` lanes a pass; the c2f stage collapses the bins
    ``2^shift``-to-1 and refines a ``2 << shift`` bin window, as
    ops/grow.py does."""
    tag = f"[{F} features, {B} bins]"
    Bc, R = ((B - 1) >> shift) + 1, 2 << shift
    bins = rng.randint(0, B, size=(F, N)).astype(np.uint8)
    g = rng.randint(-120, 121, size=N).astype(np.float32)
    h = rng.randint(0, 121, size=N).astype(np.float32)
    vals = np.stack([g, h, np.ones(N, np.float32)], -1)
    li = rng.randint(0, 200, size=N).astype(np.int32)
    xb, vb, lb = jnp.asarray(bins), jnp.asarray(vals), jnp.asarray(li)
    v8 = jnp.asarray(vals.astype(np.int8))
    selw = jnp.asarray(li % W)

    def tables(W, n_ids=200, new_lo=200, new_hi=255, rows=5):
        ids = rng.choice(n_ids, size=W, replace=False).astype(np.int32)
        t = [ids, rng.randint(0, F, size=W), rng.randint(0, B - 1, size=W),
             rng.randint(new_lo, new_hi, size=W)]
        t += [rng.randint(0, 2, size=W) for _ in range(rows - 4)]
        return np.stack(t).astype(np.int32)

    def routed_pair(name, vals_k, leaf, tbl, max_bin, **kw):
        if not routed_chunk_ok(max_bin, F, 128, RPB):
            # ops/grow.py routes in XLA at such a shape
            print(f"{tag} {name}: features chunk at {max_bin} bins, "
                  f"no routed pass", flush=True)
            return

        def pairs():
            hp, lp, sp_ = histogram_pallas_multi_routed(
                xb, vals_k, leaf, jnp.asarray(tbl), max_bin, W, RPB,
                exact=True, two_col=two_col, **kw)
            hs, ls, ss = histogram_segsum_multi_routed(
                xb, vb, leaf, jnp.asarray(tbl), max_bin, W,
                two_col=two_col, **kw)
            return {"hist": (hp, hs), "li": (lp, ls), "sel": (sp_, ss)}
        report(f"{tag} {name}", pairs)

    for mode, Wt in (("small", W), ("children", W // 2)):
        tbl = tables(Wt)
        tbl[0, Wt - 2:] = L                  # two invalid lanes
        routed_pair(f"routed {mode}", vb, lb, tbl, B, mode=mode)
        if mode == "children":
            routed_pair("routed children+shift", vb, lb, tbl, Bc,
                        shift=shift, mode=mode)
    # ids above 256 are not bf16-exact: pins the three bytes the new
    # leaf id rides as (silent corruption at num_leaves>257 otherwise;
    # from 65792 on a high byte of two would pass 256 itself)
    routed_pair("routed L>256 ids", vb,
                jnp.asarray(rng.randint(0, 500, size=N).astype(np.int32)),
                tables(W, n_ids=500, new_lo=257, new_hi=511), B,
                mode="small")
    routed_pair("routed L>65792 ids", vb,
                jnp.asarray(rng.randint(0, 500, size=N).astype(np.int32)),
                tables(W, n_ids=500, new_lo=65792, new_hi=200000), B,
                mode="small")

    # int8 value operand (quantized ints exact in int8/bf16)
    report(f"{tag} int8 multi", lambda: {"hist": (
        histogram_pallas_multi(xb, v8, selw, B, W, RPB, exact=True,
                               two_col=two_col),
        histogram_segsum_multi(xb, vb, selw, B, W, two_col=two_col))})
    # coarse pass (bins collapsed in-kernel)
    report(f"{tag} int8 multi coarse", lambda: {"hist": (
        histogram_pallas_multi(xb, v8, selw, Bc, W, RPB, exact=True,
                               two_col=two_col, shift=shift),
        histogram_segsum_multi(xb, vb, selw, Bc, W, two_col=two_col,
                               shift=shift))})

    # lane-routed windowed pass (li + child-id tables, no (N,) selector)
    ids_w = jnp.asarray(rng.choice(200, size=W, replace=False)
                        .astype(np.int32))
    lo_w = jnp.asarray(rng.randint(0, B - R, size=(W, F))
                       .astype(np.int32))
    report(f"{tag} win_lanes", lambda: {"hist": (
        histogram_pallas_multi_win_lanes(xb, v8, lb, ids_w, lo_w, R, W,
                                         RPB, exact=True,
                                         two_col=two_col),
        histogram_segsum_multi_win_lanes(xb, vb, lb, ids_w, lo_w, R, W,
                                         two_col=two_col))})

    # missing-value variants: 6-row tables + per-feature miss bins
    mb = np.full(F, B - 1, np.int32)
    mb[::3] = -1                             # some without missing
    mbj = jnp.asarray(mb)
    tbl6 = tables(W, rows=6)
    routed_pair("routed+miss", v8, lb, tbl6, B, mode="small",
                miss_bin=mbj)
    # routed coarse with the reserved missing slot (Bc value bins + 1)
    routed_pair("routed+miss+shift", v8, lb, tbl6, Bc + 1, shift=shift,
                mode="small", miss_bin=mbj)
    # windowed with missing exclusion
    report(f"{tag} win+miss", lambda: {"hist": (
        histogram_pallas_multi_win(xb, v8, selw, lo_w, R, W, RPB,
                                   exact=True, two_col=two_col,
                                   miss_bin=mbj),
        histogram_segsum_multi_win(xb, vb, selw, lo_w, R, W,
                                   two_col=two_col, miss_bin=mbj))})
    # coarse pass with the reserved missing slot, no routing: at 67
    # features and 17 slots it runs chunked (five blocks of 16)
    report(f"{tag} multi coarse+miss", lambda: {"hist": (
        histogram_pallas_multi(xb, v8, selw, Bc + 1, W, RPB, exact=True,
                               two_col=two_col, shift=shift,
                               miss_bin=mbj),
        histogram_segsum_multi(xb, vb, selw, Bc + 1, W, two_col=two_col,
                               shift=shift, miss_bin=mbj))})


def check_int8_contraction(cell: str, F: int, rows: int, W: int,
                           two_col: bool, rng) -> None:
    """The four batched kernels as the ``fast`` job of ``cell`` runs
    them (c2f shift 4: 16 coarse bins, a 32-bin window), int8 values
    against the same integers as float32: equal bit for bit, and the
    ms a pass of each."""
    n = -(-rows // RPB) * RPB
    xb = jnp.asarray(np.random.default_rng(F).integers(
        0, 255, size=(F, n), dtype=np.uint8))
    g = rng.randint(-120, 121, size=n).astype(np.int8)
    h = rng.randint(0, 121, size=n).astype(np.int8)
    v8 = jnp.asarray(np.stack([g, h, np.ones(n, np.int8)], -1))
    vf = v8.astype(jnp.float32)
    li = rng.randint(0, 200, size=n).astype(np.uint8)
    lb, selw = jnp.asarray(li), jnp.asarray(li.astype(np.int32) % W)
    ids = rng.choice(200, size=W, replace=False).astype(np.int32)
    tbl = jnp.asarray(np.stack(
        [ids, rng.randint(0, F, size=W), rng.randint(0, 254, size=W),
         rng.randint(200, 255, size=W), rng.randint(0, 2, size=W)]
    ).astype(np.int32))
    ids_w = jnp.asarray(ids)
    lo_w = jnp.asarray(rng.randint(0, 255 - 32, size=(W, F))
                       .astype(np.int32))
    kw = dict(exact=True, two_col=two_col)
    # (kind of pass, its bins): the unrouted pass of each kind takes
    # the stream's slope below
    passes = {
        "routed coarse": ("coarse", lambda v: histogram_pallas_multi_routed(
            xb, v, lb, tbl, 16, W, RPB, shift=4, mode="small", **kw)),
        "win_lanes refine": (
            "refine", lambda v: histogram_pallas_multi_win_lanes(
                xb, v, lb, ids_w, lo_w, 32, W, RPB, **kw)),
        "multi coarse": ("coarse", lambda v, x=xb: histogram_pallas_multi(
            x, v, selw, 16, W, RPB, shift=4, **kw)),
        "multi_win refine": (
            "refine", lambda v, x=xb, lo=lo_w: histogram_pallas_multi_win(
                x, v, selw, lo, 32, W, RPB, **kw)),
    }
    bins = {"coarse": 16, "refine": 32}

    def onehot_rows(f, b):
        """The int8 one-hot rows a pass of ``f`` features streams
        (``ops/histogram._accumulate``: up to the (32, 128) tile)."""
        return (f + -f % (32 // math.gcd(b, 32))) * b

    def timed(fn, *args):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))  # compiles
        first = time.perf_counter() - t0
        ms = []
        for _ in range(6):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ms.append((time.perf_counter() - t0) * 1e3)
        med = statistics.median(ms)
        return jax.tree_util.tree_leaves(out), med, first - med / 1e3

    ms8 = {}
    for name, (_, fn) in passes.items():
        took = {}

        def pairs(fn=fn, took=took):
            o8, took["int8"], took["compile_s"] = timed(fn, v8)
            of, took["float32"], _ = timed(fn, vf)
            return {f"out{i}": p for i, p in enumerate(zip(o8, of))}
        report(f"[{cell}: {rows} x {F}, {W} lanes] {name}, int8 against "
               f"float32 values", pairs)
        if took:
            ms8[name] = took["int8"]
            print(f"    ms a pass: int8 {took['int8']:.2f}, float32 "
                  f"{took.get('float32', float('nan')):.2f}; int8 kernel "
                  f"compiled in {took['compile_s']:.1f} s", flush=True)
    # what a one-hot row costs: the unrouted pass of each kind again at
    # fewer features, the difference over the one-hot rows taken away
    f2 = {28: 14, 67: 60}.get(F, F // 2)
    us_a_row = {}
    for name, kind in (("multi coarse", "coarse"),
                       ("multi_win refine", "refine")):
        if name not in ms8:
            continue
        _, ms2, _ = timed(passes[name][1], v8, xb[:f2],
                          *((lo_w[:, :f2],) if kind == "refine" else ()))
        us_a_row[kind] = (ms8[name] - ms2) * 1e3 / (
            onehot_rows(F, bins[kind]) - onehot_rows(f2, bins[kind]))
    for name, (kind, _) in passes.items():
        if name in ms8 and kind in us_a_row:
            r = onehot_rows(F, bins[kind])
            stream = r * us_a_row[kind] / 1e3
            print(f"    [{cell}] {name}: {ms8[name]:.2f} ms a pass = "
                  f"{r} one-hot rows x {us_a_row[kind]:.2f} us a row "
                  f"({stream:.2f} ms of stream, slope of {F} against {f2} "
                  f"features) + {ms8[name] - stream:.2f} ms PASS LESS "
                  f"STREAM", flush=True)


def check_leaf_stats(rng) -> None:
    """leaf-stats (renewal) kernel vs numpy: the hi/lo bf16 split
    carries ~2^-16 of each summand's magnitude."""
    li = rng.randint(0, 200, size=N).astype(np.int32)
    gf = rng.randn(N).astype(np.float32)
    hf = np.abs(rng.randn(N)).astype(np.float32)
    mf = (rng.random_sample(N) < 0.9).astype(np.float32)
    v = np.stack([gf * mf, hf * mf, mf], -1).astype(np.float64)
    ref = np.zeros((256, 3), np.float64)
    mag = np.zeros((256, 3), np.float64)
    np.add.at(ref, li, v)
    np.add.at(mag, li, np.abs(v))

    def pairs():
        lsp = np.asarray(leaf_stats_pallas(
            jnp.asarray(li), jnp.asarray(gf), jnp.asarray(hf),
            jnp.asarray(mf), RPB))
        return {"err / sum|v|": ((lsp - ref) / (mag + 1e-3), 0.0)}
    report("leaf_stats", pairs, tol=2.0 ** -14)


def main() -> int:
    acquire_chip()
    rng = np.random.RandomState(0)
    # (features, lanes, two-column): the two cells' `fast` jobs
    for F, W, two_col in ((28, 64, True), (67, 42, False)):
        check_bins(F, W, two_col, 63, 3, rng)
        check_bins(F, W, two_col, 255, 4, rng)
    check_leaf_stats(rng)
    # (cell, features, rows, lanes, two-column)
    for cell in (("higgs28.fast", 28, 21_000_000, 64, True),
                 ("criteo67.fast", 67, 20_000_000, 42, False)):
        check_int8_contraction(*cell, rng)
    print("FAILED: " + ", ".join(FAILED) if FAILED
          else "ALL KERNEL CHECKS PASS")
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
