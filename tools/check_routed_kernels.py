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
block overhangs the matrix.  A third width, 155 features on 64 lanes
(at 65,536 rows, what the oracle's memory bears), is there for the
passes whose features CHUNK at the routed bins (two blocks of 80 at 16
coarse bins, ten of 16 at 64 bins, twenty of 8 at 256): there
``histogram_routed`` routes the rows in a step of its own
(``histogram_pallas_route``) ahead of the chunked pass, and the new
leaf vector and the selector have to equal the oracle's as the
one-chunk kernel's do.  Every integer diff must be 0; the script
exits non-zero otherwise, and it refuses to run anywhere but on a TPU
with Pallas compiled (``chip_smoke.acquire_chip``): on a CPU backend
the kernels would run interpreted and prove nothing about Mosaic.  The
oracles themselves are pinned on the CPU by tests/test_routed.py and
this script closes the kernel half.

Last, the order of the int8 one-hot's rows (``ops/histogram``
``_onehot_int8``, ``_feature_bin``): the batched kernels at the three
cells' shapes, rows and lanes (21M x 28 on 64 two-column lanes, 20M x
67 on 42, and a chip of the four-rank cell: 16M x 68 on 42), the
PARENT's build of the one-hot (on the 32-bin grid the ``words`` order,
feature by feature, kept here as the other side) beside the change's,
both in this one process, each side its own trace.  The refine pass
of each kind (32 bins) is what the order changes; the controls are
the routed and the root's coarse passes (16 bins: the slabs on both
sides), the full-resolution pass at 64 and 256 bins (the words on
both sides: against the slabs there the words measured level)
and the routed coarse pass on float32 values (the bf16 contraction),
which also has to equal the int8 one.  Every diff must be 0, and the ms a
pass of each side is printed (medians of 6) with the seconds Mosaic
took to compile it, the one-hot rows it streams, the us a one-hot row
the pass measures (the unrouted pass of its kind at two feature
counts, the difference over the rows added), the stream (rows x us a
row) and PASS LESS STREAM, what does not shrink with the feature rows:
the kernel-alone table of PERF.md, by one command.  A fourth row of
that table is the wide shape of ``benchmark/configs/epsilon2000.json``
(1.2M x 2000: 25 and 50 feature chunks), and for every shape the
routing step alone and, where the features fit one chunk, the routed
coarse pass in its CHUNKED form (the tiler's budget cut for the
trace) beside the one-chunk kernel, outputs equal bit for bit.

    python tools/check_routed_kernels.py [--widths 28,155] [--table a,b]

``--widths`` keeps those feature counts of the oracle half and
``--table`` the named rows of the timing table (all by default; an
empty value, none).
"""
import argparse
import contextlib
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chip_smoke import acquire_chip  # noqa: E402
from lightgbm_tpu.ops import histogram as H  # noqa: E402
from lightgbm_tpu.ops.histogram import (  # noqa: E402
    bin_tiling, histogram_pallas_multi, histogram_pallas_multi_routed,
    histogram_pallas_multi_win, histogram_pallas_multi_win_lanes,
    histogram_pallas_route, histogram_routed,
    histogram_segsum_multi, histogram_segsum_multi_routed,
    histogram_segsum_multi_win, histogram_segsum_multi_win_lanes,
    leaf_stats_pallas)

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
               rng, N: int = N) -> None:
    """All kernel-vs-oracle pairs over ``F`` features at ``B`` fine
    bins, ``W`` lanes a pass; the c2f stage collapses the bins
    ``2^shift``-to-1 and refines a ``2 << shift`` bin window, as
    ops/grow.py does.  The routed pairs go through
    ``histogram_routed``, as ops/grow.py does: the routed kernel where
    the features fit one chunk, the routing step and the chunked pass
    where they do not."""
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
        chunks = bin_tiling(max_bin, F, 128, RPB).chunks

        def pairs():
            hp, lp, sp_ = histogram_routed(
                xb, vals_k, leaf, jnp.asarray(tbl), max_bin, W, RPB,
                exact=True, two_col=two_col, dead_id=L, **kw)
            hs, ls, ss = histogram_segsum_multi_routed(
                xb, vb, leaf, jnp.asarray(tbl), max_bin, W,
                two_col=two_col, **kw)
            return {"hist": (hp, hs), "li": (lp, ls), "sel": (sp_, ss)}
        report(f"{tag} {name} ({chunks} feature chunk"
               f"{'s: routing step' if chunks > 1 else ': routed kernel'})",
               pairs)

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


def _parent_onehot_int8(xb, b_pad):
    """The int8 one-hot as the parent commit built it, kept here as
    the other side: on the 32-bin grid the ``words`` order, feature by
    feature (int8 row ``r * b_pad + b`` holds ``xb[r] == b``: each
    feature's row broadcast over the sublanes of its ``(b_pad / 4, T)``
    words, compared with their iota and selected); off it the slabs,
    which both sides share."""
    if b_pad % 32:
        return _CHANGE["_onehot_int8"](xb, b_pad)
    from jax.experimental.pallas import tpu as pltpu
    R, T = xb.shape
    hi, byte = xb >> 2, jnp.left_shift(1, (xb & 3) << 3)
    words = jnp.where(
        hi[:, None, :] ==
        jax.lax.broadcasted_iota(jnp.int32, (R, b_pad // 4, T), 1),
        byte[:, None, :], 0).reshape(R * b_pad // 4, T)
    return pltpu.bitcast(words, jnp.int8)


def _parent_rows_to_feature_bin(acc, R, b_pad):
    if b_pad % 32:
        return _CHANGE["_rows_to_feature_bin"](acc, R, b_pad)
    return acc[..., :R * b_pad, :].reshape(
        *acc.shape[:-2], R, b_pad, acc.shape[-1])


def _parent_onehot_rows(R, b_pad):
    if b_pad % 32:
        return _CHANGE["_onehot_rows"](R, b_pad)
    return R * b_pad


_CHANGE = {k: getattr(H, k) for k in (
    "_onehot_int8", "_rows_to_feature_bin", "_onehot_rows")}
SIDES = {"parent": {"_onehot_int8": _parent_onehot_int8,
                    "_rows_to_feature_bin": _parent_rows_to_feature_bin,
                    "_onehot_rows": _parent_onehot_rows},
         "change": _CHANGE}


@contextlib.contextmanager
def build_of(side: str):
    """The three functions of ops/histogram.py that know the order of
    the int8 one-hot's rows, as ``side`` has them, while a pass
    traces."""
    for k, v in SIDES[side].items():
        setattr(H, k, v)
    try:
        yield
    finally:
        for k, v in _CHANGE.items():
            setattr(H, k, v)


def check_onehot_order(cell: str, F: int, rows: int, W: int,
                       two_col: bool, rng) -> None:
    """The batched kernels as the ``fast`` job of ``cell`` runs them
    (c2f shift 4: 16 coarse bins, a 32-bin window), the parent's build
    of the int8 one-hot beside the change's in one process: outputs
    equal bit for bit, the ms a pass of each side, the one-hot rows it
    streams and the us a row.  The refine pass of each kind is what
    the one-hot's order changes; the controls are the routed and the
    root's coarse passes (16 bins: the slabs on both sides), the
    unrouted full-resolution pass at 64 and 256 bins (which no cell
    runs, but which shares the build: the words on both sides) and the
    routed coarse pass on float32 values (the bf16 contraction), which
    must equal the int8 one bit for bit and read the same time on both
    sides."""
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
    # the un-jitted wrappers: each side jits its own, or the second
    # would be served the first's trace
    one_chunk, lanes, multi, win, route = (f.__wrapped__ for f in (
        histogram_pallas_multi_routed, histogram_pallas_multi_win_lanes,
        histogram_pallas_multi, histogram_pallas_multi_win,
        histogram_pallas_route))

    def routed(x, v, **k):
        """``histogram_routed`` out of the un-jitted wrappers: the
        routed kernel where the features fit one chunk under the
        tiler's budget of the moment, else the routing step and the
        chunked pass."""
        if bin_tiling(16, x.shape[0], 128, RPB).one_chunk:
            return one_chunk(x, v, lb, tbl, 16, W, RPB, shift=4,
                             mode="small", **k)
        li_new, sel = route(x, lb, tbl, W, RPB, "small", None, 255)
        return multi(x, v, sel, 16, W, RPB, shift=4, **k), li_new, sel

    def in_chunks(x, v, **k):
        """The routed coarse pass of a one-chunk shape in its chunked
        form: the tiler's budget cut to a fifth while it traces."""
        budget = H._VMEM_BUDGET
        H._VMEM_BUDGET = budget // 5
        try:
            assert not bin_tiling(16, x.shape[0], 128, RPB).one_chunk
            return routed(x, v, **k)
        finally:
            H._VMEM_BUDGET = budget

    chunked = not bin_tiling(16, F, 128, RPB).one_chunk
    # name -> (kind of pass, the pass over features ``x``)
    passes = {
        "routed coarse": ("coarse", lambda v, x, lo: routed(x, v, **kw)),
        "routing step alone (histogram_pallas_route)": (
            "route", lambda v, x, lo: route(x, lb, tbl, W, RPB, "small",
                                            None, 255)),
        "multi coarse (the root's)": ("coarse", lambda v, x, lo: multi(
            x, v, selw, 16, W, RPB, shift=4, **kw)),
        "win_lanes refine": ("refine", lambda v, x, lo: lanes(
            x, v, lb, ids_w, lo, 32, W, RPB, **kw)),
        "multi_win refine (the root's)": ("refine", lambda v, x, lo: win(
            x, v, selw, lo, 32, W, RPB, **kw)),
        "multi full, 64 bins": ("full64", lambda v, x, lo: multi(
            x, v, selw, 64, W, RPB, shift=2, **kw)),
        "multi full, 256 bins": ("full256", lambda v, x, lo: multi(
            x, v, selw, 256, W, RPB, **kw)),
    }
    if not chunked and F >= 32:     # a whole storage tile of rows
        passes["routed coarse in chunks (budget cut)"] = (
            "coarse", lambda v, x, lo: in_chunks(x, v, **kw))
    bins = {"coarse": 16, "refine": 32, "full64": 64, "full256": 256}

    def timed(side, fn, *args):
        """(outputs, median ms of 6, seconds to compile) of ``fn``
        traced with ``side``'s build."""
        traces = []

        def traced(*a):             # a function of its own: its own trace
            traces.append(side)
            return fn(*a)
        jitted = jax.jit(traced)
        with build_of(side):
            t0 = time.perf_counter()
            out = jax.block_until_ready(jitted(*args))  # compiles
            first = time.perf_counter() - t0
        assert traces == [side], traces
        ms = []
        for _ in range(6):
            t0 = time.perf_counter()
            jax.block_until_ready(jitted(*args))
            ms.append((time.perf_counter() - t0) * 1e3)
        med = statistics.median(ms)
        return jax.tree_util.tree_leaves(out), med, first - med / 1e3

    tag = f"[{cell}: {rows} x {F}, {W} lanes]"
    took = {}                       # (pass, side) -> ms
    routed_once = []                # the one-chunk routed pass's outputs
    for name, (kind, fn) in passes.items():
        def pairs(name=name, fn=fn):
            outs = {}
            for side in SIDES:
                outs[side], took[name, side], c = timed(
                    side, fn, v8, xb, lo_w)
                took[name, side, "compile_s"] = c
            got = {f"{side} out{i}": p for side in SIDES if side != "parent"
                   for i, p in enumerate(zip(outs[side], outs["parent"]))}
            if name == "routed coarse in chunks (budget cut)":
                got.update({f"one chunk out{i}": p for i, p in enumerate(
                    zip(outs["change"], routed_once[0]))})
            if name == "routed coarse":
                routed_once.append(outs["change"])
                for side in SIDES:
                    outs[side + " f32"], took[name + " f32", side], _ = \
                        timed(side, fn, vf, xb, lo_w)
                got.update({f"f32 out{i}": p for i, p in enumerate(
                    zip(outs["change"], outs["change f32"]))})
                got.update({f"f32 sides out{i}": p for i, p in enumerate(
                    zip(outs["change f32"], outs["parent f32"]))})
            return got
        report(f"{tag} {name}, int8 values, the change's build against "
               f"the parent's", pairs)
    # what a one-hot row costs: the unrouted pass of each kind again at
    # fewer features (whole sublane groups fewer, so both sides' rows
    # fall alike), the difference over the one-hot rows taken away
    # (a chunked shape: whole blocks of 80 fewer, so that both feature
    # counts tile alike)
    f2 = F - (80 if chunked else 32 if F > 32 else 16)
    us_a_row = {}
    for name, kind in (("multi coarse (the root's)", "coarse"),
                       ("multi_win refine (the root's)", "refine"),
                       ("multi full, 64 bins", "full64"),
                       ("multi full, 256 bins", "full256")):
        for side in SIDES:
            if (name, side) not in took:
                continue
            _, ms2, _ = timed(side, passes[name][1], v8, xb[:f2],
                              lo_w[:, :f2])
            r, r2 = (SIDES[side]["_onehot_rows"](f, bins[kind])
                     for f in (F, f2))
            us_a_row[kind, side] = (took[name, side] - ms2) * 1e3 / (r - r2)
    for name, (kind, _) in passes.items():
        for side in SIDES:
            if (name, side) not in took or (kind, side) not in us_a_row:
                continue
            r = SIDES[side]["_onehot_rows"](F, bins[kind])
            us = us_a_row[kind, side]
            print(f"    {tag} {name}, {side}: {took[name, side]:.2f} ms a "
                  f"pass = {r} one-hot rows x {us:.2f} us a row "
                  f"({r * us / 1e3:.2f} ms of stream, slope of {F} against "
                  f"{f2} features) + {took[name, side] - r * us / 1e3:.2f} "
                  f"ms PASS LESS STREAM; compiled in "
                  f"{took[name, side, 'compile_s']:.1f} s", flush=True)
    step = "routing step alone (histogram_pallas_route)"
    for side in SIDES:
        if (step, side) in took:
            print(f"    {tag} {step}, {side}: {took[step, side]:.2f} ms a "
                  f"wave of {W} live lanes", flush=True)
    for side in SIDES:
        if ("routed coarse f32", side) in took:
            print(f"    {tag} routed coarse on float32 values (bf16 "
                  f"contraction), {side}: "
                  f"{took['routed coarse f32', side]:.2f} ms a pass",
                  flush=True)


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
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    # (features, lanes, two-column, rows): the two cells' `fast` jobs,
    # and a width whose routed passes chunk (fewer rows: the oracle
    # holds a (features x rows, 3) float32 tensor a lane)
    widths = ((28, 64, True, N), (67, 42, False, N), (155, 64, True, 65536))
    # (shape, features a chip stores, rows a chip, lanes, two-column)
    table = (("higgs28.fast", 28, 21_000_000, 64, True),
             ("criteo67.fast", 67, 20_000_000, 42, False),
             ("criteo67x4.fast", 68, 16_000_000, 42, False),
             ("epsilon2000", 2000, 1_200_000, 64, True))
    ap.add_argument("--widths", default=",".join(str(w[0]) for w in widths))
    ap.add_argument("--table", default=",".join(c[0] for c in table))
    args = ap.parse_args()
    keep_w = {int(w) for w in filter(None, args.widths.split(","))}
    keep_t = set(filter(None, args.table.split(",")))
    assert keep_w <= {w[0] for w in widths}, keep_w
    assert keep_t <= {c[0] for c in table}, keep_t
    acquire_chip()
    rng = np.random.RandomState(0)
    for F, W, two_col, rows in widths:
        if F in keep_w:
            check_bins(F, W, two_col, 63, 3, rng, N=rows)
            check_bins(F, W, two_col, 255, 4, rng, N=rows)
    if keep_w:
        check_leaf_stats(rng)
    for cell in table:
        if cell[0] in keep_t:
            check_onehot_order(*cell, rng)
    print("FAILED: " + ", ".join(FAILED) if FAILED
          else "ALL KERNEL CHECKS PASS")
    return 1 if FAILED else 0


if __name__ == "__main__":
    sys.exit(main())
