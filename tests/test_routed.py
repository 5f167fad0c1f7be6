"""Routed histogram kernels: oracle pinning.

The in-kernel-routing pass (``histogram_pallas_multi_routed``) is the
default fast path for serial numeric Pallas runs; its CPU oracle
(``histogram_segsum_multi_routed``) is pinned here against a
brute-force reimplementation so a regression in the routing contract
(lane resolution, goes-left compare, small/children subset selection,
new-leaf emission) fails loudly on CPU.  The kernel half is validated
against the same oracle on real hardware by
``tools/check_routed_kernels.py`` (Pallas does not execute on the CPU
backend these tests force).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.histogram import histogram_segsum_multi_routed


def _brute(bins, vals, li, tbl, max_bin, width, mode, shift=0,
           two_col=False):
    F, N = bins.shape
    W = width if mode == "small" else width // 2
    ids, colw, thrw, neww, slw = tbl
    lanes = width
    hist = np.zeros((lanes, F, max_bin, 3), np.float64)
    li_new = li.copy()
    sel = np.full(N, -1, np.int64)
    for n in range(N):
        lane = -1
        for w in range(W):
            if li[n] == ids[w]:
                lane = w
                break
        if lane < 0:
            continue
        gl = bins[colw[lane], n] <= thrw[lane]
        if not gl:
            li_new[n] = neww[lane]
        if mode == "small":
            if gl == bool(slw[lane]):
                sel[n] = lane
        else:
            sel[n] = lane + (0 if gl else W)
        if sel[n] >= 0:
            for f in range(F):
                b = bins[f, n] >> shift
                hist[sel[n], f, b] += vals[n]
    if two_col:
        hist[..., 2] = hist[..., 1]
    return hist, li_new, sel


@pytest.mark.parametrize("mode", ["small", "children"])
@pytest.mark.parametrize("shift", [0, 2])
def test_routed_oracle_vs_brute_force(mode, shift):
    rng = np.random.RandomState(3)
    F, N, W_lane = 5, 2048, 8
    nb_fine = 16
    Bc = ((nb_fine - 1) >> shift) + 1
    L = 40
    bins = rng.randint(0, nb_fine, size=(F, N)).astype(np.int32)
    vals = rng.randn(N, 3).astype(np.float32)
    vals[:, 2] = 1.0
    li = rng.randint(0, 30, size=N).astype(np.int32)
    Wt = W_lane if mode == "small" else W_lane // 2
    ids = rng.choice(30, size=Wt, replace=False).astype(np.int32)
    ids[-1] = L  # one invalid (dummy) lane
    tbl = np.stack([ids,
                    rng.randint(0, F, size=Wt).astype(np.int32),
                    rng.randint(0, nb_fine - 1, size=Wt).astype(np.int32),
                    rng.randint(30, 40, size=Wt).astype(np.int32),
                    rng.randint(0, 2, size=Wt).astype(np.int32)])
    h, ln, s = histogram_segsum_multi_routed(
        jnp.asarray(bins), jnp.asarray(vals), jnp.asarray(li),
        jnp.asarray(tbl), Bc, W_lane, two_col=True, shift=shift,
        mode=mode)
    hb, lnb, sb = _brute(bins, vals, li, tbl, Bc, W_lane, mode,
                         shift=shift, two_col=True)
    np.testing.assert_allclose(np.asarray(h), hb, rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(ln), lnb)
    np.testing.assert_array_equal(np.asarray(s), sb)


# ---- the kernels' lane lookup (interpreted on the CPU) ----------------
#
# A tile resolves each row's lane once, by one contraction that reads
# every lane table (``ops/histogram._lane_lookup``), and derives the new
# leaf id, the selector and the right-hand side from that lane index.

def _routed_case(mode, shift, miss, bins_dtype=np.uint8, fine=256,
                 F=28, N=1024, W=16, L=300, seed=0):
    rng = np.random.RandomState(seed)
    Wt = W if mode == "small" else W // 2
    x = rng.randint(0, fine - 8, size=(F, N))
    mb = None
    if miss:
        mb = np.full(F, fine - 1, np.int32)
        mb[::3] = -1
        x = np.where((rng.random_sample((F, N)) < 0.1) & (mb[:, None] >= 0),
                     fine - 1, x)
    g = rng.randint(-127, 128, size=N)
    h = rng.randint(0, 128, size=N)
    vals = np.stack([g, h, np.ones(N, np.int64)], -1)
    # leaf ids up to num_leaves = 300: above 256 they are not
    # bf16-exact, and the new ids are all above it
    li = rng.randint(0, L, size=N).astype(np.int32)
    ids = rng.choice(np.arange(L - 100, L), size=Wt, replace=False)
    ids[-2:] = L                    # a dead lane tail: no row carries L
    tbl = np.stack([ids, rng.randint(0, F, size=Wt),
                    rng.randint(0, fine - 8, size=Wt),
                    rng.randint(65792 if L > 65792 else 257 if L > 257
                                else 0, L, size=Wt),
                    rng.randint(0, 2, size=Wt),
                    rng.randint(0, 2, size=Wt)]).astype(np.int32)
    li[:4 * Wt] = np.repeat(ids, 4)[:4 * Wt]   # every lane holds rows
    li[li == L] = 0
    max_bin = (((fine - 1) >> shift) + 1 if shift else fine) + \
        (1 if miss and shift else 0)
    return dict(x=jnp.asarray(x.astype(bins_dtype)), vals=vals,
                li=jnp.asarray(li),
                tbl=jnp.asarray(tbl if miss else tbl[:5]),
                mb=None if mb is None else jnp.asarray(mb),
                max_bin=max_bin, W=W)


@pytest.mark.parametrize("vdtype", [np.int8, np.float32],
                         ids=["int8", "float32"])
@pytest.mark.parametrize("miss", [False, True], ids=["nomiss", "miss"])
@pytest.mark.parametrize("shift", [0, 4])
@pytest.mark.parametrize("mode", ["small", "children"])
def test_routed_kernel_equals_its_oracle(monkeypatch, mode, shift, miss,
                                         vdtype):
    """Leaf ids above 256 (``num_leaves`` 300: the case the parent's
    ``Precision.HIGHEST`` contraction stood for) and a dead lane tail
    (``ids == L``); every output equal, the new leaf vector and the
    selector included."""
    from lightgbm_tpu.ops import histogram as H
    monkeypatch.setenv("LTPU_PALLAS_INTERPRET", "1")
    d = _routed_case(mode, shift, miss, fine=256 if shift else 64)
    assert H.bin_tiling(d["max_bin"], 28, 128, 256).one_chunk
    kw = dict(two_col=vdtype == np.int8, shift=shift, mode=mode,
              miss_bin=d["mb"])
    got = H.histogram_pallas_multi_routed(
        d["x"], jnp.asarray(d["vals"].astype(vdtype)), d["li"], d["tbl"],
        d["max_bin"], d["W"], 256, exact=True, **kw)
    want = histogram_segsum_multi_routed(
        d["x"], jnp.asarray(d["vals"].astype(np.float32)), d["li"],
        d["tbl"], d["max_bin"], d["W"], **kw)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float64),
                                      np.asarray(b, np.float64))
    assert int((np.asarray(got[1]) > 256).sum()) > 0    # new ids written
    assert int((np.asarray(got[2]) >= 0).sum()) > 0     # rows selected


@pytest.mark.parametrize("L,ldtype", [(255, np.uint8), (70000, np.int32)],
                         ids=["one_byte", "three_bytes"])
@pytest.mark.parametrize("mode", ["small", "children"])
def test_routed_kernel_leaf_id_widths(monkeypatch, mode, L, ldtype):
    """The new leaf id rides as three bytes, each exact in the one bf16
    pass.  ``num_leaves`` <= 255 (the benchmark's cells): the leaf
    vector is stored in one byte and comes back in that type, the upper
    bytes 0.  New ids of 65792 and more (``num_leaves`` has no
    ceiling): two bytes would put 257 in the high one, which bf16 does
    not hold."""
    from lightgbm_tpu.ops import histogram as H
    monkeypatch.setenv("LTPU_PALLAS_INTERPRET", "1")
    d = _routed_case(mode, 4, False, L=L)
    new_ids = np.asarray(d["tbl"])[3]
    assert new_ids.max() < 256 if L == 255 else new_ids.min() >= 65792
    kw = dict(two_col=True, shift=4, mode=mode)
    li = d["li"].astype(ldtype)
    got = H.histogram_pallas_multi_routed(
        d["x"], jnp.asarray(d["vals"].astype(np.int8)), li, d["tbl"],
        d["max_bin"], d["W"], 256, exact=True, **kw)
    want = histogram_segsum_multi_routed(
        d["x"], jnp.asarray(d["vals"].astype(np.float32)), d["li"],
        d["tbl"], d["max_bin"], d["W"], **kw)
    assert got[1].dtype == ldtype
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float64),
                                      np.asarray(b, np.float64))
    moved = np.asarray(got[1]) != np.asarray(li)
    assert int(moved.sum()) > 0
    assert set(np.asarray(got[1])[moved]) <= set(new_ids)


def test_routed_kernel_wide_bins(monkeypatch):
    """Bins stored wider than a byte: thresholds above 256 are not
    bf16-exact, and the lookup contracts in float32 at HIGHEST."""
    from lightgbm_tpu.ops import histogram as H
    monkeypatch.setenv("LTPU_PALLAS_INTERPRET", "1")
    d = _routed_case("small", 4, False, bins_dtype=np.uint16, fine=512,
                     F=4)
    assert int((np.asarray(d["tbl"])[2] > 256).sum()) > 0
    kw = dict(two_col=True, shift=4, mode="small")
    got = H.histogram_pallas_multi_routed(
        d["x"], jnp.asarray(d["vals"].astype(np.int8)), d["li"], d["tbl"],
        d["max_bin"], d["W"], 256, exact=True, **kw)
    want = histogram_segsum_multi_routed(
        d["x"], jnp.asarray(d["vals"].astype(np.float32)), d["li"],
        d["tbl"], d["max_bin"], d["W"], **kw)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float64),
                                      np.asarray(b, np.float64))


@pytest.mark.parametrize("vdtype", [np.int8, np.float32],
                         ids=["int8", "float32"])
@pytest.mark.parametrize("F,W,two_col", [(28, 64, True), (67, 42, False),
                                         (5, 3, False)])
def test_win_lanes_kernel_equals_its_oracle(monkeypatch, F, W, two_col,
                                            vdtype):
    """Lanes whose windows differ in every feature, dead lanes (an id
    no row carries) and rows in no lane; 42 lanes pad to 48 in the
    lookup."""
    from lightgbm_tpu.ops import histogram as H
    monkeypatch.setenv("LTPU_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(F + W)
    N, R, L = 1024, 32, 300
    x = rng.randint(0, 255, size=(F, N)).astype(np.uint8)
    vals = np.stack([rng.randint(-127, 128, size=N),
                     rng.randint(0, 128, size=N), np.ones(N, np.int64)], -1)
    ids = rng.choice(np.arange(100, L), size=W, replace=False)
    ids[-1] = L                                     # a dead lane
    li = rng.randint(0, L, size=N).astype(np.int32)
    li[:2 * W] = np.repeat(ids, 2)
    li[li == L] = 0
    # every (lane, feature) its own window start
    lo = rng.permutation(W * F).reshape(W, F) % (255 - R)
    assert all(len(set(lo[:, f])) > 1 for f in range(F))
    args = (jnp.asarray(li), jnp.asarray(ids.astype(np.int32)),
            jnp.asarray(lo.astype(np.int32)), R, W)
    got = H.histogram_pallas_multi_win_lanes(
        jnp.asarray(x), jnp.asarray(vals.astype(vdtype)), *args, 256,
        exact=True, two_col=two_col)
    want = H.histogram_segsum_multi_win_lanes(
        jnp.asarray(x), jnp.asarray(vals.astype(np.float32)), *args,
        two_col=two_col)
    np.testing.assert_array_equal(np.asarray(got, np.float64),
                                  np.asarray(want, np.float64))
    assert float(np.abs(np.asarray(got)).sum()) > 0
