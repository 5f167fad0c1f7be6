"""The feature tail of the histogram kernels lives in VMEM.

``ops/histogram._tile`` puts ``fc * b_pad`` on the 128-lane grid by
adding feature rows.  The five ``histogram_pallas*`` wrappers hand the
kernel the bin matrix AS STORED and the kernel makes that tail itself
(``BinTiling``, ``_accumulate``): no copy of the (F, N) matrix in HBM
in front of a pass.  Pinned here, in interpret mode on the CPU:

- parity of every wrapper with its segsum twin at widths with a tail
  (28 and 67 features at 16 bins, 67 at 32, 5), without one (28 at 32),
  and at a chunked shape whose last block overhangs the matrix (67 at
  8 bins: five chunks of 16), with and without ``miss_bin``, uint8
  storage, int8 values (equality is exact); and where the int8
  one-hot is built slab by slab (8, 16, 24 and 32 bins), with a
  features' tail in groups of its own (28, 67, 68), one padded to 8
  (30) and none (chunks of 16, among them a 32-bin pass whose
  features chunk under a cut budget, as a wide set's refine pass);
- the copy is gone: the wrapper's jaxpr holds no ``pad``,
  ``concatenate`` or ``dynamic_update_slice`` of an N-column array
  outside the ``pallas_call``, and a booster built with the ``fast``
  job's parameters records ``xt_copied: false`` for every kind of pass.

Mosaic's lowering of the same kernels is proven on the chip by
``tools/check_routed_kernels.py`` (F = 28 and F = 67).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops import histogram as H

N, RPB, W, FINE = 512, 256, 8, 256

# (features, bins of the pass): tail 28->32, none, 67->72, 67->68, 5->8,
# and 67->80 in five chunks of 16 (the last holds 3 stored features);
# then the int8 one-hot's slab order (ops/histogram._onehot_int8) at
# 8, 16, 24 and 32 bins with a features' tail of 4, 3, 4 of 68 and 6
# rows (28 -> 24 + 4, 67 -> 64 + 3, 30 -> 32), and chunked (67 at 24
# bins: five chunks of 16, no tail); last, 44 features at 32 bins with
# the tiler's budget cut (``CUT``), as a wide set's refine pass runs:
# three chunks of 16, the last overhanging the matrix
SHAPES = [(28, 16), (28, 32), (67, 16), (67, 32), (5, 16), (67, 8),
          (28, 8), (28, 24), (68, 16), (30, 16), (67, 24), (68, 32),
          (44, 32)]
CUT = {(44, 32): 600_000}       # the tiler's VMEM budget for the shape
WRAPPERS = ["single", "multi", "multi_win", "multi_routed",
            "multi_win_lanes"]


def _tiling(f, bins):
    """The tiling a batched pass over the shape runs with, under the
    shape's budget."""
    saved = H._VMEM_BUDGET
    H._VMEM_BUDGET = CUT.get((f, bins), saved)
    try:
        return H.bin_tiling(bins, f, 128, RPB)
    finally:
        H._VMEM_BUDGET = saved


def _cases():
    for wrapper in WRAPPERS:
        for f, bins in SHAPES:
            if wrapper == "multi_routed" and not _tiling(f, bins).one_chunk:
                continue            # the routed pass is one chunk only
            for miss in (False, True):
                if wrapper == "single" and miss:
                    continue        # the single-leaf pass has no miss_bin
                tag = "miss" if miss else "nomiss"
                yield pytest.param(wrapper, f, bins, miss,
                                   id=f"{wrapper}-F{f}-B{bins}-{tag}")


def _data(f, bins, miss, seed):
    """uint8 fine bins, int8 values and every small operand a pass
    takes.  With ``miss`` every third feature has no missing bin and the
    others keep theirs (250) above every value bin (< 224), so the
    coarse pass can hold it in its last slot."""
    rng = np.random.RandomState(seed)
    x = rng.randint(0, 224, size=(f, N))
    mb = None
    if miss:
        mb = np.full(f, 250, np.int32)
        mb[::3] = -1
        x = np.where((rng.random_sample((f, N)) < 0.1) & (mb[:, None] >= 0),
                     250, x)
    g = rng.randint(-120, 121, size=N)
    h = rng.randint(0, 121, size=N)
    vals = np.stack([g, h, np.ones(N, np.int64)], -1)
    li = rng.randint(0, 12, size=N).astype(np.int32)
    return dict(
        x=jnp.asarray(x.astype(np.uint8)),
        v8=jnp.asarray(vals.astype(np.int8)),
        vf=jnp.asarray(vals.astype(np.float32)),
        li=jnp.asarray(li), sel=jnp.asarray(li % W - (li % 5 == 0)),
        mb=None if mb is None else jnp.asarray(mb),
        lo=jnp.asarray(rng.randint(0, 224 - bins, size=(W, f))
                       .astype(np.int32)),
        ids=jnp.asarray(rng.choice(12, size=W, replace=False)
                        .astype(np.int32)),
        tbl=jnp.asarray(np.stack(
            [rng.choice(12, size=W, replace=False),
             rng.randint(0, f, size=W), rng.randint(0, 223, size=W),
             rng.randint(12, 40, size=W), rng.randint(0, 2, size=W),
             rng.randint(0, 2, size=W)]).astype(np.int32)))


def _run(wrapper, d, bins, pallas: bool, two_col: bool = False,
         int8: bool = True):
    """One pass through the Pallas wrapper (its values int8, or the
    same integers as float32) or its segsum twin, as a tuple of
    arrays."""
    shift = ((FINE - 1) // bins).bit_length()    # 256 fine -> `bins`
    vals = d["v8"] if pallas and int8 else d["vf"]
    kw = dict(exact=True) if pallas else {}
    if wrapper != "single":
        kw["two_col"] = two_col
    if wrapper == "single":
        # fine bins collapsed on the host: this pass takes no shift
        x = (d["x"] >> shift).astype(jnp.uint8)
        if pallas:
            return (H.histogram_pallas(x, d["vf"], bins, RPB, exact=True),)
        return (H.histogram_segsum(x, d["vf"], bins),)
    if wrapper == "multi":
        fn = H.histogram_pallas_multi if pallas else H.histogram_segsum_multi
        args = (d["x"], vals, d["sel"], bins, W) + ((RPB,) if pallas else ())
        return (fn(*args, shift=shift, miss_bin=d["mb"], **kw),)
    if wrapper == "multi_win":
        fn = (H.histogram_pallas_multi_win if pallas
              else H.histogram_segsum_multi_win)
        args = (d["x"], vals, d["sel"], d["lo"], bins, W) + \
            ((RPB,) if pallas else ())
        return (fn(*args, miss_bin=d["mb"], **kw),)
    if wrapper == "multi_win_lanes":
        fn = (H.histogram_pallas_multi_win_lanes if pallas
              else H.histogram_segsum_multi_win_lanes)
        args = (d["x"], vals, d["li"], d["ids"], d["lo"], bins, W) + \
            ((RPB,) if pallas else ())
        return (fn(*args, miss_bin=d["mb"], **kw),)
    assert wrapper == "multi_routed"
    fn = (H.histogram_pallas_multi_routed if pallas
          else H.histogram_segsum_multi_routed)
    tbl = d["tbl"] if d["mb"] is not None else d["tbl"][:5]
    args = (d["x"], vals, d["li"], tbl, bins, W) + ((RPB,) if pallas else ())
    return fn(*args, shift=shift, mode="small", miss_bin=d["mb"], **kw)


@pytest.mark.parametrize("wrapper,f,bins,miss", _cases())
def test_tail_parity_with_segsum(monkeypatch, wrapper, f, bins, miss):
    if (f, bins) in CUT:
        monkeypatch.setattr(H, "_VMEM_BUDGET", CUT[f, bins])
        assert not H.bin_tiling(bins, f, 128, RPB).one_chunk
    d = _data(f, bins, miss, seed=f * 100 + bins)
    got = _run(wrapper, d, bins, pallas=True)
    want = _run(wrapper, d, bins, pallas=False)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float64),
                                      np.asarray(b, np.float64))
    # the comparison is not vacuous: the histogram holds the rows
    assert float(np.abs(np.asarray(got[0])).sum()) > 0


def test_tiling_of_the_cases():
    """The shapes above are the cases their names say."""
    t = {s: _tiling(*s) for s in SHAPES}
    assert [(t[s].f_pad, t[s].fc) for s in SHAPES] == [
        (32, 32), (28, 28), (72, 72), (68, 68), (8, 8), (80, 16),
        (32, 32), (32, 32), (72, 72), (32, 32), (80, 16), (68, 68),
        (48, 16)]
    assert [t[s].block_rows for s in SHAPES] == [
        28, 28, 67, 67, 5, 16, 28, 28, 68, 30, 16, 68, 16]
    assert [t[s].f_mask for s in SHAPES] == [
        0, 0, 0, 0, 0, 67, 0, 0, 0, 0, 67, 0, 44]
    assert not any(t[s].record()["xt_copied"] for s in SHAPES)


def _n_column_copies(jaxpr, n):
    """Equations outside any ``pallas_call`` that write a new array with
    ``n`` columns from one: the copies a pass must not make of the bin
    matrix."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name in ("pad", "concatenate",
                                  "dynamic_update_slice"):
            if any(n in getattr(v.aval, "shape", ()) for v in eqn.invars):
                found.append(str(eqn))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _n_column_copies(sub, n)
    return found


@pytest.mark.parametrize("f", [67, 28])
@pytest.mark.parametrize("wrapper", WRAPPERS)
def test_no_copy_of_the_bin_matrix(wrapper, f):
    """A count a CPU run can decide: at the coarse pass's 16 bins both
    widths have a tail (72 and 32 rows), and no wrapper pads, joins or
    updates an N-column array with more rows than the value operand
    has in front of its kernel."""
    d = _data(f, 16, True, seed=1)
    jaxpr = jax.make_jaxpr(
        lambda x: _run(wrapper, dict(d, x=x), 16, pallas=True))(d["x"])
    assert "pallas_call" in str(jaxpr)
    assert _n_column_copies(jaxpr.jaxpr, N) == []


@pytest.mark.parametrize("f,extra,kinds", [
    (67, {"min_data_in_leaf": 20, "min_sum_hessian_in_leaf": 1e-3},
     {"coarse": (67, 72, 72), "refine": (67, 68, 68)}),
    (28, {"min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100.0},
     {"coarse": (28, 32, 32), "refine": (28, 28, 28)}),
])
def test_fast_job_records_its_tiling(monkeypatch, f, extra, kinds):
    """The engagement record: a booster with the benchmark's ``fast``
    parameters at the cells' widths says, for each kind of pass it
    runs, how the matrix is tiled, that no pass copies it, and that it
    contracts in int8 (a booster without ``use_quantized_grad`` reads
    ``bf16``: tests/test_hist_int8.py)."""
    import lightgbm_tpu as lgb
    monkeypatch.setenv("LTPU_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(0)
    X = rng.randn(2048, f).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    params = dict(objective="binary", num_leaves=255, max_bin=255,
                  learning_rate=0.1, wave_splits=True,
                  use_quantized_grad=True, fused_iters=8, verbose=-1,
                  tpu_rows_per_block=1024, **extra)
    g = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))._gbdt
    tiling = g.tier_decision["hist_tiling"]
    assert g.tier_decision["c2f"] and set(tiling) == set(kinds)
    for kind, (f_, f_pad, fc) in kinds.items():
        rec = tiling[kind]
        assert (rec["f"], rec["f_pad"], rec["fc"]) == (f_, f_pad, fc)
        assert rec["xt_copied"] is False
        assert rec["t"] == 1024
        assert rec["mxu"] == "int8"     # quantized: int8 values
        # 16 coarse bins and the 32-bin window: the one-hot slab by slab
        assert rec["onehot"] == {"coarse": "slabs", "refine": "slabs"}[kind]
