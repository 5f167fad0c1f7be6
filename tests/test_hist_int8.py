"""The quantized histogram passes contract in int8 on the MXU.

Where a batched ``histogram_pallas_multi*`` wrapper is given int8
values (quantized gradients: ``ops/grow.py`` ``GrowParams.int8_values``)
its kernel builds the one-hot and the right-hand side as int8 and
contracts them into int32 (``ops/histogram._accumulate``); the tile's
partial sum is then added into the float32 accumulator as before.
Float32 values keep the bf16 contraction.  Pinned here, in interpret
mode on the CPU:

- the int8-valued call equals the same call on the same integers as
  float32 bit for bit, and its segsum twin, for the four batched
  wrappers at 28 and 67 features, 8, 16, 24 and 32 bins (67 at 8 and
  at 24 chunks its features, the last block overhanging the matrix;
  67 at 16 is off the int8 tile grid: 1072 one-hot rows, built as 64
  features' slabs and a tail's), 68 and 30 at 16, two-column and
  three-column values, with and without a missing bin;
- the int8 one-hot in the order it is built in
  (``ops/histogram._onehot_int8``: slab by slab up to 32 bins and off
  the 32-bin grid, feature by feature at 64 bins and up), put back by
  the wrappers' helper (``_rows_to_feature_bin``), equals the plain
  one-hot row for row, and a refine pass gives the same bits built
  either way;
- the largest partial sum of a tile (16384 rows of +127 or -127 in one
  bin) is exact;
- the kernel's jaxpr: an int8 x int8 -> int32 ``dot_general`` and
  nothing of the one-hot's or the right-hand side's size in bfloat16
  where the values are int8 (the lane lookup's two small operands
  are); the bf16 contraction and no int8 ``dot_general`` where they
  are float32;
- the right-hand side made a 32-bit word at a time
  (``ops/histogram._rhs_int8``) equals the row-by-row form it
  replaced, kept here as the reference; the float32-valued one made
  row by row from the subset index (``_rhs_bf16``) equals the
  selector-times-values form it replaced, kept here too.

Mosaic's lowering of the same kernels, and their time a pass, is
proven on the chip by ``tools/check_routed_kernels.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops import histogram as H
from test_hist_tail import RPB, W, _data, _run

BATCHED = ["multi", "multi_win", "multi_routed", "multi_win_lanes"]


def _cases():
    for wrapper in BATCHED:
        # 68 and 30 at 16 bins: a tail of 4 of 68 and one padded to 8;
        # 67 at 8 and at 24 bins: chunked (five of 16)
        for f, bins in [(f, b) for f in (28, 67) for b in (8, 16, 24, 32)
                        ] + [(68, 16), (30, 16)]:
            if wrapper == "multi_routed" and \
                    not H.bin_tiling(bins, f, 128, RPB).one_chunk:
                continue            # the routed pass is one chunk only
            for two_col in (True, False):
                for miss in (False, True):
                    yield pytest.param(
                        wrapper, f, bins, two_col, miss,
                        id=f"{wrapper}-F{f}-B{bins}-"
                           f"{'two_col' if two_col else 'exact'}-"
                           f"{'miss' if miss else 'nomiss'}")


@pytest.mark.parametrize("wrapper,f,bins,two_col,miss", _cases())
def test_int8_equals_float32_and_segsum(wrapper, f, bins, two_col, miss):
    d = _data(f, bins, miss, seed=f * 100 + bins + two_col)
    got = _run(wrapper, d, bins, pallas=True, two_col=two_col)
    as_f32 = _run(wrapper, d, bins, pallas=True, two_col=two_col,
                  int8=False)
    want = _run(wrapper, d, bins, pallas=False, two_col=two_col)
    assert len(got) == len(as_f32) == len(want)
    for a, b, c in zip(got, as_f32, want):
        assert a.dtype == b.dtype and a.shape == b.shape == c.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a, np.float64),
                                      np.asarray(c, np.float64))
    assert float(np.abs(np.asarray(got[0])).sum()) > 0


def test_the_chunked_case_overhangs():
    """67 features at 8 bins run in five chunks of 16 and the last
    block overhangs the stored matrix; 67 at 16 bins are 1072 one-hot
    rows, off the (32, 128) int8 tile grid."""
    til = H.bin_tiling(8, 67, 128, RPB)
    assert (til.f_pad, til.fc, til.f_mask) == (80, 16, 67)
    assert (67 * H._pad_bins(16)) % 32 == 16


@pytest.mark.parametrize("R", [4, 8, 28, 67, 68, 72])
@pytest.mark.parametrize("b_pad", [8, 16, 24, 32, 48, 64])
def test_onehot_in_its_order_is_the_plain_onehot(b_pad, R):
    """The one-hot as the kernel builds it, its rows put back to
    (feature, bin) by the helper every wrapper calls on the
    accumulator, against the plain compare; bins below 0 and at or
    above ``b_pad`` among the values count nowhere."""
    import jax.experimental.pallas as pl
    T = 256
    rng = np.random.RandomState(R * 100 + b_pad)
    x = rng.randint(-6, b_pad + 6, size=(R, T)).astype(np.int32)
    x[:, :8] = np.array([-5, -4, -1, 0, b_pad - 1, b_pad, b_pad + 3,
                         b_pad + 4])
    rows = H._onehot_rows(R, b_pad)
    assert rows % 32 == 0           # whole (32, 128) int8 tiles
    assert R * b_pad <= rows <= -(-R // 8) * 8 * b_pad
    assert H._onehot_form(b_pad) == ("words" if b_pad == 64
                                     else "slabs")

    def kernel(x_ref, o_ref):
        o_ref[...] = H._onehot_int8(x_ref[...], b_pad)
    got = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((rows, T), jnp.int8),
        interpret=True)(jnp.asarray(x))
    want = (x[:, None, :] == np.arange(b_pad)[None, :, None])
    np.testing.assert_array_equal(
        np.asarray(H._rows_to_feature_bin(got, R, b_pad)),
        want.astype(np.int8))
    # and nothing in the rows the helper leaves out
    assert int(np.asarray(got, np.int32).sum()) == int(want.sum()) > 0


def test_rows_streamed_at_the_cells_shapes():
    """What the coarse and the refine passes of the benchmark's cells
    stream: a tail of 4 (of 28, of 68) or 3 (of 67) features is built
    as groups of its own, not padded to the 8 rows of a slab (the tail
    of 3 takes 4: 2176 rows of 67 features at 32 bins, where feature
    by feature took 2144); a chunk of 40 of the wide cell has none."""
    assert [H._onehot_rows(f, 16) for f in (28, 67, 68, 30, 72)] == [
        448, 1088, 1088, 512, 1152]
    assert [H._onehot_rows(f, 32) for f in (28, 67, 68, 40)] == [
        896, 2176, 2176, 1280]


@pytest.mark.parametrize("f", [28, 67])
def test_refine_pass_is_the_same_under_both_builds(monkeypatch, f):
    """A ``multi_win_lanes`` refine pass at the 32-bin window, its
    one-hot built slab by slab and built feature by feature (the
    ``words`` order the 32-bin passes took before): the same output
    bit for bit, tail of 4 (28) and of 3 (67), with a missing bin."""
    d = _data(f, 32, True, seed=f)
    lanes = H.histogram_pallas_multi_win_lanes.__wrapped__  # a trace a call
    args = (d["x"], d["v8"], d["li"], d["ids"], d["lo"], 32, W, RPB)
    kw = dict(exact=True, miss_bin=d["mb"])
    assert H._onehot_form(32) == "slabs"
    slabs = np.asarray(lanes(*args, **kw))
    asked = []

    def words(b_pad):
        asked.append(b_pad)
        return "words"
    monkeypatch.setattr(H, "_onehot_form", words)
    by_words = np.asarray(lanes(*args, **kw))
    assert 32 in asked              # the second pass was built by words
    np.testing.assert_array_equal(slabs, by_words)
    assert np.abs(slabs).sum() > 0


@pytest.mark.parametrize("wrapper", ["multi", "multi_win_lanes"])
@pytest.mark.parametrize("sign", [1, -1])
def test_largest_tile_partial_is_exact(wrapper, sign):
    """One whole 16384-row tile, every row in one bin of every feature
    and in one subset, every value at +127 or -127: the tile's int32
    partial is 16384 x 127 = 2,080,768, exact in int32 and in the
    float32 it is added into."""
    n, f, bins = 16384, 28, 16
    assert H.bin_tiling(bins, f, 128, n).t == n
    x = jnp.full((f, n), 5, jnp.uint8)
    v8 = jnp.full((n, 3), sign * 127, jnp.int8)
    if wrapper == "multi":
        h = H.histogram_pallas_multi(x, v8, jnp.full((n,), 3, jnp.int32),
                                     bins, W, n, exact=True)
    else:
        ids = jnp.arange(W, dtype=jnp.int32)
        h = H.histogram_pallas_multi_win_lanes(
            x, v8, jnp.full((n,), 3, jnp.uint8), ids,
            jnp.zeros((W, f), jnp.int32), bins, W, n, exact=True)
    h = np.asarray(h)
    assert h.shape == (W, f, bins, 3)
    want = np.zeros_like(h)
    want[3, :, 5, :] = sign * 127.0 * n
    np.testing.assert_array_equal(h, want)


def _kernel_eqns(jaxpr):
    """Every equation inside the ``pallas_call`` kernels of ``jaxpr``."""
    def walk(j, inside):
        for eqn in j.eqns:
            if inside:
                yield eqn
            kernel = eqn.primitive.name == "pallas_call"
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub, inside or kernel)
    return list(walk(jaxpr, False))


@pytest.mark.parametrize("int8", [True, False], ids=["int8", "float32"])
@pytest.mark.parametrize("wrapper", BATCHED)
def test_the_contraction_follows_the_values(wrapper, int8):
    d = _data(28, 16, True, seed=2)
    jaxpr = jax.make_jaxpr(
        lambda x: _run(wrapper, dict(d, x=x), 16, pallas=True,
                       int8=int8))(d["x"])
    eqns = _kernel_eqns(jaxpr.jaxpr)
    dots = [(tuple(str(v.aval.dtype) for v in e.invars),
             str(e.outvars[0].aval.dtype))
            for e in eqns if e.primitive.name == "dot_general"]
    bf16 = [e for e in eqns
            if any(getattr(v.aval, "dtype", None) == jnp.bfloat16
                   for v in e.outvars)]
    # the kernels that read lane tables (every one but ``multi``) do
    # so by one bf16 contraction more (``_lane_lookup``): a (rows, Wp)
    # table against the (Wp, T) one-hot of the lanes
    lookups = int(wrapper != "multi")
    wp = -(-W // 16) * 16
    if int8:
        assert dots.count((("int8", "int8"), "int32")) == 1
        assert dots.count((("bfloat16", "bfloat16"), "float32")) == lookups
        # no bf16 one-hot, no bf16 rhs: the lookup's operands alone
        assert all(wp in e.outvars[0].aval.shape and
                   e.outvars[0].aval.size <= wp * RPB for e in bf16)
    else:
        assert dots.count((("bfloat16", "bfloat16"), "float32")) == \
            1 + lookups
        assert not any("int8" in ins for ins, _ in dots)


def _rhs_rows(on, valsc):
    """The row-by-row right-hand side ``_rhs_int8`` replaced (PR 29's
    form, the reference): row ``k`` is ``on[k] ? valsc[k % C] : 0``."""
    lanes = on.shape[0]
    C = valsc.shape[0]
    c = jax.lax.rem(jax.lax.broadcasted_iota(jnp.int32, (lanes, 1), 0), C)
    v = valsc.astype(jnp.int32)
    row = v[0:1]
    for i in range(1, C):
        row = jnp.where(c == i, v[i:i + 1], row)       # (lanes, T)
    return jnp.where(on, row, 0).astype(jnp.int8)


def _rhs_row_lane(width, cols):
    """The subset rhs row ``k`` belongs to, -2 beyond the subsets."""
    k = jax.lax.broadcasted_iota(
        jnp.int32, (H._rhs_cols(width, cols), 1), 0)
    return jnp.where(k < width * cols, jax.lax.div(k, cols), -2)


@pytest.mark.parametrize("cols,width", [
    (2, 1), (2, 21), (2, 42), (2, 64), (3, 1), (3, 21), (3, 42),
    (3, 64)],       # 3 x 64: the 256-column right-hand side
    ids=lambda v: str(v))
def test_rhs_by_words_equals_rhs_by_rows(cols, width):
    """Every subset, dead rows (-1) and the extreme values -127 and
    127 in every column; three columns straddle words."""
    import jax.experimental.pallas as pl
    T = 512
    rng = np.random.RandomState(cols * 100 + width)
    lane = rng.randint(-1, width, size=(1, T)).astype(np.int32)
    lane[0, :width] = np.arange(width)
    lane[0, width:width + 8] = -1
    vals = rng.randint(-127, 128, size=(cols, T)).astype(np.int8)
    vals[:, :width:2] = 127
    vals[:, 1:width:2] = -127
    vals[:, T // 2:] = rng.choice([-127, 127], size=(cols, T - T // 2))
    lane, vals = jnp.asarray(lane), jnp.asarray(vals)
    lanes = H._rhs_cols(width, cols)
    assert lanes == (256 if width * cols > 128 else 128)

    def kernel(l_ref, v_ref, o_ref):
        o_ref[...] = H._rhs_int8(l_ref[...], v_ref[...], width)
    got = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((lanes, T), jnp.int8),
        interpret=True)(lane, vals)
    want = _rhs_rows(lane == _rhs_row_lane(width, cols), vals)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.abs(np.asarray(got, np.int32)).sum() > 0


def _rhs_selector_times_values(sel_oh, valsc):
    """The float32-valued right-hand side ``_rhs_bf16`` replaced (the
    reference): a (W, T) selector times the (C, T) values in bf16,
    regrouped (W, C, T) -> (W * C, T)."""
    W_, T = sel_oh.shape
    C = valsc.shape[0]
    rhs = (sel_oh.astype(jnp.bfloat16)[:, None, :] *
           valsc.astype(jnp.bfloat16)[None, :, :]).reshape(W_ * C, T)
    return jnp.pad(rhs, ((0, H._rhs_cols(W_, C) - W_ * C), (0, 0)))


@pytest.mark.parametrize("cols,width", [
    (2, 1), (2, 64), (3, 42), (3, 64), (6, 1), (6, 16), (6, 21)],
    ids=lambda v: str(v))
def test_rhs_by_rows_equals_selector_times_values(cols, width):
    """float32 values (hi/lo-split ones at 6 columns: the lo residual
    rounds to bf16 either way), every subset, dead rows (-1)."""
    import jax.experimental.pallas as pl
    T = 512
    rng = np.random.RandomState(cols * 100 + width)
    lane = rng.randint(-1, width, size=(1, T)).astype(np.int32)
    lane[0, :width] = np.arange(width)
    lane[0, width:width + 8] = -1
    v = (rng.randn(3, T) * 10.0 ** rng.randint(-3, 4, size=(3, T))
         ).astype(np.float32)
    vals = np.asarray(H._split_hi_lo(jnp.asarray(v)))[:cols] \
        if cols == 6 else v[:cols]
    lane, vals = jnp.asarray(lane), jnp.asarray(vals)
    lanes = H._rhs_cols(width, cols)

    def kernel(l_ref, v_ref, o_ref):
        o_ref[...] = H._rhs(l_ref[...], v_ref[...], width)
    got = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((lanes, T), jnp.bfloat16),
        interpret=True)(lane, vals)
    sel_oh = lane == jax.lax.broadcasted_iota(jnp.int32, (width, T), 0)
    want = _rhs_selector_times_values(sel_oh, vals)
    # the same bf16 values (the product form writes -0.0 where an
    # unselected value is negative: the same nothing in the sum)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    assert np.abs(np.asarray(got, np.float32)).sum() > 0


def test_leaf_stats_kernel_sums(monkeypatch):
    """The renewal kernel's per-leaf sums (its right-hand side is
    ``_rhs_bf16`` over the leaf id's high nibble) against float64: the
    hi/lo split carries ~2^-16 of each summand's magnitude."""
    monkeypatch.setenv("LTPU_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(3)
    n = 4096
    li = rng.randint(0, 200, size=n).astype(np.int32)
    gf = rng.randn(n).astype(np.float32)
    hf = np.abs(rng.randn(n)).astype(np.float32)
    mf = (rng.random_sample(n) < 0.9).astype(np.float32)
    v = np.stack([gf * mf, hf * mf, mf], -1).astype(np.float64)
    ref = np.zeros((256, 3), np.float64)
    mag = np.zeros((256, 3), np.float64)
    np.add.at(ref, li, v)
    np.add.at(mag, li, np.abs(v))
    got = np.asarray(H.leaf_stats_pallas(
        jnp.asarray(li.astype(np.uint8)), jnp.asarray(gf), jnp.asarray(hf),
        jnp.asarray(mf), 1024), np.float64)
    assert np.abs((got - ref) / (mag + 1e-3)).max() <= 2.0 ** -14
    np.testing.assert_array_equal(got[:, 2], ref[:, 2])     # counts exact


def test_float_job_records_bf16(monkeypatch):
    """A booster without ``use_quantized_grad`` hands its passes
    float32 values: every kind of pass records the bf16 contraction."""
    import lightgbm_tpu as lgb
    monkeypatch.setenv("LTPU_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(0)
    X = rng.randn(2048, 28).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    params = dict(objective="binary", num_leaves=31, max_bin=255,
                  wave_splits=True, verbose=-1, tpu_rows_per_block=1024)
    g = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))._gbdt
    tiling = g.tier_decision["hist_tiling"]
    assert tiling and not g.grow_params.int8_values
    assert {rec["mxu"] for rec in tiling.values()} == {"bf16"}
    assert {rec["onehot"] for rec in tiling.values()} == {"plain"}
