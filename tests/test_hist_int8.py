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
  wrappers at 28 and 67 features, 8, 16 and 32 bins (67 at 8 chunks
  its features, the last block overhanging the matrix; 67 at 16 is
  off the int8 tile grid: 1072 one-hot rows), two-column and
  three-column values, with and without a missing bin;
- the largest partial sum of a tile (16384 rows of +127 or -127 in one
  bin) is exact;
- the kernel's jaxpr: an int8 x int8 -> int32 ``dot_general`` and
  nothing in bfloat16 where the values are int8; the bf16 contraction
  and no int8 ``dot_general`` where they are float32.

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
        for f in (28, 67):
            for bins in (8, 16, 32):
                if wrapper == "multi_routed" and \
                        not H.bin_tiling(bins, f, 128, RPB).one_chunk:
                    continue        # the routed pass is one chunk only
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
    if int8:
        assert dots.count((("int8", "int8"), "int32")) == 1
        assert bf16 == []           # no bf16 one-hot, no bf16 rhs
    else:
        assert dots.count((("bfloat16", "bfloat16"), "float32")) == 1
        assert not any("int8" in ins for ins, _ in dots)


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
