"""The routed batched pass over SEVERAL feature chunks.

Where the tiler splits the features into blocks the routed kernel
cannot hold every feature's tile, so ``ops/histogram.histogram_routed``
routes the wave's rows in a step of its own
(``histogram_pallas_route``: a row tile walks the live lanes and is
handed, for each, the one storage tile of rows that holds the lane's
split column) and hands the selector to the batched pass that walks
the blocks.  Pinned here on the interpret lane:

- the chunked form equals the one-chunk routed kernel and the segsum
  twin bit for bit, histogram, new leaf vector and selector, over
  chunk counts, bins, value columns, value types and missing bins;
- the routed leaf vector equals a select chain written out in numpy;
- the routing step reads the (F, N) matrix 32 rows at a time and no
  other operation of it touches the matrix;
- ``build_tree`` grows the same tree routed and unrouted on a shape
  that chunks.

A small VMEM budget stands in for a wide feature set: the tiler then
chunks a few dozen features the way it chunks 2,000 at the real
budget (``tests/test_tier_plan.py`` holds the real shape's tiling).
The kernel half on the chip: ``tools/check_routed_kernels.py``.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops import grow as G
from lightgbm_tpu.ops import histogram as H
from lightgbm_tpu.ops.split import SplitParams

N, RPB = 512, 256
DEAD = 255          # the leaf id of a lane that holds no split


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("LTPU_PALLAS_INTERPRET", "1")


@contextlib.contextmanager
def vmem_budget(budget):
    """The tiler's budget for a while; the jitted passes read the
    tiling as they trace, so their traces go with it."""
    old = H._VMEM_BUDGET
    H._VMEM_BUDGET = budget
    jax.clear_caches()
    try:
        yield
    finally:
        H._VMEM_BUDGET = old
        jax.clear_caches()


def budget_for(bins, F, chunks):
    """A budget under which ``F`` features at ``bins`` bins tile as
    ``chunks`` blocks with the last one overhanging."""
    old = H._VMEM_BUDGET
    try:
        for budget in range(64 * 1024, 2 * 1024 * 1024, 16 * 1024):
            H._VMEM_BUDGET = budget         # the tiler alone: no trace
            til = H.bin_tiling(bins, F, 128, RPB)
            if til.chunks == chunks and til.f_pad > F:
                return budget
    finally:
        H._VMEM_BUDGET = old
    raise AssertionError((bins, F, chunks))


def case(F, bins, two_col, miss, mode="small", seed=0):
    """A wave's operands: 16 bins is the coarse pass of a 255-bin job
    (shift 4; with a missing bin the reserved 17th slot), 32 a
    full-resolution pass."""
    rng = np.random.RandomState(seed + F)
    shift = 4 if bins == 16 else 0
    fine = 256 if shift else bins
    W = 64 if two_col else 42
    Wl = W if mode == "small" else W // 2
    x = rng.randint(0, fine - 2, size=(F, N))
    mb = None
    if miss:
        mb = np.full(F, fine - 1, np.int32)
        mb[::3] = -1
        x = np.where((rng.random_sample((F, N)) < 0.1) & (mb[:, None] >= 0),
                     fine - 1, x)
    vals = np.stack([rng.randint(-127, 128, size=N),
                     rng.randint(0, 128, size=N), np.ones(N, np.int64)], -1)
    ids = rng.choice(np.arange(100, 200), size=Wl, replace=False)
    ids[-2:] = DEAD                 # a dead lane tail: no row carries it
    li = rng.randint(0, 200, size=N)
    tbl = np.stack([ids, rng.randint(0, F, size=Wl),
                    rng.randint(0, fine - 2, size=Wl),
                    rng.randint(200, 255, size=Wl),
                    rng.randint(0, 2, size=Wl),
                    rng.randint(0, 2, size=Wl)]).astype(np.int32)
    # every chunk holds a split column, the last feature among them
    tbl[1, :3] = (0, F // 2, F - 1)
    return dict(
        x=jnp.asarray(x.astype(np.uint8)), vals=vals,
        li=jnp.asarray(li.astype(np.uint8)),
        tbl=jnp.asarray(tbl if miss else tbl[:5]),
        mb=None if mb is None else jnp.asarray(mb),
        max_bin=(bins + 1 if miss else bins) if shift else bins, W=W,
        kw=dict(exact=True, two_col=two_col, shift=shift, mode=mode,
                miss_bin=None if mb is None else jnp.asarray(mb)))


def routed(d, vdtype):
    return H.histogram_routed(
        d["x"], jnp.asarray(d["vals"].astype(vdtype)), d["li"], d["tbl"],
        d["max_bin"], d["W"], RPB, dead_id=DEAD, **d["kw"])


def assert_same(got, want):
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float64),
                                      np.asarray(b, np.float64))


@pytest.mark.parametrize("vdtype", [np.int8, np.float32],
                         ids=["int8", "float32"])
@pytest.mark.parametrize("miss", [False, True], ids=["nomiss", "miss"])
@pytest.mark.parametrize("two_col", [True, False],
                         ids=["two_col", "three_col"])
@pytest.mark.parametrize("bins", [16, 32])
@pytest.mark.parametrize("F,chunks", [(61, 2), (45, 3)])
def test_chunked_routed_pass_equals_one_chunk_and_segsum(
        F, chunks, bins, two_col, miss, vdtype):
    d = case(F, bins, two_col, miss)
    assert H.bin_tiling(d["max_bin"], F, 128, RPB).one_chunk
    one = routed(d, vdtype)             # the routed kernel itself
    seg = H.histogram_segsum_multi_routed(
        d["x"], jnp.asarray(d["vals"].astype(np.float32)), d["li"],
        d["tbl"], d["max_bin"], d["W"],
        **{k: v for k, v in d["kw"].items() if k != "exact"})
    with vmem_budget(budget_for(d["max_bin"], F, chunks)):
        til = H.bin_tiling(d["max_bin"], F, 128, RPB)
        assert til.chunks == chunks and til.f_mask == F
        many = routed(d, vdtype)
    assert_same(many, one)
    assert_same(many, seg)
    assert many[1].dtype == jnp.uint8
    assert int((np.asarray(many[2]) >= 0).sum()) > 0
    assert float(np.abs(np.asarray(many[0])).sum()) > 0


@pytest.mark.parametrize("miss", [False, True], ids=["nomiss", "miss"])
def test_chunked_children_mode(miss):
    """Both children in lanes (the full-resolution wave of the
    parallel learners): lane ``w`` left, ``W + w`` right."""
    d = case(45, 16, False, miss, mode="children")
    one = routed(d, np.int8)
    with vmem_budget(budget_for(d["max_bin"], 45, 3)):
        many = routed(d, np.int8)
    assert_same(many, one)
    assert int((np.asarray(many[2]) >= d["W"] // 2).sum()) > 0


@pytest.mark.parametrize("ldtype", [np.uint8, np.int32])
@pytest.mark.parametrize("miss", [False, True], ids=["nomiss", "miss"])
def test_routed_leaf_vector_equals_the_select_chain(miss, ldtype):
    """``histogram_pallas_route`` against the select chain the XLA
    routing runs (``ops/grow.py`` ``route_wave``), written out in
    numpy: a row's lane by its leaf id, its split column's bin, the
    threshold compare (a missing bin goes the default way), the new id
    where it goes right, the smaller child's lane as selector."""
    d = case(45, 16, True, miss)
    x, li, tbl = (np.asarray(d[k]).astype(np.int64)
                  for k in ("x", "li", "tbl"))
    got_li, got_sel = H.histogram_pallas_route(
        d["x"], d["li"].astype(ldtype), d["tbl"], d["W"], RPB, "small",
        d["mb"], DEAD)
    assert got_li.dtype == ldtype
    lane = np.full(N, -1)
    for w in range(d["W"]):
        lane = np.where(li == tbl[0, w], w, lane)
    safe = np.maximum(lane, 0)
    col = x[tbl[1, safe], np.arange(N)]
    left = col <= tbl[2, safe]
    if miss:
        mb = np.asarray(d["mb"])[tbl[1, safe]]
        left |= (tbl[5, safe] > 0) & (col == mb) & (mb >= 0)
    left &= lane >= 0
    want_li = np.where((lane >= 0) & ~left, tbl[3, safe], li)
    want_sel = np.where((lane >= 0) & (left == (tbl[4, safe] > 0)),
                        lane, -1)
    np.testing.assert_array_equal(np.asarray(got_li), want_li)
    np.testing.assert_array_equal(np.asarray(got_sel), want_sel)
    assert int((want_li != li).sum()) > 0


@pytest.mark.parametrize("dtype", [np.uint16, np.int32])
def test_route_kernel_wider_bins(dtype):
    """Bins stored two or four bytes wide: a storage tile is 16 or 8
    rows and a word holds two bins or one."""
    d = case(45, 16, True, False)
    want = H.histogram_pallas_route(d["x"], d["li"], d["tbl"], d["W"], RPB,
                                    "small", None, DEAD)
    got = H.histogram_pallas_route(d["x"].astype(dtype), d["li"], d["tbl"],
                                   d["W"], RPB, "small", None, DEAD)
    assert_same(got, want)


def test_route_kernel_fewer_features_than_a_storage_tile():
    """20 features are fewer than the 32 rows of a uint8 storage tile:
    the matrix comes whole and is read row by row."""
    d = case(20, 16, True, False)
    one = routed(d, np.int8)
    with vmem_budget(budget_for(d["max_bin"], 20, 3)):
        many = routed(d, np.int8)
    assert_same(many, one)


def _eqns(jaxpr):
    """Every operation, those inside a nested jit in the jit's place."""
    for eqn in jaxpr.eqns:
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        if eqn.primitive.name == "pallas_call" or not subs:
            yield eqn
        else:
            for sub in subs:
                yield from _eqns(sub)


def test_routing_step_reads_no_feature_sized_operand():
    """Routing a wave is proportional to rows x lanes: the routing
    kernel is the one operation of the routing step that is handed the
    (F, N) matrix, it reads it a storage tile of 32 rows at a time (no
    block of it has a feature dimension), and nothing the step makes
    is as large as lanes x rows."""
    F, n, W = 2000, 4 * RPB, 64
    S = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(
        lambda x, li, tbl, mb: H.histogram_pallas_route(
            x, li, tbl, W, RPB, "small", mb, 255)
    )(S((F, n), jnp.uint8), S((n,), jnp.uint8), S((6, W), jnp.int32),
      S((F,), jnp.int32)).jaxpr
    touched = [e for e in _eqns(jaxpr)
               if any(getattr(v.aval, "shape", ()) == (F, n)
                      for v in e.invars if hasattr(v, "aval"))]
    assert [e.primitive.name for e in touched] == ["pallas_call"]
    (kernel,) = touched
    blocks = [tuple(int(getattr(d, "block_size", d))
                    for d in b.block_shape)
              for b in kernel.params["grid_mapping"].block_mappings]
    assert (32, n) in blocks            # the matrix, a storage tile
    for shape in blocks:
        assert F not in shape and int(np.prod(shape)) <= 32 * n, blocks
    for e in _eqns(jaxpr):
        for v in e.outvars:
            assert int(np.prod(v.aval.shape)) <= W * n, (e.primitive, v.aval)


def _tree(bins, grad, hess, p):
    F, n = bins.shape
    return G.build_tree(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.ones(n, jnp.float32), jnp.ones(F, bool),
        jnp.full(F, 255, jnp.int32), jnp.zeros(F, jnp.int32),
        jnp.zeros(F, bool), p, quant_key=jax.random.PRNGKey(7))


@pytest.mark.parametrize("refine_shift", [4, 0], ids=["c2f", "full"])
def test_build_tree_same_tree_routed_and_unrouted(monkeypatch,
                                                  refine_shift):
    """The two-column quantized wave tier on a shape whose passes
    chunk: routing by the routing step grows, split for split and row
    for row, the tree that routing by XLA's select chain grows."""
    rng = np.random.RandomState(5)
    F, n = 45, 2048
    bins = rng.randint(0, 255, size=(F, n)).astype(np.uint8)
    logit = (bins[0] / 255.0 - 0.5) + 0.7 * (bins[F - 1] > 140) - \
        0.4 * (bins[20] < 60)
    y = (rng.random_sample(n) < 1 / (1 + np.exp(-3 * logit))
         ).astype(np.float32)
    grad = (y.mean() - y).astype(np.float32)
    hess = np.full(n, y.mean() * (1 - y.mean()), np.float32)
    p = G.GrowParams(
        split=SplitParams(max_bin=256, min_data_in_leaf=1,
                          min_sum_hessian_in_leaf=2.0, any_cat=False,
                          any_missing=False, counts_proxy=True),
        num_leaves=15, hist_impl="pallas", rows_per_block=RPB, wave=True,
        speculate=64, two_col=True, quantize=120,
        refine_shift=refine_shift)
    bins_routed = 16 if refine_shift else 256
    with vmem_budget(96 * 1024 if refine_shift else 600 * 1024):
        til = H.bin_tiling(bins_routed, F, 128, RPB)
        assert til.chunks > 1, til
        assert G.route_kind(p, "serial", bins_routed, F) == "gather"
        routed_rec = _tree(bins, grad, hess, p)
        monkeypatch.setattr(G, "routed_gate",
                            lambda *a, **k: "test: routing left to XLA")
        jax.clear_caches()
        assert G.route_kind(p, "serial", bins_routed, F) == "xla"
        plain_rec = _tree(bins, grad, hess, p)
    assert int(routed_rec["n_leaves"]) == 15
    assert routed_rec["leaf_idx"].dtype == jnp.uint8
    assert plain_rec["leaf_idx"].dtype == jnp.int32
    for k in sorted(routed_rec):
        np.testing.assert_array_equal(
            np.asarray(routed_rec[k], np.float64),
            np.asarray(plain_rec[k], np.float64), err_msg=k)
