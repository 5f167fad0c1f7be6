"""Histogram construction — the hottest op.

Reference: ``Bin::ConstructHistogram`` (``include/LightGBM/bin.h:346-371``,
``src/io/dense_bin.hpp:43``) on CPU and the OpenCL kernels
(``src/treelearner/ocl/histogram256.cl``) on GPU accumulate
``(sum_grad, sum_hess, count)`` per (feature, bin).

TPU-first design: no atomics on TPU, so the scatter-add becomes a
one-hot × values matmul on the MXU.  Two implementations:

- ``histogram_segsum``: jnp reference (segment-sum), used on CPU/tests
  and as the numerical oracle for the kernel.
- ``histogram_pallas``: Pallas kernel — grid over row tiles, each step
  loads an (FC, T) bin tile + (3, T) value tile into VMEM, builds the
  (FC, B, T) one-hot per feature and accumulates ``onehot @ vals`` into
  an (FC*B, C) accumulator that lives across grid steps.

Tiling notes (measured on v5e):
- The accumulator's row count FC*B must be a multiple of the 128-lane
  MXU tile or the streamed matmul pays ~40% — bins are padded to
  ``_pad_bins`` and sliced off on exit, and ``_tile`` adds a feature
  tail.  The one-hot rows STREAMED into it need not be on that grid:
  the tail is never built (``_accumulate``), and a dot over 28*16 =
  448 or 67*16 = 1072 rows runs 3.5-4% faster than over the 512 or
  1152 of the whole block (PERF.md, PR 26).
- The bin matrix is every kernel's operand as stored: no pass copies
  it in HBM to add the tail (``BinTiling``).
- FC=32 features per chunk with 512-row tiles beats 16×1024 by ~25%
  (fewer, larger one-hot builds against the same accumulator traffic).

Value columns:
- default: values are split into a bf16 hi part via mantissa masking
  (which ``--xla_allow_excess_precision`` cannot fold away) plus a bf16
  residual, so two bf16 passes reach ~2^-16 relative accuracy at full
  bf16 throughput → 6 columns per histogram triple.
- ``exact=True``: the caller guarantees values are integers with
  |v| ≤ 256 (quantized gradients) — exactly representable in bf16, so
  3 columns suffice.  This doubles the leaf width of the speculative
  multi-leaf pass (21 → 42 histograms per matmul) for free.
- int8 values (``exact`` or ``two_col``; ops/grow.py hands them over
  where ``GrowParams.int8_values``): the batched kernels build the
  one-hot and the rhs as int8 and contract int8 x int8 -> int32, the
  MXU's faster mode (393 TOP/s against 197 TFLOP/s bf16 on a v5e);
  the tile's int32 partial is converted to float32 and added into the
  float32 accumulator, every output bit the bf16 contraction's
  (``_accumulate``, ``_onehot_int8``, ``_rhs_int8``).  The operand's
  dtype alone decides: float32 values keep the bf16 path, and the
  tier record's ``mxu`` says which a booster runs.
- the ORDER of the int8 one-hot's rows is the kernel's own business:
  the contraction sums over data rows, so a permutation of the
  one-hot's rows is the same permutation of the accumulator's.  The
  one-hot is made four rows to a 32-bit word at every bin count, slab
  by slab up to 32 bins (the coarse passes' 16 and the refine window's
  32) and off the 32-bin grid, feature by feature at 64 bins and up,
  whichever the chip runs faster: at 32 bins the slabs take each
  feature's row as it stands where the words broadcast it over a
  sublane group for every group they make (``_onehot_form``,
  ``_onehot_int8``; the tier record's ``onehot``), and ONE helper on
  the XLA side, ``_feature_bin``, which every wrapper calls on its
  accumulator, puts the rows back to (feature, bin).  Nothing else
  knows the order.
- the per-row prologue, what a tile does before its contraction: each
  row's subset is ONE (1, T) index (the callers' selector, or the
  lane the kernel looks up with every lane table in one contraction:
  ``_lane_lookup``), and the rhs is made from it (``_rhs``): int8
  values a 32-bit word at a time, float32 ones row by row as bf16
  (the tier record's ``prologue``).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..utils.env import pallas_interpret

__all__ = ["histogram", "histogram_segsum", "histogram_segsum_into",
           "histogram_pallas",
           "histogram_segsum_multi", "histogram_pallas_multi",
           "histogram_segsum_multi_win", "histogram_pallas_multi_win",
           "histogram_routed", "histogram_pallas_route",
           "multi_width"]


def multi_width(exact: bool, two_col: bool = False) -> int:
    """Leaves per speculative pass: 6 columns each (hi/lo) fills the
    128-lane MXU tile at 21; exact 3-column values fit 42; dropping
    the count column (provably redundant when min_data_in_leaf<=1 and
    min_sum_hessian>0 — see GrowParams.two_col) fits 64."""
    if two_col:
        return 64
    return 42 if exact else 21


def histogram_segsum(bins_t: jax.Array, vals: jax.Array, max_bin: int
                     ) -> jax.Array:
    """(F, N) int bins × (N, 3) values -> (F, B, 3) histogram."""
    f, n = bins_t.shape
    ids = bins_t.astype(jnp.int32) + \
        jnp.arange(f, dtype=jnp.int32)[:, None] * max_bin
    flat = jax.ops.segment_sum(
        jnp.broadcast_to(vals[None, :, :], (f, n, 3)).reshape(-1, 3),
        ids.reshape(-1), num_segments=f * max_bin)
    return flat.reshape(f, max_bin, 3)


def histogram_segsum_into(h: jax.Array, bins_t: jax.Array,
                          vals: jax.Array, max_bin: int) -> jax.Array:
    """Accumulate one ROW PAGE into a carried (F, B, 3) histogram.

    The out-of-core pager (io/pager.py) folds a shard's row range one
    fixed-size page at a time; this op is its accumulation step.  It
    is BIT-identical to one :func:`histogram_segsum` over the
    concatenated pages: a scatter-add visits each (feature, bin)
    bucket's rows in ascending row order — the same per-bucket fold
    order ``jax.ops.segment_sum`` uses — so carrying ``h`` across
    contiguous pages in page order reproduces the monolithic sum
    add-for-add.  (Summing independent per-page partial histograms
    does NOT have this property: it reassociates the per-bucket fold
    and drifts in the last ulp.)
    """
    f, n = bins_t.shape
    ids = bins_t.astype(jnp.int32) + \
        jnp.arange(f, dtype=jnp.int32)[:, None] * max_bin
    upd = jnp.broadcast_to(vals[None, :, :], (f, n, 3)).reshape(-1, 3)
    flat = h.reshape(f * max_bin, 3).at[ids.reshape(-1)].add(upd)
    return flat.reshape(f, max_bin, 3)


def _pad_bins(max_bin: int) -> int:
    # multiple of 8: the tiler below only accepts (fc, b_pad) pairs with
    # fc*b_pad on the 128-lane grid, so 8-bin coarse histograms pair with
    # fc=16/32 chunks; padded bins hold no rows and are sliced off on exit
    return (max_bin + 7) // 8 * 8


_VMEM_BUDGET = 56 * 1024 * 1024     # what one grid step may hold


def _tile(b_pad: int, f: int, cols: int, rows_per_block: int
          ) -> Tuple[int, int, int]:
    """(padded features, features-per-chunk, rows-per-tile).

    The pass is MXU-STREAM bound: cost ∝ f_pad * b_pad * N (the one-hot
    rows fed through the systolic array, at the bf16 peak, or at the
    int8 peak where the values are int8: ``_accumulate``), so the
    FIRST objective is the smallest f_pad with a legal chunking (fc
    divides f_pad, fc*b_pad a multiple of the 128-lane tile) — e.g.
    28 features stay 28 at 64 bins (28*64 = 14*128) instead of
    padding to 32 and paying +14%.
    Then prefer large row tiles (fewer grid steps / accumulator
    revisits) under a VMEM budget of one-hot (FC, B, T) bf16 +
    accumulator (FC*B, cols) f32 + double-buffered inputs."""
    budget = _VMEM_BUDGET
    for f_pad in range(max(f, 2), f + 9):
        best = None
        for fc in range(f_pad, 0, -1):
            # legal Mosaic block: fc the full feature dim or a multiple
            # of the 8-sublane tile; fc*b_pad on the 128-lane grid
            if f_pad % fc or (fc * b_pad) % 128 or \
                    (fc != f_pad and fc % 8):
                continue
            for t in (16384, 8192, 4096, 2048, 1024, 512, 256):
                if t % rows_per_block and rows_per_block % t:
                    continue
                t_eff = min(t, rows_per_block)
                vmem = b_pad * (fc * t_eff * 2 + fc * cols * 4) \
                    + fc * t_eff * 4 * 2
                if vmem > budget:
                    continue
                cand = (fc * t_eff, t_eff, fc)
                if best is None or cand > best:
                    best = cand
                break  # largest feasible t for this fc
        if best is not None:
            return f_pad, best[2], best[1]
    # fallback: smallest legal chunk — fc*b_pad on the 128-lane grid
    # AND fc on the 8-sublane grid (lcm of both constraints)
    fc = 128 // math.gcd(b_pad, 128)
    fc = fc * 8 // math.gcd(fc, 8)
    f_pad = (f + fc - 1) // fc * fc
    if rows_per_block % 256 == 0:
        return f_pad, fc, 256
    return f_pad, fc, rows_per_block


class BinTiling(NamedTuple):
    """How one kind of pass tiles the stored (F, N) bin matrix.

    The matrix is the kernel's operand AS STORED: no pass pads it in
    HBM.  ``f_pad - f`` is the FEATURE TAIL the tiler asks for (it puts
    ``fc * b_pad`` on the 128-lane grid by adding features); the kernel
    makes it in VMEM (:func:`_accumulate`):

    - one chunk (``fc == f_pad``): the block is the stored feature
      dimension whole, ``(f, t)`` — legal because it is the array's
      full dimension — and the kernel works on ``f`` rows; the tail's
      accumulator rows are zeroed once and never touched;
    - several chunks: blocks are ``(fc, t)`` and the last one overhangs
      the array; the kernel masks it by feature index.
    """
    f: int
    f_pad: int
    fc: int
    t: int
    b_pad: int      # the pass's bins, padded (``_pad_bins``)

    @property
    def one_chunk(self) -> bool:
        return self.fc == self.f_pad

    @property
    def rows(self) -> int:
        """Feature rows the kernel sees across the grid: what the small
        per-feature operands (window starts, missing bins) pad to."""
        return self.f if self.one_chunk else self.f_pad

    @property
    def block_rows(self) -> int:
        return self.f if self.one_chunk else self.fc

    @property
    def chunks(self) -> int:
        """Feature blocks the pass's grid walks."""
        return self.f_pad // self.fc

    @property
    def f_mask(self) -> int:
        """``f`` where the last feature block overhangs the stored
        matrix and the kernel has to mask it, else 0."""
        return self.f if not self.one_chunk and self.f_pad != self.f else 0

    def record(self, int8: bool = False) -> dict:
        """The engagement record (``GBDT.tier_decision["hist_tiling"]``).
        ``xt_copied``: whether the pass copies the matrix in HBM before
        its kernel starts.  No shape does: Mosaic takes both blocks
        above (on the chip: tools/check_routed_kernels.py).  ``mxu``:
        the type the pass contracts in (:func:`_accumulate`);
        ``prologue``: how a tile makes its right-hand side, ``words``
        from each row's subset index a 32-bit word at a time
        (:func:`_rhs_int8`), ``rows`` from it row by row
        (:func:`_rhs_bf16`); ``onehot``: the order the one-hot's rows
        are built in, by the bins where it is int8
        (:func:`_onehot_form`): ``slabs`` up to 32 bins (the coarse
        and the refine passes) and off the 32-bin grid, ``words`` at
        64 bins and up (a full-resolution pass); ``plain`` (feature,
        bin) where it is bf16.  All three follow the values: the
        caller says whether the pass is given int8 ones (ops/grow.py
        ``GrowParams.int8_values``).  ``chunks``: the feature blocks
        the grid walks (``f_pad // fc``)."""
        return {"f": self.f, "f_pad": self.f_pad, "fc": self.fc,
                "chunks": self.chunks, "t": self.t, "xt_copied": False,
                "mxu": "int8" if int8 else "bf16",
                "prologue": "words" if int8 else "rows",
                "onehot": _onehot_form(self.b_pad) if int8 else "plain"}


def bin_tiling(max_bin: int, f: int, cols: int = 128,
               rows_per_block: int = 1024) -> BinTiling:
    """The tiling a pass over ``f`` stored features at ``max_bin`` bins
    runs with (``cols``: 128 for the batched passes, the value columns
    for the single-leaf one)."""
    b_pad = _pad_bins(max_bin)
    return BinTiling(f, *_tile(b_pad, f, cols, rows_per_block), b_pad)


def _miss_operand(miss_bin: jax.Array, til: BinTiling) -> jax.Array:
    """(F,) per-feature missing bins -> the (til.rows, 1) kernel operand
    (-1: no missing bin)."""
    return jnp.pad(miss_bin.astype(jnp.int32), (0, til.rows - til.f),
                   constant_values=-1)[:, None]


def _compiler_params():
    """Raise Mosaic's scoped-VMEM ceiling (default ~16-32 MB) so the
    large one-hot row tiles the tiler picks actually compile; v5e has
    128 MB of VMEM."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(vmem_limit_bytes=100 * 1024 * 1024)


def _split_hi_lo(v: jax.Array) -> jax.Array:
    """(3, T) f32 -> (6, T): exact truncation split, hi = top 16 bits."""
    v_hi = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(v, jnp.uint32) &
        jnp.uint32(0xFFFF0000), jnp.float32)
    return jnp.concatenate([v_hi, v - v_hi], axis=0)


def _rhs_cols(width: int, cols: int) -> int:
    """rhs lane count for a pass: one 128-lane MXU tile when the
    subsets fit, two tiles (256) for the WIDE passes (e.g. all 2W
    children of a wave in ONE windowed pass — same total MXU work as
    two 128-lane passes, but one bins-matrix read and one launch)."""
    need = width * cols
    assert need <= 256, (width, cols)
    return 128 if need <= 128 else 256


def _rhs(lane: jax.Array, valsc: jax.Array, width: int) -> jax.Array:
    """The rhs of a batched pass's contraction (:func:`_accumulate`):
    (128 or 256, T), row ``k`` is ``valsc[k % C]`` where the row's
    subset is ``k // C`` and 0 elsewhere.  lane (1, T) int32: each
    row's subset in ``[-1, width)`` (-1: none); valsc (C, T).  The
    values' type decides the form: int8 by words (:func:`_rhs_int8`),
    float32 row by row as bf16 (:func:`_rhs_bf16`)."""
    if valsc.dtype == jnp.int8:
        return _rhs_int8(lane, valsc, width)
    return _rhs_bf16(lane, valsc, width)


def _rhs_bf16(lane: jax.Array, valsc: jax.Array, width: int
              ) -> jax.Array:
    """The bf16 rhs of float32 values, C up to 6 (the hi/lo split:
    the hi part is bf16-exact by construction and the lo residual is
    rounded to bf16 here, which reaches ~2^-16 RELATIVE accuracy, not
    exactness — see the module header).

    Built row by row in two dimensions from the subset index: rhs row
    ``k`` compares the index with ``k // C`` and selects value row
    ``k % C``.  A (W, T) selector times the values, regrouped
    (W, C, T) -> (W * C, T), gives the same values and was what a
    float32-valued pass spent most of its time on: 41.7 against 18.0
    ms a routed pass of 21M x 28, 43.3 against 13.7 unrouted
    (PERF.md, PR 31)."""
    C = valsc.shape[0]
    n = _rhs_cols(width, C)
    k = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    row_lane = jnp.where(k < width * C, jax.lax.div(k, C), -2)
    c = jax.lax.rem(k, C)
    row = valsc[0:1]
    for i in range(1, C):
        row = jnp.where(c == i, valsc[i:i + 1], row)   # (n, T)
    return jnp.where(lane == row_lane, row, 0.0).astype(jnp.bfloat16)


def _rhs_int8(lane: jax.Array, valsc: jax.Array, width: int
              ) -> jax.Array:
    """The int8 rhs (:func:`_rhs`), the quantized integers as they
    are; valsc (C, T) int8, C 2 or 3.

    Made a 32-bit WORD at a time, as :func:`_onehot_int8` makes the
    one-hot: int8 rows ``4j .. 4j + 3`` are the bytes of int32 row
    ``j`` (``pltpu.bitcast``).  A row belongs to one subset at most,
    so its C values are C adjacent bytes of the column, put together
    once a tile at (1, T).  Two columns: subset ``L``'s 16 bits are
    the half ``L & 1`` of word ``L >> 1``.  Three: its 24 bits start
    at byte ``3L`` and lie in word ``3L >> 2`` and, from byte 2 or 3
    on, in the next.  A compare and a select (or two) on (32, T)
    words, where the row-by-row form was a compare, C selects and two
    narrowing packs on (128, T) elements.  Two-dimensional throughout:
    a (W, C, T) -> (W * C, T) regrouping is what Mosaic takes longest
    to compile in a pass (46 s of an int32 one at T = 16384; PERF.md,
    PR 29)."""
    from jax.experimental.pallas import tpu as pltpu
    C, T = valsc.shape
    assert C in (2, 3), C
    v = valsc.astype(jnp.int32) & 0xFF                 # the bytes
    j = jax.lax.broadcasted_iota(
        jnp.int32, (_rhs_cols(width, C) // 4, T), 0)
    if C == 2:
        gh = v[0:1] | (v[1:2] << 8)
        words = jnp.where(j == (lane >> 1), gh << ((lane & 1) << 4), 0)
    else:
        ghc = v[0:1] | (v[1:2] << 8) | (v[2:3] << 16)
        at = lane * 3                                  # first byte
        j0, s = at >> 2, (at & 3) << 3
        # a dead row (-1) starts at byte 1 of word -1: no word holds
        # it, and its spill into word 0 shifts out to 0
        words = jnp.where(j == j0, ghc << s,
                          jnp.where(j == j0 + 1, (ghc >> 8) >> (24 - s),
                                    0))
    return pltpu.bitcast(words, jnp.int8)


# ---- the int8 one-hot and the order of its rows ---------------------
#
# The contraction sums over data rows, so the ORDER of the one-hot's
# rows is the kernel's own business: any permutation gives the same
# accumulator with its rows permuted.  Three functions know the order
# and nothing else does: :func:`_onehot_form` names it,
# :func:`_onehot_int8` builds the one-hot in it, and
# :func:`_feature_bin` (through :func:`_rows_to_feature_bin`) puts the
# accumulator's rows back to (feature, bin) in XLA, where every wrapper
# reshapes and moves the accumulator's axes anyway.

_TAIL = 4       # a features' tail of up to this many rows: own slabs


def _onehot_form(b_pad: int) -> str:
    """The order :func:`_onehot_int8` builds in at ``b_pad`` bins:
    ``words`` (feature by feature) where the ``b_pad / 4`` words of a
    feature fill two or more whole 8-sublane groups (64, 128, 256
    bins), ``slabs`` elsewhere (8 to 56 bins, the 32-bin window
    among them, and every ``b_pad`` off the 32-bin grid).

    Measured alone on the chip, ms a pass, words against slabs
    (``tools/check_routed_kernels.py``; PERF.md section 5).  At 32 bins
    a feature's 8 words are ONE sublane group, and the words order
    broadcasts the feature's row over it twice for that one group: the
    refine pass 15.34 against 14.89 at 21M x 28, 32.45 against 32.11
    at 20M x 67 (2144 one-hot rows against 2176: a tail of 3 takes
    4), 26.41 against 25.71 at 16M x 68, 58.69 against 56.93 at
    1.2M x 2000 (50 chunks), and Mosaic compiles it 0.8 to 1.7 s
    sooner.  At 64 and 256 bins the broadcast serves two or eight
    groups and the orders are level (25.96 and 25.82 at 21M x 28 and
    64 bins, 47.00 and 46.83 at 16M x 68; 100.56 and 100.45, 184.81
    and 184.86 at 256), or the slabs behind where they pad a tail
    (57.73 against 58.37 at 20M x 67 and 64 bins); and a tail's slabs
    may overrun a one-chunk accumulator there (67 features at 256
    bins tile as ``f_pad`` 67)."""
    return "words" if b_pad % 32 == 0 and b_pad > 32 else "slabs"


def _slab_split(R: int) -> Tuple[int, int]:
    """``R`` feature rows in slab order: (rows of the slabs, a
    multiple of 8; features of the tail, which has slabs of ``_TAIL``
    rows of its own, or 0)."""
    tail = R % 8
    if tail > _TAIL:
        tail = 0                    # 5 to 7 rows: a group of 8 as it is
    return -(-(R - tail) // 8) * 8, tail


def _onehot_rows(R: int, b_pad: int) -> int:
    """int8 one-hot rows :func:`_onehot_int8` makes of ``R`` features."""
    if _onehot_form(b_pad) == "words":
        return R * b_pad
    main, tail = _slab_split(R)
    return (main + (_TAIL if tail else 0)) * b_pad


def _onehot_int8(xb: jax.Array, b_pad: int) -> jax.Array:
    """(R, T) int32 bins -> the int8 one-hot, ``_onehot_rows(R, b_pad)``
    rows on the (32, 128) int8 tile grid; a bin outside ``[0, b_pad)``
    counts nowhere.

    Made four rows to a 32-bit WORD with no narrowing: int8 rows
    ``4j .. 4j + 3`` are the bytes of int32 row ``j``
    (``pltpu.bitcast``), so the word that holds bins ``4q .. 4q + 3``
    of a feature is ``1 << 8 * (x & 3)`` where ``x >> 2 == q`` and 0
    elsewhere (a bin outside ``[0, b_pad)`` meets no ``q``): a compare
    and a select a WORD, where the element-by-element form (compare in
    int32, regroup ``(R, b_pad, T) -> (R * b_pad, T)``, narrow twice)
    was some eleven vector operations a (32, 128) tile and set a coarse
    pass's pace at 18.2 to 18.5 us a one-hot row against the MXU's 13
    (PERF.md, PR 31).  The ``Q = b_pad / 4`` words of a feature lie
    (:func:`_onehot_form` says which, and why)

    - ``words``, ``Q`` a multiple of 8 and at least 16: feature by
      feature, word ``r * Q + q``: int8 row ``r * b_pad + b`` holds
      ``xb[r] == b``.  The regrouping ``(R, Q, T) -> (R * Q, T)``
      fills whole sublane groups and costs nothing, but each feature's
      row is broadcast over its groups' sublanes, once for ``hi`` and
      once for ``byte``: at ``Q`` = 8 that is two broadcasts for one
      group, some 4.75 vector operations a word vreg where the slabs
      take 2.75;
    - ``slabs``, every other ``Q`` (16 bins: 4; the 32-bin window: 8):
      at 16 bins that regrouping is a relayout (52.6 against 30.2 ms a
      routed coarse pass of 20M x 67, on the chip), so no
      three-dimensional value exists: word ``q`` of EVERY feature is
      one compare and select on the ``(R8, T)`` block as it stands,
      and the ``Q`` slabs are concatenated on the sublane grid: word
      ``q * R8 + r``, int8 row ``4 * (q * R8 + r) + k`` holds bin
      ``4q + k`` of feature ``r``.  A features' tail of up to 4 rows
      (28 = 24 + 4, 67 = 64 + 3) would add 8 rows to every slab; it
      follows the whole groups in slabs of 4 rows of its own, two to a
      sublane group (the tail tiled twice, compared with a per-sublane
      ``q``): 448 and 1088 one-hot rows stream at 16 bins, not 512 and
      1152, which is 0.6 to 1.0 ms of a routed coarse pass at the
      benchmark's shapes (9.60 against 10.58 ms at 21M x 28, 18.40
      against 19.23 at 20M x 67, where the plain form took 12.05 and
      23.66; a row of the slabs costs 13.1 to 14.0 us: PERF.md, PR
      34), and 896, 2176 (of 67 and of 68) and 1280 (a chunk of 40)
      at 32 bins."""
    from jax.experimental.pallas import tpu as pltpu
    R, T = xb.shape
    Q = b_pad // 4

    def parts(x):
        return x >> 2, jnp.left_shift(1, (x & 3) << 3)

    if _onehot_form(b_pad) == "words":
        hi, byte = parts(xb)
        words = jnp.where(
            hi[:, None, :] ==
            jax.lax.broadcasted_iota(jnp.int32, (R, Q, T), 1),
            byte[:, None, :], 0).reshape(R * Q, T)
        return pltpu.bitcast(words, jnp.int8)

    def rows(lo, hi_, n):
        """Feature rows ``lo .. hi_`` up to ``n`` rows of bin -1."""
        if hi_ - lo == n:
            return xb[lo:hi_]
        return jnp.concatenate(
            [xb[lo:hi_], jnp.full((n - (hi_ - lo), T), -1, jnp.int32)],
            axis=0)

    main, tail = _slab_split(R)
    slabs = []
    if main:
        hi, byte = parts(rows(0, R - tail, main))
        slabs = [jnp.where(hi == q, byte, 0) for q in range(Q)]
    if tail:
        hi, byte = parts(jnp.concatenate(
            [rows(R - tail, R, _TAIL)] * 2, axis=0))         # (8, T)
        q01 = jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0) // _TAIL
        # b_pad is a multiple of 8: Q is even
        slabs += [jnp.where(hi == q01 + q, byte, 0)
                  for q in range(0, Q, 2)]
    return pltpu.bitcast(jnp.concatenate(slabs, axis=0), jnp.int8)


def _rows_to_feature_bin(acc: jax.Array, R: int, b_pad: int) -> jax.Array:
    """(..., rows, lanes) in :func:`_onehot_int8`'s order of ``R``
    features (rows beyond ``_onehot_rows`` are ignored) ->
    (..., R, b_pad, lanes)."""
    lead, lanes = acc.shape[:-2], acc.shape[-1]
    if _onehot_form(b_pad) == "words":
        return acc[..., :R * b_pad, :].reshape(*lead, R, b_pad, lanes)
    n, Q = len(lead), b_pad // 4

    def unslab(lo, r, keep):
        """``r * b_pad`` rows from ``lo`` on, Q slabs of ``r`` words,
        as the first ``keep`` features' (bins, lanes)."""
        blk = acc[..., lo:lo + r * b_pad, :].reshape(*lead, Q, r, 4, lanes)
        return jnp.swapaxes(blk, n, n + 1).reshape(
            *lead, r, b_pad, lanes)[..., :keep, :, :]

    main, tail = _slab_split(R)
    parts = ([unslab(0, main, R - tail)] if main else []) + \
        ([unslab(main * b_pad, _TAIL, tail)] if tail else [])
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=n)


def _feature_bin(out: jax.Array, til: BinTiling, int8: bool) -> jax.Array:
    """A pass's accumulator (f_pad * b_pad, lanes), as its kernel left
    it, -> (f, b_pad, lanes): the ONE place outside the kernel that
    knows the order of the accumulator's rows.  A float32-valued pass
    (bf16 one-hot) keeps (feature, bin); an int8-valued one has each
    feature block's rows in :func:`_onehot_int8`'s order.  Integer
    sums moved, not re-added: every output bit is what it was."""
    f, f_pad, fc, _, b_pad = til
    lanes = out.shape[1]
    if not int8:
        return out.reshape(f_pad, b_pad, lanes)[:f]
    blocks = out.reshape(f_pad // fc, fc * b_pad, lanes)
    return _rows_to_feature_bin(blocks, til.block_rows, b_pad).reshape(
        -1, b_pad, lanes)[:f]


def _batched_hists(out: jax.Array, til: BinTiling, int8: bool,
                   width: int, cols: int, n_bins: int, two_col: bool,
                   exact: bool) -> jax.Array:
    """A batched pass's accumulator (f_pad * b_pad, 128 or 256), lane
    ``w * cols + c`` column ``c`` of subset ``w`` -> (width, F,
    n_bins, 3)."""
    out = _feature_bin(out[:, :cols * width], til, int8).reshape(
        til.f, til.b_pad, width, cols)
    if two_col:
        # count := hess copy keeps every downstream shape at (..., 3);
        # the gate guarantees nothing reads it as a real count
        out = jnp.concatenate([out, out[..., 1:2]], axis=-1)
    elif not exact:
        out = out[..., :3] + out[..., 3:]    # hi + lo
    return jnp.moveaxis(out[:, :n_bins], 2, 0)


def _accumulate(out_ref, xb: jax.Array, rhs: jax.Array, b_pad: int,
                f_mask: int = 0, row0=0) -> None:
    """Last stage of every histogram kernel: the one-hot x values MXU
    contraction of one tile, added into the accumulator block.

    xb (R, T) int32: the bin each row counts in, per feature (a value
    outside [0, b_pad) counts nowhere); rhs (128 or 256, T) bf16 or
    int8; out_ref (>= R * b_pad, lanes) f32.  The one-hot is laid out
    (rows, T) so the dot STREAMS its rows through the MXU while the
    tiny (T, lanes) value matrix sits stationary as weights; the
    reverse orientation reloads K x B weight tiles to stream only a
    few rows and is ~100x slower.

    The contraction's type follows the rhs, which follows the values
    the kernel was given.  int8 (quantized gradients: integers within
    +-127 against a 0/1 one-hot): int8 x int8 -> int32, the MXU's
    faster mode; a tile's partial sum is at most ``T`` x 127 = 2.08M
    at ``T`` = 16384, exact in int32 and in the float32 it is
    converted to and added into, so every output bit is what the bf16
    contraction of the same integers gives.  Its one-hot's rows, and
    so the accumulator block's, are in :func:`_onehot_int8`'s order,
    which :func:`_feature_bin` undoes outside the kernel.  Anything
    else: bf16 x bf16 -> f32, rows in (feature, bin) order.

    The FEATURE TAIL is made here (see :class:`BinTiling`).  One
    chunk: ``xb`` has the stored features' rows only, fewer than the
    accumulator block; their one-hot rows alone are built, streamed
    and added into the block's head, and the rows beyond keep the
    zeros ``_init`` wrote.  Several chunks (``f_mask`` > 0): the last
    block overhangs the stored matrix, and rows whose feature index
    ``row0 + i`` is not below ``f_mask`` hold whatever VMEM held:
    they are sent to bin -1."""
    R, T = xb.shape
    if f_mask:
        feat = row0 + jax.lax.broadcasted_iota(jnp.int32, (R, T), 0)
        xb = jnp.where(feat < f_mask, xb, -1)
    if rhs.dtype == jnp.int8:
        acc = jax.lax.dot_general(
            _onehot_int8(xb, b_pad), rhs, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32)
    else:
        onehot = (xb[:, None, :] ==
                  jax.lax.broadcasted_iota(jnp.int32, (R, b_pad, T), 1)
                  ).astype(jnp.bfloat16)
        acc = jax.lax.dot_general(
            onehot.reshape(R * b_pad, T), rhs.T,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (R*B, lanes)
    assert acc.shape[0] <= out_ref.shape[0], (acc.shape, out_ref.shape)
    if acc.shape[0] == out_ref.shape[0]:
        out_ref[...] += acc
    else:
        out_ref[:acc.shape[0], :] += acc


def _hist_kernel(x_ref, v_ref, out_ref, *, b_pad: int, cols: int,
                 exact: bool, f_mask: int = 0):
    """One grid step: accumulate one (feature-chunk × row-tile) into the
    shared accumulator.

    x_ref: (FC, T) stored bins; v_ref: (3, T) f32 [grad, hess, count];
    out_ref: (FC*B, cols) f32 accumulated over the row-tile grid dim.

    Design: the scatter-add of the reference's CPU/OpenCL histogram
    kernels becomes one one-hot × values MXU contraction per tile
    (:func:`_accumulate`).
    """
    import jax.experimental.pallas as pl

    # row tiles are the MINOR grid dim so each out block's revisits are
    # consecutive — accumulation across non-consecutive revisits races
    # with the pipeline's block write-back
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.int32)  # (FC, T); widen narrow storage
    v = v_ref[...]  # (3, T) f32
    rhs = (v if exact else _split_hi_lo(v)).astype(jnp.bfloat16)
    _accumulate(out_ref, x, rhs, b_pad, f_mask,
                pl.program_id(0) * x.shape[0])


@functools.partial(jax.jit,
                   static_argnames=("max_bin", "rows_per_block", "exact"))
def histogram_pallas(bins_t: jax.Array, vals: jax.Array, max_bin: int,
                     rows_per_block: int = 1024, exact: bool = False
                     ) -> jax.Array:
    """Pallas histogram. bins_t (F, N) integer, vals (N, 3) f32.

    N must be a multiple of rows_per_block (pad with bin 0 / value 0 rows
    upstream).  Returns (F, B, 3).
    """
    import jax.experimental.pallas as pl

    f, n = bins_t.shape
    b_pad = _pad_bins(max_bin)
    cols = 3 if exact else 6
    til = bin_tiling(max_bin, f, cols, rows_per_block)
    f_pad, fc, t = til.f_pad, til.fc, til.t
    assert n % t == 0, (n, t)
    vt = vals.astype(jnp.float32).T  # (3, N)

    # bins_t goes in AS STORED, in its NARROW dtype (uint8 at <=256
    # bins: 4x less HBM than int32); the kernel widens per tile and
    # makes the feature tail in VMEM (BinTiling)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, b_pad=b_pad, cols=cols,
                          exact=exact, f_mask=til.f_mask),
        grid=(f_pad // fc, n // t),  # (feature chunks, row tiles)
        in_specs=[
            pl.BlockSpec((til.block_rows, t), lambda j, i: (j, i)),
            pl.BlockSpec((3, t), lambda j, i: (0, i)),
        ],
        out_specs=pl.BlockSpec((fc * b_pad, cols), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((f_pad * b_pad, cols), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=pallas_interpret(),
    )(bins_t, vt)
    if not exact:
        out = out[:, :3] + out[:, 3:]  # hi + lo passes
    return _feature_bin(out, til, False)[:, :max_bin]


def _pad_rows(n: int, block: int) -> int:
    return (n + block - 1) // block * block


def histogram(bins_t: jax.Array, vals: jax.Array, max_bin: int,
              impl: str = "auto", rows_per_block: int = 1024,
              exact: bool = False) -> jax.Array:
    """Dispatching entry point. ``impl``: auto | segsum | pallas."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() not in ("cpu",) else "segsum"
    if impl == "segsum":
        return histogram_segsum(bins_t, vals, max_bin)
    n = bins_t.shape[1]
    padded = _pad_rows(n, rows_per_block)
    if padded != n:
        bins_t = jnp.pad(bins_t, ((0, 0), (0, padded - n)))
        vals = jnp.pad(vals, ((0, padded - n), (0, 0)))
        # padded rows land in (feature, bin 0) with value 0 — harmless
    return histogram_pallas(bins_t, vals, max_bin, rows_per_block,
                            exact=exact)


def _hist_kernel_multi(x_ref, v_ref, s_ref, *rest, b_pad: int,
                       width: int, exact: bool, two_col: bool = False,
                       shift: int = 0, miss_idx: int = -1,
                       f_mask: int = 0):
    """Multi-leaf variant: one pass accumulates histograms for up to
    ``width`` row-disjoint subsets (the speculative child-arming pass).

    x_ref: (FC, T) int32 bins; v_ref: (3, T) f32; s_ref: (1, T) int32
    subset selector in [-1, width); out_ref: (FC*B, 128) f32, columns
    beyond cols*width are zero padding.  With ``miss_idx >= 0`` an
    extra (FC, 1) per-feature missing-bin ref precedes out_ref and
    rows at their feature's missing bin map to the RESERVED coarse
    slot ``miss_idx`` instead of ``bin >> shift``.

    The rhs grows from cols to cols*width columns, filling the MXU lane
    dimension (126/128 at width 21×6 or 42×3, 128/128 at 64×2) that the
    single-leaf pass leaves ~95% idle — a batched pass costs barely
    more than a single-leaf one.
    """
    import jax.experimental.pallas as pl

    if miss_idx >= 0:
        mb_ref, out_ref = rest
    else:
        (out_ref,) = rest

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    FC = x_ref.shape[0]
    x = x_ref[...].astype(jnp.int32)
    if shift:
        # coarse pass: bins collapsed 2^shift-to-1 on the fly — the
        # coarse-to-fine first stage streams b_pad/2^shift one-hot rows
        if miss_idx >= 0:
            mb = mb_ref[...].astype(jnp.int32)      # (FC, 1)
            x = jnp.where(x == mb, miss_idx, x >> shift)
        else:
            x = x >> shift
    v = v_ref[...]                      # (3, T)
    sel = s_ref[...]                    # (1, T)
    if two_col:
        valsc = v[:2]                   # grad, hess only
    else:
        valsc = v if exact else _split_hi_lo(v)        # (cols, T) f32
    _accumulate(out_ref, x, _rhs(sel, valsc, width), b_pad, f_mask,
                pl.program_id(0) * FC)


@functools.partial(jax.jit, static_argnames=("max_bin", "width",
                                             "rows_per_block", "exact",
                                             "two_col", "shift"))
def histogram_pallas_multi(bins_t: jax.Array, vals: jax.Array,
                           sel: jax.Array, max_bin: int, width: int,
                           rows_per_block: int = 1024,
                           exact: bool = False,
                           two_col: bool = False,
                           shift: int = 0, miss_bin=None):
    """Batched histogram over ``width`` disjoint row subsets.

    bins_t (F, N) ints; vals (N, 3) f32; sel (N,) int32 subset id per
    row (-1 = no subset).  Returns (width, F, B, 3).  With ``two_col``
    only grad/hess are accumulated (64 leaves per pass) and the count
    channel is a COPY of the hess channel — callers must run under the
    gate that makes counts redundant (see GrowParams.two_col).

    With ``shift`` > 0 the stored fine bins are collapsed ``2^shift``-
    to-1 in the kernel (coarse-to-fine first stage); ``max_bin`` is
    then the COARSE bin count.  ``miss_bin`` (F,) int32 (with shift):
    rows at their feature's missing bin map to the reserved last
    coarse slot instead (see the segsum reference).
    """
    import jax.experimental.pallas as pl

    f, n = bins_t.shape
    b_pad = _pad_bins(max_bin)
    cols = 2 if two_col else (3 if exact else 6)
    W = width
    assert W * cols <= 128, (W, cols)
    til = bin_tiling(max_bin, f, 128, rows_per_block)
    f_pad, fc, t = til.f_pad, til.fc, til.t
    assert n % t == 0, (n, t)
    # narrow value operand: quantized gradients are small ints, exact
    # in int8/bf16 — keep the (3, N) operand at 1 byte/entry (it is
    # re-read from HBM EVERY pass).  Only the exact/two_col kernels
    # may take it (the hi/lo float split needs f32).
    if vals.dtype == jnp.int8:
        assert exact or two_col, "int8 values need exact/two_col"
        vt = vals.T                          # (3, N) int8
    else:
        vt = vals.astype(jnp.float32).T      # (3, N)
    st = sel.astype(jnp.int32)[None, :]      # (1, N)

    in_specs = [
        pl.BlockSpec((til.block_rows, t), lambda j, i: (j, i)),
        pl.BlockSpec((3, t), lambda j, i: (0, i)),
        pl.BlockSpec((1, t), lambda j, i: (0, i)),
    ]
    operands = [bins_t, vt, st]              # bins as stored, narrow
    miss_idx = -1
    if miss_bin is not None and shift:
        miss_idx = max_bin - 1
        in_specs.append(pl.BlockSpec((til.block_rows, 1),
                                     lambda j, i: (j, 0)))
        operands.append(_miss_operand(miss_bin, til))
    out = pl.pallas_call(
        functools.partial(_hist_kernel_multi, b_pad=b_pad, width=W,
                          exact=exact, two_col=two_col, shift=shift,
                          miss_idx=miss_idx, f_mask=til.f_mask),
        grid=(f_pad // fc, n // t),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((fc * b_pad, 128), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((f_pad * b_pad, 128), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=pallas_interpret(),
    )(*operands)
    return _batched_hists(out, til, vt.dtype == jnp.int8, W, cols,
                          max_bin, two_col, exact)     # (W, F, B, 3)


def histogram_segsum_multi(bins_t: jax.Array, vals: jax.Array,
                           sel: jax.Array, max_bin: int, width: int,
                           two_col: bool = False,
                           shift: int = 0, miss_bin=None) -> jax.Array:
    """jnp reference for :func:`histogram_pallas_multi` (CPU/tests).

    ``miss_bin`` (F,) int32 (or None): with ``shift``, rows whose fine
    bin equals the feature's missing bin map to the RESERVED last
    coarse slot ``max_bin - 1`` instead of ``bin >> shift`` (-1 =
    feature has no missing bin)."""
    f, n = bins_t.shape
    if shift:
        x = bins_t.astype(jnp.int32)
        cb = x >> shift
        if miss_bin is not None:
            cb = jnp.where(x == miss_bin[:, None], max_bin - 1, cb)
        bins_t = cb
    outs = []
    for w in range(width):
        m = (sel == w).astype(vals.dtype)[:, None]
        outs.append(histogram_segsum(bins_t, vals * m, max_bin))
    out = jnp.stack(outs)
    if two_col:
        out = jnp.concatenate([out[..., :2], out[..., 1:2]], axis=-1)
    return out


# ---- a row's lane, and what its lane's tables hold ------------------
#
# The routed and the lane-routed kernels are handed the leaf vector and
# per-lane tables (the lane's leaf id, split feature, threshold, ...,
# window starts); the windowed kernel a ready selector and the window
# starts.  A tile resolves each row's lane ONCE: the (W, T) one-hot of
# the leaf ids against the lanes' (of the selector against 0 .. W - 1)
# is contracted with ONE stacked table, and the result's column ``t``
# is the table column of the lane row ``t`` is in (zeros where it is
# in none).  Row 0 holds ``lane + 1``, so the lane index, -1 for none,
# comes out of the same contraction and everything after it (the
# routing's selects, the right-hand side: :func:`_rhs`) derives from
# that (1, T) index.  An (N,)-element gather of any of it is poison
# (60-90 ms at bench shape).

_LANE_HEAD = 8      # scalar rows ahead of a table's per-feature rows


def _lane_head(W: int, scalars) -> jax.Array:
    """The (8, W) int32 head of a lane table: ``lane + 1``, then the
    callers' scalar rows, then zeros."""
    head = jnp.stack([jnp.arange(1, W + 1, dtype=jnp.int32)] +
                     [r.astype(jnp.int32) for r in scalars])
    return jnp.pad(head, ((0, _LANE_HEAD - head.shape[0]), (0, 0)))


def _lane_operands(lane_ids: jax.Array, scalars, per_feat: jax.Array,
                   til: BinTiling):
    """The two operands :func:`_lane_lookup` takes.

    lane_ids (W,) leaf id of each lane; scalars: up to 7 (W,) int
    rows, which ride as rows 1.. of the head behind ``lane + 1``;
    per_feat (F, W): the rows a feature block needs (its features'
    one-hot of the lane's split feature; its window starts).  Returns
    ids (Wp, 1) int32, lanes padded with -2, which neither a leaf id
    nor a selector is, and the table, (chunks * (8 + rows), Wp)
    float32: every feature block finds the head above its own rows
    (blocks of ``8 + rows``)."""
    W = lane_ids.shape[0]
    wp = -W % 16
    rows = til.fc if not til.one_chunk else -(-til.f // 8) * 8
    chunks = til.chunks
    head = _lane_head(W, scalars)
    feat = jnp.pad(per_feat.astype(jnp.int32),
                   ((0, chunks * rows - per_feat.shape[0]), (0, 0)))
    tab = jnp.concatenate(
        [jnp.broadcast_to(head, (chunks, _LANE_HEAD, W)),
         feat.reshape(chunks, rows, W)], axis=1)
    tab = jnp.pad(tab.reshape(chunks * (_LANE_HEAD + rows), W),
                  ((0, 0), (0, wp)))
    ids = jnp.pad(lane_ids.astype(jnp.int32), (0, wp),
                  constant_values=-2)
    return ids[:, None], tab.astype(jnp.float32)


def _lane_lookup(li: jax.Array, ids: jax.Array, tab: jax.Array,
                 narrow: bool):
    """li (1, T) int32 leaf ids, ids (Wp, 1), tab (8 + R, Wp) float32
    -> (lane (1, T) int32 in [-1, W), looked-up (8 + R, T) float32).

    ``narrow``: the table's entries are integers of at most 8 bits
    (the bins are stored as uint8, and the callers split a leaf id
    into its bytes), exact in ONE bf16 pass of the MXU.  Wider bins
    take the float32 contraction at HIGHEST, exact below 2^24."""
    dt = jnp.bfloat16 if narrow else jnp.float32
    got = jax.lax.dot_general(
        tab.astype(dt), (li == ids).astype(dt), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=None if narrow else jax.lax.Precision.HIGHEST)
    return got[0:1].astype(jnp.int32) - 1, got


# ---- coarse-to-fine refine stage -----------------------------------
#
# The multi-leaf pass is MXU-stream bound: cost ∝ f_pad·b_pad·N
# regardless of output width, so at 255 bins nearly the whole stream is
# zeros.  The coarse-to-fine scheme replaces one full-resolution pass
# with (a) a coarse pass (``shift`` above, b_pad/2^shift one-hot rows)
# and (b) THIS windowed pass: per (leaf, feature) only a 2-coarse-bin
# window of R fine bins around the best coarse boundary is resolved,
# streaming R ≪ b_pad one-hot rows.  The per-row window start
# ``win_lo[leaf, feature]`` would be an (N,)-element gather (measured
# 60-90 ms at bench shape — poison); instead the kernel reads it by the
# lane lookup above (:func:`_lane_lookup`), ~3% of the pass FLOPs, on
# the MXU.


def _hist_kernel_multi_win(x_ref, v_ref, l_ref, ids_ref, tab_ref, *rest,
                           r_pad: int, width: int, exact: bool,
                           two_col: bool, with_miss: bool = False,
                           f_mask: int = 0):
    """Windowed refine step: accumulate (leaf, feature)-windowed fine
    histograms.  x_ref (FC, T) bins; v_ref (3, T); l_ref (1, T) what
    says a row's subset: a selector in [-1, width)
    (:func:`histogram_pallas_multi_win`) or the leaf vector
    (:func:`histogram_pallas_multi_win_lanes`); ids_ref, tab_ref the
    lane operands (:func:`_lane_operands`: what ``l_ref`` holds for
    each subset, the table's per-feature rows the fine-bin window
    starts); out_ref (FC*R, 128).  With ``with_miss`` an extra (FC, 1)
    missing-bin ref precedes out_ref and rows at their feature's
    missing bin are excluded (windowed stats cover VALUE bins only)."""
    import jax.experimental.pallas as pl

    if with_miss:
        mb_ref, out_ref = rest
    else:
        (out_ref,) = rest

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    FC = x_ref.shape[0]
    x = x_ref[...].astype(jnp.int32)
    if with_miss:
        mb = mb_ref[...].astype(jnp.int32)  # (FC, 1)
        x = jnp.where(x == mb, -1, x)       # miss rows match no window
    v = v_ref[...]                      # (3, T)
    if two_col:
        valsc = v[:2]
    else:
        valsc = v if exact else _split_hi_lo(v)
    # per-row window start lo[lane, f] and the lane itself
    lane, got = _lane_lookup(
        l_ref[...].astype(jnp.int32), ids_ref[...], tab_ref[...],
        x_ref.dtype.itemsize == 1)
    rbin = x - got[_LANE_HEAD:_LANE_HEAD + FC].astype(jnp.int32)
    # a row in no subset reads window start 0 and may fall inside it:
    # its rhs column is all zeros, so it counts nowhere
    # out-of-window rows (rbin outside [0, r_pad)) match no iota column
    _accumulate(out_ref, rbin, _rhs(lane, valsc, width), r_pad, f_mask,
                pl.program_id(0) * FC)


@functools.partial(jax.jit, static_argnames=("r_bins", "width",
                                             "rows_per_block", "exact",
                                             "two_col"))
def histogram_pallas_multi_win(bins_t: jax.Array, vals: jax.Array,
                               sel: jax.Array, win_lo: jax.Array,
                               r_bins: int, width: int,
                               rows_per_block: int = 1024,
                               exact: bool = False,
                               two_col: bool = False,
                               miss_bin=None) -> jax.Array:
    """Windowed multi-subset histogram: per (subset, feature) only the
    fine bins in [win_lo, win_lo + r_bins) are accumulated, at relative
    positions.  win_lo (width, F) int32.  Returns (width, F, R, 3).
    ``miss_bin`` (F,) int32 or None: missing-bin rows are excluded."""
    import jax.experimental.pallas as pl

    f, n = bins_t.shape
    r_pad = _pad_bins(r_bins)
    cols = 2 if two_col else (3 if exact else 6)
    W = width
    assert W * cols <= 128, (W, cols)
    til = bin_tiling(r_bins, f, 128, rows_per_block)
    f_pad, fc, t = til.f_pad, til.fc, til.t
    assert n % t == 0, (n, t)
    if vals.dtype == jnp.int8:               # see histogram_pallas_multi
        assert exact or two_col, "int8 values need exact/two_col"
        vt = vals.T                          # (3, N) int8
    else:
        vt = vals.astype(jnp.float32).T      # (3, N)
    st = sel.astype(jnp.int32)[None, :]      # (1, N)
    ids, tab = _lane_operands(jnp.arange(W), [], win_lo.T, til)

    in_specs = [
        pl.BlockSpec((til.block_rows, t), lambda j, i: (j, i)),
        pl.BlockSpec((3, t), lambda j, i: (0, i)),
        pl.BlockSpec((1, t), lambda j, i: (0, i)),
        pl.BlockSpec(ids.shape, lambda j, i: (0, 0)),
        # feature block j: the head and its own window starts
        pl.BlockSpec((tab.shape[0] // (f_pad // fc), tab.shape[1]),
                     lambda j, i: (j, 0)),
    ]
    operands = [bins_t, vt, st, ids, tab]
    if miss_bin is not None:
        in_specs.append(pl.BlockSpec((til.block_rows, 1),
                                     lambda j, i: (j, 0)))
        operands.append(_miss_operand(miss_bin, til))
    out = pl.pallas_call(
        functools.partial(_hist_kernel_multi_win, r_pad=r_pad, width=W,
                          exact=exact, two_col=two_col,
                          with_miss=miss_bin is not None,
                          f_mask=til.f_mask),
        grid=(f_pad // fc, n // t),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((fc * r_pad, 128), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((f_pad * r_pad, 128),
                                       jnp.float32),
        compiler_params=_compiler_params(),
        interpret=pallas_interpret(),
    )(*operands)
    return _batched_hists(out, til, vt.dtype == jnp.int8, W, cols,
                          r_bins, two_col, exact)      # (W, F, R, 3)


# ---- routed multi-leaf pass ----------------------------------------
#
# The wave bodies used to route rows in XLA-land: an unrolled
# select-chain reading leaf_idx plus EVERY xt row from HBM, once more
# a wave.  The histogram pass already streams the bins matrix, so this
# variant does the routing IN the kernel: per row it resolves its wave
# lane and its lane's tables (:func:`_lane_lookup`), its split column
# value (the lane's feature one-hot against the resident x tile), the
# threshold compare, and the subset selector — and writes the NEW leaf
# assignment and selector as side outputs.  That takes the whole
# feature dimension in one chunk (fc == f_pad): the split column can
# be any feature's, and only then is every feature's tile resident.
#
# A feature set in SEVERAL chunks (2,000 features at 16 coarse bins:
# 25 blocks of 80) routes its rows in a step of its own before the
# contraction (:func:`histogram_pallas_route`): a row's split column is
# its lane's, so a row tile walks the wave's live lanes, fetches for
# each the one storage tile of rows that holds the lane's split column
# (32 rows of uint8 bins) and keeps that column's bins for the lane's
# rows; then lane, goes-left, new leaf and selector come out of the
# same routing math, once a row tile.  The batched pass
# (:func:`histogram_pallas_multi`) then walks the feature blocks with
# that selector.  What a wave pays to route is the leaf vector and 32
# bins a row and live lane, whatever the feature count (XLA's own
# gather of the lanes' rows cost 7 to 9 ms a wave at 1.2M x 2000, twice
# a read of the whole matrix: PERF.md, PR 35).
# :func:`histogram_routed` picks the form by the tiling alone.
#
# The callers' lane tables are a (5-6, W) int32 array, which the
# wrapper stacks into the kernel's operand (:func:`_lane_operands`):
#   row 0: lane leaf ids   row 1: lane split column
#   row 2: lane threshold  row 3: lane new (right-child) leaf id
#   row 4: smaller-child-is-left flag (mode="small" only)
#   row 5: default-left flag (with missing values)


def _routed_parts(x, li, ids, tab, width: int, mode: str, narrow: bool,
                  li_bytes: int, mb=None):
    """The routing math: returns (li_new, sel_out), the new leaf
    vector and the subset selector, both (1, T).
    x (FC, T) int32; li (1, T) int32, stored in ``li_bytes`` bytes;
    ids, tab: the lane operands (:func:`_lane_operands`; head rows 1-6
    are the lane's threshold, its new leaf id in three byte-wide
    parts, its smaller-child-is-left flag and its default-left flag,
    the per-feature rows the one-hot of its split feature).  With
    ``mb`` (FC, 1) per-feature missing bins, a row AT its lane
    feature's missing bin routes by the default direction instead of
    the threshold compare.

    A row in no lane reads 0 from every table row, its lane is -1 and
    its column value 0: it is never above its threshold, so it goes
    nowhere and is selected nowhere without a mask of its own."""
    FC = x.shape[0]
    lane, got = _lane_lookup(li, ids, tab, narrow)
    # per-row split-column value: the lane's feature one-hot against
    # the resident x tile, an FC*T multiply-reduce
    fsel = got[_LANE_HEAD:_LANE_HEAD + FC]          # (FC, T)
    col = jnp.sum(x.astype(jnp.float32) * fsel, axis=0,
                  keepdims=True)                    # (1, T)
    mb_pr = None
    if mb is not None:
        # per-row missing bin of the lane's feature
        mb_pr = jnp.sum(mb.astype(jnp.float32) * fsel, axis=0,
                        keepdims=True)              # (1, T)
    return _route_decide(col, mb_pr, lane, got, li, width, mode, li_bytes)


def _route_decide(col, mb_pr, lane, got, li, width: int, mode: str,
                  li_bytes: int):
    """(li_new, sel_out) from each row's split-column bin ``col``
    (1, T) float32, its lane and its lane's table column ``got``
    (:func:`_routed_parts`); ``mb_pr`` (1, T): the missing bin of the
    lane's split feature (-1: none), or None."""
    W = width if mode == "small" else width // 2
    gr = col > got[1:2]                             # goes right
    if mb_pr is not None:
        # a row AT the missing bin goes the default way
        is_miss = (col == mb_pr) & (mb_pr >= 0)
        gr = gr & ~((got[6:7] > 0.5) & is_miss)
    # a leaf id above 256 is not bf16-exact: it rides as its bytes in
    # place (``id & 0xFF``, ``id & 0xFF00``, ``id & 0xFF0000``: eight
    # bits times a power of two each, exact in bf16), exact below 2^24
    # as the float32 sum is.  The parts the leaf vector's type cannot
    # hold are 0 and stay out of the sum: each addition on (1, T) is
    # 0.13 ms of a 21M-row pass (PERF.md, PR 31)
    new_pr = got[2:3]
    for b in range(1, min(li_bytes, 3)):
        new_pr = new_pr + got[2 + b:3 + b]
    li_new = jnp.where(gr, new_pr.astype(jnp.int32), li)
    if mode == "small":
        # the smaller child: left where the flag is set, else right
        sel_out = jnp.where(gr != (got[5:6] > 0.5), lane, -1)
    else:
        # children mode: left child of lane w -> slot w, right -> W+w
        sel_out = lane + jnp.where(gr, W, 0)
    return li_new, sel_out


def _hist_kernel_multi_routed(x_ref, v_ref, li_ref, ids_ref, tab_ref,
                              *rest, b_pad: int, width: int,
                              exact: bool, two_col: bool, shift: int,
                              mode: str, miss_idx: int = -1,
                              with_miss: bool = False):
    import jax.experimental.pallas as pl

    rest = list(rest)
    mb_ref = rest.pop(0) if with_miss else None
    out_ref, li_out_ref, sel_out_ref = rest

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.int32)
    v = v_ref[...]
    li = li_ref[...].astype(jnp.int32)
    mb = mb_ref[...].astype(jnp.int32) if with_miss else None  # (FC, 1)
    li_new, sel_out = _routed_parts(
        x, li, ids_ref[...], tab_ref[...], width, mode,
        x_ref.dtype.itemsize == 1, li_ref.dtype.itemsize, mb=mb)
    li_out_ref[...] = li_new.astype(li_out_ref.dtype)
    sel_out_ref[...] = sel_out
    if two_col:
        valsc = v[:2]
    else:
        valsc = v if exact else _split_hi_lo(v)
    rhs = _rhs(sel_out, valsc, width)
    if shift:
        xb = x >> shift
        if with_miss and miss_idx >= 0:
            # rows at their feature's missing bin land in the RESERVED
            # last coarse slot (see histogram_segsum_multi)
            xb = jnp.where(x == mb, miss_idx, xb)
    else:
        xb = x
    _accumulate(out_ref, xb, rhs, b_pad)    # one chunk: nothing to mask


@functools.partial(jax.jit, static_argnames=(
    "max_bin", "width", "rows_per_block", "exact", "two_col", "shift",
    "mode"))
def histogram_pallas_multi_routed(bins_t: jax.Array, vals: jax.Array,
                                  leaf_idx: jax.Array,
                                  tables: jax.Array, max_bin: int,
                                  width: int,
                                  rows_per_block: int = 1024,
                                  exact: bool = False,
                                  two_col: bool = False,
                                  shift: int = 0,
                                  mode: str = "small",
                                  miss_bin=None):
    """Multi-subset histogram with IN-KERNEL row routing.

    bins_t (F, N); vals (N, 3) f32; leaf_idx (N,) int32; tables
    (5-6, W) int32 (see module comment; row 5 = per-lane default-left,
    required with ``miss_bin``).  ``mode="small"``: subsets are the
    smaller children (width W lanes); ``mode="children"``: both
    children (lanes 2W, width counts the OUTPUT lanes = 2W).
    ``miss_bin`` (F,) int32 or None: rows at their lane feature's
    missing bin route by the default direction, and with ``shift``
    they land in the reserved last coarse slot.
    Returns (hist (width, F, B, 3), new_leaf_idx (N,), sel (N,)).
    One feature chunk only: :func:`histogram_routed` is the entry that
    also serves the shapes that chunk.
    """
    import jax.experimental.pallas as pl

    f, n = bins_t.shape
    b_pad = _pad_bins(max_bin)
    cols = 2 if two_col else (3 if exact else 6)
    Wl = width
    assert Wl * cols <= 128, (Wl, cols)
    til = bin_tiling(max_bin, f, 128, rows_per_block)
    f_pad, fc, t = til.f_pad, til.fc, til.t
    assert til.one_chunk, "in-kernel routing needs one feature chunk"
    assert n % t == 0, (n, t)
    if vals.dtype == jnp.int8:               # see histogram_pallas_multi
        assert exact or two_col, "int8 values need exact/two_col"
        vt = vals.T
    else:
        vt = vals.astype(jnp.float32).T
    # keep the leaf vector in its NARROW storage dtype (uint8 at
    # num_leaves<=255): it is re-read every pass
    lt = leaf_idx[None, :]
    # the tables as the kernel reads them: one stacked operand
    tbl = tables[:, :Wl if mode == "small" else Wl // 2].astype(jnp.int32)
    ids, tab = _lane_operands(
        tbl[0], [tbl[2], tbl[3] & 0xFF, tbl[3] & 0xFF00, tbl[3] & 0xFF0000,
                 *tbl[4:6]],
        tbl[1][None, :] == jnp.arange(f, dtype=jnp.int32)[:, None], til)

    in_specs = [
        pl.BlockSpec((til.block_rows, t), lambda i: (0, i)),
        pl.BlockSpec((3, t), lambda i: (0, i)),
        pl.BlockSpec((1, t), lambda i: (0, i)),
        pl.BlockSpec(ids.shape, lambda i: (0, 0)),
        pl.BlockSpec(tab.shape, lambda i: (0, 0)),
    ]
    operands = [bins_t, vt, lt, ids, tab]
    miss_idx = -1
    if miss_bin is not None:
        assert tables.shape[0] >= 6, \
            "missing routing needs the default-left row"
        if shift:
            miss_idx = max_bin - 1
        in_specs.append(pl.BlockSpec((til.block_rows, 1),
                                     lambda i: (0, 0)))
        operands.append(_miss_operand(miss_bin, til))
    out_specs = [
        pl.BlockSpec((fc * b_pad, 128), lambda i: (0, 0)),
        pl.BlockSpec((1, t), lambda i: (0, i)),
        pl.BlockSpec((1, t), lambda i: (0, i)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((f_pad * b_pad, 128), jnp.float32),
        jax.ShapeDtypeStruct((1, n), leaf_idx.dtype),
        jax.ShapeDtypeStruct((1, n), jnp.int32),
    ]
    out, li_new, sel = pl.pallas_call(
        functools.partial(_hist_kernel_multi_routed, b_pad=b_pad,
                          width=Wl, exact=exact, two_col=two_col,
                          shift=shift, mode=mode, miss_idx=miss_idx,
                          with_miss=miss_bin is not None),
        grid=(n // t,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_compiler_params(),
        interpret=pallas_interpret(),
    )(*operands)
    hist = _batched_hists(out, til, vt.dtype == jnp.int8, Wl, cols,
                          max_bin, two_col, exact)
    return hist, li_new[0], sel[0]


def _route_kernel(grp_ref, row_ref, x_ref, li_ref, ids_ref, tab_ref,
                  li_out_ref, sel_out_ref, got_ref, col_ref, *,
                  width: int, mode: str, with_miss: bool):
    """One (row tile, lane) step of :func:`histogram_pallas_route`.

    x_ref (g, T): the storage tile of rows that holds this lane's
    split column (block ``grp_ref[w]`` of the matrix), row
    ``row_ref[w]`` of it the column, -1 for a lane no row is in;
    li_ref (1, T) the leaf vector.  The tile's first step looks every
    row's lane and table column up (``got_ref`` (8, T) keeps them), a
    live lane's step keeps its column's bins for its own rows
    (``col_ref`` (1, T) int32), and the last step decides."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w = pl.program_id(1)
    g, T = x_ref.shape
    # stored bins to a 32-bit word (a matrix of fewer rows than a
    # storage tile comes whole and is read row by row)
    per = 4 // x_ref.dtype.itemsize if g * x_ref.dtype.itemsize == 32 else 1

    @pl.when(w == 0)
    def _lookup():
        _, got = _lane_lookup(li_ref[...].astype(jnp.int32), ids_ref[...],
                              tab_ref[...], x_ref.dtype.itemsize == 1)
        got_ref[...] = got
        col_ref[...] = jnp.zeros_like(col_ref)

    r = row_ref[w]

    @pl.when(r >= 0)
    def _keep_column():
        # the column is one stored row: take its word row (4 uint8 rows
        # to a 32-bit word), then its byte
        words = x_ref[...] if per == 1 else \
            pltpu.bitcast(x_ref[...], jnp.int32)            # (g / per, T)
        words = words.astype(jnp.int32)
        word = words[0:1]
        for k in range(1, g // per):        # a tile's 8 word rows
            word = jnp.where(r // per == k, words[k:k + 1], word)
        bits = 32 // per
        col = word if per == 1 else \
            (word >> ((r % per) * bits)) & ((1 << bits) - 1)
        lane = got_ref[0:1].astype(jnp.int32) - 1
        col_ref[...] = jnp.where(lane == w, col, col_ref[...])

    @pl.when(w == pl.num_programs(1) - 1)
    def _decide():
        got = got_ref[...]
        li = li_ref[...].astype(jnp.int32)
        li_new, sel_out = _route_decide(
            col_ref[...].astype(jnp.float32),
            got[7:8] if with_miss else None,
            got[0:1].astype(jnp.int32) - 1, got, li, width, mode,
            li_ref.dtype.itemsize)
        li_out_ref[...] = li_new.astype(li_out_ref.dtype)
        sel_out_ref[...] = sel_out


@functools.partial(jax.jit, static_argnames=("width", "rows_per_block",
                                             "mode"))
def histogram_pallas_route(bins_t: jax.Array, leaf_idx: jax.Array,
                           tables: jax.Array, width: int,
                           rows_per_block: int = 1024,
                           mode: str = "small", miss_bin=None,
                           dead_id=None):
    """A wave's row routing as a kernel of its own, for the batched
    pass whose features do not fit one chunk.

    bins_t (F, N), leaf_idx (N,), tables (5-6, W) int32, ``width``,
    ``mode`` and ``miss_bin`` as :func:`histogram_pallas_multi_routed`
    takes them.  ``dead_id``: the leaf id the callers give a lane that
    holds no split (no row carries it); such lanes are skipped.
    Returns (new_leaf_idx (N,), sel (N,)), the one-chunk kernel's side
    outputs bit for bit: the decision is the same function
    (:func:`_route_decide`) of each row's split-column bin, lane and
    lane tables.

    The grid is (row tiles, lanes).  The matrix stays in HBM and a
    step is handed ONE storage tile of its rows, the ``32 /
    itemsize`` rows that hold the lane's split column (the block index
    comes from the lane tables by scalar prefetch), so a wave reads
    the leaf vector and, a live lane, 32 bins a row: proportional to
    rows x live lanes, whatever F.  Dead lanes repeat block 0, which
    the pipeline does not fetch again, and do no work."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f, n = bins_t.shape
    Wl = width if mode == "small" else width // 2
    assert n % rows_per_block == 0, (n, rows_per_block)
    # a step is a few microseconds of work and there are row tiles x
    # lanes of them: the largest row tile that divides the rows
    # (6.3 ms a wave of 64 live lanes at 16384 rows a tile and 1.2M
    # rows, 1.3 us a step against 0.6 us of fetch)
    t = rows_per_block
    while t < 65536 and n % (2 * t) == 0:
        t *= 2
    g = min(32 // bins_t.dtype.itemsize, f)     # rows of one storage tile
    tbl = tables[:, :Wl].astype(jnp.int32)
    feat = jnp.clip(tbl[1], 0, f - 1)
    live = jnp.ones((Wl,), bool) if dead_id is None else tbl[0] != dead_id
    grp = jnp.where(live, feat // g, 0)
    row = jnp.where(live, feat % g, -1)
    scalars = [tbl[2], tbl[3] & 0xFF, tbl[3] & 0xFF00, tbl[3] & 0xFF0000,
               *tbl[4:6]]
    if miss_bin is not None:
        assert tables.shape[0] >= 6, \
            "missing routing needs the default-left row"
        # head row 7: the missing bin of the lane's split feature
        scalars.append(jnp.take(miss_bin.astype(jnp.int32), feat))
    wp = -Wl % 16
    tab = jnp.pad(_lane_head(Wl, scalars), ((0, 0), (0, wp))
                  ).astype(jnp.float32)
    ids = jnp.pad(tbl[0], (0, wp), constant_values=-2)[:, None]
    li_new, sel = pl.pallas_call(
        functools.partial(_route_kernel, width=width, mode=mode,
                          with_miss=miss_bin is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // t, Wl),
            in_specs=[
                pl.BlockSpec((g, t), lambda i, w, grp, row: (grp[w], i)),
                pl.BlockSpec((1, t), lambda i, w, grp, row: (0, i)),
                pl.BlockSpec(ids.shape, lambda i, w, grp, row: (0, 0)),
                pl.BlockSpec(tab.shape, lambda i, w, grp, row: (0, 0)),
            ],
            out_specs=[pl.BlockSpec((1, t),
                                    lambda i, w, grp, row: (0, i))] * 2,
            scratch_shapes=[pltpu.VMEM((_LANE_HEAD, t), jnp.float32),
                            pltpu.VMEM((1, t), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((1, n), leaf_idx.dtype),
                   jax.ShapeDtypeStruct((1, n), jnp.int32)],
        compiler_params=_compiler_params(),
        interpret=pallas_interpret(),
    )(grp, row, bins_t, leaf_idx[None, :], ids, tab)
    return li_new[0], sel[0]


def histogram_routed(bins_t: jax.Array, vals: jax.Array,
                     leaf_idx: jax.Array, tables: jax.Array,
                     max_bin: int, width: int, rows_per_block: int = 1024,
                     exact: bool = False, two_col: bool = False,
                     shift: int = 0, mode: str = "small", miss_bin=None,
                     dead_id=None):
    """The routed batched pass at any feature count: arguments and
    results of :func:`histogram_pallas_multi_routed`.  Features in one
    chunk: that kernel, routing inside the pass.  Several chunks: the
    rows are routed once a wave (:func:`histogram_pallas_route`, which
    ``dead_id`` is for) and the batched pass walks the feature blocks
    with the selector (:func:`histogram_pallas_multi`); the outputs
    are the same bit for bit.  The tiling alone decides
    (``BinTiling.one_chunk``), which is what the tier record's
    ``route`` reports."""
    kw = dict(exact=exact, two_col=two_col, shift=shift, miss_bin=miss_bin)
    if bin_tiling(max_bin, bins_t.shape[0], 128, rows_per_block).one_chunk:
        return histogram_pallas_multi_routed(
            bins_t, vals, leaf_idx, tables, max_bin, width,
            rows_per_block, mode=mode, **kw)
    li_new, sel = histogram_pallas_route(
        bins_t, leaf_idx, tables, width, rows_per_block, mode, miss_bin,
        dead_id)
    hist = histogram_pallas_multi(bins_t, vals, sel, max_bin, width,
                                  rows_per_block, **kw)
    return hist, li_new, sel


def histogram_segsum_multi_routed(bins_t, vals, leaf_idx, tables,
                                  max_bin: int, width: int,
                                  two_col: bool = False, shift: int = 0,
                                  mode: str = "small", miss_bin=None):
    """jnp reference for :func:`histogram_pallas_multi_routed`.

    With missing support, ``tables`` carries a 6th row: the per-lane
    default-left flag; ``miss_bin`` (F,) gives each feature's missing
    bin (-1 = none).  A row at its lane feature's missing bin routes
    by the default direction instead of the threshold compare."""
    W = width if mode == "small" else width // 2
    ids, colw, thrw, neww, slw = (tables[k, :W] for k in range(5))
    li = leaf_idx.astype(jnp.int32)
    lane = jnp.full(li.shape, -1, jnp.int32)
    for w in range(W):
        lane = jnp.where(li == ids[w], w, lane)
    in_wave = lane >= 0
    safe = jnp.clip(lane, 0, W - 1)
    col_id = colw[safe]
    col = jnp.take_along_axis(bins_t.astype(jnp.int32),
                              col_id[None, :], axis=0)[0]
    gl_thr = col <= thrw[safe]
    if tables.shape[0] >= 6 and miss_bin is not None:
        dlw = tables[5, :W]
        mb_row = miss_bin[col_id]
        is_miss = (col == mb_row) & (mb_row >= 0)
        gl = in_wave & (gl_thr | ((dlw[safe] > 0) & is_miss))
    else:
        gl = in_wave & gl_thr
    li_new = jnp.where(in_wave & ~gl, neww[safe], li)
    if mode == "small":
        to_small = gl == (slw[safe] > 0)
        sel = jnp.where(in_wave & to_small, lane, -1)
    else:
        sel = jnp.where(in_wave, lane + W * (~gl).astype(jnp.int32), -1)
    hist = histogram_segsum_multi(bins_t, vals, sel, max_bin, width,
                                  two_col=two_col, shift=shift,
                                  miss_bin=miss_bin)
    return hist, li_new, sel


# ---- lane-routed windowed pass -------------------------------------
#
# The c2f wave's refine stage used an (N,) int32 subset selector
# written by the coarse pass (42 MB written + re-read per wave).  The
# leaf vector ALREADY encodes the routing after the coarse pass
# updated it: each row's leaf id IS its child leaf id.  This variant
# takes the (uint8/int32) leaf vector plus a per-lane child-leaf-id
# table and resolves the lane one-hot in-kernel (the windowed kernel,
# :func:`_hist_kernel_multi_win`, given the leaf vector) — reading
# ~10 MB instead of 42, and writing nothing.


@functools.partial(jax.jit, static_argnames=("r_bins", "width",
                                             "rows_per_block", "exact",
                                             "two_col"))
def histogram_pallas_multi_win_lanes(bins_t: jax.Array, vals: jax.Array,
                                     leaf_idx: jax.Array,
                                     lane_ids: jax.Array,
                                     win_lo: jax.Array,
                                     r_bins: int, width: int,
                                     rows_per_block: int = 1024,
                                     exact: bool = False,
                                     two_col: bool = False,
                                     miss_bin=None) -> jax.Array:
    """Windowed multi-subset histogram routed by the LEAF VECTOR.

    Like :func:`histogram_pallas_multi_win`, but subset membership is
    ``leaf_idx[n] == lane_ids[w]`` instead of an explicit (N,)
    selector.  lane_ids (width,) int32 child leaf ids (use an
    out-of-range id for dead lanes); win_lo (width, F) int32.
    Returns (width, F, R, 3).
    """
    import jax.experimental.pallas as pl

    f, n = bins_t.shape
    r_pad = _pad_bins(r_bins)
    cols = 2 if two_col else (3 if exact else 6)
    W = width
    assert W * cols <= 128, (W, cols)
    til = bin_tiling(r_bins, f, 128, rows_per_block)
    f_pad, fc, t = til.f_pad, til.fc, til.t
    assert n % t == 0, (n, t)
    if vals.dtype == jnp.int8:
        assert exact or two_col, "int8 values need exact/two_col"
        vt = vals.T
    else:
        vt = vals.astype(jnp.float32).T
    lt = leaf_idx[None, :]                   # narrow storage dtype
    ids, tab = _lane_operands(lane_ids, [], win_lo.T, til)

    in_specs = [
        pl.BlockSpec((til.block_rows, t), lambda j, i: (j, i)),
        pl.BlockSpec((3, t), lambda j, i: (0, i)),
        pl.BlockSpec((1, t), lambda j, i: (0, i)),
        pl.BlockSpec(ids.shape, lambda j, i: (0, 0)),
        # feature block j: the head and its own window starts
        pl.BlockSpec((tab.shape[0] // (f_pad // fc), tab.shape[1]),
                     lambda j, i: (j, 0)),
    ]
    operands = [bins_t, vt, lt, ids, tab]
    if miss_bin is not None:
        in_specs.append(pl.BlockSpec((til.block_rows, 1),
                                     lambda j, i: (j, 0)))
        operands.append(_miss_operand(miss_bin, til))
    out = pl.pallas_call(
        functools.partial(_hist_kernel_multi_win, r_pad=r_pad,
                          width=W, exact=exact, two_col=two_col,
                          with_miss=miss_bin is not None,
                          f_mask=til.f_mask),
        grid=(f_pad // fc, n // t),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((fc * r_pad, 128), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((f_pad * r_pad, 128),
                                       jnp.float32),
        compiler_params=_compiler_params(),
        interpret=pallas_interpret(),
    )(*operands)
    return _batched_hists(out, til, vt.dtype == jnp.int8, W, cols,
                          r_bins, two_col, exact)      # (W, F, R, 3)


def histogram_segsum_multi_win_lanes(bins_t, vals, leaf_idx, lane_ids,
                                     win_lo, r_bins: int, width: int,
                                     two_col: bool = False,
                                     miss_bin=None) -> jax.Array:
    """jnp reference for :func:`histogram_pallas_multi_win_lanes`."""
    li = leaf_idx.astype(jnp.int32)
    sel = jnp.full(li.shape, -1, jnp.int32)
    for w in range(width):
        sel = jnp.where(li == lane_ids[w], w, sel)
    return histogram_segsum_multi_win(bins_t, vals, sel, win_lo,
                                      r_bins, width, two_col=two_col,
                                      miss_bin=miss_bin)


# ---- leaf-stats (renewal) kernel -----------------------------------
#
# Quantized training renews leaf outputs from FULL-PRECISION per-leaf
# gradient sums (RenewIntGradTreeOutput).  A generic 256-bin histogram
# pass costs ~25 ms at bench shape, mostly intermediates: the (N, 3)
# f32 value stack (126 MB written + re-read), the nibble-split bins
# and an int32 selector.  This kernel reads ONLY the already-resident
# arrays — leaf vector (uint8/int32) + grad + hess + mask — and
# resolves the (hi, lo) leaf-nibble factorization internally: lo-
# nibble one-hot rows (16, T) against an rhs of hi-nibble selectors x
# hi/lo-split values (16 x 6 = 96 lanes).  acc[lo, hi*6+c] is then the
# exact sum for leaf hi*16+lo.


def _leaf_stats_kernel(li_ref, g_ref, h_ref, m_ref, out_ref):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    li = li_ref[...].astype(jnp.int32)          # (1, T)
    m = m_ref[...]
    g = g_ref[...] * m
    h = h_ref[...] * m
    T = li.shape[1]
    v = jnp.concatenate([g, h, m], axis=0)      # (3, T) f32
    rhs = _rhs_bf16(li >> 4, _split_hi_lo(v), 16)        # (128, T)
    onehot = ((li & 15) == jax.lax.broadcasted_iota(
        jnp.int32, (16, T), 0)).astype(jnp.bfloat16)     # (16, T)
    out_ref[...] += jax.lax.dot_general(
        onehot, rhs.T, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)     # (16, 128)


@functools.partial(jax.jit, static_argnames=("rows_per_block",))
def leaf_stats_pallas(leaf_idx: jax.Array, grad: jax.Array,
                      hess: jax.Array, mask: jax.Array,
                      rows_per_block: int = 1024) -> jax.Array:
    """Exact per-leaf [sum_grad, sum_hess, count] for up to 256 leaves.

    leaf_idx (N,) uint8/int32 in [0, 256); grad/hess/mask (N,) f32
    (mask applied in-kernel).  Returns (256, 3) f32 at hi/lo-split
    (~2^-16 relative) accuracy — the same accuracy class as the
    default histogram path.
    """
    import jax.experimental.pallas as pl

    n = leaf_idx.shape[0]
    t = min(16384, rows_per_block)
    while n % t:
        t //= 2
    out = pl.pallas_call(
        _leaf_stats_kernel,
        grid=(n // t,),
        in_specs=[pl.BlockSpec((1, t), lambda i: (0, i))] * 4,
        out_specs=pl.BlockSpec((16, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
        compiler_params=_compiler_params(),
        interpret=pallas_interpret(),
    )(leaf_idx[None, :], grad[None, :], hess[None, :], mask[None, :])
    acc = out[:, :96].reshape(16, 16, 6)        # (lo, hi, cols)
    acc = jnp.transpose(acc, (1, 0, 2)).reshape(256, 6)
    return acc[:, :3] + acc[:, 3:]              # hi + lo parts


def histogram_segsum_multi_win(bins_t: jax.Array, vals: jax.Array,
                               sel: jax.Array, win_lo: jax.Array,
                               r_bins: int, width: int,
                               two_col: bool = False,
                               miss_bin=None) -> jax.Array:
    """jnp reference for :func:`histogram_pallas_multi_win`.
    ``miss_bin`` (F,) int32 or None: rows at the feature's missing bin
    are excluded from the window (windowed stats are VALUE bins only;
    missing stats live in the reserved coarse slot)."""
    f, n = bins_t.shape
    x = bins_t.astype(jnp.int32)
    outs = []
    for w in range(width):
        rbin = x - win_lo[w][:, None]                  # (F, N)
        in_win = (rbin >= 0) & (rbin < r_bins)
        if miss_bin is not None:
            in_win = in_win & (x != miss_bin[:, None])
        m = (sel == w)[None, :] & in_win
        ids = jnp.where(m, rbin, r_bins) + \
            jnp.arange(f, dtype=jnp.int32)[:, None] * (r_bins + 1)
        flat = jax.ops.segment_sum(
            jnp.broadcast_to(vals[None, :, :], (f, n, 3)).reshape(-1, 3),
            ids.reshape(-1), num_segments=f * (r_bins + 1))
        outs.append(flat.reshape(f, r_bins + 1, 3)[:, :r_bins])
    out = jnp.stack(outs)
    if two_col:
        out = jnp.concatenate([out[..., :2], out[..., 1:2]], axis=-1)
    return out
