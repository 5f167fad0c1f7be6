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
    budget = 56 * 1024 * 1024
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
    def f_mask(self) -> int:
        """``f`` where the last feature block overhangs the stored
        matrix and the kernel has to mask it, else 0."""
        return self.f if not self.one_chunk and self.f_pad != self.f else 0

    def record(self, int8: bool = False) -> dict:
        """The engagement record (``GBDT.tier_decision["hist_tiling"]``).
        ``xt_copied``: whether the pass copies the matrix in HBM before
        its kernel starts.  No shape does: Mosaic takes both blocks
        above (on the chip: tools/check_routed_kernels.py).  ``mxu``:
        the type the pass contracts in (:func:`_accumulate`); the
        caller says whether the pass is given int8 values (ops/grow.py
        ``GrowParams.int8_values``)."""
        return {"f": self.f, "f_pad": self.f_pad, "fc": self.fc,
                "t": self.t, "xt_copied": False,
                "mxu": "int8" if int8 else "bf16"}


def bin_tiling(max_bin: int, f: int, cols: int = 128,
               rows_per_block: int = 1024) -> BinTiling:
    """The tiling a pass over ``f`` stored features at ``max_bin`` bins
    runs with (``cols``: 128 for the batched passes, the value columns
    for the single-leaf one)."""
    return BinTiling(f, *_tile(_pad_bins(max_bin), f, cols,
                               rows_per_block))


def _miss_operand(miss_bin: jax.Array, til: BinTiling) -> jax.Array:
    """(F,) per-feature missing bins -> the (til.rows, 1) kernel operand
    (-1: no missing bin)."""
    return jnp.pad(miss_bin.astype(jnp.int32), (0, til.rows - til.f),
                   constant_values=-1)[:, None]


def _win_lo_operand(win_lo: jax.Array, til: BinTiling) -> jax.Array:
    """(W, F) window starts -> the (til.rows, W) kernel operand: W on
    the lane axis is always a full dimension, F on it is not a legal
    block whenever features chunk."""
    return jnp.pad(win_lo.astype(jnp.int32).T,
                   ((0, til.rows - til.f), (0, 0)))


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


def _rhs_from(sel_oh: jax.Array, valsc: jax.Array) -> jax.Array:
    """(W, T) subset selector x (C, T) values -> (128 or 256, T) bf16
    rhs.

    Built IN bf16, halving the stage's register traffic vs an f32
    multiply followed by a cast.  Numerically identical to the old
    f32-multiply-then-cast: 0/1 selectors and quantized ints are
    bf16-exact, and for the float path the hi part is bf16-exact by
    construction while the lo residual was ALREADY rounded to bf16 by
    the final cast (the hi/lo split reaches ~2^-16 RELATIVE accuracy,
    not exactness — see the module header)."""
    W, T = sel_oh.shape
    C = valsc.shape[0]
    rhs = (sel_oh.astype(jnp.bfloat16)[:, None, :] *
           valsc.astype(jnp.bfloat16)[None, :, :]).reshape(W * C, T)
    return jnp.pad(rhs, ((0, _rhs_cols(W, C) - W * C), (0, 0)))


def _rhs_int8(on: jax.Array, valsc: jax.Array) -> jax.Array:
    """The rhs of the int8 contraction (:func:`_accumulate`): row
    ``k`` is ``on[k] ? valsc[k % C] : 0``, the quantized integers as
    they are.  on (128 or 256, T) bool: the rows of the subset that
    rhs row ``k`` belongs to (:func:`_rhs_row_lane`); valsc (C, T)
    int8.  Built row by row in two dimensions: the (W, C, T) ->
    (W * C, T) regrouping of :func:`_rhs_from` is what Mosaic takes
    longest to compile in a pass (46 s of an int32 one at T = 16384,
    against 1.4 s of this)."""
    lanes = on.shape[0]
    C = valsc.shape[0]
    c = jax.lax.rem(jax.lax.broadcasted_iota(jnp.int32, (lanes, 1), 0), C)
    v = valsc.astype(jnp.int32)
    row = v[0:1]
    for i in range(1, C):
        row = jnp.where(c == i, v[i:i + 1], row)       # (lanes, T)
    return jnp.where(on, row, 0).astype(jnp.int8)


def _rhs_row_lane(width: int, cols: int) -> jax.Array:
    """(128 or 256, 1) int32: the subset that rhs row ``k`` belongs to,
    ``k // cols``; -2, which no selector holds, beyond ``width *
    cols``."""
    k = jax.lax.broadcasted_iota(jnp.int32, (_rhs_cols(width, cols), 1), 0)
    return jnp.where(k < width * cols, jax.lax.div(k, cols), -2)


def _onehot_int8(xb: jax.Array, b_pad: int) -> jax.Array:
    """(R, T) int32 bins -> the (R * b_pad, T) int8 one-hot, row
    ``r * b_pad + b`` holding ``xb[r] == b``.  ``R * b_pad`` is on the
    (32, 128) int8 tile grid (:func:`_accumulate`).

    Where ``b_pad`` is a multiple of 32 the one-hot is made four rows
    to a 32-bit word with no narrowing: int8 rows ``4j .. 4j + 3`` are
    the bytes of int32 row ``j`` (``pltpu.bitcast``), so word ``q`` of
    a feature is ``1 << 8 * (x & 3)`` where ``x >> 2 == q`` and 0
    elsewhere (a bin outside ``[0, b_pad)`` meets no ``q``): a compare
    and a select a WORD, against a compare, a select and two
    narrowing packs an ELEMENT.  The words regroup ``(R, b_pad / 4,
    T) -> (R * b_pad / 4, T)`` for nothing only where ``b_pad / 4``
    fills the 8 sublanes; at 16 bins it is a relayout, and the plain
    form (compare in int32, regroup, narrow) is the faster one: 30.2
    against 52.6 ms a routed coarse pass of 20M x 67, where at 32 bins
    the words take a refine pass from 40.2 to 34.8 (PERF.md, PR 29)."""
    R, T = xb.shape
    if b_pad % 32 == 0:
        from jax.experimental.pallas import tpu as pltpu
        q = b_pad // 4
        byte = jnp.left_shift(1, (xb & 3) << 3)              # (R, T)
        words = jnp.where(
            (xb >> 2)[:, None, :] ==
            jax.lax.broadcasted_iota(jnp.int32, (R, q, T), 1),
            byte[:, None, :], 0)
        return pltpu.bitcast(words.reshape(R * q, T), jnp.int8)
    onehot = (xb[:, None, :] ==
              jax.lax.broadcasted_iota(jnp.int32, (R, b_pad, T), 1)
              ).astype(jnp.int32)
    return onehot.reshape(R * b_pad, T).astype(jnp.int8)


def _accumulate(out_ref, xb: jax.Array, rhs: jax.Array, b_pad: int,
                f_mask: int = 0, row0=0) -> None:
    """Last stage of every histogram kernel: the one-hot x values MXU
    contraction of one tile, added into the accumulator block.

    xb (R, T) int32: the bin each row counts in, per feature (a value
    outside [0, b_pad) counts nowhere); rhs (128 or 256, T) bf16 or
    int8; out_ref (>= R * b_pad, lanes) f32.  The one-hot is laid out
    (R*B, T) so the dot STREAMS R*B rows through the MXU while the
    tiny (T, lanes) value matrix sits stationary as weights; the
    reverse orientation reloads K x B weight tiles to stream only a
    few rows and is ~100x slower.

    The contraction's type follows the rhs, which follows the values
    the kernel was given.  int8 (quantized gradients: integers within
    +-127 against a 0/1 one-hot): int8 x int8 -> int32, the MXU's
    faster mode; a tile's partial sum is at most ``T`` x 127 = 2.08M
    at ``T`` = 16384, exact in int32 and in the float32 it is
    converted to and added into, so every output bit is what the bf16
    contraction of the same integers gives.  Anything else: bf16 x
    bf16 -> f32.

    The FEATURE TAIL is made here (see :class:`BinTiling`).  One
    chunk: ``xb`` has the stored features' rows only, fewer than the
    accumulator block; their one-hot rows alone are built, streamed
    and added into the block's head, and the tail's rows keep the
    zeros ``_init`` wrote.  Several chunks (``f_mask`` > 0): the last
    block overhangs the stored matrix, and rows whose feature index
    ``row0 + i`` is not below ``f_mask`` hold whatever VMEM held:
    they are sent to bin -1."""
    R, T = xb.shape
    if f_mask:
        feat = row0 + jax.lax.broadcasted_iota(jnp.int32, (R, T), 0)
        xb = jnp.where(feat < f_mask, xb, -1)
    if rhs.dtype == jnp.int8:
        # int8 tiles are (32, 128): one-hot rows off that grid (67
        # features x 16 bins = 33.5 tiles) go up to the next multiple
        # on feature rows of bin -1, which match nothing (1088 rows,
        # not the 1152 of the whole accumulator block)
        extra = -R % (32 // math.gcd(b_pad, 32))
        if extra:
            xb = jnp.concatenate(
                [xb, jnp.full((extra, T), -1, jnp.int32)], axis=0)
            R += extra
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
    if R * b_pad == out_ref.shape[0]:
        out_ref[...] += acc
    else:
        out_ref[:R * b_pad, :] += acc


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
    _, f_pad, fc, t = til
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
    return out.reshape(f_pad, b_pad, 3)[:f, :max_bin]


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

    FC, T = x_ref.shape
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
        cols = 2
        valsc = v[:2]                   # grad, hess only
    else:
        cols = 3 if exact else 6
        valsc = v if exact else _split_hi_lo(v)        # (cols, T) f32
    if v.dtype == jnp.int8:
        rhs = _rhs_int8(sel == _rhs_row_lane(width, cols), valsc)
    else:
        sel_oh = (sel == jax.lax.broadcasted_iota(
            jnp.int32, (width, T), 0)).astype(jnp.bfloat16)  # (W, T)
        rhs = _rhs_from(sel_oh, valsc)                 # (128, T) bf16
    _accumulate(out_ref, x, rhs, b_pad, f_mask, pl.program_id(0) * FC)


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
    _, f_pad, fc, t = til
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
    out = out[:, :cols * W].reshape(f_pad, b_pad, W, cols)
    if two_col:
        # count := hess copy keeps every downstream shape at (..., 3);
        # the gate guarantees nothing reads it as a real count
        out = jnp.concatenate([out, out[..., 1:2]], axis=-1)
    elif not exact:
        out = out[..., :3] + out[..., 3:]    # hi + lo
    return jnp.moveaxis(out[:f, :max_bin], 2, 0)   # (W, F, B, 3)


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
# 60-90 ms at bench shape — poison); instead the kernel resolves it as
# a tiny (FC, W) × (W, T) matmul against the already-built subset
# one-hot — ~3% of the pass FLOPs, on the MXU.


def _hist_kernel_multi_win(x_ref, v_ref, s_ref, lo_ref, *rest,
                           r_pad: int, width: int, exact: bool,
                           two_col: bool, with_miss: bool = False,
                           f_mask: int = 0):
    """Windowed refine step: accumulate (leaf, feature)-windowed fine
    histograms.  x_ref (FC, T) bins; v_ref (3, T); s_ref (1, T) subset
    selector in [-1, width); lo_ref (width, FC) per-(subset, feature)
    fine-bin window starts; out_ref (FC*R, 128).  With ``with_miss``
    an extra (FC, 1) missing-bin ref precedes out_ref and rows at
    their feature's missing bin are excluded (windowed stats cover
    VALUE bins only)."""
    import jax.experimental.pallas as pl

    if with_miss:
        mb_ref, out_ref = rest
    else:
        (out_ref,) = rest

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    FC, T = x_ref.shape
    x = x_ref[...].astype(jnp.int32)
    if with_miss:
        mb = mb_ref[...].astype(jnp.int32)  # (FC, 1)
        x = jnp.where(x == mb, -1, x)       # miss rows match no window
    v = v_ref[...]                      # (3, T)
    sel = s_ref[...]                    # (1, T)
    if two_col:
        cols = 2
        valsc = v[:2]
    else:
        cols = 3 if exact else 6
        valsc = v if exact else _split_hi_lo(v)
    sel_oh = (sel == jax.lax.broadcasted_iota(
        jnp.int32, (width, T), 0)).astype(jnp.float32)  # (W, T)
    # per-row window start: lo[sel[t], f] via MXU instead of a gather.
    # lo arrives (FC, W): a (W, FC) block would put FC on the 128-lane
    # axis, which Mosaic rejects whenever features chunk (FC < F)
    lo = lo_ref[...].astype(jnp.float32)                # (FC, W)
    lo_pr = jax.lax.dot_general(
        lo, sel_oh, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (FC, T)
    rbin = x - lo_pr.astype(jnp.int32)
    if v.dtype == jnp.int8:
        rhs = _rhs_int8(sel == _rhs_row_lane(width, cols), valsc)
    else:
        rhs = _rhs_from(sel_oh, valsc)
    # out-of-window rows (rbin outside [0, r_pad)) match no iota column
    _accumulate(out_ref, rbin, rhs, r_pad, f_mask, pl.program_id(0) * FC)


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
    _, f_pad, fc, t = til
    assert n % t == 0, (n, t)
    if vals.dtype == jnp.int8:               # see histogram_pallas_multi
        assert exact or two_col, "int8 values need exact/two_col"
        vt = vals.T                          # (3, N) int8
    else:
        vt = vals.astype(jnp.float32).T      # (3, N)
    st = sel.astype(jnp.int32)[None, :]      # (1, N)

    in_specs = [
        pl.BlockSpec((til.block_rows, t), lambda j, i: (j, i)),
        pl.BlockSpec((3, t), lambda j, i: (0, i)),
        pl.BlockSpec((1, t), lambda j, i: (0, i)),
        pl.BlockSpec((til.block_rows, W), lambda j, i: (j, 0)),
    ]
    operands = [bins_t, vt, st, _win_lo_operand(win_lo, til)]
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
    out = out[:, :cols * W].reshape(f_pad, r_pad, W, cols)
    if two_col:
        out = jnp.concatenate([out, out[..., 1:2]], axis=-1)
    elif not exact:
        out = out[..., :3] + out[..., 3:]
    return jnp.moveaxis(out[:f, :r_bins], 2, 0)    # (W, F, R, 3)


# ---- routed multi-leaf pass ----------------------------------------
#
# The wave bodies used to route rows in XLA-land: an unrolled
# select-chain reading leaf_idx plus EVERY xt row from HBM, once more
# a wave.  The histogram pass already streams the bins matrix, so this
# variant does the routing IN the kernel: per row it resolves its wave lane (a
# table compare against the lane leaf-ids), its split column value (a
# feature-one-hot contraction over the resident x tile), the
# goes-left compare, and the subset selector — and writes the NEW leaf
# assignment and selector as side outputs.  Requires the whole feature
# dimension in one chunk (fc == f_pad, i.e. F <= ~32 at 8 bins) —
# callers fall back to the XLA routing otherwise.
#
# Lane tables ride in a (5, W) int32 operand:
#   row 0: lane leaf ids   row 1: lane split column
#   row 2: lane threshold  row 3: lane new (right-child) leaf id
#   row 4: smaller-child-is-left flag (mode="small" only)


def _routed_parts(x, li, tbl, width: int, mode: str, mb=None):
    """Shared routing math: returns (sel_oh, li_new, sel_out).
    x (FC, T) int32; li (1, T) int32; tbl (5-6, W) int32 (row 5 = the
    per-lane default-left flag, used with ``mb`` (FC, 1) per-feature
    missing bins: a row AT its lane feature's missing bin routes by
    the default direction instead of the threshold compare)."""
    FC, T = x.shape
    W = width if mode == "small" else width // 2
    ids = tbl[0:1, :W]                              # (1, W)
    lane_oh = (li == ids.T).astype(jnp.float32)     # (W, T)
    in_wave = jnp.sum(lane_oh, axis=0, keepdims=True) > 0.5
    # per-row split-column value: feature-one-hot contraction against
    # the resident x tile (an (N,) gather is poison; this is 2 tiny
    # MXU dots + an FC*T multiply-reduce)
    featoh = (tbl[1:2, :W].T ==
              jax.lax.broadcasted_iota(jnp.int32, (W, FC), 1)
              ).astype(jnp.float32)                 # (W, FC)
    fsel = jax.lax.dot_general(
        featoh.T, lane_oh, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # (FC, T)
    col = jnp.sum(x.astype(jnp.float32) * fsel, axis=0,
                  keepdims=True)                    # (1, T)
    thr_pr = jax.lax.dot_general(
        tbl[2:3, :W].astype(jnp.float32), lane_oh,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # (1, T)
    gl = in_wave & (col <= thr_pr)                  # (1, T)
    if mb is not None and tbl.shape[0] >= 6:
        # per-row missing bin of the lane's feature + default-left
        mb_pr = jnp.sum(mb.astype(jnp.float32) * fsel, axis=0,
                        keepdims=True)              # (1, T)
        dl_pr = jax.lax.dot_general(
            tbl[5:6, :W].astype(jnp.float32), lane_oh,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        is_miss = (col == mb_pr) & (mb_pr >= 0)
        gl = gl | (in_wave & (dl_pr > 0.5) & is_miss)
    glf = gl.astype(jnp.float32)
    # leaf ids can exceed 256 (num_leaves>257), which is NOT bf16-exact
    # — TPU f32 dots execute as bf16 passes at default precision, so
    # this one contraction must run at HIGHEST (exact for ints < 2^24)
    new_pr = jax.lax.dot_general(
        tbl[3:4, :W].astype(jnp.float32), lane_oh,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)
    li_new = jnp.where(in_wave & ~gl, new_pr.astype(jnp.int32), li)
    if mode == "small":
        sl_pr = jax.lax.dot_general(
            tbl[4:5, :W].astype(jnp.float32), lane_oh,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        to_small = (glf == sl_pr)                   # (1, T)
        sel_oh = lane_oh * to_small                 # (W, T)
    else:
        # children mode: left child of lane w -> slot w, right -> W+w
        sel_oh = jnp.concatenate(
            [lane_oh * glf, lane_oh * (1.0 - glf)], axis=0) * \
            in_wave.astype(jnp.float32)             # (2W, T)
    lane_idx = jax.lax.dot_general(
        jnp.arange(sel_oh.shape[0], dtype=jnp.int32)[None, :].astype(
            jnp.float32), sel_oh,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # (1, T)
    any_sel = jnp.sum(sel_oh, axis=0, keepdims=True) > 0.5
    sel_out = jnp.where(any_sel, lane_idx.astype(jnp.int32),
                        jnp.int32(-1))
    return sel_oh, li_new, sel_out


def _hist_kernel_multi_routed(x_ref, v_ref, li_ref, tbl_ref, *rest,
                              b_pad: int, width: int, exact: bool,
                              two_col: bool, shift: int, mode: str,
                              miss_idx: int = -1,
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
    tbl = tbl_ref[...]
    mb = mb_ref[...].astype(jnp.int32) if with_miss else None  # (FC, 1)
    sel_oh, li_new, sel_out = _routed_parts(x, li, tbl, width, mode,
                                            mb=mb)
    li_out_ref[...] = li_new.astype(li_out_ref.dtype)
    sel_out_ref[...] = sel_out
    if two_col:
        cols = 2
        valsc = v[:2]
    else:
        cols = 3 if exact else 6
        valsc = v if exact else _split_hi_lo(v)
    if v.dtype == jnp.int8:
        rhs = _rhs_int8(sel_out == _rhs_row_lane(sel_oh.shape[0], cols),
                        valsc)
    else:
        rhs = _rhs_from(sel_oh, valsc)
    if shift:
        xb = x >> shift
        if with_miss and miss_idx >= 0:
            # rows at their feature's missing bin land in the RESERVED
            # last coarse slot (see histogram_segsum_multi)
            xb = jnp.where(x == mb, miss_idx, xb)
    else:
        xb = x
    _accumulate(out_ref, xb, rhs, b_pad)    # one chunk: nothing to mask


def routed_chunk_ok(max_bin: int, f: int, cols: int = 128,
                    rows_per_block: int = 1024) -> bool:
    """True when the tiler keeps the whole feature dimension in one
    chunk — the routed kernel's requirement."""
    return bin_tiling(max_bin, f, cols, rows_per_block).one_chunk


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
    """
    import jax.experimental.pallas as pl

    f, n = bins_t.shape
    b_pad = _pad_bins(max_bin)
    cols = 2 if two_col else (3 if exact else 6)
    Wl = width
    assert Wl * cols <= 128, (Wl, cols)
    til = bin_tiling(max_bin, f, 128, rows_per_block)
    _, f_pad, fc, t = til
    assert til.one_chunk, "routed kernel needs a single feature chunk"
    assert n % t == 0, (n, t)
    if vals.dtype == jnp.int8:               # see histogram_pallas_multi
        assert exact or two_col, "int8 values need exact/two_col"
        vt = vals.T
    else:
        vt = vals.astype(jnp.float32).T
    # keep the leaf vector in its NARROW storage dtype (uint8 at
    # num_leaves<=255): it is re-read every pass
    lt = leaf_idx[None, :]
    W_tbl = tables.shape[1]
    R_tbl = tables.shape[0]

    in_specs = [
        pl.BlockSpec((til.block_rows, t), lambda i: (0, i)),
        pl.BlockSpec((3, t), lambda i: (0, i)),
        pl.BlockSpec((1, t), lambda i: (0, i)),
        pl.BlockSpec((R_tbl, W_tbl), lambda i: (0, 0)),
    ]
    operands = [bins_t, vt, lt, tables]
    miss_idx = -1
    if miss_bin is not None:
        assert R_tbl >= 6, "missing routing needs the default-left row"
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
    out = out[:, :cols * Wl].reshape(f_pad, b_pad, Wl, cols)
    if two_col:
        out = jnp.concatenate([out, out[..., 1:2]], axis=-1)
    elif not exact:
        out = out[..., :3] + out[..., 3:]
    hist = jnp.moveaxis(out[:f, :max_bin], 2, 0)
    return hist, li_new[0], sel[0]


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
# table and resolves the lane one-hot in-kernel — reading ~10 MB
# instead of 42, and writing nothing.


def _hist_kernel_multi_win_lanes(x_ref, v_ref, li_ref, ids_ref, lo_ref,
                                 *rest, r_pad: int, width: int,
                                 exact: bool, two_col: bool,
                                 with_miss: bool = False,
                                 f_mask: int = 0):
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
        mb = mb_ref[...].astype(jnp.int32)              # (FC, 1)
        x = jnp.where(x == mb, -1, x)   # miss rows match no window
    v = v_ref[...]
    li = li_ref[...].astype(jnp.int32)                  # (1, T)
    ids = ids_ref[...]                                  # (1, W)
    if two_col:
        cols = 2
        valsc = v[:2]
    else:
        cols = 3 if exact else 6
        valsc = v if exact else _split_hi_lo(v)
    sel_oh_f = (li == ids.T).astype(jnp.float32)        # (W, T)
    lo = lo_ref[...].astype(jnp.float32)                # (FC, W)
    lo_pr = jax.lax.dot_general(
        lo, sel_oh_f, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (FC, T)
    rbin = x - lo_pr.astype(jnp.int32)
    in_lane = jnp.sum(sel_oh_f, axis=0, keepdims=True) > 0.5
    rbin = jnp.where(in_lane, rbin, -1)
    if v.dtype == jnp.int8:
        # the lane ids, one a rhs row (-1, no leaf's id, beyond them)
        row_ids = jnp.repeat(ids.T, cols, axis=0)       # (W * C, 1)
        row_ids = jnp.pad(
            row_ids, ((0, _rhs_cols(width, cols) - width * cols), (0, 0)),
            constant_values=-1)
        rhs = _rhs_int8(li == row_ids, valsc)
    else:
        rhs = _rhs_from(sel_oh_f.astype(jnp.bfloat16), valsc)
    _accumulate(out_ref, rbin, rhs, r_pad, f_mask, pl.program_id(0) * FC)


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
    _, f_pad, fc, t = til
    assert n % t == 0, (n, t)
    if vals.dtype == jnp.int8:
        assert exact or two_col, "int8 values need exact/two_col"
        vt = vals.T
    else:
        vt = vals.astype(jnp.float32).T
    lt = leaf_idx[None, :]                   # narrow storage dtype
    it = lane_ids.astype(jnp.int32)[None, :]  # (1, W)

    in_specs = [
        pl.BlockSpec((til.block_rows, t), lambda j, i: (j, i)),
        pl.BlockSpec((3, t), lambda j, i: (0, i)),
        pl.BlockSpec((1, t), lambda j, i: (0, i)),
        pl.BlockSpec((1, W), lambda j, i: (0, 0)),
        pl.BlockSpec((til.block_rows, W), lambda j, i: (j, 0)),
    ]
    operands = [bins_t, vt, lt, it, _win_lo_operand(win_lo, til)]
    if miss_bin is not None:
        in_specs.append(pl.BlockSpec((til.block_rows, 1),
                                     lambda j, i: (j, 0)))
        operands.append(_miss_operand(miss_bin, til))
    out = pl.pallas_call(
        functools.partial(_hist_kernel_multi_win_lanes, r_pad=r_pad,
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
    out = out[:, :cols * W].reshape(f_pad, r_pad, W, cols)
    if two_col:
        out = jnp.concatenate([out, out[..., 1:2]], axis=-1)
    elif not exact:
        out = out[..., :3] + out[..., 3:]
    return jnp.moveaxis(out[:f, :r_bins], 2, 0)    # (W, F, R, 3)


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
    valsc = _split_hi_lo(v)                     # (6, T)
    sel_oh = ((li >> 4) == jax.lax.broadcasted_iota(
        jnp.int32, (16, T), 0)).astype(jnp.bfloat16)     # (16, T)
    rhs = _rhs_from(sel_oh, valsc)              # (128, T) bf16
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
