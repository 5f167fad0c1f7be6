"""Leaf-wise (best-first) tree growth, fully on device.

Reference: ``SerialTreeLearner::Train`` (``src/treelearner/
serial_tree_learner.cpp:157-221``): repeat {find best split per leaf →
split the globally-best leaf → build child histograms with the
histogram-subtraction trick (smaller child from scratch, larger =
parent − smaller, ``:506-511``)} until ``num_leaves-1`` splits or no
positive gain.

TPU-first re-design: leaf membership is a dense ``(N,)`` partition-id
vector instead of index lists (``DataPartition``), the growth loop is a
``lax.fori_loop`` with a static ``num_leaves-1`` trip count (no-gain
iterations are masked no-ops), and per-leaf histograms live in a
``(num_leaves, F, B, 3)`` pool (the ``HistogramPool`` analog) enabling
subtraction.  The output is a flat record-of-splits that the host turns
into a :class:`~lightgbm_tpu.models.tree.Tree`.

Distributed growth (``DistConfig``) runs the same loop SPMD under
``jax.shard_map`` over a named mesh axis, with the reference's three
parallel learners re-expressed as XLA collectives:

- ``data``: rows sharded; per-leaf histograms ``psum_scatter``-ed over
  the feature axis so each shard owns full histograms for its feature
  block, finds its block-local best split, and the winner is merged by
  an all-gather arg-max — mirroring ``DataParallelTreeLearner``
  (``data_parallel_tree_learner.cpp:147-239``, reducer ``bin.h:40-56``).
- ``feature``: features sharded, rows replicated; no histogram traffic
  at all, only the tiny best-split merge plus a one-bit row-routing
  broadcast from the winning feature's owner — mirroring
  ``FeatureParallelTreeLearner`` (``feature_parallel_tree_learner.cpp``).
- ``voting``: rows sharded; each shard votes its local top-k features,
  the global top-2k by votes are elected, and ONLY those features'
  histograms are ``psum``-ed — mirroring the PV-Tree
  ``VotingParallelTreeLearner`` (``voting_parallel_tree_learner.cpp``).
- ``data2d``: rows AND feature tiles sharded on a 2-D
  ``Mesh(("data", "feature"))`` — each device holds an R-th of the rows
  x an F-th of the features.  The collective schedule factors per axis:
  histograms ``psum`` over the ROW axis only (each device then holds
  complete histograms for its own feature tile, so per-pass bytes drop
  from O(F·B) to O(F·B/F_axis)), per-tile best splits merge by an
  all-gather arg-max over the FEATURE axis, and row routing broadcasts
  one owner bit per local row over the feature axis — the data x
  feature composition the 1-D learners force a choice between.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .histogram import (bin_tiling, histogram_pallas,
                        histogram_pallas_multi,
                        histogram_pallas_multi_win,
                        histogram_pallas_multi_win_lanes,
                        histogram_routed,
                        histogram_segsum, histogram_segsum_multi,
                        histogram_segsum_multi_win,
                        histogram_segsum_multi_win_lanes)
from ..io.pager import PagedXt
from .split import (NEG_INF, SplitParams, choose_window,
                    eval_forced_split, find_best_split,
                    find_best_split_c2f, find_best_split_pallas,
                    leaf_output)

__all__ = ["DistConfig", "GrowParams", "build_tree", "build_tree_impl",
           "collective_bytes_per_pass", "wave_collective_plan",
           "GROW_COUNTERS"]

# What the growth loop counts of its own work, as int32 scalars in the
# loop state (wave and speculative tiers); they return with the tree's
# records and become process counters at the tree's or block's commit
# (models/gbdt.py ``_count_growth``).  ``n_arm_passes`` is every
# batched histogram pass after the root's, ``n_waves`` the waves (the
# arming passes on the speculative tier).  A wave's first pass routes
# the rows (coarse bins under c2f); the windowed refine passes of c2f
# are ``n_arm_passes - n_waves``, and on the wave tiers the lanes of
# ``W_spec`` that the waves filled are the tree's splits, so the host
# derives both.
GROW_COUNTERS = ("n_arm_passes", "n_waves")


@dataclasses.dataclass(frozen=True)
class DistConfig:
    """Static distribution strategy for the growth loop.

    ``kind``: serial | data | feature | voting (``tree_learner`` values,
    ``tree_learner.cpp:9-33``) | data2d (2-D row x feature-tile mesh).
    ``num_shards`` is the ROW-axis size; ``axis`` the mesh axis name the
    row-scoped collectives run over.  ``top_k`` is the per-shard ballot
    size for voting-parallel (``config.h:349``).

    ``data2d`` factors the collective schedule per axis: histograms are
    ``psum``-ed over the ``axis`` (row) axis only — each device then
    holds the COMPLETE histograms of its own feature tile — the
    per-tile best splits ballot-gather over ``feat_axis``
    (``feat_shards`` tiles), and routing broadcasts one owner bit per
    local row over ``feat_axis``.  1-D kinds leave
    ``feat_shards == 1``.
    """
    kind: str = "serial"
    axis: str = "shard"
    num_shards: int = 1
    top_k: int = 20
    feat_axis: str = "feature"
    feat_shards: int = 1


@dataclasses.dataclass(frozen=True)
class GrowParams:
    split: SplitParams
    num_leaves: int
    max_depth: int = -1
    hist_impl: str = "segsum"  # segsum | pallas
    rows_per_block: int = 1024
    dist: DistConfig = DistConfig()
    # forced splits (ForceSplits, serial_tree_learner.cpp:544) in BFS
    # order as (leaf_id, global_feature, threshold_bin) triples —
    # precomputed on host from the forcedsplits JSON; serial only
    forced: tuple = ()
    # EFB: xt rows are bundles, not features; histograms expand to
    # logical features at split time (serial learner only)
    bundled: bool = False
    # False = recompute both children's histograms fresh each split
    # instead of keeping the (L, G, B, 3) pool for the subtraction
    # trick — the HistogramPool memory policy (histogram_pool_size)
    use_hist_pool: bool = True
    # speculative child arming: each histogram pass batches the
    # smaller-child histograms of the top-`speculate` unarmed leaves
    # (their cached best splits fully determine the children), filling
    # the MXU lane dimension a single 6-wide pass leaves idle; splits
    # whose children were pre-armed cost no pass at all.  0 = off.
    # Exact best-first semantics either way.  Serial learner only.
    speculate: int = 0
    # >0: histogram gradients/hessians as stochastically-rounded ints in
    # [-q, q] (LightGBM 4's quantized-training idea re-cast for the MXU:
    # small ints are exact in bf16, so the hi/lo mantissa split drops
    # from 6 value columns to 3 and the speculative pass packs 42
    # leaves per matmul).  Serial learner only.
    quantize: int = 0
    # wave growth: apply the top-W splittable leaves per loop step in
    # ONE batched histogram pass instead of one leaf per step.  The
    # split criterion per leaf is unchanged (greedy max-gain); only the
    # ORDER differs from strict best-first (bulk-synchronous waves, the
    # same deviation class as spec_tolerance).  Cuts the sequential
    # loop from num_leaves-1 iterations to ~log2(W)+num_leaves/W and
    # the histogram passes to one per wave.  Requires speculate>1
    # (the batched kernel); serial learner only.
    wave: bool = False
    # two-column quantized passes: accumulate only (grad, hess) so the
    # 128 MXU lanes fit W=64 leaves per pass (10 passes per 255-leaf
    # tree instead of 12).  The histogram count channel becomes a HESS
    # COPY; legal only when the count channel is provably redundant —
    # min_data_in_leaf <= 1 and min_sum_hessian_in_leaf > 0 (a side
    # with hess_sum >= msh > 0 necessarily holds a row), no
    # categorical features (their scans read counts), no bundling
    # (FixHistogram reads counts), no missing values (the default-
    # direction test reads the missing bin's count, and a hess copy
    # can quantize to zero there).  Real per-leaf counts are restored
    # on the host from the full-precision renewal stats.  Requires
    # quantize>0 and the wave path; the plan gates all of this.
    two_col: bool = False
    # >0: coarse-to-fine histogram refinement on the wave path.  Each
    # wave runs one COARSE pass (fine bins collapsed 2^refine_shift-
    # to-1, streaming B/2^shift one-hot rows) over the SMALLER child
    # of each of the top-W_spec splits — the larger children come from
    # a COARSE-resolution (L, F, Bc, 3) pool by the subtraction trick
    # — then 1-2 WINDOWED passes resolving only the 2 coarse bins
    # straddling each (child, feature)'s best coarse boundary at fine
    # resolution (~0.21x the MXU stream of a full 255-bin pass; the
    # plan only enables it where the stream saving beats the extra
    # per-pass fixed cost — see models/tier.py).  The fine-resolution
    # pool is dropped.  Split choice is exact whenever the best fine
    # threshold lies in the chosen window (see ops/split.py).
    # Missing values ARE supported: the per-feature missing bin maps
    # to a RESERVED last coarse slot and both default directions are
    # scanned.  Requires the wave path, numerical (non-categorical)
    # features, no bundling.
    refine_shift: int = 0
    # best-split engine: "xla" = the vectorized jnp scans in
    # ops/split.py (every tier); "pallas" = the on-chip kernel
    # (find_best_split_pallas, every child) — numerical features,
    # serial learner, no EFB/forced/c2f; the PLAN gates this
    # (models/tier.py records the gate that rejected it), build_tree
    # only falls back silently for the sub-paths the kernel cannot serve
    split_kernel: str = "xla"
    # >0: relative gain tolerance for preferring an already-ARMED leaf
    # over a fresh unarmed one when their best gains are within
    # tol*|best|.  Late boosting iterations have near-flat gains and
    # chain-miss the armer on every split (measured 19 -> 44 passes per
    # tree over 40 iterations); a small tolerance recovers the pass
    # floor at a bounded deviation from strict best-first order (the
    # deferred leaf stays in the queue and splits next).  0 = exact
    # best-first (default).
    spec_tolerance: float = 0.0

    @property
    def int8_values(self) -> bool:
        """The batched passes take their values as int8 — quantized
        gradients are small ints (|v| <= quantize <= 127), exact in
        int8, and the (3, N) operand is re-read from HBM every pass at
        1 byte an entry instead of 4 (the float hi/lo path needs f32) —
        and the kernels then contract in int8 on the MXU
        (ops/histogram.py ``_accumulate``): what the tier record's
        ``mxu`` says."""
        return self.hist_impl == "pallas" and 0 < self.quantize <= 127


def batched_width(params: GrowParams, kind: str) -> int:
    """Lanes of the batched (speculative or wave) histogram pass under
    learner ``kind``; 0 where the growth loop runs single-leaf passes
    only.  Serial always batches; a parallel learner under wave growth."""
    p = params
    wave_par = p.wave and kind in ("data", "feature", "voting")
    if (kind == "serial" or wave_par) and p.use_hist_pool \
            and not p.forced and p.speculate > 1:
        return min(p.speculate, p.num_leaves)
    return 0


def routed_gate(params: GrowParams, kind: str, max_bin: int,
                g_cols: int) -> Optional[str]:
    """Why the batched pass at ``max_bin`` bins (the coarse count under
    c2f) over ``g_cols`` stored columns cannot route its rows inside
    the kernel (ops/histogram.py routed kernels), or None where it
    does.  The one statement of routed feasibility: ``build_tree_impl``
    branches on it and the tier record (models/tier.py) reports it.

    The wave's row-routing select chain re-reads leaf_idx + every xt
    row from HBM; where splits are plain threshold compares the
    kernels resolve lanes/goes-left and emit the new leaf vector:
    inside the pass where every feature fits one kernel chunk, in a
    step of its own over the lanes' split columns where they do not
    (:func:`route_kind`; ops/histogram.py ``histogram_routed``).
    Feature-parallel is excluded: the lane's split column lives on one
    shard only, so goes-left needs a cross-shard psum the kernel
    cannot do.  Missing values ARE supported: the lane tables carry a
    default-left row and the kernel resolves the per-row missing bin
    by a feature contraction."""
    p = params
    if p.hist_impl != "pallas":
        return "cpu backend (segsum histograms)"
    if p.bundled:
        return "EFB bundles active"
    if p.split.any_cat:
        return "categorical splits need bin masks"
    if kind == "feature":
        return "feature-parallel: split column lives on one shard"
    if batched_width(p, kind) <= 1:
        return "no batched pass (single-leaf passes route nothing)"
    return None


def route_kind(params: GrowParams, kind: str, max_bin: int,
               g_cols: int) -> str:
    """Where a wave's rows are routed (the tier record's ``route``):
    ``kernel``, inside the routed batched pass (one feature chunk);
    ``gather``, by the routing kernel, which fetches each live lane's
    split column itself (the 32-row storage tile that holds it, by a
    scalar-prefetched block index), ahead of a pass that walks several
    feature chunks;
    ``xla``, by the select chain over every stored column (or, off the
    batched passes, a split at a time)."""
    if routed_gate(params, kind, max_bin, g_cols) is not None:
        return "xla"
    one_chunk = bin_tiling(max_bin, g_cols, 128,
                           params.rows_per_block).one_chunk
    return "kernel" if one_chunk else "gather"


def collective_bytes_per_pass(params: GrowParams, num_features: int,
                              num_rows: int) -> dict:
    """Static per-shard estimate of the collective payload ONE
    histogram pass (plus its best-split merge and row-routing
    collectives) moves under this strategy — the accounting GPU
    boosting systems report to attribute time to comms (arXiv:
    1806.11248 §reducing histograms; arXiv:2005.09148).

    The estimate mirrors the collectives in :func:`build_tree`:

    - ``data``  — wave: full ``psum`` of the (W, F, B, 3) f32 batched
      pass; non-wave: ``psum_scatter`` of one (F, B, 3) leaf histogram
      plus the all-gathered best-split merge.
    - ``feature`` — no histogram traffic; per-child best merge
      all-gather plus one (N,) f32 owner-bit routing psum per wave.
    - ``voting`` — ballot all-gather plus the elected-only (2k, B, 3)
      psum per scanned child.
    - ``data2d`` — the (F/Fx, B, 3) feature-TILE histogram psum over
      the row axis only (the O(F·B) -> O(F·B/Fx) drop this learner
      exists for), one best-record all-gather over the feature axis,
      one (N/R,) owner-bit routing psum over the feature axis.

    Keys: hist / merge / route / total (bytes), ``ops`` (the number
    of collective operations the pass issues — the count a weak-scaling
    reader checks stays O(1) in shard count) and ``per_axis`` — the
    same bytes/ops attributed to the mesh axis they cross (one entry
    for 1-D kinds; ``data`` + ``feature`` entries for data2d).
    Coarse-to-fine and two-column passes stream fewer bins; this
    reports the full-resolution upper bound (telemetry consumers care
    about order of magnitude and trend, not exact wire bytes).
    """
    p = params
    kind = p.dist.kind
    D = max(p.dist.num_shards, 1)
    Fx = max(p.dist.feat_shards, 1)
    F = max(num_features, 1)
    B = p.split.max_bin
    W = p.speculate if (p.wave and p.speculate > 1) else 1
    out = {"hist": 0, "merge": 0, "route": 0, "total": 0, "ops": 0,
           "per_axis": {}}
    if kind in ("serial", "") or D * Fx <= 1:
        return out
    # one _MERGE_KEYS record: gain f32 + feature/threshold i32 +
    # default_left/is_cat bool + (B,) bool left_mask + (3,) f32 stats
    rec_bytes = 4 + 4 + 4 + 1 + 1 + B + 12
    n_children = 2 * W if p.wave else 1
    if kind == "data":
        if p.wave:
            out["hist"] = W * F * B * 3 * 4
            out["ops"] = 1                      # one whole-tensor psum
        else:
            out["hist"] = F * B * 3 * 4
            out["merge"] = rec_bytes * D
            out["ops"] = 2                      # psum_scatter + merge
    elif kind == "feature":
        out["merge"] = n_children * rec_bytes * D
        out["route"] = num_rows * 4
        out["ops"] = 2                          # merge + routing psum
    elif kind == "voting":
        n_vote = min(p.dist.top_k, F)
        n_elect = min(2 * p.dist.top_k, F)
        out["merge"] = n_children * n_vote * 4 * D
        out["hist"] = n_children * n_elect * B * 3 * 4
        out["ops"] = 2                          # ballot gather + psum
    elif kind == "data2d":
        # per-device feature tile: the row-axis psum moves F/Fx of the
        # full histogram — the 1/F_axis collective-byte scaling
        out["hist"] = (F // Fx) * B * 3 * 4
        out["merge"] = rec_bytes * Fx
        out["route"] = (num_rows // D) * 4
        out["ops"] = 3            # row psum + tile merge + routing psum
    out["total"] = out["hist"] + out["merge"] + out["route"]
    if kind == "data2d":
        out["per_axis"] = {
            p.dist.axis: {"bytes": out["hist"], "ops": 1},
            p.dist.feat_axis: {"bytes": out["merge"] + out["route"],
                               "ops": 2},
        }
    else:
        out["per_axis"] = {p.dist.axis: {"bytes": out["total"],
                                         "ops": out["ops"]}}
    return out


def wave_collective_plan(params: GrowParams, num_features: int) -> dict:
    """What one shard of the data learner's WAVE growth really hands to
    the row axis' collectives, by what the growth loop counts: bytes
    and operations of one batched pass of each kind (``coarse`` and
    ``refine`` under c2f, else ``full``: the whole-wave psum of the
    pass's own (lanes, F, bins, 3) float32 tensor) and the fixed part
    of a tree (the root's passes, the root statistics' psum, the
    quantization scales' two pmax, the exact leaf statistics' psum).
    ``build_tree_impl`` holds every tensor it psums against this plan
    as it traces, so the count and the program cannot disagree;
    ``GBDT._count_growth`` multiplies it by the trees' own pass counts.
    Empty where the learner is not the wave data learner."""
    p = params
    W = batched_width(p, p.dist.kind)
    if not (p.dist.kind == "data" and p.wave and W > 1):
        return {}
    F = max(num_features, 1)

    def pass_bytes(bins):
        return W * F * bins * 3 * 4

    if p.refine_shift:
        coarse, window = c2f_bins(p.split.max_bin, p.refine_shift,
                                  p.split.any_missing)
        passes = {"coarse": pass_bytes(coarse),
                  "refine": pass_bytes(window)}
    else:
        passes = {"full": pass_bytes(p.split.max_bin)}
    # the root runs one pass of each kind, then the statistics
    tree_bytes = sum(passes.values()) + 3 * 4
    tree_ops = len(passes) + 1
    if p.quantize:
        tree_bytes += 2 * 4 + p.num_leaves * 3 * 4
        tree_ops += 3
    return {"passes": passes, "tree_bytes": tree_bytes,
            "tree_ops": tree_ops}


def c2f_bins(max_bin: int, shift: int, any_missing: bool):
    """(coarse bins, window bins) of the coarse-to-fine passes at
    ``refine_shift`` = ``shift``.  +1 coarse slot with missing values:
    the last one is RESERVED for the per-feature missing bin.  Value
    bins can never alias it: they run to nv-1 <= B-2, so their coarse
    ids stay below the unreserved slot count (ops/split.py:_c2f_miss).
    The window is 2 coarse bins at fine resolution."""
    return ((max_bin - 1) >> shift) + 1 + int(any_missing), 2 << shift


def _hist(xt, vals, p: GrowParams):
    if isinstance(xt, PagedXt):
        # paged lane: the SAME accumulation as histogram_segsum, as a
        # page loop (bit-identical fold — see PagedXt.hist)
        return xt.hist(vals, p.split.max_bin)
    if p.hist_impl == "pallas":
        return histogram_pallas(xt, vals, p.split.max_bin, p.rows_per_block,
                                exact=p.quantize > 0)
    return histogram_segsum(xt, vals, p.split.max_bin)


def mask_lookup(mask_row: jax.Array, col: jax.Array) -> jax.Array:
    """Gather-free bin-mask lookup: ``mask_row[col]`` for a (B,) bool
    mask and (N,) int bins.

    XLA's gather lowers poorly on TPU (serialized element loads); the
    mask is instead packed into B/32 uint32 words and each row resolves
    its word with a static chain of broadcast selects — pure VPU ops.
    """
    B = mask_row.shape[0]
    nw = (B + 31) // 32
    pad = nw * 32 - B
    bits = jnp.pad(mask_row.astype(jnp.uint32), (0, pad))
    words = jnp.sum(bits.reshape(nw, 32) <<
                    jnp.arange(32, dtype=jnp.uint32)[None, :], axis=1)
    col = col.astype(jnp.int32)
    hi = col >> 5
    acc = jnp.zeros(col.shape, dtype=jnp.uint32)
    for k in range(nw):
        acc = acc | jnp.where(hi == k, words[k], jnp.uint32(0))
    return ((acc >> (col & 31).astype(jnp.uint32)) & 1) > 0


_MERGE_KEYS = ("gain", "feature", "threshold", "default_left", "is_cat",
               "left_mask", "left_stats")


def _merge_best(best, axis):
    """All-gather per-shard winners and keep the arg-max — the
    ``SyncUpGlobalBestSplit`` allreduce (``parallel_tree_learner.h:183``).
    Ties resolve to the lowest shard, matching the serial scan's
    feature-major arg-max order."""
    small = {k: best[k] for k in _MERGE_KEYS}
    stacked = jax.lax.all_gather(small, axis)  # each leaf: (D, ...)
    i = jnp.argmax(stacked["gain"])
    return jax.tree.map(lambda a: a[i], stacked)


def build_tree_impl(xt: jax.Array, grad: jax.Array, hess: jax.Array,
                    sample_mask: jax.Array, feature_mask: jax.Array,
                    num_bins: jax.Array, missing_type: jax.Array,
                    is_cat: jax.Array, params: GrowParams,
                    bundle_maps=None, quant_key=None):
    """Grow one tree.

    xt: (F, N) binned features (transposed layout — contiguous per-feature
    rows for the histogram kernel and O(1) column fetch at split time);
    grad/hess/sample_mask: (N,) f32 (mask carries bagging weights and row
    padding); feature_mask: (F,) bool (feature_fraction);
    num_bins/missing_type: (F,) i32; is_cat: (F,) bool.

    With ``params.bundled`` (EFB), xt is the (G, N) BUNDLE matrix and
    ``bundle_maps`` = (group_id (F,), to_bundle (F, B),
    from_bundle (F, B), fix_default (F, B) one-hot of the skipped
    default bin, zero rows for singleton groups); histograms are built
    per bundle and expanded to logical features for the split search,
    the default bin reconstructed from leaf totals (``FixHistogram``,
    ``dataset.h:411``).

    Under a distributed strategy all array arguments are the LOCAL
    shards (rows sharded for data/voting, features for feature) and the
    function must run inside ``shard_map`` over ``params.dist.axis``.

    Returns a dict of per-split records (length num_leaves-1), final
    leaf assignment, per-leaf values and the realized leaf count.
    """
    p = params
    L = p.num_leaves
    B = p.split.max_bin
    if p.bundled:
        assert p.dist.kind == "serial", \
            "EFB bundling is supported by the serial learner only"
        assert bundle_maps is not None
        G_cols, N = xt.shape
        F = num_bins.shape[0]
        bm_group, bm_to, bm_from, bm_fix = bundle_maps
    else:
        F, N = xt.shape
        G_cols = F
    sp = p.split
    dist = p.dist
    kind = dist.kind
    ax = dist.axis
    D = dist.num_shards
    fax = dist.feat_axis
    Fx = dist.feat_shards
    # row-parallel kinds: rows sharded over ``ax``, so per-row state
    # (stats, quantization scales, noise streams, leaf renewal) needs a
    # reduction over that axis.  data2d's feature axis replicates rows,
    # so the SAME row-axis collectives serve it unchanged.
    row_par = kind in ("data", "voting", "data2d")

    assert p.quantize == 0 or kind in ("serial", "data", "data2d") \
        or p.wave, \
        "quantized histograms: serial/data/data2d learners, or any " \
        "parallel learner under wave growth"
    assert not (p.wave and kind == "data2d"), \
        "data2d runs the non-wave growth loop (wave composes with the " \
        "1-D learners only)"
    assert not p.two_col or (p.quantize > 0 and p.wave and
                             not p.bundled and p.split.counts_proxy), \
        "two_col requires quantized wave growth with counts_proxy"
    # wave growth composes with ALL THREE parallel learners the way
    # the reference composes its accelerated learner with every
    # parallel learner by template (DataParallelTreeLearner<GPU...>,
    # data_parallel_tree_learner.cpp:258-259, tree_learner.cpp:9-33):
    # - data: the batched multi-leaf pass runs per row shard and is
    #   psum-ed whole, so every shard scans identical histograms and
    #   takes identical split decisions — no best-split merge needed.
    # - feature: each shard builds the batched pass over ITS feature
    #   block only (no histogram traffic), children's bests merge by
    #   one batched all-gather arg-max, and row routing needs one
    #   (N,) owner-bit psum per wave (rows are replicated).
    # - voting: per-child ballots are scanned on the local batched
    #   hists, the top-2k electorate is voted batched, and ONLY the
    #   elected features' histograms are psum-ed (in raw integer
    #   units under quantization — exact in f32).
    wave_dist = p.wave and kind == "data"
    wave_feat = p.wave and kind == "feature"
    wave_vote = p.wave and kind == "voting"
    hist_scale = None
    if p.quantize:
        # stochastic rounding to ±quantize integer levels; sample_mask
        # must be 0/1 here (fractional weights ride grad/hess, which
        # the driver pre-multiplies)
        q = jnp.float32(p.quantize)
        key = quant_key if quant_key is not None else jax.random.PRNGKey(0)
        kg, kh = jax.random.split(key)
        grad_raw, hess_raw = grad, hess   # for the renewal kernel
        g_w = grad * sample_mask
        h_w = hess * sample_mask
        sg = jnp.maximum(jnp.max(jnp.abs(g_w)), jnp.float32(1e-30))
        sh = jnp.maximum(jnp.max(jnp.abs(h_w)), jnp.float32(1e-30))
        if row_par:
            # shard-consistent scale: quantization must agree across
            # shards or the psum-ed integer histograms mix units
            # (data2d: rows replicate over the feature axis, so the
            # row-axis pmax already yields the global max everywhere)
            sg = jax.lax.pmax(sg, ax)
            sh = jax.lax.pmax(sh, ax)
        sg, sh = sg / q, sh / q
        # rounding noise is a hash of the GLOBAL row index (not
        # jax.random.uniform, whose stream depends on the local shape):
        # the same row gets the same noise under any row sharding, so
        # an 8-shard data-parallel tree is bit-identical to the serial
        # one (integer sums are exact in f32 up to 2^24)
        if row_par:
            idx0 = jax.lax.axis_index(ax).astype(jnp.uint32) * \
                jnp.uint32(N)
        else:
            idx0 = jnp.uint32(0)
        ridx = idx0 + jnp.arange(N, dtype=jnp.uint32)

        def _row_uniform(k):
            # Wang-style integer mix of (row index, key word)
            kw = jnp.asarray(k, jnp.uint32).ravel()
            h = ridx ^ (kw[0] ^ kw[-1])
            h = (h ^ (h >> 16)) * jnp.uint32(0x7feb352d)
            h = (h ^ (h >> 15)) * jnp.uint32(0x846ca68b)
            h = h ^ (h >> 16)
            # 24-bit mantissa: (h>>8)*2^-24 is exact in f32 and strictly
            # < 1.0, keeping the [0, 1) contract (a full 32-bit value
            # within ~128 of 2^32 rounds UP to 2^32, making u == 1.0 and
            # overshooting the quantization range by one level)
            return (h >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)

        grad = jnp.floor(g_w / sg + _row_uniform(kg))
        hess = jnp.floor(h_w / sh + _row_uniform(kh))
        # two_col: the count channel is a hess copy and must dequantize
        # with the hess scale to stay in one unit system
        hist_scale = jnp.stack([sg, sh,
                                sh if p.two_col else jnp.float32(1.0)])

    # static per-feature monotone directions / gain penalties; the
    # tuples are GLOBAL (padded) feature descriptors
    has_mono = sp.has_monotone
    has_pen = sp.has_penalty
    mono_g = jnp.asarray(sp.monotone, jnp.int32) if has_mono else None
    pen_g = jnp.asarray(sp.penalty, jnp.float32) if has_pen else None
    BIG = jnp.float32(jnp.inf)

    if kind == "data" and not wave_dist:
        # each shard owns histograms for one contiguous feature block
        # after the reduce-scatter (data_parallel_tree_learner.cpp:147)
        assert F % D == 0, (F, D)
        F_hist = F // D
        f_offset = jax.lax.axis_index(ax) * F_hist
        blk = lambda a: jax.lax.dynamic_slice_in_dim(a, f_offset, F_hist)
        nb_l, mt_l = blk(num_bins), blk(missing_type)
        cat_l, fmask_l = blk(is_cat), blk(feature_mask)
    elif kind == "feature":
        # features are sharded in memory; descriptor arrays arrive local
        F_hist = F
        f_offset = jax.lax.axis_index(ax) * F
        blk = lambda a: jax.lax.dynamic_slice_in_dim(a, f_offset, F)
        nb_l, mt_l, cat_l, fmask_l = (num_bins, missing_type, is_cat,
                                      feature_mask)
    elif kind == "data2d":
        # feature tiles are sharded in memory over the FEATURE axis
        # (descriptors arrive local, like the feature learner); the
        # tile offset indexes that axis, not the row axis
        F_hist = F
        f_offset = jax.lax.axis_index(fax) * F
        blk = lambda a: jax.lax.dynamic_slice_in_dim(a, f_offset, F)
        nb_l, mt_l, cat_l, fmask_l = (num_bins, missing_type, is_cat,
                                      feature_mask)
    else:
        F_hist = G_cols  # histogram rows = device columns (bundles)
        f_offset = jnp.int32(0)
        blk = lambda a: a
        nb_l, mt_l, cat_l, fmask_l = (num_bins, missing_type, is_cat,
                                      feature_mask)
    mono_l = blk(mono_g) if has_mono else None
    pen_l = blk(pen_g) if has_pen else None
    # per-feature missing-bin ids (-1 = none): the missing bin is
    # always the LAST bin (io/binning.py appends it)
    mb_l = jnp.where(mt_l != 0, nb_l - 1, -1).astype(jnp.int32) \
        if sp.any_missing else None

    def expand(hist_cols, stats):
        """Bundle histogram (G, B, 3) -> logical features (F, B, 3):
        gather each feature's slot range and rebuild its skipped
        default bin from the leaf totals."""
        if not p.bundled:
            return hist_cols
        hf = hist_cols[bm_group]                       # (F, B, 3)
        idx = jnp.clip(bm_to, 0, B - 1)
        hf = jnp.take_along_axis(hf, idx[..., None], axis=1)
        hf = hf * (bm_to >= 0)[..., None]
        rem = stats[None, :] - jnp.sum(hf, axis=1)     # (F, 3)
        return hf + bm_fix[..., None] * rem[:, None, :]

    if kind == "voting":
        # local ballots use constraints scaled by 1/num_machines
        # (voting_parallel_tree_learner.cpp:53-55)
        vote_sp = dataclasses.replace(
            sp, min_data_in_leaf=max(sp.min_data_in_leaf // D, 1),
            min_sum_hessian_in_leaf=sp.min_sum_hessian_in_leaf / D)
        n_vote = min(dist.top_k, F)
        n_elect = min(2 * dist.top_k, F)

    def masked_hist(leaf_idx, leaf_id):
        """Histogram of one leaf — local pass + strategy collective."""
        m = sample_mask * (leaf_idx == leaf_id)
        vals = jnp.stack([grad * m, hess * m, m], axis=-1)
        h = _hist(xt, vals, p)
        # collectives run BEFORE dequantization: quantized histograms
        # are integers, summed exactly in f32 in any order — reducing
        # after the scale multiply would drift by reduction order and
        # break serial<->sharded bit-equality
        if kind == "data":
            if wave_dist:
                # wave path: full psum — every shard scans identical
                # histograms and takes identical decisions
                h = jax.lax.psum(h, ax)
            else:
                # HistogramBinEntry::SumReducer over the wire becomes
                # one XLA reduce-scatter over the feature dimension
                h = jax.lax.psum_scatter(h, ax, scatter_dimension=0,
                                         tiled=True)
        elif kind == "data2d":
            # axis-scoped: the row-axis psum alone completes THIS
            # feature tile's histograms (replicated down the mesh
            # column) — F/Fx of the bytes a 1-D data psum would move;
            # the feature axis never carries histogram traffic
            h = jax.lax.psum(h, ax)
        if hist_scale is not None:
            h = h * hist_scale  # dequantize: ints -> gradient units
        if p.two_col:
            # hess-as-count everywhere, so pool subtraction stays in
            # one unit system (see GrowParams.two_col)
            h = jnp.concatenate([h[..., :2], h[..., 1:2]], axis=-1)
        return h  # (F_hist, B, 3); local (not yet summed) for voting

    # speculative child arming: one batched pass fills the MXU lanes
    # with up to `speculate` smaller-child histograms (serial always;
    # parallel learners under wave growth)
    wave_par = wave_dist or wave_feat or wave_vote
    W_spec = batched_width(p, kind)
    do_spec = W_spec > 1
    use_wave = p.wave and do_spec and (kind == "serial" or wave_par) \
        and not p.forced
    use_c2f = use_wave and p.refine_shift > 0
    if use_c2f:
        assert not sp.any_cat and not p.bundled, \
            "coarse-to-fine refinement requires numerical features " \
            "and no bundling"
        assert kind in ("serial", "data"), \
            "coarse-to-fine runs under the serial/data learners only"
    # Pallas best-split tier (GrowParams.split_kernel): the numerical
    # scan runs as the on-chip kernel instead of the XLA scan.
    # The plan (models/tier.py) gates eligibility and records why a
    # config fell back; the asserts here are the backstop for direct
    # build_tree users.
    paged = isinstance(xt, PagedXt)
    if paged:
        # driver-gated (models/gbdt.py _paged_eligibility); backstop
        # for direct build_tree users.  The paged lane IS the baseline
        # segsum+xla lane with the matrix reads swapped for page
        # callbacks — the accelerated tiers read xt in access patterns
        # a page stream cannot serve.
        assert p.hist_impl == "segsum" and not p.wave \
            and p.speculate <= 1 and p.split_kernel == "xla", \
            "paged training requires the baseline lane: " \
            "hist_impl=segsum, no wave growth, speculate<=1, " \
            "split_kernel=xla (driver-gated)"
    use_split_pallas = p.split_kernel == "pallas"
    if use_split_pallas:
        assert kind == "serial" and not sp.any_cat and not p.bundled \
            and not p.forced and not use_c2f, \
            "split_kernel=pallas: serial learner, numerical features, " \
            "no EFB/forced splits/c2f refinement (driver-gated)"
    if do_spec:
        base_vals = jnp.stack([grad * sample_mask, hess * sample_mask,
                               sample_mask], axis=-1)
        # (a pre-transposed (2, N) bf16 value operand was measured
        # SLOWER than this (N, 3) f32 layout — 0.61 vs 0.55 s/iter at
        # 63 bins interleaved; sub-8-sublane bf16 blocks don't pay.
        # int8 is different: quantized ints are EXACT in int8 and cut
        # the per-pass value read 4x)
        kvals = (base_vals.astype(jnp.int8) if p.int8_values
                 else base_vals)

        def _wave_hist_finish(h):
            """Strategy collective + unit policy for batched passes:
            data psums whole (replicated scans), feature stays local
            (feature-sharded scans), voting stays local AND raw —
            the elected-only psum must run on integer units."""
            if wave_dist:
                # the count GBDT._count_growth makes of this psum
                assert h.size * h.dtype.itemsize in \
                    wave_collective_plan(p, F_hist)["passes"].values(), \
                    (h.shape, wave_collective_plan(p, F_hist))
                h = jax.lax.psum(h, ax)
            if wave_vote:
                return h
            return h if hist_scale is None else h * hist_scale

        def multi_hist(sel):
            if p.hist_impl == "pallas":
                h = histogram_pallas_multi(xt, kvals, sel, B, W_spec,
                                           p.rows_per_block,
                                           exact=p.quantize > 0,
                                           two_col=p.two_col)
            else:
                h = histogram_segsum_multi(xt, base_vals, sel, B, W_spec,
                                           two_col=p.two_col)
            return _wave_hist_finish(h)
    # in-kernel routing of the batched full-resolution pass
    # (:func:`routed_gate`; the coarse pass of c2f asks again below)
    routed_full_ok = routed_gate(p, kind, B, G_cols) is None
    # leaf vector in uint8 when every pass goes through the routed
    # kernel and ids fit (dummy id L included): it is re-read per pass
    # and per score-update, 4x less HBM than int32
    li_narrow = L <= 255

    def routed_call(li, tbl, max_bin_r, shift_r, mode):
        hist, li_new, sel = histogram_routed(
            xt, kvals, li, tbl, max_bin_r, W_spec,
            p.rows_per_block, exact=p.quantize > 0, two_col=p.two_col,
            shift=shift_r, mode=mode, miss_bin=mb_l, dead_id=L)
        return _wave_hist_finish(hist), li_new, sel

    def lane_tables(ids_leaf, feat_w, thr_w, new_ids, flag_w, dl_w):
        """(5-6, W) routed lane tables; the default-left row rides
        along only when the dataset has missing values."""
        rows = [ids_leaf, feat_w, thr_w, new_ids,
                flag_w.astype(jnp.int32)]
        if sp.any_missing:
            rows.append(dl_w.astype(jnp.int32))
        return jnp.stack(rows)

    if use_c2f:
        c2f_shift = p.refine_shift
        Bc_c2f, R_c2f = c2f_bins(B, c2f_shift, sp.any_missing)
        routed_coarse_ok = routed_gate(p, kind, Bc_c2f, G_cols) is None

        def multi_hist_coarse(sel):
            if p.hist_impl == "pallas":
                h = histogram_pallas_multi(xt, kvals, sel, Bc_c2f,
                                           W_spec, p.rows_per_block,
                                           exact=p.quantize > 0,
                                           two_col=p.two_col,
                                           shift=c2f_shift,
                                           miss_bin=mb_l)
            else:
                h = histogram_segsum_multi(xt, base_vals, sel, Bc_c2f,
                                           W_spec, two_col=p.two_col,
                                           shift=c2f_shift,
                                           miss_bin=mb_l)
            return _wave_hist_finish(h)

        def multi_hist_win(sel, lo_all):
            if p.hist_impl == "pallas":
                h = histogram_pallas_multi_win(xt, kvals, sel, lo_all,
                                               R_c2f, W_spec,
                                               p.rows_per_block,
                                               exact=p.quantize > 0,
                                               two_col=p.two_col,
                                               miss_bin=mb_l)
            else:
                h = histogram_segsum_multi_win(xt, base_vals, sel, lo_all,
                                               R_c2f, W_spec,
                                               two_col=p.two_col,
                                               miss_bin=mb_l)
            return _wave_hist_finish(h)

        def multi_hist_win_lanes(li_new, ids_g, lo_g):
            # windowed refine routed by the (already-updated) leaf
            # vector: no (N,) selector intermediate at all
            if p.hist_impl == "pallas":
                h = histogram_pallas_multi_win_lanes(
                    xt, kvals, li_new, ids_g, lo_g, R_c2f, W_spec,
                    p.rows_per_block, exact=p.quantize > 0,
                    two_col=p.two_col, miss_bin=mb_l)
            else:
                h = histogram_segsum_multi_win_lanes(
                    xt, base_vals, li_new, ids_g, lo_g, R_c2f, W_spec,
                    two_col=p.two_col, miss_bin=mb_l)
            return _wave_hist_finish(h)

        def c2f_window(c, s, mn, mx):
            return choose_window(c, s, nb_l, sp, c2f_shift, mono_l,
                                 mn, mx, missing_type=mt_l)

        def c2f_best(c, wh, lo, s, mn, mx):
            return find_best_split_c2f(c, wh, lo, s, nb_l, fmask_l, sp,
                                       c2f_shift, monotone=mono_l,
                                       penalty=pen_l, min_output=mn,
                                       max_output=mx,
                                       missing_type=mt_l)

    def global_stats(local):
        if row_par:
            return jax.lax.psum(local, ax)
        return local

    def best_of(hist_leaf, stats, depth, mn=None, mx=None):
        """Best split for one leaf from its (strategy-local) histogram.
        Returns a record with a GLOBAL feature index.  ``mn``/``mx`` are
        the leaf's inherited monotone output bounds."""
        if kind == "voting":
            b = _best_voting(hist_leaf, stats, mn, mx)
        else:
            if use_split_pallas:
                # on-chip numerical scan (EFB gated off: expand is the
                # identity here)
                b = find_best_split_pallas(hist_leaf, stats, nb_l,
                                           mt_l, fmask_l, sp,
                                           monotone=mono_l,
                                           penalty=pen_l, min_output=mn,
                                           max_output=mx)
            else:
                b = find_best_split(expand(hist_leaf, stats), stats,
                                    nb_l, mt_l, cat_l, fmask_l, sp,
                                    monotone=mono_l, penalty=pen_l,
                                    min_output=mn, max_output=mx)
            b["feature"] = b["feature"] + f_offset
            if kind == "data2d":
                # ballot-gather over the FEATURE axis only: devices
                # down a mesh column scanned identical tile histograms
                # and hold identical per-tile winners, so the row axis
                # needs no merge; gather order along the feature axis
                # is tile-major == global feature-major, preserving the
                # serial tie-break
                b = _merge_best(b, fax)
            elif kind in ("data", "feature") and not wave_dist:
                # wave_dist scans replicated histograms — every shard
                # already holds the identical global winner
                b = _merge_best(b, ax)
        allowed = (p.max_depth <= 0) | (depth < p.max_depth)
        b["gain"] = jnp.where(allowed, b["gain"], NEG_INF)
        return b

    def _best_voting(hist_local, stats, mn=None, mx=None):
        # ``hist_local`` arrives in RAW units on the quantized wave
        # path (pre-dequantize): ballots scan a dequantized copy, but
        # the elected-feature psum runs on raw integers — exact in f32
        # in any reduction order, preserving shard-count invariance
        deq = hist_local if hist_scale is None \
            else hist_local * hist_scale
        # stage 1: every shard votes its top-k features by local gain
        local_stats = jnp.sum(deq[0], axis=0)  # any feature's bins
        lb = find_best_split(deq, local_stats, num_bins,
                             missing_type, is_cat, feature_mask, vote_sp,
                             monotone=mono_g, penalty=pen_g,
                             min_output=mn, max_output=mx)
        _, ballot = jax.lax.top_k(lb["per_feature_gain"], n_vote)
        # stage 2: elect global top-2k by vote count (GlobalVoting:166)
        all_ballots = jax.lax.all_gather(ballot, ax).reshape(-1)
        votes = jnp.zeros(F, jnp.int32).at[all_ballots].add(1)
        _, elected = jax.lax.top_k(votes, n_elect)  # replicated
        # stage 3: sum ONLY the elected features' histograms
        h_sel = jax.lax.psum(hist_local[elected], ax)  # (2k, B, 3)
        if hist_scale is not None:
            h_sel = h_sel * hist_scale
        b = find_best_split(h_sel, stats, num_bins[elected],
                            missing_type[elected], is_cat[elected],
                            feature_mask[elected], sp,
                            monotone=None if mono_g is None
                            else mono_g[elected],
                            penalty=None if pen_g is None
                            else pen_g[elected],
                            min_output=mn, max_output=mx)
        b["feature"] = elected[b["feature"]]
        return b

    def child_bounds(ls, rs, mn_p, mx_p, feat, cat_flag):
        """Monotone child output-bound propagation
        (``serial_tree_learner.cpp:767-777``): a numerical split on a
        monotone feature pins the children on either side of
        ``mid = (left_output + right_output) / 2``.  Elementwise — the
        same code serves the scalar serial split and the (W,)-batched
        wave.  Returns (l_min, l_max, r_min, r_max)."""
        l1_, l2_, mds_ = sp.lambda_l1, sp.lambda_l2, sp.max_delta_step
        lo = jnp.clip(leaf_output(ls[..., 0], ls[..., 1], l1_, l2_, mds_),
                      mn_p, mx_p)
        ro = jnp.clip(leaf_output(rs[..., 0], rs[..., 1], l1_, l2_, mds_),
                      mn_p, mx_p)
        mid = 0.5 * (lo + ro)
        mono_f = mono_g[feat]
        up = (mono_f > 0) & ~cat_flag
        dn = (mono_f < 0) & ~cat_flag
        return (jnp.where(dn, mid, mn_p), jnp.where(up, mid, mx_p),
                jnp.where(up, mid, mn_p), jnp.where(dn, mid, mx_p))

    def goes_left_of(feat, left_mask_row):
        """Row routing for the winning split.  For data/voting/serial the
        winner's column is locally present; for feature-parallel only the
        owner shard has it and broadcasts a one-bit mask."""
        if p.bundled:
            # translate the feature-bin mask onto the bundle's bins
            g = jax.lax.dynamic_index_in_dim(bm_group, feat,
                                             keepdims=False)
            fb = jax.lax.dynamic_index_in_dim(bm_from, feat, axis=0,
                                              keepdims=False)  # (B,)
            col = xt.column(g) if paged else \
                jax.lax.dynamic_index_in_dim(xt, g, axis=0,
                                             keepdims=False)
            bundle_mask = jnp.take(left_mask_row, fb)
            return mask_lookup(bundle_mask, col)
        if kind in ("feature", "data2d"):
            # only the winning tile's owner holds the column; it
            # broadcasts one bit per (local) row over the axis the
            # features shard on — (N,) for feature-parallel, (N/R,)
            # for data2d (rows already sharded over the row axis)
            local_f = feat - f_offset
            owner = (local_f >= 0) & (local_f < F)
            clamped = jnp.clip(local_f, 0, F - 1)
            col = xt.column(clamped) if paged else \
                jax.lax.dynamic_index_in_dim(xt, clamped, axis=0,
                                             keepdims=False)
            cand = mask_lookup(left_mask_row, col)
            route_ax = fax if kind == "data2d" else ax
            return jax.lax.psum(
                jnp.where(owner, cand.astype(jnp.float32), 0.0),
                route_ax) > 0.5
        col = xt.column(feat) if paged else \
            jax.lax.dynamic_index_in_dim(xt, feat, axis=0, keepdims=False)
        return mask_lookup(left_mask_row, col)

    # ---- init: root ------------------------------------------------
    li_dtype = jnp.uint8 if (
        li_narrow and use_wave and
        (routed_coarse_ok if use_c2f else routed_full_ok)) else jnp.int32
    leaf_idx = jnp.zeros(N, dtype=li_dtype)
    root_count = jnp.sum(hess * sample_mask) if p.two_col \
        else jnp.sum(sample_mask)
    root_stats = global_stats(jnp.stack([jnp.sum(grad * sample_mask),
                                         jnp.sum(hess * sample_mask),
                                         root_count]))
    if hist_scale is not None:
        # keep root stats in the same (dequantized) units as the
        # histograms so subtraction and FixHistogram stay consistent
        root_stats = root_stats * hist_scale
    root_mn = -BIG if has_mono else None
    root_mx = BIG if has_mono else None
    if use_c2f:
        # coarse + windowed refine for the root too — no full-
        # resolution pass anywhere on the c2f path
        sel0 = jnp.zeros(N, jnp.int32)
        root_coarse = multi_hist_coarse(sel0)[0]
        root_win_lo = c2f_window(root_coarse, root_stats,
                                 root_mn, root_mx)
        lo0 = jnp.zeros((W_spec, F_hist), jnp.int32).at[0].set(
            root_win_lo)
        root_winh = multi_hist_win(sel0, lo0)[0]
        root_best = c2f_best(root_coarse, root_winh, root_win_lo,
                             root_stats, root_mn, root_mx)
    elif use_wave:
        # the batched pass with a single live lane: same stream cost
        # as the single-leaf pass but reuses the wave's (narrow) value
        # operand instead of materializing a fresh (N, 3) f32 stack
        root_hist = multi_hist(jnp.zeros(N, jnp.int32))[0]
        root_best = best_of(root_hist, root_stats, jnp.int32(0),
                            root_mn, root_mx)
    else:
        root_hist = masked_hist(leaf_idx, 0)
        root_best = best_of(root_hist, root_stats, jnp.int32(0),
                            root_mn, root_mx)

    n_forced = min(len(p.forced), L - 1)
    if n_forced:
        assert kind == "serial", \
            "forced splits are supported by the serial learner only"
        assert p.use_hist_pool, \
            "forced splits require the histogram pool"
        leaves, feats, thrs = (list(x) for x in zip(*p.forced))
        pad = [0] * ((L - 1) - n_forced)
        forced_leaf = jnp.asarray((leaves + pad)[:L - 1], jnp.int32)
        forced_feat = jnp.asarray((feats + pad)[:L - 1], jnp.int32)
        forced_thr = jnp.asarray((thrs + pad)[:L - 1], jnp.int32)

    state = {
        "leaf_idx": leaf_idx,
        "leaf_stats": jnp.zeros((L, 3), jnp.float32).at[0].set(root_stats),
        "leaf_depth": jnp.zeros(L, jnp.int32),
        "best_gain": jnp.full(L, NEG_INF, jnp.float32).at[0].set(
            root_best["gain"].astype(jnp.float32)),
        "best_feature": jnp.zeros(L, jnp.int32).at[0].set(
            root_best["feature"]),
        "best_threshold": jnp.zeros(L, jnp.int32).at[0].set(
            root_best["threshold"]),
        "best_default_left": jnp.zeros(L, bool).at[0].set(
            root_best["default_left"]),
        "best_is_cat": jnp.zeros(L, bool).at[0].set(root_best["is_cat"]),
        "best_left_mask": jnp.zeros((L, B), bool).at[0].set(
            root_best["left_mask"]),
        "best_left_stats": jnp.zeros((L, 3), jnp.float32).at[0].set(
            root_best["left_stats"].astype(jnp.float32)),
        "rec_leaf": jnp.zeros(L - 1, jnp.int32),
        "rec_feature": jnp.zeros(L - 1, jnp.int32),
        "rec_threshold": jnp.zeros(L - 1, jnp.int32),
        "rec_default_left": jnp.zeros(L - 1, bool),
        "rec_is_cat": jnp.zeros(L - 1, bool),
        "rec_gain": jnp.zeros(L - 1, jnp.float32),
        "rec_left_stats": jnp.zeros((L - 1, 3), jnp.float32),
        "rec_right_stats": jnp.zeros((L - 1, 3), jnp.float32),
        "rec_left_mask": jnp.zeros((L - 1, B), bool),
        "rec_valid": jnp.zeros(L - 1, bool),
        "n_leaves": jnp.int32(1),
    }
    if p.use_hist_pool and not use_c2f:
        # the HistogramPool analog: per-leaf histograms enabling the
        # parent-minus-smaller-child subtraction trick
        state["hist"] = jnp.zeros((L, F_hist, B, 3),
                                  jnp.float32).at[0].set(root_hist)
    if use_c2f:
        # COARSE-level pool (L, F, Bc, 3): the subtraction trick at
        # coarse resolution lets each c2f wave measure only the
        # SMALLER children (full lane width W_spec of splits per
        # coarse pass instead of W_spec/2 with both children in
        # lanes); ~1.4 MB at 255 leaves x 28 features x 16 bins
        state["hist_c"] = jnp.zeros((L, F_hist, Bc_c2f, 3),
                                    jnp.float32).at[0].set(root_coarse)
    if do_spec and not use_wave:
        # smaller-child histograms keyed by PARENT leaf; slot L is the
        # write target for unused arming lanes
        state["armed"] = jnp.zeros(L + 1, bool)
        state["armed_hist"] = jnp.zeros((L + 1, F_hist, B, 3),
                                        jnp.float32)
    if do_spec:
        for k in GROW_COUNTERS:
            state[k] = jnp.int32(0)
    if has_mono:
        # per-leaf inherited output bounds (LeafSplits min/max
        # constraint propagation, leaf_splits.hpp:16)
        state["leaf_min"] = jnp.full(L, -BIG, jnp.float32)
        state["leaf_max"] = jnp.full(L, BIG, jnp.float32)
        state["rec_left_min"] = jnp.full(L - 1, -BIG, jnp.float32)
        state["rec_left_max"] = jnp.full(L - 1, BIG, jnp.float32)
        state["rec_right_min"] = jnp.full(L - 1, -BIG, jnp.float32)
        state["rec_right_max"] = jnp.full(L - 1, BIG, jnp.float32)
    if n_forced:
        state["force_active"] = jnp.asarray(True)

    def arm_pass(st):
        """One batched pass arming the smaller-child histograms of the
        top-``W_spec`` unarmed splittable leaves (their cached best
        splits determine the children exactly)."""
        gains = jnp.where(st["armed"][:L] | ~(st["best_gain"] > 0),
                          NEG_INF, st["best_gain"])
        topg, ids = jax.lax.top_k(gains, W_spec)
        valid_w = topg > 0.5 * NEG_INF
        ids_safe = jnp.where(valid_w, ids, L)
        if routed_full_ok:
            # resolve lanes/goes-left INSIDE the pass (the exact-tier
            # analog of the wave's routed kernel): the XLA select
            # chain below re-reads leaf_idx + every xt column per
            # armed lane, ~10x this pass's HBM floor at bench shape.
            # The kernel's leaf-vector output is discarded — arming
            # must not move rows (the split is not applied yet), so
            # the new-id table row is the dummy L.
            ls_w = st["best_left_stats"][ids]
            ps_w = st["leaf_stats"][ids]
            small_left_w = ls_w[:, 2] <= ps_w[:, 2] - ls_w[:, 2]
            tbl = lane_tables(ids_safe, st["best_feature"][ids],
                              st["best_threshold"][ids],
                              jnp.full((W_spec,), L, jnp.int32),
                              small_left_w,
                              st["best_default_left"][ids])
            hists, _, _ = routed_call(st["leaf_idx"], tbl, B, 0,
                                      "small")
        else:
            sel = jnp.full(N, -1, jnp.int32)

            def per_w(w, sel):
                l = ids[w]
                feat = st["best_feature"][l]
                goes_left = goes_left_of(feat, st["best_left_mask"][l])
                ls = st["best_left_stats"][l]
                ps = st["leaf_stats"][l]
                small_is_left = ls[2] <= ps[2] - ls[2]
                pick = (st["leaf_idx"] == l) & \
                    (goes_left == small_is_left) & valid_w[w]
                return jnp.where(pick, jnp.int32(w), sel)

            sel = jax.lax.fori_loop(0, W_spec, per_w, sel)
            hists = multi_hist(sel)  # (W, F_hist, B, 3)
        st = dict(st)
        st["armed_hist"] = st["armed_hist"].at[ids_safe].set(hists)
        st["armed"] = st["armed"].at[ids_safe].set(valid_w) \
                                 .at[L].set(False)
        st["n_arm_passes"] = st["n_arm_passes"] + 1
        st["n_waves"] = st["n_waves"] + 1
        return st

    def body(t, st):
        best_l_id = jnp.argmax(st["best_gain"]).astype(jnp.int32)
        if do_spec and p.spec_tolerance > 0:
            # near-tie preference for armed leaves (see spec_tolerance)
            g_max = st["best_gain"][best_l_id]
            armed_gain = jnp.where(st["armed"][:L], st["best_gain"],
                                   NEG_INF)
            a_id = jnp.argmax(armed_gain).astype(jnp.int32)
            close = armed_gain[a_id] >= \
                g_max - p.spec_tolerance * jnp.abs(g_max)
            best_l_id = jnp.where(close & (g_max > 0), a_id, best_l_id)

        if n_forced:
            # forced phase: split the BFS-scheduled leaf at the fixed
            # (feature, threshold) while feasible; the first infeasible
            # forced split aborts forcing (aborted_last_force_split)
            in_force = (t < n_forced) & st["force_active"]
            fl = forced_leaf[t]
            f_mn = st["leaf_min"][fl] if has_mono else None
            f_mx = st["leaf_max"][fl] if has_mono else None
            frec = eval_forced_split(
                expand(st["hist"][fl], st["leaf_stats"][fl]),
                st["leaf_stats"][fl], forced_feat[t],
                forced_thr[t], nb_l, mt_l, sp, monotone=mono_l,
                min_output=f_mn, max_output=f_mx)
            usef = in_force & frec["feasible"]
            st = dict(st)
            st["force_active"] = st["force_active"] & \
                (~in_force | frec["feasible"])
            l = jnp.where(usef, fl, best_l_id)
        else:
            l = best_l_id

        # the split to apply this iteration: the globally-best stored
        # candidate of leaf l, or the forced record
        cand = {k: st["best_" + k][l] for k in
                ("gain", "feature", "threshold", "default_left",
                 "is_cat", "left_mask", "left_stats")}
        if n_forced:
            for k in cand:
                cand[k] = jnp.where(usef, frec[k].astype(cand[k].dtype),
                                    cand[k])
            valid = jnp.where(usef, True, cand["gain"] > 0)
        else:
            valid = cand["gain"] > 0
        gain = cand["gain"]

        if do_spec:
            # cache miss: the chosen leaf's children are not armed —
            # run one batched arming pass (it always includes l, the
            # top unarmed leaf by gain)
            st = jax.lax.cond(valid & ~st["armed"][l], arm_pass,
                              lambda s: s, st)

        def do_split(st):
            new = jnp.int32(t + 1)
            feat = cand["feature"]
            goes_left = goes_left_of(feat, cand["left_mask"])
            mine = st["leaf_idx"] == l
            leaf_idx = jnp.where(mine & ~goes_left, new, st["leaf_idx"])

            left_stats = cand["left_stats"]
            parent_stats = st["leaf_stats"][l]
            right_stats = parent_stats - left_stats
            if p.use_hist_pool:
                # subtraction trick: smaller child from scratch,
                # larger = parent − smaller (:506-511)
                small_is_left = left_stats[2] <= right_stats[2]
                small_id = jnp.where(small_is_left, l, new)
                if do_spec:
                    # the arming cond above guarantees a cache hit
                    hist_small = st["armed_hist"][l]
                else:
                    hist_small = masked_hist(leaf_idx, small_id)
                hist_large = st["hist"][l] - hist_small
                hist_l = jnp.where(small_is_left, hist_small, hist_large)
                hist_r = jnp.where(small_is_left, hist_large, hist_small)
            else:
                # no-pool memory policy: two fresh passes, nothing kept
                hist_l = masked_hist(leaf_idx, l)
                hist_r = masked_hist(leaf_idx, new)

            depth = st["leaf_depth"][l] + 1
            if has_mono:
                l_min, l_max, r_min, r_max = child_bounds(
                    left_stats, right_stats, st["leaf_min"][l],
                    st["leaf_max"][l], feat, cand["is_cat"])
            else:
                l_min = l_max = r_min = r_max = None

            best_l = best_of(hist_l, left_stats, depth, l_min, l_max)
            best_r = best_of(hist_r, right_stats, depth, r_min, r_max)

            st = dict(st)
            st["leaf_idx"] = leaf_idx
            if do_spec:
                # both children are fresh leaves with unknown splits
                st["armed"] = st["armed"].at[l].set(False) \
                                         .at[new].set(False)
            if p.use_hist_pool:
                st["hist"] = st["hist"].at[l].set(hist_l) \
                                       .at[new].set(hist_r)
            st["leaf_stats"] = st["leaf_stats"].at[l].set(left_stats) \
                                               .at[new].set(right_stats)
            st["leaf_depth"] = st["leaf_depth"].at[l].set(depth) \
                                               .at[new].set(depth)
            if has_mono:
                st["leaf_min"] = st["leaf_min"].at[l].set(l_min) \
                                               .at[new].set(r_min)
                st["leaf_max"] = st["leaf_max"].at[l].set(l_max) \
                                               .at[new].set(r_max)
                st["rec_left_min"] = st["rec_left_min"].at[t].set(l_min)
                st["rec_left_max"] = st["rec_left_max"].at[t].set(l_max)
                st["rec_right_min"] = st["rec_right_min"].at[t].set(r_min)
                st["rec_right_max"] = st["rec_right_max"].at[t].set(r_max)
            for key, src in (("best_gain", "gain"),
                             ("best_feature", "feature"),
                             ("best_threshold", "threshold"),
                             ("best_default_left", "default_left"),
                             ("best_is_cat", "is_cat"),
                             ("best_left_mask", "left_mask"),
                             ("best_left_stats", "left_stats")):
                arr = st[key]
                st[key] = arr.at[l].set(best_l[src].astype(arr.dtype)) \
                             .at[new].set(best_r[src].astype(arr.dtype))
            return st, left_stats, right_stats, gain

        def skip(st):
            return st, jnp.zeros(3, jnp.float32), jnp.zeros(3, jnp.float32), \
                jnp.float32(0)

        # record fields that need pre-split candidate values
        pre = {
            "feature": cand["feature"],
            "threshold": cand["threshold"],
            "default_left": cand["default_left"],
            "is_cat": cand["is_cat"],
            "left_mask": cand["left_mask"],
        }
        st2, ls, rs, g = jax.lax.cond(valid, do_split, skip, st)
        st2["rec_leaf"] = st2["rec_leaf"].at[t].set(
            jnp.where(valid, l, -1))
        st2["rec_feature"] = st2["rec_feature"].at[t].set(pre["feature"])
        st2["rec_threshold"] = st2["rec_threshold"].at[t].set(
            pre["threshold"])
        st2["rec_default_left"] = st2["rec_default_left"].at[t].set(
            pre["default_left"])
        st2["rec_is_cat"] = st2["rec_is_cat"].at[t].set(pre["is_cat"])
        st2["rec_left_mask"] = st2["rec_left_mask"].at[t].set(
            pre["left_mask"])
        st2["rec_gain"] = st2["rec_gain"].at[t].set(g)
        st2["rec_left_stats"] = st2["rec_left_stats"].at[t].set(ls)
        st2["rec_right_stats"] = st2["rec_right_stats"].at[t].set(rs)
        st2["rec_valid"] = st2["rec_valid"].at[t].set(valid)
        st2["n_leaves"] = st2["n_leaves"] + valid.astype(jnp.int32)
        return st2

    # ---- wave growth ------------------------------------------------
    # One loop step = one batched histogram pass + up to W_spec splits.
    # Each lane w handles one splittable leaf: its cached best split is
    # applied, its smaller child's histogram comes from lane w of the
    # multi-pass, the larger child by subtraction, and both children's
    # best splits are found by ONE vmapped scan over all 2W children.
    # Greedy per-leaf split choice is identical to best-first; only the
    # split ORDER is bulk-synchronous.
    def wave_cond(st):
        return (st["n_leaves"] < L) & (jnp.max(st["best_gain"]) > 0)

    def route_wave(li, ids_leaf, col_of_lane, thr_w, lane_mask,
                   extras=()):
        """Gather-free row routing shared by the wave bodies.

        XLA's (N,)-element gather runs at well under 1 GB/s on TPU
        (measured: a single table[leaf_idx] take costs ~60-90 ms at
        bench shape), so every per-row lookup is an unrolled
        select-chain against scalars — XLA fuses the whole block into
        one streaming pass over leaf_idx and the xt rows.

        Returns (w_row, in_wave, goes_left, extras_rows) where each
        (W,) table in ``extras`` is broadcast to its per-row value.
        """
        W = ids_leaf.shape[0]
        w_row = jnp.full(N, -1, jnp.int32)
        for w in range(W):                          # leaf -> lane
            w_row = jnp.where(li == ids_leaf[w], jnp.int32(w), w_row)
        in_wave = w_row >= 0
        csel = jnp.zeros(N, jnp.int32)              # lane -> column id
        for w in range(W):
            csel = jnp.where(w_row == w, col_of_lane[w], csel)
        if kind == "feature":
            # feature-parallel: the lane's column ids are GLOBAL but
            # only the owner shard holds the column — each shard
            # resolves goes-left for the rows whose lane feature it
            # owns and ONE (N,) psum merges the owner bits (rows are
            # replicated; a row has exactly one owner)
            csel = csel - f_offset
            owned = in_wave & (csel >= 0) & (csel < F_hist)
        else:
            owned = None
        col = jnp.zeros(N, jnp.int32)               # per-row split bin
        for g in range(G_cols):
            col = jnp.where(csel == g, xt[g].astype(jnp.int32), col)
        if not sp.any_cat and not sp.any_missing and not p.bundled:
            # numerical splits with no missing bin: goes-left is a
            # plain threshold compare — W scalar selects instead of
            # the W x B/32 mask-word chain
            thr_row = jnp.zeros(N, jnp.int32)
            for w in range(W):
                thr_row = jnp.where(w_row == w, thr_w[w], thr_row)
            goes_left = in_wave & (col <= thr_row)
        else:
            nw = (B + 31) // 32
            bits = jnp.pad(lane_mask.astype(jnp.uint32),
                           ((0, 0), (0, nw * 32 - B)))
            words = jnp.sum(
                bits.reshape(W, nw, 32) <<
                jnp.arange(32, dtype=jnp.uint32)[None, None, :],
                axis=2)                             # (W, nw)
            hi = col >> 5
            wd = jnp.zeros(N, jnp.uint32)           # per-row mask word
            for w in range(W):
                for h in range(nw):
                    wd = jnp.where((w_row == w) & (hi == h),
                                   words[w, h], wd)
            goes_left = in_wave & \
                (((wd >> (col & 31).astype(jnp.uint32)) & 1) > 0)
        if owned is not None:
            goes_left = jax.lax.psum(
                jnp.where(goes_left & owned, 1.0, 0.0), ax) > 0.5
        ex_rows = []
        for tbl in extras:
            r = jnp.zeros(N, tbl.dtype)
            for w in range(W):
                r = jnp.where(w_row == w, tbl[w], r)
            ex_rows.append(r)
        return w_row, in_wave, goes_left, ex_rows

    def commit_wave(st, ids_leaf, new_leaf, ids_rec, bests, ch_stats,
                    ch_depth, recs, valid_w, mono_vals=None,
                    ch_ids=None):
        """Shared state-commit tail of the wave bodies: scatter the
        children's stats/depth/best-split caches and the wave's split
        records.  Invalid lanes carry OUT-OF-BOUNDS indices and rely on
        mode="drop" (the default promise_in_bounds CLAMPS and corrupts
        the last real slot).  ``ch_ids`` overrides the child ordering
        (the c2f body interleaves [l0, r0, l1, r1, ...])."""
        if ch_ids is None:
            ch_ids = jnp.concatenate([ids_leaf, new_leaf])
        st = dict(st)
        st["leaf_stats"] = st["leaf_stats"].at[ch_ids].set(
            ch_stats, mode="drop")
        st["leaf_depth"] = st["leaf_depth"].at[ch_ids].set(
            ch_depth, mode="drop")
        if mono_vals is not None:
            ch_mn, ch_mx, l_min, l_max, r_min, r_max = mono_vals
            st["leaf_min"] = st["leaf_min"].at[ch_ids].set(
                ch_mn, mode="drop")
            st["leaf_max"] = st["leaf_max"].at[ch_ids].set(
                ch_mx, mode="drop")
            st["rec_left_min"] = st["rec_left_min"].at[ids_rec].set(
                l_min, mode="drop")
            st["rec_left_max"] = st["rec_left_max"].at[ids_rec].set(
                l_max, mode="drop")
            st["rec_right_min"] = st["rec_right_min"].at[ids_rec].set(
                r_min, mode="drop")
            st["rec_right_max"] = st["rec_right_max"].at[ids_rec].set(
                r_max, mode="drop")
        for key, src in (("best_gain", "gain"),
                         ("best_feature", "feature"),
                         ("best_threshold", "threshold"),
                         ("best_default_left", "default_left"),
                         ("best_is_cat", "is_cat"),
                         ("best_left_mask", "left_mask"),
                         ("best_left_stats", "left_stats")):
            arr = st[key]
            st[key] = arr.at[ch_ids].set(bests[src].astype(arr.dtype),
                                         mode="drop")
        for key, val in recs:
            st[key] = st[key].at[ids_rec].set(
                val.astype(st[key].dtype), mode="drop")
        st["n_leaves"] = st["n_leaves"] + \
            jnp.sum(valid_w.astype(jnp.int32))
        st["n_arm_passes"] = st["n_arm_passes"] + 1
        st["n_waves"] = st["n_waves"] + 1
        return st

    def child_best(h, s, mn, mx):
        return find_best_split(expand(h, s), s, nb_l, mt_l, cat_l,
                               fmask_l, sp, monotone=mono_l,
                               penalty=pen_l, min_output=mn,
                               max_output=mx)

    def _wave_best_voting(ch_hist, ch_stats, ch_mn, ch_mx):
        """Batched PV-Tree stages for all 2W children at once: the
        collectives run OUTSIDE the vmapped scans (one all-gather of
        ballots, one elected-only psum), mirroring per-leaf
        ``_best_voting``.  ``ch_hist`` is LOCAL and RAW-unit."""
        deq = ch_hist if hist_scale is None else ch_hist * hist_scale
        local_stats = jnp.sum(deq[:, 0], axis=1)        # (2W, 3)

        def ballot_scan(h, ls, mn, mx):
            return find_best_split(
                h, ls, num_bins, missing_type, is_cat, feature_mask,
                vote_sp, monotone=mono_g, penalty=pen_g,
                min_output=mn, max_output=mx)["per_feature_gain"]

        if has_mono:
            pf = jax.vmap(ballot_scan)(deq, local_stats, ch_mn, ch_mx)
        else:
            pf = jax.vmap(lambda h, ls: ballot_scan(h, ls, None, None))(
                deq, local_stats)
        _, ballot = jax.lax.top_k(pf, n_vote)           # (2W, k)
        all_b = jax.lax.all_gather(ballot, ax)          # (D, 2W, k)
        W2_ = ballot.shape[0]
        ab = jnp.moveaxis(all_b, 1, 0).reshape(W2_, -1)
        votes = jnp.zeros((W2_, F), jnp.int32).at[
            jnp.arange(W2_, dtype=jnp.int32)[:, None], ab].add(1)
        _, elected = jax.lax.top_k(votes, n_elect)      # (2W, 2k)
        h_sel = jnp.take_along_axis(
            ch_hist, elected[:, :, None, None], axis=1)
        h_sel = jax.lax.psum(h_sel, ax)                 # raw ints
        if hist_scale is not None:
            h_sel = h_sel * hist_scale

        def final_scan(h, el, s, mn, mx):
            b = find_best_split(
                h, s, num_bins[el], missing_type[el], is_cat[el],
                feature_mask[el], sp,
                monotone=None if mono_g is None else mono_g[el],
                penalty=None if pen_g is None else pen_g[el],
                min_output=mn, max_output=mx)
            b["feature"] = el[b["feature"]]
            return b

        if has_mono:
            return jax.vmap(final_scan)(h_sel, elected, ch_stats,
                                        ch_mn, ch_mx)
        return jax.vmap(lambda h, el, s: final_scan(h, el, s, None,
                                                    None))(
            h_sel, elected, ch_stats)

    def children_bests(ch_hist, ch_stats, ch_mn, ch_mx):
        """Per-strategy children best-split stage of a wave."""
        if wave_vote:
            return _wave_best_voting(ch_hist, ch_stats, ch_mn, ch_mx)
        if use_split_pallas:
            # lane-batched on-chip scan: the kernel grid runs all 2W
            # children natively — no vmap over pallas_call
            return find_best_split_pallas(ch_hist, ch_stats, nb_l,
                                          mt_l, fmask_l, sp,
                                          monotone=mono_l,
                                          penalty=pen_l,
                                          min_output=ch_mn,
                                          max_output=ch_mx)
        if has_mono:
            bests = jax.vmap(child_best)(ch_hist, ch_stats, ch_mn,
                                         ch_mx)
        else:
            bests = jax.vmap(lambda h, s: child_best(h, s, None, None))(
                ch_hist, ch_stats)
        if wave_feat:
            # batched SyncUpGlobalBestSplit: one all-gather, arg-max
            # per child; ties resolve to the lowest shard, matching
            # the serial feature-major scan order
            bests["feature"] = bests["feature"] + f_offset
            small = {k: bests[k] for k in _MERGE_KEYS}
            stacked = jax.lax.all_gather(small, ax)     # (D, 2W, ...)
            i = jnp.argmax(stacked["gain"], axis=0)     # (2W,)

            def pick(a):
                idx = i.reshape((1,) + i.shape + (1,) * (a.ndim - 2))
                return jnp.take_along_axis(a, idx, axis=0)[0]

            for k in _MERGE_KEYS:
                bests[k] = pick(stacked[k])
        return bests

    def wave_body(st):
        W = W_spec
        t0 = st["n_leaves"] - 1           # next free split-record slot
        remaining = (L - 1) - t0
        topg, ids = jax.lax.top_k(st["best_gain"], W)
        w_ar = jnp.arange(W, dtype=jnp.int32)
        # top_k sorts descending, so valid lanes form a prefix and the
        # record slots t0..t0+K-1 stay contiguous
        valid_w = (topg > 0) & (w_ar < remaining)
        ids_leaf = jnp.where(valid_w, ids, L)       # scatter-dummy: OOB
        t_j = t0 + w_ar
        ids_rec = jnp.where(valid_w, t_j, L - 1)    # OOB for (L-1,) recs
        new_ids = t_j + 1
        new_leaf = jnp.where(valid_w, new_ids, L)

        feat_w = st["best_feature"][ids]
        thr_w = st["best_threshold"][ids]
        dl_w = st["best_default_left"][ids]
        cat_w = st["best_is_cat"][ids]
        mask_w = st["best_left_mask"][ids]          # (W, B)
        lstat_w = st["best_left_stats"][ids]        # (W, 3)
        pstat_w = st["leaf_stats"][ids]
        rstat_w = pstat_w - lstat_w
        small_left_w = lstat_w[:, 2] <= rstat_w[:, 2]

        depth_w = st["leaf_depth"][ids] + 1
        if has_mono:
            l_min, l_max, r_min, r_max = child_bounds(
                lstat_w, rstat_w, st["leaf_min"][ids],
                st["leaf_max"][ids], feat_w, cat_w)
            ch_mn = jnp.concatenate([l_min, r_min])
            ch_mx = jnp.concatenate([l_max, r_max])

        li = st["leaf_idx"]
        if routed_full_ok:
            # routing resolved inside the pass itself; the kernel
            # also emits the updated leaf vector
            tbl = lane_tables(ids_leaf, feat_w, thr_w, new_ids,
                              small_left_w, dl_w)
            hist_small, leaf_idx, _ = routed_call(li, tbl, B, 0, "small")
        else:
            # route every in-wave row through ITS leaf's split
            if p.bundled:
                col_of_lane = bm_group[feat_w]
                fb_w = bm_from[feat_w]              # (W, B)
                lane_mask = jnp.take_along_axis(mask_w, fb_w, axis=1)
            else:
                col_of_lane = feat_w
                lane_mask = mask_w
            w_row, in_wave, goes_left, (small_left_row, new_id_row) = \
                route_wave(li, ids_leaf, col_of_lane, thr_w, lane_mask,
                           extras=(small_left_w, new_ids))
            to_small = goes_left == small_left_row
            sel = jnp.where(in_wave & to_small, w_row, jnp.int32(-1))
            hist_small = multi_hist(sel)            # (W, F_hist, B, 3)
            leaf_idx = jnp.where(in_wave & ~goes_left, new_id_row, li)

        hist_parent = st["hist"][ids]
        hist_large = hist_parent - hist_small
        sl4 = small_left_w[:, None, None, None]
        hist_l = jnp.where(sl4, hist_small, hist_large)
        hist_r = jnp.where(sl4, hist_large, hist_small)

        ch_stats = jnp.concatenate([lstat_w, rstat_w], axis=0)
        ch_depth = jnp.concatenate([depth_w, depth_w])
        # children best splits: ONE batched scan over all 2W children
        ch_hist = jnp.concatenate([hist_l, hist_r], axis=0)
        bests = children_bests(ch_hist, ch_stats,
                               ch_mn if has_mono else None,
                               ch_mx if has_mono else None)
        allowed = (p.max_depth <= 0) | (ch_depth < p.max_depth)
        bests["gain"] = jnp.where(allowed, bests["gain"], NEG_INF)
        # materialization fence: without it XLA fuses the vmapped scan's
        # output selects into the state scatters and (observed on the
        # CPU backend) the default-left stats/flag pair comes out of
        # DIFFERENT recomputations — leaf stats then disagree with the
        # recorded mask.  The barrier pins `bests` to single values.
        bests = jax.lax.optimization_barrier(bests)
        st = dict(st)
        st["leaf_idx"] = leaf_idx
        st["hist"] = st["hist"].at[ids_leaf].set(hist_l, mode="drop") \
                               .at[new_leaf].set(hist_r, mode="drop")
        mono_vals = (ch_mn, ch_mx, l_min, l_max, r_min, r_max) \
            if has_mono else None
        recs = (("rec_leaf", ids), ("rec_feature", feat_w),
                ("rec_threshold", thr_w), ("rec_default_left", dl_w),
                ("rec_is_cat", cat_w), ("rec_gain", topg),
                ("rec_left_stats", lstat_w),
                ("rec_right_stats", rstat_w),
                ("rec_left_mask", mask_w), ("rec_valid", valid_w))
        return commit_wave(st, ids_leaf, new_leaf, ids_rec, bests,
                           ch_stats, ch_depth, recs, valid_w, mono_vals)

    # ---- coarse-to-fine wave ----------------------------------------
    # One loop step = one COARSE pass over the SMALLER children of the
    # top-W splits (the larger children come from the coarse pool by
    # subtraction), then 1-2 WINDOWED refine passes over all 2W
    # children (each group holds W_spec lanes; the second group only
    # runs when more than W_spec/2 lanes are live — ramp waves skip
    # it), then the c2f split search per child.  Compared to the
    # both-children-in-lanes design this doubles the splits per wave
    # (W = W_spec, not W_spec/2): 3 passes per W_spec splits instead
    # of 4, and half the wave-loop iterations.
    def wave_body_c2f(st):
        W = W_spec
        W2 = 2 * W
        t0 = st["n_leaves"] - 1
        remaining = (L - 1) - t0
        topg, ids = jax.lax.top_k(st["best_gain"], W)
        w_ar = jnp.arange(W, dtype=jnp.int32)
        valid_w = (topg > 0) & (w_ar < remaining)
        ids_leaf = jnp.where(valid_w, ids, L)
        t_j = t0 + w_ar
        ids_rec = jnp.where(valid_w, t_j, L - 1)
        new_ids = t_j + 1
        new_leaf = jnp.where(valid_w, new_ids, L)
        live = jnp.sum(valid_w.astype(jnp.int32))

        feat_w = st["best_feature"][ids]
        thr_w = st["best_threshold"][ids]
        dl_w = st["best_default_left"][ids]
        cat_w = st["best_is_cat"][ids]
        mask_w = st["best_left_mask"][ids]
        lstat_w = st["best_left_stats"][ids]
        pstat_w = st["leaf_stats"][ids]
        rstat_w = pstat_w - lstat_w
        small_left_w = lstat_w[:, 2] <= rstat_w[:, 2]

        li = st["leaf_idx"]
        if routed_coarse_ok:
            # routing + smaller-child coarse histograms in ONE pass;
            # the kernel also emits the updated leaf vector, which the
            # windowed passes route from directly
            tbl = lane_tables(ids_leaf, feat_w, thr_w, new_ids,
                              small_left_w, dl_w)
            hist_small_c, leaf_idx, _ = routed_call(
                li, tbl, Bc_c2f, c2f_shift, "small")
        else:
            # gather-free routing (route_wave); the c2f gate guarantees
            # numerical-only splits, so goes-left is a threshold compare
            w_row, in_wave, goes_left, (small_left_row, new_id_row) = \
                route_wave(li, ids_leaf, feat_w, thr_w, mask_w,
                           extras=(small_left_w, new_ids))
            to_small = goes_left == small_left_row
            sel_small = jnp.where(in_wave & to_small, w_row,
                                  jnp.int32(-1))
            hist_small_c = multi_hist_coarse(sel_small)  # (W, F, Bc, 3)
            leaf_idx = jnp.where(in_wave & ~goes_left, new_id_row, li)

        # coarse subtraction trick against the coarse pool
        hist_large_c = st["hist_c"][ids] - hist_small_c
        sl4 = small_left_w[:, None, None, None]
        hist_l_c = jnp.where(sl4, hist_small_c, hist_large_c)
        hist_r_c = jnp.where(sl4, hist_large_c, hist_small_c)

        # children INTERLEAVED [l0, r0, l1, r1, ...]: live lanes are a
        # top_k prefix, so live children form a prefix too and the
        # second windowed group is skippable when <= W_spec/2 lanes
        # are live (every ramp wave)
        ch_ids = jnp.stack([ids_leaf, new_leaf], 1).reshape(W2)
        ch_hist_c = jnp.stack([hist_l_c, hist_r_c], 1).reshape(
            (W2,) + hist_l_c.shape[1:])
        ch_stats = jnp.stack([lstat_w, rstat_w], 1).reshape(W2, 3)
        depth_w = st["leaf_depth"][ids] + 1
        ch_depth = jnp.stack([depth_w, depth_w], 1).reshape(W2)
        if has_mono:
            l_min, l_max, r_min, r_max = child_bounds(
                lstat_w, rstat_w, st["leaf_min"][ids],
                st["leaf_max"][ids], feat_w, cat_w)
            ch_mn = jnp.stack([l_min, r_min], 1).reshape(W2)
            ch_mx = jnp.stack([l_max, r_max], 1).reshape(W2)
            win_lo = jax.vmap(c2f_window)(ch_hist_c, ch_stats,
                                          ch_mn, ch_mx)
        else:
            win_lo = jax.vmap(
                lambda c, s: c2f_window(c, s, None, None))(
                    ch_hist_c, ch_stats)         # (2W, F)

        # windowed refine: groups of W_spec children, leaf-vector
        # routed (no (N,) selector intermediate); group 2 runs under
        # lax.cond only when needed
        winh1 = multi_hist_win_lanes(leaf_idx, ch_ids[:W_spec],
                                     win_lo[:W_spec])
        if W2 > W_spec:
            need2 = 2 * live > W_spec
            winh2 = jax.lax.cond(
                need2,
                lambda: multi_hist_win_lanes(leaf_idx, ch_ids[W_spec:],
                                             win_lo[W_spec:]),
                lambda: jnp.zeros((W_spec, F_hist, R_c2f, 3),
                                  jnp.float32))
            winh = jnp.concatenate([winh1, winh2])[:W2]
            extra_passes = need2.astype(jnp.int32)
        else:
            winh = winh1[:W2]
            extra_passes = jnp.int32(0)

        if has_mono:
            bests = jax.vmap(c2f_best)(ch_hist_c, winh, win_lo,
                                       ch_stats, ch_mn, ch_mx)
        else:
            bests = jax.vmap(
                lambda c, wh, lo, s: c2f_best(c, wh, lo, s, None, None))(
                    ch_hist_c, winh, win_lo, ch_stats)
        allowed = (p.max_depth <= 0) | (ch_depth < p.max_depth)
        bests["gain"] = jnp.where(allowed, bests["gain"], NEG_INF)
        # same materialization fence as wave_body
        bests = jax.lax.optimization_barrier(bests)
        st = dict(st)
        st["leaf_idx"] = leaf_idx
        st["hist_c"] = st["hist_c"].at[ch_ids].set(ch_hist_c,
                                                   mode="drop")
        mono_vals = (ch_mn, ch_mx, l_min, l_max, r_min, r_max) \
            if has_mono else None
        recs = (("rec_leaf", ids), ("rec_feature", feat_w),
                ("rec_threshold", thr_w), ("rec_default_left", dl_w),
                ("rec_is_cat", cat_w), ("rec_gain", topg),
                ("rec_left_stats", lstat_w),
                ("rec_right_stats", rstat_w),
                ("rec_left_mask", mask_w), ("rec_valid", valid_w))
        st = commit_wave(st, ids_leaf, new_leaf, ids_rec, bests,
                         ch_stats, ch_depth, recs, valid_w, mono_vals,
                         ch_ids=ch_ids)
        # coarse (counted by commit) + 1-2 windowed refine passes
        st["n_arm_passes"] = st["n_arm_passes"] + 1 + extra_passes
        return st

    if use_wave:
        state = jax.lax.while_loop(
            wave_cond, wave_body_c2f if use_c2f else wave_body, state)
    else:
        state = jax.lax.fori_loop(0, L - 1, body, state)

    leaf_values = leaf_output(state["leaf_stats"][:, 0],
                              state["leaf_stats"][:, 1],
                              sp.lambda_l1, sp.lambda_l2,
                              sp.max_delta_step)
    if has_mono:
        leaf_values = jnp.clip(leaf_values, state["leaf_min"],
                               state["leaf_max"])
    # score-ready values: what the host-side tree will predict after
    # renewal + the no-split gate — lets the driver update the training
    # score WITHOUT waiting for the host materialization (pipelined
    # boosting).  Mirrors gbdt._records_to_tree exactly: quantized mode
    # renews from the full-precision sums; an unsplit tree contributes
    # nothing.
    leaf_values_final = leaf_values
    extra = {}
    if has_mono:
        extra = {k: state[k] for k in
                 ("rec_left_min", "rec_left_max",
                  "rec_right_min", "rec_right_max")}
    if do_spec:
        for k in GROW_COUNTERS:
            extra[k] = state[k]
    if p.quantize:
        # leaf-output renewal from FULL-PRECISION gradient sums — the
        # quantized-training leaf refit (RenewIntGradTreeOutput,
        # src/treelearner/gradient_discretizer.cpp): leaf sums of the
        # pre-quantization grad/hess keyed by the final leaf assignment
        from .histogram import histogram, leaf_stats_pallas
        if p.hist_impl == "pallas" and L <= 256:
            # dedicated leaf-stats kernel: reads ONLY the already-
            # resident arrays (leaf vector + raw grad/hess/mask, mask
            # applied in-kernel) — no (N, 3) value stack, no nibble-
            # split bins, no int32 selector intermediates (~10 ms
            # saved per tree at bench shape)
            ex = leaf_stats_pallas(state["leaf_idx"], grad_raw,
                                   hess_raw, sample_mask,
                                   p.rows_per_block)[None, :L]
        else:
            ex_vals = jnp.stack([g_w, h_w, sample_mask], axis=-1)
            ex = histogram(state["leaf_idx"][None, :], ex_vals,
                           max_bin=L, impl=p.hist_impl,
                           rows_per_block=p.rows_per_block)
        if row_par:
            ex = jax.lax.psum(ex, ax)
        extra["leaf_stats_exact"] = ex[0, :L]
        leaf_values_final = jnp.where(
            ex[0, :L, 2] > 0,
            leaf_output(ex[0, :L, 0], ex[0, :L, 1], sp.lambda_l1,
                        sp.lambda_l2, sp.max_delta_step),
            leaf_values_final)
    return {
        **extra,
        "leaf": state["rec_leaf"],
        "feature": state["rec_feature"],
        "threshold": state["rec_threshold"],
        "default_left": state["rec_default_left"],
        "is_cat": state["rec_is_cat"],
        "gain": state["rec_gain"],
        "left_stats": state["rec_left_stats"],
        "right_stats": state["rec_right_stats"],
        "left_mask": state["rec_left_mask"],
        "valid": state["rec_valid"],
        "leaf_idx": state["leaf_idx"],
        "leaf_values": leaf_values,
        "leaf_values_final": jnp.where(state["n_leaves"] > 1,
                                       leaf_values_final, 0.0),
        "leaf_stats": state["leaf_stats"],
        "n_leaves": state["n_leaves"],
    }


# The standalone jitted entry point.  ``build_tree_impl`` stays
# exported UNJITTED so the fused training super-step
# (models/gbdt.py:_train_superstep) can capture it inside a
# ``lax.scan`` body — the whole K-iteration block then compiles as ONE
# program instead of K dispatches of this one.  The implementation is
# already scan-compatible by construction: static trip counts
# (fori/while with traced state), no data-dependent Python, and a flat
# record-of-splits output that lax.scan stacks into (K, ...) arrays.
build_tree = functools.partial(jax.jit, static_argnames=("params",))(
    build_tree_impl)


@functools.partial(jax.jit, static_argnames=("num_leaves",))
def route_rows(xt: jax.Array, rec_leaf: jax.Array, rec_feature: jax.Array,
               rec_left_mask: jax.Array, rec_valid: jax.Array,
               num_leaves: int, bundle_maps=None) -> jax.Array:
    """Replay a tree's split records over a binned matrix.

    Routes every row of ``xt`` (F, N binned ints) through the splits
    recorded by :func:`build_tree`, producing the (N,) leaf assignment.
    This is the device-side scorer for binned validation sets — the
    TPU-first replacement for the reference's per-row tree traversal in
    ``ScoreUpdater::AddScore`` (``score_updater.hpp:17``): one gather
    per split instead of a host walk per row.

    With ``bundle_maps`` (EFB), xt is the (G, N) bundle matrix and the
    per-feature bin masks are translated onto bundle bins.
    """
    N = xt.shape[1]
    leaf_idx = jnp.zeros(N, dtype=jnp.int32)
    bundled = bundle_maps is not None
    if bundled:
        bm_group, _, bm_from, _ = bundle_maps

    def body(t, li):
        feat = rec_feature[t]
        mask_row = rec_left_mask[t]
        if bundled:
            g = jax.lax.dynamic_index_in_dim(bm_group, feat,
                                             keepdims=False)
            fb = jax.lax.dynamic_index_in_dim(bm_from, feat, axis=0,
                                              keepdims=False)
            col = jax.lax.dynamic_index_in_dim(xt, g, axis=0,
                                               keepdims=False)
            mask_row = jnp.take(mask_row, fb)
        else:
            col = jax.lax.dynamic_index_in_dim(xt, feat, axis=0,
                                               keepdims=False)
        goes_left = mask_lookup(mask_row, col)
        mine = li == rec_leaf[t]
        move = rec_valid[t] & mine & ~goes_left
        return jnp.where(move, jnp.int32(t + 1), li)

    return jax.lax.fori_loop(0, num_leaves - 1, body, leaf_idx)
