"""The growth-tier plan: which tier and which kernels a booster's trees
are built on, decided once, each decision together with its reason.

:func:`plan_tier` is a pure function of a :class:`Config` and the
:class:`TierFacts` the booster worked out about its data and devices
(plain Python values: no ``GBDT``, no array, no device call).  It
returns the one ``GrowParams`` of the library and the tier record
(``GBDT.tier_decision``: telemetry's ``run_start``, the benchmark's
``tier record:`` line and its ``expect_tier`` check).  Every gate is a
ladder that returns the first reason that refuses the tier, or None;
the tier is on where its reason is None, so the choice and its
explanation cannot drift apart.  What ``build_tree`` itself decides
from ``GrowParams`` (routed feasibility) is asked of ``ops/grow.py``'s
own predicate, never re-derived here.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

from ..config import Config
from ..ops.grow import (DistConfig, GrowParams, batched_width, c2f_bins,
                        route_kind, routed_gate)
from ..ops.histogram import _pad_bins, bin_tiling, multi_width
from ..ops.split import SplitParams
from ..utils.log import Log


class TierFacts(NamedTuple):
    """What ``GBDT.__init__`` knows before the tier is chosen."""
    use_pallas: bool        # the histogram passes run as Pallas kernels
    learner: str            # tree learner in force ("serial" on 1 device)
    num_shards: int
    mesh_shape2d: Optional[Tuple[int, int]]     # data2d's (rows, features)
    features: int           # used features F
    g_cols: int             # stored columns: bundles, or F padded to shards
    max_bin: int            # device bin width
    any_cat: bool
    any_missing: bool
    efb_groups: int         # EFB bundles (0: not bundled)
    forced: tuple           # flattened forced splits
    use_pool: bool          # the histogram pool fits its budget
    rows_per_block: int
    monotone: tuple
    penalty: tuple
    # why the objective's per-row tensors cannot live on the shard
    # (``Objective.shard_refusal``), or None: it is pointwise and hands
    # its row tensors over as arguments
    objective_rows: Optional[str] = None
    # how a pairwise objective lays out its pairs (``Objective.layout``:
    # lambdarank's ``buckets``), or None
    rank_layout: Optional[str] = None

    @property
    def dist_active(self) -> bool:
        return self.learner not in ("serial", "") and self.num_shards > 1

    @property
    def kind(self) -> str:
        """The learner kind ``build_tree`` runs under (DistConfig.kind)."""
        return self.learner if self.dist_active else "serial"

    @property
    def bundled(self) -> bool:
        return self.efb_groups > 0

    @property
    def local_cols(self) -> int:
        """Stored columns one device holds: the feature-sharded
        learners split them, ``build_tree`` sees its shard's."""
        if self.dist_active and self.learner in ("feature", "data2d"):
            return self.g_cols // (self.mesh_shape2d[1]
                                   if self.mesh_shape2d
                                   else self.num_shards)
        return self.g_cols


class TierPlan(NamedTuple):
    grow_params: GrowParams
    record: dict


def _wave_gate(config: Config, facts: TierFacts) -> Optional[str]:
    # wave growth composes with the 1-D parallel learners the way the
    # reference's GPU learner composes by template parameter
    # (data_parallel_tree_learner.cpp:258-259, tree_learner.cpp:9-33):
    # data psums whole-wave histograms, feature merges children bests
    # by a batched all-gather arg-max, voting psums only the elected
    # features' histograms (ops/grow.py).  data2d runs the non-wave
    # loop: its per-axis collective schedule (row-axis hist psum,
    # feature-axis merge) is defined on the per-leaf passes, and the
    # wave path's whole-tensor psum would forfeit the O(1/F_axis)
    # histogram-byte cut
    if not config.wave_splits:
        return "wave_splits=false"
    if facts.learner == "data2d":
        return "data2d runs the non-wave per-axis collective schedule"
    if not facts.use_pool:
        return "histogram pool over budget (histogram_pool_size)"
    if facts.forced:
        return "forced splits"
    return None


def _two_col_gate(config: Config, facts: TierFacts,
                  wave_on: bool) -> Optional[str]:
    # two-column quantized passes (W=64): legal only when the count
    # channel is provably redundant (GrowParams.two_col contract).
    # With missing values the default-direction "any missing data
    # here?" test reads the hess-copy channel instead of a count — a
    # row whose quantized hess rounds to 0 is then treated as absent
    # for direction choice only (both directions tie in gain in that
    # case; quality is pinned by the NaN-injection oracle test).
    # Categorical features still gate it off: their scans read REAL
    # counts (cnt_ok, min_data_per_group)
    if not config.use_quantized_grad:
        return "use_quantized_grad=false"
    if not wave_on:
        return "wave growth off"
    if facts.bundled:
        return "EFB bundles active (FixHistogram reads counts)"
    if facts.any_cat:
        return ("categorical scans read real counts "
                "(cnt_ok, min_data_per_group)")
    if config.min_data_in_leaf > 1:
        return "min_data_in_leaf > 1 needs counts"
    if not (config.min_sum_hessian_in_leaf > 0):
        return "min_sum_hessian_in_leaf <= 0"
    return None


def _c2f_gate(config: Config, facts: TierFacts,
              wave_on: bool) -> Optional[str]:
    # coarse-to-fine refinement (hist_refinement): wave passes stream
    # Bc + R one-hot rows instead of the full padded bin count;
    # exactness caveat documented at GrowParams.refine_shift.  c2f
    # pays a pass's fixed cost (one read of the matrix, routing, the
    # selectors, the right-hand side) twice a wave, so it wins only
    # where the one-hot stream, ∝ F x padded(B), dominates that cost:
    # hence a stream-size gate rather than a pure bin-count one.  The
    # constants 48 and 7000 predate the ledger; no cell has measured
    # them (ROADMAP, Design debts)
    if not config.hist_refinement:
        return "hist_refinement=false"
    if not wave_on:
        return "wave growth off"
    if facts.dist_active and facts.learner != "data":
        return f"tree_learner={facts.learner}"
    if facts.bundled:
        return "EFB bundles active"
    if facts.any_cat:
        return "categorical features"
    if facts.max_bin < 48:
        return f"max_bin={facts.max_bin} < 48"
    if facts.features * _pad_bins(facts.max_bin) < 7000:
        return ("stream below the per-pass fixed-cost "
                "break-even (features x bins < ~7000)")
    return None


def _split_gate(config: Config, facts: TierFacts,
                refine_shift: int) -> Optional[str]:
    # best-split engine (split_kernel=auto|pallas|xla): the Pallas
    # kernel scans histograms on-chip, eliminating the histogram→split
    # HBM round-trip.  Numerical serial configs only; every rejection
    # is recorded so a TPU run silently landing on the XLA scan is
    # triageable (tools/triage_run.py MED anomaly)
    split_req = str(config.split_kernel).lower() or "auto"
    if split_req not in ("auto", "pallas", "xla"):
        # an unrecognized value must NOT silently land on the
        # interpreter lane (pallas-on-cpu is orders of magnitude
        # slower than the XLA scan it would replace)
        Log.warning("unknown split_kernel=%r; using auto",
                    config.split_kernel)
        split_req = "auto"
    if split_req == "xla":
        return "split_kernel=xla"
    if facts.any_cat:
        return ("categorical scans (one-vs-other / sorted "
                "many-vs-many) read the XLA path")
    if facts.bundled:
        return "EFB bundles active (histogram expansion)"
    if facts.dist_active:
        return f"tree_learner={facts.learner}"
    if facts.forced:
        return "forced splits"
    if refine_shift:
        return "c2f refinement scans coarse+window (hist_refinement)"
    if split_req == "auto" and not facts.use_pallas:
        return ("cpu backend (split_kernel=pallas or "
                "LTPU_PALLAS_INTERPRET=1 runs the interpret lane)")
    # split_req "pallas" on a CPU backend is honored via the
    # interpret lane (ops/split.py pallas_interpret)
    return None


def _row_state_gate(config: Config, facts: TierFacts) -> Optional[str]:
    # where the per-row state of the boosting loop lives (the score
    # carry, labels and weights, gradients, the leaf index, the score
    # update).  On the shard: every device holds its own rows' state and
    # no array of the whole job's rows is an operand, a constant or a
    # result of its program; what crosses devices is each pass's
    # histogram psum, the quantization scale's pmax and scalars.  That
    # takes the row-sharded fused scan, an objective whose gradients
    # are elementwise over rows, and no sampling that reads the whole
    # job.  Everything else keeps the state replicated (the reference
    # keeps a rank's rows on the rank, data_parallel_tree_learner.cpp;
    # its other learners are not held to that here yet)
    if not facts.dist_active:
        return (f"tree_learner={facts.kind}: one device holds every "
                f"row")
    if facts.learner != "data":
        return (f"tree_learner={facts.learner} keeps the replicated "
                f"row state (only the data learner's fused scan is "
                f"held to the shard)")
    boosting = str(config.boosting).lower()
    if boosting in ("dart", "rf", "random_forest"):
        return f"boosting={boosting} runs the per-iteration loop"
    if config.fused_iters <= 1:
        return ("fused_iters <= 1: the per-iteration loops keep the "
                "replicated row state")
    if facts.objective_rows is not None:
        return facts.objective_rows
    if boosting == "goss":
        return ("GOSS ranks the whole job's gradients (the top-rate "
                "threshold is a statistic of every row)")
    if boosting == "mvs":
        return ("MVS thresholds the whole job's gradient norms (a "
                "statistic of every row)")
    if config.bagging_freq > 0 and (
            config.bagging_fraction < 1.0 or
            config.pos_bagging_fraction < 1.0 or
            config.neg_bagging_fraction < 1.0):
        return ("the bagging mask is one random stream drawn over the "
                "whole job's rows")
    if str(config.paged_training).lower() == "on" or \
            config.hbm_budget_mb > 0:
        return "paged training pages replicated row state"
    return None


def _passes(facts: TierFacts, gp: GrowParams) -> dict:
    """(bins, value columns) of each kind of histogram pass the booster
    runs.  c2f runs a coarse and a windowed refine pass (the root
    too); otherwise the batched full-resolution pass, and off the wave
    path the single-leaf pass ("root": the root and every leaf no
    batched pass armed)."""
    passes = {}
    if gp.refine_shift:
        coarse, window = c2f_bins(facts.max_bin, gp.refine_shift,
                                  facts.any_missing)
        passes["coarse"] = (coarse, 128)
        passes["refine"] = (window, 128)
    elif batched_width(gp, facts.kind) > 1:
        passes["full"] = (facts.max_bin, 128)
    if not gp.wave:
        passes["root"] = (facts.max_bin, 3 if gp.quantize else 6)
    return passes


def plan_tier(config: Config, facts: TierFacts) -> TierPlan:
    """Choose the growth tier and its kernels for ``config`` on
    ``facts``; returns the ``GrowParams`` and the tier record.  The
    record's ``mesh_shape`` is the planned one: ``GBDT.__init__``
    overwrites it with the built mesh's."""
    dist_active, learner = facts.dist_active, facts.learner
    why_wave = _wave_gate(config, facts)
    wave_on = why_wave is None
    why_two_col = _two_col_gate(config, facts, wave_on)
    two_col = why_two_col is None
    why_c2f = _c2f_gate(config, facts, wave_on)
    # missing values ride a RESERVED last coarse slot (grow.py Bc_c2f)
    # and a default-left row in the routed lane tables
    refine_shift = 0 if why_c2f else (4 if facts.max_bin > 64 else 3)
    why_split = _split_gate(config, facts, refine_shift)
    split_kernel = "xla" if why_split else "pallas"
    # a parallel learner asked for on one device trains serial (the
    # driver warns); the record carries both so a smoke can assert
    requested = config.tree_learner or "serial"
    why_learner = None
    if requested != "serial" and not dist_active:
        why_learner = (f"tree_learner={requested} needs more than one "
                       f"device; found {facts.num_shards}")

    grow_params = GrowParams(
        split=SplitParams(
            max_bin=facts.max_bin,
            lambda_l1=config.lambda_l1,
            lambda_l2=config.lambda_l2,
            min_data_in_leaf=config.min_data_in_leaf,
            min_sum_hessian_in_leaf=config.min_sum_hessian_in_leaf,
            min_gain_to_split=config.min_gain_to_split,
            max_delta_step=config.max_delta_step,
            max_cat_to_onehot=config.max_cat_to_onehot,
            max_cat_threshold=config.max_cat_threshold,
            cat_l2=config.cat_l2,
            cat_smooth=config.cat_smooth,
            min_data_per_group=config.min_data_per_group,
            monotone=facts.monotone,
            penalty=facts.penalty,
            # static dataset facts: trace-time dead-branch removal in
            # the split scan (no cat -> no bin sorts, no missing ->
            # one threshold direction)
            any_cat=facts.any_cat,
            any_missing=facts.any_missing,
            counts_proxy=two_col),
        num_leaves=config.num_leaves,
        max_depth=config.max_depth,
        hist_impl="pallas" if facts.use_pallas else "segsum",
        rows_per_block=facts.rows_per_block,
        dist=DistConfig(top_k=config.top_k),
        forced=facts.forced,
        bundled=facts.bundled,
        use_hist_pool=facts.use_pool,
        # quantized-gradient histograms: small ints are exact in bf16,
        # halving the value columns; serial learner, or any parallel
        # learner under wave growth (shard-consistent scale via pmax;
        # noise hashed from global row index)
        quantize=(config.num_grad_quant_bins
                  if (config.use_quantized_grad and
                      (not dist_active or wave_on or learner == "data2d"))
                  else 0),
        spec_tolerance=float(config.speculative_tolerance),
        # wave growth (wave_splits): top-W splits applied per loop step
        # from one batched pass; rides the speculative kernel
        wave=wave_on,
        two_col=two_col,
        refine_shift=refine_shift,
        split_kernel=split_kernel,
        # speculative child arming fills the MXU lanes (21 leaves x 6
        # value columns, 42 x 3 quantized, 64 x 2 two-column); enabled
        # on the accelerator path where the batched pallas kernel
        # exists, or anywhere when wave growth asks for it
        speculate=(min(multi_width(config.use_quantized_grad, two_col),
                       config.num_leaves)
                   if ((facts.use_pallas or config.wave_splits) and
                       (not dist_active or wave_on) and
                       facts.use_pool and not facts.forced)
                   else 0))

    why_rows = _row_state_gate(config, facts)

    passes = _passes(facts, grow_params)
    # build_tree's own answer, for the batched pass it would route in
    # (the coarse one under c2f) over the columns one device holds
    routed_pass = (grow_params, facts.kind,
                   passes.get("coarse", (facts.max_bin,))[0],
                   facts.local_cols)
    why_routed = routed_gate(*routed_pass)
    # how each pass tiles the stored bin matrix (ops/histogram.py
    # BinTiling).  The batched passes contract in int8 where their
    # values are int8; the single-leaf pass ("root") takes float32
    hist_tiling = {} if not facts.use_pallas else {
        kind: bin_tiling(bins, facts.local_cols, cols,
                         facts.rows_per_block).record(
                             int8=grow_params.int8_values and kind != "root")
        for kind, (bins, cols) in passes.items()}

    if two_col:
        tier = "two_col"
    elif wave_on:
        tier = "wave_quant" if grow_params.quantize else "wave"
    elif grow_params.speculate:
        tier = "speculative"
    else:
        tier = "exact"
    gates = {name: why for name, why in (
        ("two_col", why_two_col), ("wave", why_wave), ("c2f", why_c2f),
        ("routed", why_routed), ("split", why_split),
        ("learner", why_learner), ("row_state", why_rows))
        if why is not None}
    record = {
        "tier": tier,
        "gates": gates,
        "split_kernel": split_kernel,
        "routed": why_routed is None,
        # where a wave's rows are routed: inside the routed pass
        # (``kernel``), by the routing step, whose kernel fetches the
        # lanes' split columns itself, ahead of a pass in several
        # feature chunks (``gather``), or by XLA's select chain
        # (``xla``)
        "route": route_kind(*routed_pass),
        "c2f": bool(refine_shift),
        "refine_shift": refine_shift,
        "quantize": grow_params.quantize,
        "speculate": grow_params.speculate,
        "wave": wave_on,
        "hist_impl": grow_params.hist_impl,
        "hist_tiling": hist_tiling,
        "use_hist_pool": facts.use_pool,
        "efb_groups": facts.efb_groups,
        "learner": facts.kind,
        "learner_requested": requested,
        "num_shards": facts.num_shards if dist_active else 1,
        "mesh_shape": (list(facts.mesh_shape2d or (facts.num_shards,))
                       if dist_active else [1]),
        "row_state": "replicated" if why_rows else "shard",
    }
    if facts.rank_layout is not None:
        record["rank_layout"] = facts.rank_layout
    return TierPlan(grow_params, record)
