"""Many-model battery training: B independent boosters in ONE
compiled program.

The fused super-step (``gbdt.py``) trains exactly one booster per
compiled scan.  The battery lifts that SAME scan over a leading model
axis with ``jax.vmap``: the shared binned matrix stays resident once,
per-model state (scores, bagging carries, learning rates, PRNG keys,
per-iteration feature masks, fold weights) is stacked on axis 0, and
one XLA program trains every member — k-fold CV and hyperparameter
sweeps stop paying B compiles and B dispatch streams for B models
(ROADMAP item 4; the same amortize-the-host-boundary move that made
single-model training fast).

Bit-exactness contract: every battery member's trees are byte-equal to
the same params trained solo (pinned by ``tests/test_sweep.py``).  The
anchors:

- ``_superstep_core(batched=True)`` is the solo scan body verbatim;
  per-model values enter as TRACED leading-axis operands while every
  program-shaping knob stays static, so vmap adds a batch dimension
  without touching the per-member expression tree.
- CV fold masks ride as the objective's per-row weight
  (``Objective.weight_override``), multiplying at exactly the point
  solo weighted training multiplies metadata weights.  Unweighted
  members ride a unit vector — ``x * 1.0`` is bitwise ``x``.
- PRNG independence: member ``i``'s bagging/GOSS/MVS stream is
  ``fold_in(PRNGKey(seed_i), global_iter)`` and its quantization
  stream ``fold_in(PRNGKey(qseed_i), tree_id)`` — a pure function of
  ITS seeds and the global counters, unchanged by B.
- Host feature-fraction draws replay each member's solo
  ``RandomState`` stream in iteration order.

Members whose resolved configs agree on everything but the traced
per-model values (learning rate, seeds, feature_fraction, weights)
share one compiled program; a sweep over those knobs costs ONE XLA
compile however many members it has.  Members the fused scan cannot
express (DART/RF, distributed learners, objectives with leaf-renewal
hooks or baked-in weights) fall back to per-member solo training —
same results, no shared compile.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from ..utils.log import Log
from .tree import Tree
from .gbdt import _KEPS, records_to_tree

__all__ = ["MemberSpec", "MemberResult", "BatteryReport",
           "train_battery", "objective_string", "member_model_string"]

# params that ride the batched program as TRACED per-model operands —
# members differing only in these share one compiled program.  Every
# other param shapes the program (tree topology, sampling structure,
# scan length, ...) and splits the battery into static groups.
TRACED_EXEMPT = frozenset({
    "learning_rate", "shrinkage_rate", "eta",
    "bagging_seed", "bagging_fraction_seed",
    "feature_fraction_seed",
    "data_random_seed",
    "feature_fraction", "sub_feature", "colsample_bytree",
})


@dataclasses.dataclass
class MemberSpec:
    """One battery member: a full param dict plus optional per-row
    training weights (CV fold masks — the COMPLETE effective weight,
    i.e. already multiplied with any dataset weight) and an optional
    boolean row mask scored for the eval curve."""
    params: Dict[str, Any]
    weight: Optional[np.ndarray] = None
    eval_mask: Optional[np.ndarray] = None
    tag: str = ""


@dataclasses.dataclass
class MemberResult:
    spec: MemberSpec
    trees: List[Tree] = dataclasses.field(default_factory=list)
    init_score: float = 0.0
    curve: Optional[List[float]] = None   # per-iteration eval metric
    stopped_at: Optional[int] = None      # iteration of the stop tree
    lane: str = "vmap"                    # vmap | solo
    failed: bool = False
    error: str = ""
    num_tree_per_iteration: int = 1
    average_output: bool = False  # RF: trees average instead of sum


@dataclasses.dataclass
class BatteryReport:
    results: List[MemberResult]
    groups: int = 0                 # static-signature groups (vmap lane)
    vmap_members: int = 0
    solo_members: int = 0
    xla_compiles: int = 0           # compile delta across vmap dispatches
    expected_compiles: int = 0      # == groups when nothing retraced
    duration_s: float = 0.0

    @property
    def retraces_per_model(self) -> float:
        if self.vmap_members <= 0:
            return 0.0
        return max(0, self.xla_compiles - self.expected_compiles) \
            / float(self.vmap_members)


def objective_string(config) -> str:
    """Model-file objective line for a config — mirrors
    ``basic.Booster._objective_string`` so battery exports are
    byte-equal to solo booster exports."""
    obj = config.objective
    if obj in ("none", "custom", "null", "na"):
        return ""
    if obj == "binary":
        return f"binary sigmoid:{config.sigmoid:g}"
    if obj in ("multiclass", "multiclassova"):
        return f"{obj} num_class:{config.num_class}"
    if obj == "lambdarank":
        return "lambdarank"
    return obj


def member_model_string(result: MemberResult, config, train_set,
                        num_iteration: int = -1) -> str:
    """Serialize one member's trees exactly as
    ``Booster.model_to_string`` would (same header fields, same
    truncation semantics) — the export path for sweep winners."""
    from . import model_io
    return model_io.save_model_to_string(
        result.trees, num_class=int(getattr(config, "num_class", 1) or 1),
        num_tree_per_iteration=result.num_tree_per_iteration,
        label_index=0,
        max_feature_idx=train_set.num_total_features - 1,
        objective_str=objective_string(config),
        feature_names=train_set.feature_names,
        feature_infos=train_set.feature_infos(),
        num_iteration=num_iteration, parameters="",
        average_output=result.average_output)


# ----------------------------------------------------------------------
def _group_key(spec: MemberSpec):
    return tuple(sorted((k, repr(v)) for k, v in spec.params.items()
                        if k not in TRACED_EXEMPT))


class _MetaView:
    """Metadata facade with an overridden weight — what a per-member
    objective instance init()s against so its host-side
    ``boost_from_score`` sees exactly the weights the solo reference
    (dataset weight = fold mask) would."""

    def __init__(self, md, weight):
        self.num_data = md.num_data
        self.label = md.label
        self.weight = weight
        self.query_boundaries = md.query_boundaries
        self.init_score = md.init_score


def _vmap_lane_ok(gbdt) -> Optional[str]:
    """None when the fused scan can express this member's whole
    training run; otherwise the gate that rejected it (the solo
    fallback reason)."""
    from ..objectives import Objective
    if not getattr(gbdt, "_superstep_enabled", False):
        return "boosting mode opts out of the fused scan"
    if gbdt.num_tree_per_iteration != 1:
        return "multiclass trains k trees per iteration"
    if gbdt.objective is None:
        return "custom objective supplies gradients"
    if gbdt.num_features == 0:
        return "no usable features"
    if type(gbdt.objective).renew_tree_output is not \
            Objective.renew_tree_output:
        return "objective renews leaf outputs on host"
    if gbdt.objective.gradient_fn() is None:
        return "objective opted out of the pure gradient contract"
    if gbdt._dist is not None:
        return "distributed tree learner owns the mesh"
    if not gbdt.objective.supports_weight_override:
        return "objective bakes weights in at init"
    if gbdt.grow_params.split.has_monotone:
        # the monotone gain recompute reassociates under a batch axis
        # (cancellation-amplified ULP drift in recorded split gains)
        return "monotone gain recompute is not bit-stable under vmap"
    return None


def _feature_masks(gbdt, config, T: int) -> np.ndarray:
    """Replay one member's host feature-fraction stream: T draws in
    iteration order from the member's own RandomState — exactly the
    solo ``_feature_fraction_mask`` consumption."""
    rng = np.random.RandomState(config.feature_fraction_seed & 0x7FFFFFFF)
    F, F_pad = gbdt.num_features, gbdt._F_pad
    frac = config.feature_fraction
    masks = np.zeros((T, F_pad), bool)
    for t in range(T):
        if frac >= 1.0:
            masks[t, :F] = True
        else:
            k = max(1, int(frac * F))
            masks[t, rng.choice(F, size=k, replace=False)] = True
    return masks


def _model_mesh(B: int):
    """A 1-D mesh over ALL devices for the model axis, or None when it
    cannot tile B members evenly (the vmap lane then runs unsharded on
    one device — never a silent wrong answer, members are
    independent)."""
    import jax
    devs = jax.devices()
    if len(devs) <= 1 or B % len(devs) != 0:
        return None
    from jax.sharding import Mesh
    return Mesh(np.asarray(devs), ("battery",))


def _train_group_vmapped(dataset, specs: Sequence[MemberSpec],
                         results: Dict[int, MemberResult],
                         indices: Sequence[int],
                         metric: Optional[Callable],
                         shard_models: bool,
                         compile_counter: List[int]) -> None:
    """Train one static-signature group of B members through a single
    vmapped (optionally model-sharded) dispatch of the fused scan."""
    import jax
    import jax.numpy as jnp
    from ..basic import Booster
    from ..config import Config
    from ..objectives import create_objective
    from ..utils import telemetry as _telemetry

    template = Booster(params=dict(specs[0].params), train_set=dataset)
    gbdt = template._gbdt
    tds = dataset._constructed
    md = tds.metadata
    B = len(specs)
    n = gbdt.num_data
    cfgs = [gbdt.config] + [Config(dict(s.params)) for s in specs[1:]]
    T = int(gbdt.config.num_iterations)
    quantize = bool(gbdt.grow_params.quantize)

    # ---- per-member stacks -------------------------------------------
    base_score = np.asarray(gbdt._score)          # (k, n) f32: 0 + init
    score0 = np.repeat(base_score[None], B, axis=0)
    inits = np.zeros(B, np.float64)
    wvec = np.ones((B, n), np.float32)
    lr = np.zeros(B, np.float32)
    fmasks = np.zeros((B, T, gbdt._F_pad), bool)
    bag_keys = np.zeros((B, 2), np.uint32)
    quant_keys = np.zeros((B, 2), np.uint32)
    qk0 = np.asarray(jax.random.PRNGKey(0))
    for b, (spec, cfg) in enumerate(zip(specs, cfgs)):
        lr[b] = np.float32(cfg.learning_rate)
        fmasks[b] = _feature_masks(gbdt, cfg, T)
        bag_keys[b] = np.asarray(
            jax.random.PRNGKey(cfg.bagging_seed & 0x7FFFFFFF))
        quant_keys[b] = (np.asarray(jax.random.PRNGKey(
            cfg.data_random_seed & 0x7FFFFFFF)) if quantize else qk0)
        if spec.weight is not None:
            wvec[b] = np.asarray(spec.weight, np.float32).reshape(-1)
        elif md.weight is not None:
            wvec[b] = np.asarray(md.weight, np.float32).reshape(-1)
        # boost_from_average: solo runs iteration 0 unfused with the
        # bias pre-added to the score and absorbed by tree 0; the
        # battery pre-adds it on host (f32 add — same IEEE op as the
        # device .add) and absorbs it at materialization
        if (cfg.boost_from_average and md.init_score is None and
                gbdt.num_features > 0):
            w_view = (np.asarray(spec.weight, np.float32).reshape(-1)
                      if spec.weight is not None else md.weight)
            obj_b = create_objective(cfg.objective, cfg)
            obj_b.init(_MetaView(md, w_view), n)
            init = float(obj_b.boost_from_score(0))
            if abs(init) > _KEPS:
                inits[b] = init
                score0[b, 0, :] += np.float32(init)

    iters = jnp.arange(0, T, dtype=jnp.int32)
    tree_ids = jnp.arange(0, T, dtype=jnp.int32)
    bag0 = jnp.ones((B, n), jnp.float32)

    # ---- one compiled program for the whole group --------------------
    core = gbdt._superstep_core(batched=True)
    fn = jax.vmap(core, in_axes=(0, 0, 0, 0, None, None, None, None,
                                 None, None, 0, None, 0, 0))
    mesh = _model_mesh(B) if shard_models else None
    if mesh is not None:
        # model-axis sharding: members are embarrassingly parallel, so
        # every per-member operand splits on its leading axis and the
        # shared dataset replicates — no collectives, hence the exact
        # same per-member program (parity preserved by construction)
        from jax.sharding import PartitionSpec as P
        Pb, R = P("battery"), P()
        in_specs = (Pb, Pb, Pb, Pb, R, R, R, R, R, R, Pb, R, Pb, Pb)
        fn = jax.shard_map(
            fn, mesh=mesh, check_vma=False, in_specs=in_specs,
            out_specs=(Pb, Pb, Pb, Pb, Pb, Pb, Pb))
    fn = jax.jit(fn)

    args = (jnp.asarray(score0), bag0, jnp.asarray(lr),
            jnp.asarray(quant_keys),
            gbdt._xt, gbdt._base_mask, gbdt._num_bins,
            gbdt._missing_type, gbdt._is_cat, iters,
            jnp.asarray(fmasks), tree_ids, jnp.asarray(wvec),
            jnp.asarray(bag_keys))
    if mesh is not None:
        # pre-place operands on the mesh so the one-time input layout
        # (split / replicate) transfer programs compile OUTSIDE the
        # retrace bracket below — they are per-shape data movement, not
        # retraces of the member program
        from jax.sharding import NamedSharding
        args = tuple(jax.device_put(a, NamedSharding(mesh, s))
                     for a, s in zip(args, in_specs))
        jax.block_until_ready(args)
    _telemetry.install_jax_hooks()
    pre = _telemetry.counters.snapshot().get("xla_compiles", 0)
    outs = fn(*args)
    jax.block_until_ready(outs[2])
    post = _telemetry.counters.snapshot().get("xla_compiles", 0)
    compile_counter[0] += int(post - pre)
    _telemetry.counters.incr("battery_dispatches")

    # ---- one packed fetch, then per-member host materialization ------
    host = gbdt._fetch_records(outs[4])            # (B, K, ...) stacks
    leaf_idx_k = np.asarray(outs[5])               # (B, K, n) narrow
    vals_k = np.asarray(outs[6])                   # (B, K, num_leaves)
    bad = np.asarray(host.pop("nonfinite", np.zeros((B, T))), bool)
    n_leaves = np.asarray(host["n_leaves"])

    for b, (spec, cfg) in enumerate(zip(specs, cfgs)):
        res = results[indices[b]]
        res.lane = "vmap"
        res.init_score = float(inits[b])
        rows = (np.nonzero(np.asarray(spec.eval_mask).reshape(-1))[0]
                if spec.eval_mask is not None else None)
        sc = score0[b, 0, rows].copy() if rows is not None else None
        curve: List[float] = []
        trees: List[Tree] = []
        for t in range(T):
            stop = int(n_leaves[b, t]) <= 1
            if bad[b, t] and not stop:
                res.failed = True
                res.error = (f"non-finite values at iteration {t} "
                             f"(member {spec.tag or b})")
                Log.warning("battery member %s: %s", spec.tag or b,
                            res.error)
                break
            if stop:
                # constant stop tree; post-stop scan iterations are
                # phantom state the replay discards (solo semantics)
                tree = Tree(2)
                if t == 0 and abs(inits[b]) > _KEPS:
                    tree.leaf_value[0] = inits[b]
                trees.append(tree)
                res.stopped_at = t
                break
            rec_t = {k: v[b, t] for k, v in host.items()}
            tree = records_to_tree(rec_t, cfg, tds,
                                   counts_proxy=getattr(
                                       gbdt, "_counts_proxy", False))
            # host shrinkage uses the config's exact f64 rate (the
            # device scan got the f32 cast) — solo does the same
            tree.apply_shrinkage(float(cfg.learning_rate))
            if t == 0 and abs(inits[b]) > _KEPS:
                tree.add_bias(inits[b])
            trees.append(tree)
            if rows is not None:
                # f32 adds per row in scan order — bit-equal to the
                # device score carry, so the CV curve scores exactly
                # the model the member trained
                sc += vals_k[b, t][leaf_idx_k[b, t][rows].astype(
                    np.int64)]
                if metric is not None:
                    curve.append(float(metric(sc, rows)))
        res.trees = trees
        res.curve = curve if rows is not None else None
        res.num_tree_per_iteration = gbdt.num_tree_per_iteration


def _train_member_solo(dataset, spec: MemberSpec, res: MemberResult,
                       metric: Optional[Callable], reason: str) -> None:
    """Fallback lane: solo-train one member on the SHARED dataset with
    its weights swapped in (and restored) — identical results to the
    vmap lane's contract, without the shared compile."""
    from ..basic import Booster

    tds = dataset._constructed
    md = tds.metadata if tds is not None else None
    saved_ds_w, saved_md_w = dataset.weight, (md.weight if md else None)
    try:
        if spec.weight is not None:
            w = np.asarray(spec.weight, np.float32).reshape(-1)
            dataset.weight = w
            if md is not None:
                md.weight = w
        bst = Booster(params=dict(spec.params), train_set=dataset)
        g = bst._gbdt
        T = int(g.config.num_iterations)
        rows = (np.nonzero(np.asarray(spec.eval_mask).reshape(-1))[0]
                if spec.eval_mask is not None else None)
        curve: List[float] = []
        for it in range(T):
            stop = bst.update()
            if rows is not None and metric is not None and not stop:
                sc = np.asarray(g._score)[0, rows]
                curve.append(float(metric(sc, rows)))
            if stop:
                res.stopped_at = it
                break
        res.trees = list(g.models)
        res.curve = curve if rows is not None else None
        res.lane = "solo"
        res.error = reason
        res.num_tree_per_iteration = g.num_tree_per_iteration
        res.average_output = bool(g.average_output)
    except Exception as exc:  # noqa: BLE001 - one member, not the sweep
        res.failed = True
        res.lane = "solo"
        res.error = f"{reason}; solo fallback raised: {exc}"
        Log.warning("battery member %s failed: %s", spec.tag, res.error)
    finally:
        dataset.weight = saved_ds_w
        if md is not None:
            md.weight = saved_md_w


def train_battery(dataset, specs: Sequence[MemberSpec], *,
                  metric: Optional[Callable] = None,
                  shard_models: bool = False) -> BatteryReport:
    """Train every member spec against one shared constructed dataset.

    ``metric``: optional ``(scores_f32, row_indices) -> float`` scored
    per iteration on each member's ``eval_mask`` rows (the CV curve).
    ``shard_models``: lay the model axis onto the device mesh when it
    tiles evenly (``sweep_shard_models``).

    Members are grouped by static signature; each group dispatches as
    ONE compiled vmapped program.  Ineligible members run the solo
    fallback lane.  Returns per-member trees/curves plus the compile
    accounting the ``sweep`` telemetry record reports."""
    from ..basic import Booster

    t0 = time.perf_counter()
    dataset.construct()
    results = {i: MemberResult(spec=s) for i, s in enumerate(specs)}
    groups: Dict[Any, List[int]] = {}
    for i, s in enumerate(specs):
        groups.setdefault(_group_key(s), []).append(i)

    compile_counter = [0]
    n_vmap_groups = 0
    vmap_members = solo_members = 0
    for key, idxs in groups.items():
        probe = Booster(params=dict(specs[idxs[0]].params),
                        train_set=dataset)
        reason = _vmap_lane_ok(probe._gbdt)
        del probe
        if reason is None:
            try:
                _train_group_vmapped(dataset, [specs[i] for i in idxs],
                                     results, idxs, metric,
                                     shard_models, compile_counter)
                n_vmap_groups += 1
                vmap_members += len(idxs)
                continue
            except Exception as exc:  # noqa: BLE001
                reason = f"vmapped dispatch failed: {exc}"
                Log.warning("battery group falls back to solo: %s",
                            reason)
        for i in idxs:
            _train_member_solo(dataset, specs[i], results[i], metric,
                               reason)
            solo_members += 1

    return BatteryReport(
        results=[results[i] for i in range(len(specs))],
        groups=n_vmap_groups, vmap_members=vmap_members,
        solo_members=solo_members, xla_compiles=compile_counter[0],
        expected_compiles=n_vmap_groups,
        duration_s=time.perf_counter() - t0)
