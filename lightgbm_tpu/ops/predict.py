"""Ensemble-flattened jitted batch inference engine.

``GBDT.predict_raw`` historically walked the forest tree by tree on the
host — O(n_trees) numpy traversals per request.  This module flattens
the whole forest into struct-of-arrays node tables once and scores all
rows through all trees inside a single jitted kernel, the way GPU
boosting stacks batch their forests (XGBoost: Scalable GPU Accelerated
Learning, arXiv:1806.11248; GPU-acceleration for Large-scale Tree
Boosting, arXiv:1706.08359).

Kernel design (CPU-backend measured; XLA gathers cost ~15ns per random
LOCATION, so a per-depth-step pointer chase can never win):

- **QuickScorer bitmask scoring** (Lucchese et al., SIGIR'15): leaves
  are renumbered in DFS order at flatten time; every internal node
  carries a bitmask clearing its left-subtree leaves.  A row's exit
  leaf is the lowest set bit of the AND of the masks of all
  false-evaluating nodes — no per-row pointer chasing, no random
  gathers in the hot loop, just column-sliced SIMD compares.
- **Missing-value transform trick**: the reference's per-node
  None/Zero/NaN + default-left logic collapses into a pure ``v <= thr``
  compare against one of five per-feature transformed copies of the
  input (NaN→0 / miss→-inf / miss→+inf variants); a sixth integer-coded
  copy serves categorical bitset membership.  Only variants actually
  used by the forest are materialized.
- **Tree-chunked scan**: trees are processed in chunks (``lax.scan``)
  so the live accumulators stay cache-resident, with the node loop
  unrolled (``unroll=8``) to amortize XLA loop overhead.  The chunk
  boundary doubles as the prediction early-stopping boundary: chunk
  size = ``early_stop_freq * k`` reproduces the reference's per-row
  margin checks exactly (``prediction_early_stop.cpp``).
- **Shape-bucketed compile cache**: row batches are cut into
  fixed-size chunks padded to power-of-two buckets, and compiled
  predictors are kept in an LRU keyed by (bucket, n_trees, k, layout
  statics), so steady-state serving never re-traces.

Float64 end to end (thresholds, leaf values, accumulation) under a
locally-scoped ``jax.enable_x64(True)`` so the global f32 default used
by training kernels is untouched, and computed on the host CPU
(:func:`engine_device`) whatever the session's default backend is.
Accumulation order differs from the per-tree host loop only within a
tree chunk (a k-strided reshape-sum instead of tree-by-tree adds); raw
scores agree with the host loop to ~1e-13 relative.

``Tree.predict`` (models/tree.py) remains the single-tree oracle; the
flatten→traverse round-trip is pinned against it in
``tests/test_tree.py`` and ``tests/test_predict_engine.py``.
"""
from __future__ import annotations

import dataclasses
import os
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..utils.telemetry import counters as _tele_counters

_KZERO = 1e-35

# x-matrix variant rows, in slot order.  Slot v of feature f lives at
# row  base[v] + f  of the transformed matrix (unused variants are not
# materialized; base holds compacted offsets).
#   0: NaN -> 0                 (MissingType::None, and Zero/NaN non-miss)
#   1: miss(NaN) -> -inf        (NaN-type node, default_left)
#   2: miss(NaN) -> +inf        (NaN-type node, default right)
#   3: miss(0 or NaN) -> -inf   (Zero-type node, default_left)
#   4: miss(0 or NaN) -> +inf   (Zero-type node, default right)
#   5: integer category code, invalid/NaN -> -1   (categorical nodes)
N_VARIANTS = 6
_CAT_VARIANT = 5

_DEFAULT_CHUNK_ROWS = 16384
_DEFAULT_TREE_CHUNK = 32
_NODE_UNROLL = 8
_MIN_BUCKET = 512
# cap the transformed x-matrix a compiled chunk streams (wide-feature
# models shrink the row bucket instead of blowing the cache)
_XMAT_BYTES_CAP = 32 << 20


@dataclasses.dataclass
class FlatForest:
    """SoA node tables for a forest, padded to (n_trees, max_nodes).

    All arrays are host numpy; device mirrors (sliced to the first
    ``n`` trees and reshaped to tree chunks) are memoized in
    ``_dev``."""
    n_trees: int
    k: int                    # trees per iteration (= model outputs)
    num_features: int         # 1 + max feature id referenced
    max_leaves: int           # Lm: leaf-value table width
    max_nodes: int            # M: internal-node slots per tree
    wbits: int                # QuickScorer mask word width (32/64)
    n_words: int              # W: words per mask
    n_cat_nodes: int          # Mc: categorical-node slots per tree
    n_cat_words: int          # 64-bit bitset words per categorical node
    used_variants: Tuple[int, ...]   # sorted x-matrix variants in use
    var_base: Tuple[int, ...]        # variant -> compacted row base (-1)
    cols: np.ndarray          # (T, M) i32: compacted x-matrix row id
    thrs: np.ndarray          # (T, M) f64 (+inf pads: always-true)
    masks: np.ndarray         # (T, M, W) i32/i64 left-subtree-clear masks
    vals: np.ndarray          # (T, Lm) f64 leaf values in DFS order
    leaf_orig: np.ndarray     # (T, Lm) i32 DFS position -> model leaf id
    cat_cols: np.ndarray      # (T, Mc) i32 x-matrix row of cat feature
    cat_masks: np.ndarray     # (T, Mc, W)
    cat_words: np.ndarray     # (T, Mc, n_cat_words) int64 bitsets
    requires_features: int = 0  # min input width (0: no real splits)
    _dev: "OrderedDict" = dataclasses.field(default_factory=OrderedDict,
                                            repr=False)

    def device_tables(self, n_trees: int, tree_chunk: int):
        """First ``n_trees`` trees reshaped to (C, Tc, ...) device
        arrays (dummy zero-value trees pad the last chunk).  The memo
        is a small LRU — per-iteration staged predicts (num_iteration
        = 1..T) must not accumulate T full forest copies."""
        key = (n_trees, tree_chunk)
        hit = self._dev.get(key)  # .get: concurrent predicts may evict
        if hit is not None:
            try:
                self._dev.move_to_end(key)
            except KeyError:
                pass
            return hit
        import jax.numpy as jnp
        Tc = tree_chunk
        C = max((n_trees + Tc - 1) // Tc, 1)
        Tp = C * Tc

        def padded(a, fill=0):
            out = np.full((Tp,) + a.shape[1:], fill, a.dtype)
            out[:n_trees] = a[:n_trees]
            return out

        wfill = self.masks.dtype.type(-1)
        tabs = (padded(self.cols), padded(self.thrs, np.inf),
                padded(self.masks, wfill), padded(self.vals),
                padded(self.leaf_orig))
        if self.n_cat_nodes:
            tabs += (padded(self.cat_cols), padded(self.cat_masks, wfill),
                     padded(self.cat_words))
        dev = tuple(jnp.asarray(t.reshape((C, Tc) + t.shape[1:]))
                    for t in tabs)
        self._dev[key] = dev
        while len(self._dev) > 4:
            self._dev.popitem(last=False)
        return dev


def _dfs_layout(tree) -> Tuple[List[int], np.ndarray, np.ndarray]:
    """DFS (left-first) leaf visit order plus, per internal node, the
    [lo, hi) range of DFS leaf positions under its LEFT subtree.
    Iterative — chain-shaped trees exceed Python's recursion limit."""
    n_inner = max(tree.num_leaves - 1, 1)
    lo = np.zeros(n_inner, np.int64)
    hi = np.zeros(n_inner, np.int64)
    order: List[int] = []
    if tree.num_leaves <= 1:
        return [0], lo, hi
    # phases: 0 = descend left, 1 = record left range + descend right
    stack = [(0, 0)]
    while stack:
        node, phase = stack.pop()
        if node < 0:
            order.append(~node)
            continue
        if phase == 0:
            lo[node] = len(order)
            stack.append((node, 1))
            stack.append((int(tree.left_child[node]), 0))
        else:
            hi[node] = len(order)
            stack.append((int(tree.right_child[node]), 0))
    return order, lo, hi


# bounded + locked: concurrent flattens (serve hot-swaps racing a
# predict) share this module-level memo, and a pathological mix of
# mask widths must not grow it without bound
_PREFIX_CACHE: "OrderedDict[Tuple[int, int], np.ndarray]" = OrderedDict()
_PREFIX_CACHE_SLOTS = 8
_PREFIX_LOCK = threading.Lock()


def _prefix_table(W: int, wbits: int) -> np.ndarray:
    """prefix[j] = words with bits [0, j) set; forest-constant, so
    memoized (flatten calls this once per TREE otherwise)."""
    key = (W, wbits)
    with _PREFIX_LOCK:
        hit = _PREFIX_CACHE.get(key)
        if hit is not None:
            _PREFIX_CACHE.move_to_end(key)
            return hit
    # build outside the lock (pure + idempotent; a racing duplicate
    # build just overwrites with an identical table)
    n_bits = W * wbits
    prefix = np.zeros((n_bits + 1, W), np.uint64)
    for j in range(1, n_bits + 1):
        prefix[j] = prefix[j - 1]
        w, b = divmod(j - 1, wbits)
        prefix[j, w] |= np.uint64(1) << np.uint64(b)
    prefix.setflags(write=False)      # shared across threads: freeze
    with _PREFIX_LOCK:
        _PREFIX_CACHE[key] = prefix
        while len(_PREFIX_CACHE) > _PREFIX_CACHE_SLOTS:
            _PREFIX_CACHE.popitem(last=False)
    return prefix


def _range_masks(lo, hi, W: int, wbits: int) -> np.ndarray:
    """(n, W) masks with bits [lo, hi) CLEARED, all others set."""
    prefix = _prefix_table(W, wbits)
    rng = prefix[hi] & ~prefix[lo]          # bits [lo, hi)
    inv = ~rng
    if wbits == 32:
        return inv.astype(np.uint32).view(np.int32).reshape(-1, W)
    return inv.view(np.int64).reshape(-1, W)


@dataclasses.dataclass
class TreeFlat:
    """ONE tree's flattened predictor row, unpadded — the per-tree
    half of :func:`flatten_forest`, split out so the train->predict
    handoff (:func:`flatten_forest_device`) can extract it once per
    tree as trees materialize from the training fetch and never pay a
    full-forest repack.  Forest-level padding, the QuickScorer range
    masks (which need the forest-wide word width) and the compacted
    x-matrix row remap happen at assembly (:func:`assemble_forest`)."""
    num_leaves: int
    vals: np.ndarray          # (L,) f64 leaf values in DFS order
    leaf_orig: np.ndarray     # (L,) i32 DFS position -> model leaf id
    ni: int                   # internal nodes with real slots (0: stump)
    var: np.ndarray           # (ni,) i64 x-matrix variant per node
    feats: np.ndarray         # (ni,) i64 split feature per node
    thrs: np.ndarray          # (ni,) f64 numeric thresholds
    is_cat: np.ndarray        # (ni,) bool categorical-node flags
    lo: np.ndarray            # (ni,) i64 DFS left-subtree ranges
    hi: np.ndarray
    cat_nodes: np.ndarray     # (nc,) i64 node index of each cat node
    cat_words: List[np.ndarray]   # per cat node: packed u64 bitset
    max_feature: int          # 1 + max feature id referenced (min 1)
    # per-(W, wbits) memo of the materialized QuickScorer range masks:
    # repeated handoffs (a serve loop publishing after every block)
    # re-assemble the forest with unchanged layout statics, and the
    # mask build is the per-tree assembly cost worth skipping
    _masks: Dict = dataclasses.field(default_factory=dict, repr=False)

    def node_masks(self, W: int, wbits: int) -> np.ndarray:
        hit = self._masks.get((W, wbits))
        if hit is None:
            hit = _range_masks(self.lo, self.hi, W, wbits)
            self._masks.clear()     # layouts change monotonically
            self._masks[(W, wbits)] = hit
        return hit


def flatten_one_tree(t) -> TreeFlat:
    """Extract one tree's :class:`TreeFlat` (the host-side per-tree
    walk: DFS layout + staged node columns).  Pure function of the
    tree — safe to run at materialization time, concurrent with the
    next block's device compute."""
    from ..models.tree import _CAT_MASK, _DEFAULT_LEFT_MASK

    order, lo, hi = _dfs_layout(t)
    vals = np.asarray(t.leaf_value[order], np.float64)
    leaf_orig = np.asarray(order, np.int32)
    empty64 = np.zeros(0, np.int64)
    if t.num_leaves <= 1:
        return TreeFlat(max(t.num_leaves, 1), vals, leaf_orig, 0,
                        empty64, empty64, np.zeros(0, np.float64),
                        np.zeros(0, bool), empty64, empty64, empty64,
                        [], 1)
    ni = t.num_leaves - 1
    dtv = np.asarray(t.decision_type[:ni], np.int64)
    is_cat = (dtv & _CAT_MASK) != 0
    mt = (dtv >> 2) & 3
    dl = (dtv & _DEFAULT_LEFT_MASK) != 0
    var = np.zeros(ni, np.int64)
    var[(mt == 2) & dl] = 1
    var[(mt == 2) & ~dl] = 2
    var[(mt == 1) & dl] = 3
    var[(mt == 1) & ~dl] = 4
    feats = np.asarray(t.split_feature[:ni], np.int64)
    cat_nodes = np.nonzero(is_cat)[0].astype(np.int64)
    cat_words = []
    for nd in cat_nodes:
        kk = int(t.threshold_bin[nd])
        b0, b1 = t.cat_boundaries[kk], t.cat_boundaries[kk + 1]
        w32 = np.asarray(t.cat_threshold[b0:b1], np.uint64)
        w64 = np.zeros(max((len(w32) + 1) // 2, 1), np.uint64)
        for wi in range(len(w32)):
            w64[wi // 2] |= w32[wi] << np.uint64(32 * (wi % 2))
        cat_words.append(w64)
    return TreeFlat(t.num_leaves, vals, leaf_orig, ni, var, feats,
                    np.asarray(t.threshold[:ni], np.float64), is_cat,
                    lo[:ni].astype(np.int64), hi[:ni].astype(np.int64),
                    cat_nodes, cat_words,
                    int(feats.max()) + 1 if ni else 1)


def assemble_forest(flats: List[TreeFlat],
                    num_tree_per_iteration: int = 1) -> FlatForest:
    """Pad + stack per-tree :class:`TreeFlat` rows into the engine's
    forest tables.  Byte-identical to :func:`flatten_forest` on the
    same trees (same numbers flow in, in the same order) — pinned by
    ``tests/test_pipeline.py``."""
    T = len(flats)
    k = max(num_tree_per_iteration, 1)
    M = max([max(f.num_leaves - 1, 1) for f in flats] or [1])
    Lm = max([f.num_leaves for f in flats] or [1])
    if Lm <= 32:
        wbits, wdt = 32, np.int32
    else:
        wbits, wdt = 64, np.int64
    W = (Lm + wbits - 1) // wbits

    Mc = max([len(f.cat_nodes) for f in flats] or [0])
    nw64 = max([len(w) for f in flats for w in f.cat_words] or [1])

    # variant ids and features are staged in int64 (variant, feature)
    # pairs, then remapped to compacted x-matrix row ids once the used
    # variant set is final
    vcols = np.zeros((T, M), np.int64)
    fcols = np.zeros((T, M), np.int64)
    thrs = np.full((T, M), np.inf, np.float64)
    masks = np.full((T, M, W), -1, wdt)
    vals = np.zeros((T, Lm), np.float64)
    leaf_orig = np.zeros((T, Lm), np.int32)
    vcat = np.full((T, max(Mc, 1)), _CAT_VARIANT, np.int64)
    fcat = np.zeros((T, max(Mc, 1)), np.int64)
    cat_masks = np.full((T, max(Mc, 1), W), -1, wdt)
    cat_words = np.zeros((T, max(Mc, 1), nw64), np.int64)

    used = set()
    num_features = 1
    requires_features = 0
    for i, f in enumerate(flats):
        L = len(f.vals)
        vals[i, :L] = f.vals
        leaf_orig[i, :L] = f.leaf_orig
        if f.ni <= 0:
            continue
        ni = f.ni
        num_features = max(num_features, f.max_feature)
        requires_features = num_features
        used.update(int(v) for v in np.unique(f.var[~f.is_cat]))
        node_masks = f.node_masks(W, wbits)
        num = ~f.is_cat
        # numerical nodes occupy their slots; categorical nodes are
        # no-ops in the numeric pass (thr stays +inf -> condition
        # true -> mask untouched) and get real slots in the cat pass
        vcols[i, :ni] = np.where(num, f.var, 0)
        fcols[i, :ni] = np.where(num, f.feats, 0)
        thrs[i, :ni][num] = f.thrs[num]
        masks[i, :ni][num] = node_masks[num]
        for j, nd in enumerate(f.cat_nodes):
            fcat[i, j] = f.feats[nd]
            cat_masks[i, j] = node_masks[nd]
            w64 = np.zeros(nw64, np.uint64)
            w64[:len(f.cat_words[j])] = f.cat_words[j]
            cat_words[i, j] = w64.view(np.int64)
    if Mc > 0:
        used.add(_CAT_VARIANT)
    if not used:
        used.add(0)
    used_variants = tuple(sorted(used))
    var_base = [-1] * N_VARIANTS
    for pos, v in enumerate(used_variants):
        var_base[v] = pos * num_features
    base_lut = np.asarray([b if b >= 0 else 0 for b in var_base],
                          np.int64)
    cols = (base_lut[vcols] + fcols).astype(np.int32)
    cat_cols = (base_lut[vcat] + fcat).astype(np.int32)

    return FlatForest(
        n_trees=T, k=k, num_features=num_features, max_leaves=Lm,
        max_nodes=M, wbits=wbits, n_words=W, n_cat_nodes=Mc,
        n_cat_words=nw64, used_variants=used_variants,
        var_base=tuple(var_base), cols=cols, thrs=thrs, masks=masks,
        vals=vals, leaf_orig=leaf_orig, cat_cols=cat_cols,
        cat_masks=cat_masks, cat_words=cat_words,
        requires_features=requires_features)


def flatten_forest(models: List, num_tree_per_iteration: int = 1
                   ) -> FlatForest:
    """Pack ``models`` (a list of :class:`~..models.tree.Tree`) into
    SoA device-ready tables — the COLD path (model-file load, handoff
    disabled): every tree is walked here, a full-forest host repack.
    Same-process train->predict uses :func:`flatten_forest_device`
    instead; the ``flatten_full_repacks`` counter pins which path a
    run took."""
    _tele_counters.incr("flatten_full_repacks")
    return assemble_forest([flatten_one_tree(t) for t in models],
                           num_tree_per_iteration)


def flatten_forest_device(models: List, num_tree_per_iteration: int,
                          flats: List[TreeFlat]) -> FlatForest:
    """The train->predict HANDOFF path: build the engine's SoA tables
    from the per-tree :class:`TreeFlat` cache a live booster maintains
    alongside its model list, extracting rows ONLY for trees not yet
    cached (the delta since the last handoff) — so a booster that
    trains and then predicts/serves/publishes in the same process
    never re-walks its whole forest the way the cold
    :func:`flatten_forest` path must (its per-tree DFS walk is Python-
    bound and grows with trees x nodes, exactly the repack the r04
    profile showed riding the train->serve seam).

    ``flats`` is extended IN PLACE (the booster owns it and clears it
    when trees mutate in place — DART renormalization, refit, merge).
    Counters: ``flatten_device_handoffs`` (this path ran) and
    ``flatten_tree_extracts`` (per-tree rows extracted — the delta,
    not the forest).  Output is byte-identical to
    :func:`flatten_forest` on the same models (one shared
    :func:`assemble_forest`), pinned by ``tests/test_pipeline.py``."""
    if len(flats) > len(models):
        # the model list shrank without an invalidation sweep
        # (defensive: rollback paths clear the cache explicitly)
        del flats[len(models):]
    for t in models[len(flats):]:
        flats.append(flatten_one_tree(t))
        _tele_counters.incr("flatten_tree_extracts")
    _tele_counters.incr("flatten_device_handoffs")
    return assemble_forest(flats, num_tree_per_iteration)


# ----------------------------------------------------------------------
# compiled-kernel construction
# ----------------------------------------------------------------------
TRACE_COUNT = 0     # bumped at TRACE time; tests pin "no recompile"

_XMAT_JIT = None    # module-level: jax.jit caches by function identity


def _xmat_compiled():
    global _XMAT_JIT
    if _XMAT_JIT is None:
        import jax
        _XMAT_JIT = jax.jit(_build_xmat,
                            static_argnames=("used_variants",))
    return _XMAT_JIT


def _build_xmat(Xt, used_variants):
    """Transformed feature matrix: the used variant blocks of
    ``Xt`` (features, rows), concatenated along axis 0."""
    import jax.numpy as jnp
    nan = jnp.isnan(Xt)
    blocks = []
    for v in used_variants:
        if v == 0:
            blocks.append(jnp.where(nan, 0.0, Xt))
        elif v == 1:
            blocks.append(jnp.where(nan, -jnp.inf, Xt))
        elif v == 2:
            blocks.append(jnp.where(nan, jnp.inf, Xt))
        elif v in (3, 4):
            miss = nan | (jnp.abs(Xt) <= _KZERO)
            fill = -jnp.inf if v == 3 else jnp.inf
            blocks.append(jnp.where(miss, fill, Xt))
        else:  # categorical integer code; invalid -> -1
            c = jnp.where(nan | ~jnp.isfinite(Xt), -1.0, Xt)
            valid = (c >= 0) & (c == jnp.floor(c)) & (c < 2.0 ** 62)
            blocks.append(jnp.where(valid, c, -1.0))
    return jnp.concatenate(blocks, axis=0)


def _make_kernels(st):
    """Build the jitted (raw, leaf) kernels for one static layout.

    ``st`` is the static key tuple — see :meth:`PredictEngine._key`.
    """
    import jax
    import jax.numpy as jnp

    (B, C, Tc, M, Mc, W, wbits, Lm, nw64, k, es, used, nfeat) = st
    wdt = jnp.int32 if wbits == 32 else jnp.int64

    def chunk_masks(xmat, tabs):
        """(W, Tc, B) QuickScorer accumulators for one tree chunk."""
        ccols, cthrs, cmasks = tabs[0], tabs[1], tabs[2]
        acc = jnp.full((W, Tc, B), -1, wdt)

        def node_step(acc, inp):
            ci, ti, mi = inp                       # (Tc,) each
            v = xmat[ci]                           # (Tc, B) row slices
            dec = v <= ti[:, None]
            for w in range(W):
                mw = jnp.where(dec, wdt(-1), mi[:, w, None])
                acc = acc.at[w].set(acc[w] & mw)
            return acc, None

        acc, _ = jax.lax.scan(
            node_step, acc,
            (ccols.swapaxes(0, 1), cthrs.swapaxes(0, 1),
             cmasks.swapaxes(0, 1)), unroll=_NODE_UNROLL)
        if Mc:
            catc, catm, catw = tabs[5], tabs[6], tabs[7]

            def cat_step(acc, inp):
                ci, mi, wi = inp                   # (Tc,), (Tc,W), (Tc,nw)
                ic = xmat[ci].astype(jnp.int64)    # (Tc, B)
                widx = ic >> 6
                word = jnp.zeros(ic.shape, jnp.int64)
                for wj in range(nw64):
                    word = jnp.where(widx == wj, wi[:, wj, None], word)
                dec = ((word >> (ic & 63)) & 1) == 1
                for w in range(W):
                    mw = jnp.where(dec, wdt(-1), mi[:, w, None])
                    acc = acc.at[w].set(acc[w] & mw)
                return acc, None

            acc, _ = jax.lax.scan(
                cat_step, acc,
                (catc.swapaxes(0, 1), catm.swapaxes(0, 1),
                 catw.swapaxes(0, 1)), unroll=min(_NODE_UNROLL, max(Mc, 1)))
        return acc

    def first_set_bit(acc):
        leaf = jnp.zeros(acc.shape[1:], jnp.int32)
        found = jnp.zeros(acc.shape[1:], bool)
        for w in range(W):
            a = acc[w]
            nz = a != 0
            ffs = jax.lax.population_count(
                (a & -a) - wdt(1)).astype(jnp.int32)
            leaf = jnp.where(~found & nz, wbits * w + ffs, leaf)
            found = found | nz
        return leaf

    def raw_fn(xmat, tabs, margin):
        global TRACE_COUNT
        TRACE_COUNT += 1

        def chunk_fn(carry, x):
            out, active = carry
            acc = chunk_masks(xmat, x)
            leaf = first_set_bit(acc)
            v = jnp.take_along_axis(x[3], leaf, axis=1)   # (Tc, B)
            contrib = v.reshape(Tc // k, k, B).sum(axis=0)
            if es:
                out = out + contrib * active[None, :]
                if k == 1:
                    m = 2.0 * jnp.abs(out[0])
                else:
                    top1 = jnp.max(out, axis=0)
                    am = jnp.argmax(out, axis=0)
                    masked = jnp.where(
                        jnp.arange(k)[:, None] == am[None, :],
                        -jnp.inf, out)
                    m = top1 - jnp.max(masked, axis=0)
                active = active & (m < margin)
            else:
                out = out + contrib
            return (out, active), None

        carry = (jnp.zeros((k, B)), jnp.ones((B,), bool))
        (out, _), _ = jax.lax.scan(chunk_fn, carry, tabs)
        return out

    def leaf_fn(xmat, tabs):
        global TRACE_COUNT
        TRACE_COUNT += 1

        def chunk_fn(carry, x):
            acc = chunk_masks(xmat, x)
            leaf = first_set_bit(acc)
            return carry, jnp.take_along_axis(x[4], leaf, axis=1)

        _, leaves = jax.lax.scan(chunk_fn, 0, tabs)       # (C, Tc, B)
        return leaves.reshape(C * Tc, B)

    return jax.jit(raw_fn), jax.jit(leaf_fn)


class PredictEngine:
    """Shape-bucketed compile cache + host-side row chunking around the
    flattened traversal kernels."""

    def __init__(self, chunk_rows: int = _DEFAULT_CHUNK_ROWS,
                 tree_chunk: int = _DEFAULT_TREE_CHUNK,
                 cache_size: int = 16):
        self.chunk_rows = int(chunk_rows)
        self.tree_chunk = int(tree_chunk)
        self.cache_size = int(cache_size)
        self._cache: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._cache_lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    # -- cache -----------------------------------------------------------
    def _compiled(self, key):
        # concurrent predicts share the process-wide engine; the LRU
        # reorder/evict must be atomic.  jax.jit is lazy, so holding
        # the lock through _make_kernels wraps closures only — the
        # actual XLA compile happens at call time, outside the lock.
        with self._cache_lock:
            hit = self._cache.get(key)
            if hit is not None:
                self._cache.move_to_end(key)
                self.hits += 1
                _tele_counters.incr("predict_cache_hits")
                return hit
            self.misses += 1
            _tele_counters.incr("predict_cache_misses")
            kernels = _make_kernels(key)
            self._cache[key] = kernels
            while len(self._cache) > self.cache_size:
                self._cache.popitem(last=False)
                self.evictions += 1
                _tele_counters.incr("predict_cache_evictions")
            return kernels

    def set_cache_size(self, n: int) -> None:
        """Resize the compiled-kernel LRU (``predict_cache_slots``
        config param).  The engine is process-wide, so the last caller
        wins; shrinking evicts immediately (oldest first)."""
        n = max(int(n), 1)
        with self._cache_lock:
            self.cache_size = n
            while len(self._cache) > n:
                self._cache.popitem(last=False)
                self.evictions += 1
                _tele_counters.incr("predict_cache_evictions")

    def cache_info(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions,
                "entries": len(self._cache),
                "capacity": self.cache_size, "traces": TRACE_COUNT}

    # -- bucketing -------------------------------------------------------
    def _max_chunk(self, flat: FlatForest,
                   chunk_rows: Optional[int] = None) -> int:
        rows = len(flat.used_variants) * flat.num_features
        cap = _XMAT_BYTES_CAP // max(rows * 8, 1)
        cap = max(_MIN_BUCKET, 1 << max(int(cap).bit_length() - 1, 0))
        return max(_MIN_BUCKET, min(chunk_rows or self.chunk_rows, cap))

    @staticmethod
    def _buckets(n: int, max_chunk: int):
        """Yield (start, rows, padded_bucket) row chunks: full
        ``max_chunk`` chunks, then one power-of-two remainder bucket."""
        pos = 0
        while n - pos >= max_chunk:
            yield pos, max_chunk, max_chunk
            pos += max_chunk
        if n - pos:
            rem = n - pos
            b = 1 << (rem - 1).bit_length()
            yield pos, rem, min(max(b, _MIN_BUCKET), max_chunk)

    def bucket_set(self, flat: FlatForest,
                   chunk_rows: Optional[int] = None) -> List[int]:
        """Every padded row-bucket size a request can hit for this
        layout: the power-of-two ladder from ``_MIN_BUCKET`` up to the
        max chunk, plus the max chunk itself.  The serve layer warms
        exactly this set so steady-state serving never compiles."""
        mx = self._max_chunk(flat, chunk_rows)
        out = []
        b = _MIN_BUCKET
        while b < mx:
            out.append(b)
            b <<= 1
        out.append(mx)
        return out

    def padded_rows(self, flat: FlatForest, n: int,
                    chunk_rows: Optional[int] = None) -> int:
        """Total device rows ``n`` input rows occupy after chunk
        padding — the serve batch-occupancy denominator."""
        mx = self._max_chunk(flat, chunk_rows)
        return sum(b for _, _, b in self._buckets(n, mx))

    @staticmethod
    def fast_bucket_set(max_rows: int) -> List[int]:
        """The single-row fast path's tiny power-of-two ladder:
        1, 2, 4, ... up to ``max_rows`` rounded up.  The serve layer
        warms this set per published fingerprint alongside
        :meth:`bucket_set` so a low-occupancy request never compiles."""
        cap = 1 << max(int(max_rows) - 1, 0).bit_length()
        out = []
        b = 1
        while b <= cap:
            out.append(b)
            b <<= 1
        return out

    def _tree_chunk(self, flat: FlatForest, early_stop: bool,
                    freq: int, n_trees: int) -> int:
        k = flat.k
        if early_stop:
            # the chunk boundary IS the margin-check boundary; a freq
            # beyond the forest means no check ever fires, so clamp to
            # one chunk instead of padding the tables with dummies
            iters = max((n_trees + k - 1) // k, 1)
            return max(min(freq, iters), 1) * k
        return max(self.tree_chunk // k, 1) * k

    def _key(self, flat: FlatForest, B: int, n_trees: int, Tc: int,
             es: bool):
        C = max((n_trees + Tc - 1) // Tc, 1)
        return (B, C, Tc, flat.max_nodes, flat.n_cat_nodes, flat.n_words,
                flat.wbits, flat.max_leaves, flat.n_cat_words, flat.k,
                es, flat.used_variants, flat.num_features)

    # -- execution -------------------------------------------------------
    def _run(self, flat: FlatForest, X: np.ndarray, n_trees: int,
             want_leaf: bool, es: bool, freq: int, margin: float,
             chunk_rows: Optional[int] = None, buckets=None):
        import jax
        import jax.numpy as jnp

        n = X.shape[0]
        if X.shape[1] < flat.requires_features:
            # the per-tree loop would IndexError; zero-filling missing
            # feature columns would return confidently wrong scores
            raise ValueError(
                f"input has {X.shape[1]} features but the model "
                f"references feature {flat.requires_features - 1}")
        Tc = self._tree_chunk(flat, es, freq, n_trees)
        max_chunk = self._max_chunk(flat, chunk_rows)
        if buckets is None:
            buckets = self._buckets(n, max_chunk)
        outs = []
        with jax.default_device(engine_device()), jax.enable_x64(True):
            tabs = flat.device_tables(n_trees, Tc)
            xmat_fn = _xmat_compiled()
            for start, rows, B in buckets:
                key = self._key(flat, B, n_trees, Tc, es)
                raw_k, leaf_k = self._compiled(key)
                blk = X[start:start + rows, :flat.num_features]
                if rows != B or blk.shape[1] != flat.num_features:
                    pad = np.zeros((B, flat.num_features))
                    pad[:rows, :blk.shape[1]] = blk
                    blk = pad
                xt = jnp.asarray(np.ascontiguousarray(blk.T))
                xmat = xmat_fn(xt, flat.used_variants)
                # fetch the FULL padded output and slice host-side: a
                # device-side r[:, :rows] would compile one
                # dynamic_slice executable per distinct request size,
                # breaking the serving layer's zero-steady-state-
                # compile contract (the padded tail is < one bucket of
                # f64 — transfer noise)
                if want_leaf:
                    r = np.asarray(leaf_k(xmat, tabs))  # (C*Tc, B)
                    outs.append(r[:n_trees, :rows])
                else:
                    r = np.asarray(raw_k(xmat, tabs,
                                         jnp.float64(margin)))
                    outs.append(r[:, :rows])
        return np.concatenate(outs, axis=1)

    def predict_raw(self, flat: FlatForest, X: np.ndarray,
                    n_trees: Optional[int] = None,
                    early_stop: bool = False, early_stop_freq: int = 10,
                    early_stop_margin: float = 10.0,
                    chunk_rows: Optional[int] = None) -> np.ndarray:
        """Raw scores, shape (k, rows) float64.  ``chunk_rows`` is a
        per-call row-chunk override (never written to engine state —
        concurrent callers keep their own bucketing)."""
        n_trees = flat.n_trees if n_trees is None else n_trees
        if n_trees <= 0 or X.shape[0] == 0:
            return np.zeros((flat.k, X.shape[0]))
        return self._run(flat, X, n_trees, False, bool(early_stop),
                         int(early_stop_freq), float(early_stop_margin),
                         chunk_rows)

    def predict_raw_fast(self, flat: FlatForest, X: np.ndarray,
                         n_trees: Optional[int] = None) -> np.ndarray:
        """The serve tier's single-row fast path: pad to the tiny
        power-of-two bucket (no ``_MIN_BUCKET`` clamp) instead of a
        full serving bucket.  Same kernels, same compile-cache key
        space — every per-row operation in the kernel is independent
        of the padding width, so outputs are bit-identical to the
        bucketed path (pinned by tests/test_shap_engine.py)."""
        n_trees = flat.n_trees if n_trees is None else n_trees
        n = X.shape[0]
        if n_trees <= 0 or n == 0:
            return np.zeros((flat.k, n))
        B = 1 << max(n - 1, 0).bit_length()
        return self._run(flat, X, n_trees, False, False, 10, 10.0,
                         buckets=[(0, n, B)])

    def predict_leaf_index(self, flat: FlatForest, X: np.ndarray,
                           n_trees: Optional[int] = None,
                           chunk_rows: Optional[int] = None) -> np.ndarray:
        """Leaf indices, shape (rows, n_trees) int32 (model leaf ids)."""
        n_trees = flat.n_trees if n_trees is None else n_trees
        if n_trees <= 0 or X.shape[0] == 0:
            return np.zeros((X.shape[0], max(n_trees, 0)), np.int32)
        out = self._run(flat, X, n_trees, True, False, 10, 10.0,
                        chunk_rows)
        return np.ascontiguousarray(out.T.astype(np.int32))


def engine_device():
    """The device the predict and SHAP engines compute on: the host
    CPU, on every backend.  Their kernels are float64 end to end and
    gather-heavy; nothing has moved them to an accelerator yet, so a
    process that only scores never needs one (serve replicas run with
    ``JAX_PLATFORMS=cpu``, serve/fleet.py).  Raises where the process
    has no CPU backend (``JAX_PLATFORMS=tpu`` alone) instead of
    computing somewhere unstated."""
    import jax
    return jax.local_devices(backend="cpu")[0]


def engine_device_info() -> Dict[str, str]:
    """``engine_device`` as a record for run_info and smoke output."""
    dev = engine_device()
    return {"platform": dev.platform, "device_kind": dev.device_kind}


_ENGINE: Optional[PredictEngine] = None


def get_engine() -> PredictEngine:
    """Process-wide engine (the compile cache is global by design —
    boosters with identical layouts share compiled predictors).
    Chunk-size preferences are per-call arguments, not engine state."""
    global _ENGINE
    if _ENGINE is None:
        _ENGINE = PredictEngine()
    return _ENGINE


def engine_enabled() -> bool:
    """Kill switch: LTPU_PREDICT_ENGINE=0 forces the per-tree host
    loop (oracle path for tests and A/B benchmarks)."""
    return os.environ.get("LTPU_PREDICT_ENGINE", "1") != "0"
