"""TreeSHAP feature contributions: host reference + device engine.

Capability parity with the reference's path-dependent TreeSHAP
(``src/io/tree.cpp:591-650``: ``ExtendPath`` / ``UnwindPath`` /
``UnwoundPathSum`` / ``TreeSHAP`` recursion, exposed as
``PredictContrib``).  The top half of this module is the host-side
numpy implementation of the published Tree SHAP algorithm (Lundberg et
al.) using node covers (internal_count / leaf_count) for the
path-dependent weighting — it stays the single-row oracle.

The bottom half is the serve-visible **explanation engine**: the PR 1
flattened-forest treatment applied to SHAP.  Key observation making
the recursion batchable: at a leaf, the unique-feature path entries'
*zero* fractions (products of cover ratios along the path) and the
entry order are pure functions of the (tree, leaf) pair, while the
*one* fractions are 0/1 per row (did the row follow the path's
direction at every node of that feature).  So flatten once on the
host — per-(tree, leaf) path descriptors into SoA tables — and the
per-row work collapses to: decision bits at every node (the
``ops/predict.py`` x-matrix variant trick, shared ``_build_xmat``
jit), an AND-reduction per unique slot, the EXTEND pweight DP
vectorized over the pweight index, and a masked UNWOUND-sum loop
vectorized over slots.  A ``lax.scan`` over leaves keeps the working
set at (tree_chunk, depth+1, bucket) instead of materializing
per-leaf pweights for the whole forest.

Engine discipline is shared with :class:`~.predict.PredictEngine`:
f64 under scoped ``jax.enable_x64(True)`` on the host CPU
(``predict.engine_device``), a locked LRU of
compiled kernels keyed by static layout + bucket, power-of-two row
buckets with full-padded-output fetch and host-side slicing (a
device-side slice would compile one executable per request size and
break the serving layer's zero-steady-state-compile contract), and a
``bucket_set`` the serve tier pre-warms at publish.

Output layout matches the reference: ``(rows, num_features + 1)`` with
the last column holding the expected value (bias) term.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..models.tree import Tree, _CAT_MASK, _DEFAULT_LEFT_MASK
from ..utils.telemetry import counters as _tele_counters


class _Path:
    __slots__ = ("feature", "zero", "one", "pweight")

    def __init__(self, depth_cap: int):
        self.feature = np.zeros(depth_cap, dtype=np.int64)
        self.zero = np.zeros(depth_cap, dtype=np.float64)
        self.one = np.zeros(depth_cap, dtype=np.float64)
        self.pweight = np.zeros(depth_cap, dtype=np.float64)

    def copy_to(self, other: "_Path", n: int) -> None:
        other.feature[:n] = self.feature[:n]
        other.zero[:n] = self.zero[:n]
        other.one[:n] = self.one[:n]
        other.pweight[:n] = self.pweight[:n]


def _extend(p: _Path, unique_depth: int, zero: float, one: float,
            fi: int) -> None:
    p.feature[unique_depth] = fi
    p.zero[unique_depth] = zero
    p.one[unique_depth] = one
    p.pweight[unique_depth] = 1.0 if unique_depth == 0 else 0.0
    for i in range(unique_depth - 1, -1, -1):
        p.pweight[i + 1] += one * p.pweight[i] * (i + 1) / (unique_depth + 1)
        p.pweight[i] = zero * p.pweight[i] * \
            (unique_depth - i) / (unique_depth + 1)


def _unwind(p: _Path, unique_depth: int, path_index: int) -> None:
    one = p.one[path_index]
    zero = p.zero[path_index]
    n = p.pweight[unique_depth]
    for i in range(unique_depth - 1, -1, -1):
        if one != 0.0:
            t = p.pweight[i]
            p.pweight[i] = n * (unique_depth + 1) / ((i + 1) * one)
            n = t - p.pweight[i] * zero * (unique_depth - i) / \
                (unique_depth + 1)
        else:
            p.pweight[i] = p.pweight[i] * (unique_depth + 1) / \
                (zero * (unique_depth - i))
    for i in range(path_index, unique_depth):
        p.feature[i] = p.feature[i + 1]
        p.zero[i] = p.zero[i + 1]
        p.one[i] = p.one[i + 1]


def _unwound_sum(p: _Path, unique_depth: int, path_index: int) -> float:
    one = p.one[path_index]
    zero = p.zero[path_index]
    total = 0.0
    n = p.pweight[unique_depth]
    for i in range(unique_depth - 1, -1, -1):
        if one != 0.0:
            t = n * (unique_depth + 1) / ((i + 1) * one)
            total += t
            n = p.pweight[i] - t * zero * (unique_depth - i) / \
                (unique_depth + 1)
        else:
            total += p.pweight[i] * (unique_depth + 1) / \
                (zero * (unique_depth - i))
    return total


def _decide_left(tree: Tree, node: int, x: np.ndarray) -> bool:
    v = float(x[tree.split_feature[node]])
    dt = int(tree.decision_type[node])
    if dt & _CAT_MASK:
        if not np.isfinite(v):
            return False
        c = int(v)
        if c < 0 or c != v:
            return False
        k = tree.threshold_bin[node]
        lo, hi = tree.cat_boundaries[k], tree.cat_boundaries[k + 1]
        w, b = divmod(c, 32)
        return w < hi - lo and bool((tree.cat_threshold[lo + w] >> b) & 1)
    mt = (dt >> 2) & 3
    if mt == 2:  # NaN
        if np.isnan(v):
            return bool(dt & _DEFAULT_LEFT_MASK)
    elif mt == 1:  # Zero
        if np.isnan(v) or abs(v) <= 1e-35:
            return bool(dt & _DEFAULT_LEFT_MASK)
    if np.isnan(v):
        v = 0.0
    return v <= tree.threshold[node]


def _tree_shap(tree: Tree, x: np.ndarray, phi: np.ndarray, node: int,
               unique_depth: int, parent: _Path, p_zero: float, p_one: float,
               p_fi: int) -> None:
    path = _Path(tree.num_leaves + 2)
    parent.copy_to(path, unique_depth)
    _extend(path, unique_depth, p_zero, p_one, p_fi)
    if node < 0:  # leaf
        leaf = ~node
        value = tree.leaf_value[leaf]
        for i in range(1, unique_depth + 1):
            w = _unwound_sum(path, unique_depth, i)
            phi[path.feature[i]] += w * (path.one[i] - path.zero[i]) * value
        return
    node_count = float(tree.internal_count[node]) or 1.0
    left, right = int(tree.left_child[node]), int(tree.right_child[node])
    hot, cold = (left, right) if _decide_left(tree, node, x) else \
        (right, left)

    def child_count(c):
        return float(tree.leaf_count[~c] if c < 0 else
                     tree.internal_count[c])

    hot_zero = child_count(hot) / node_count
    cold_zero = child_count(cold) / node_count
    incoming_zero, incoming_one = 1.0, 1.0
    fi = int(tree.split_feature[node])
    # same feature already on the path → unwind the previous occurrence
    path_index = -1
    for i in range(1, unique_depth + 1):
        if path.feature[i] == fi:
            path_index = i
            break
    if path_index >= 0:
        incoming_zero = path.zero[path_index]
        incoming_one = path.one[path_index]
        _unwind(path, unique_depth, path_index)
        unique_depth -= 1
    _tree_shap(tree, x, phi, hot, unique_depth + 1, path,
               hot_zero * incoming_zero, incoming_one, fi)
    _tree_shap(tree, x, phi, cold, unique_depth + 1, path,
               cold_zero * incoming_zero, 0.0, fi)


def _expected_value(tree: Tree) -> float:
    n = tree.num_leaves
    if n <= 1:
        return float(tree.leaf_value[0])
    counts = tree.leaf_count[:n].astype(np.float64)
    total = counts.sum()
    if total <= 0:
        return float(np.mean(tree.leaf_value[:n]))
    return float(np.dot(counts, tree.leaf_value[:n]) / total)


def shap_values_one_tree(tree: Tree, X: np.ndarray) -> np.ndarray:
    """(rows, num_features+1) contributions of one tree (last col = bias)."""
    rows, nf = X.shape
    out = np.zeros((rows, nf + 1), dtype=np.float64)
    base = _expected_value(tree)
    out[:, -1] = base
    if tree.num_leaves <= 1:
        return out
    root_path = _Path(tree.num_leaves + 2)
    for r in range(rows):
        _tree_shap(tree, X[r], out[r, :-1], 0, 0, root_path, 1.0, 1.0, -1)
    return out


def predict_contrib(models: List[Tree], X: np.ndarray,
                    num_iteration: int = -1,
                    num_tree_per_iteration: int = 1) -> np.ndarray:
    """Sum of per-tree SHAP contributions (``PredictContrib``).

    Multiclass returns (rows, num_class * (num_features+1)) like the
    reference's flattened layout.
    """
    X = np.ascontiguousarray(np.asarray(X, np.float64))
    k = max(num_tree_per_iteration, 1)
    n_trees = len(models)
    if num_iteration is not None and num_iteration > 0:
        n_trees = min(n_trees, num_iteration * k)
    rows, nf = X.shape
    out = np.zeros((rows, k, nf + 1), dtype=np.float64)
    for i in range(n_trees):
        out[:, i % k, :] += shap_values_one_tree(models[i], X)
    if k == 1:
        return out[:, 0, :]
    return out.reshape(rows, k * (nf + 1))


# ======================================================================
# Device explanation engine
# ======================================================================
_SHAP_CHUNK_ROWS = 2048
_SHAP_TREE_CHUNK = 16
_SHAP_MIN_BUCKET = 128
# cap the per-bucket device working set (xmat + decision bits + the
# per-leaf pweight DP state); wide/deep forests shrink the row bucket
_SHAP_BYTES_CAP = 32 << 20

TRACE_COUNT = 0     # bumped at TRACE time; tests pin "no recompile"


def _pow2_dim(n: int, floor: int = 8) -> int:
    """Round a layout dimension up to a power of two (min ``floor``)
    so forests that differ only by a node or two of tree shape share
    one compile key — the padded slots are fully masked in the kernel."""
    return max(floor, 1 << max(int(n) - 1, 0).bit_length())


@dataclasses.dataclass
class ShapForest:
    """SoA path-descriptor tables for a forest, padded to
    (n_trees, max_leaves, max_path/max_unique).

    Node tables (``cols``/``thrs``/``cat_*``) mirror
    :class:`~.predict.FlatForest`'s x-matrix variant encoding so the
    decision at every internal node is one ``v <= thr`` compare (plus
    a bitset-membership fixup at categorical slots).  Per (tree, leaf)
    the root-to-leaf path is stored twice: position-wise (node id,
    direction, unique-slot id — feeds the per-row *one* fractions) and
    slot-wise (feature, combined *zero* cover fraction — the
    row-independent half of the pweight DP)."""
    n_trees: int
    k: int
    num_features: int
    max_nodes: int            # M: internal-node slots per tree
    max_leaves: int           # Lm
    max_path: int             # P: path positions (duplicates included)
    max_unique: int           # D: unique-feature slots
    n_cat_nodes: int          # Mc
    n_cat_words: int
    used_variants: Tuple[int, ...]
    cols: np.ndarray          # (T, M) i32 compacted x-matrix row id
    thrs: np.ndarray          # (T, M) f64 (+inf at cat/pad slots)
    cat_idx: np.ndarray       # (T, Mc) i32 node slot (pad: M -> dropped)
    cat_cols: np.ndarray      # (T, Mc) i32
    cat_words: np.ndarray     # (T, Mc, n_cat_words) int64 bitsets
    path_node: np.ndarray     # (T, Lm, P) i32
    path_dir: np.ndarray      # (T, Lm, P) bool (True: path goes left)
    path_ok: np.ndarray       # (T, Lm, P) bool (False: padding)
    path_slot: np.ndarray     # (T, Lm, P) i32 0-based unique slot
    slot_feat: np.ndarray     # (T, Lm, D) i32
    slot_zero: np.ndarray     # (T, Lm, D) f64 (pad 1.0)
    leaf_udep: np.ndarray     # (T, Lm) i32 unique depth per leaf
    leaf_val: np.ndarray      # (T, Lm) f64
    expval: np.ndarray        # (T,) f64 per-tree expected value
    requires_features: int = 0
    _dev: "OrderedDict" = dataclasses.field(default_factory=OrderedDict,
                                            repr=False)

    def device_tables(self, n_trees: int, tree_chunk: int):
        """First ``n_trees`` trees reshaped to (C, Tc, ...) device
        arrays (zero-value dummy trees pad the last chunk); small LRU
        memo like :meth:`~.predict.FlatForest.device_tables`."""
        key = (n_trees, tree_chunk)
        hit = self._dev.get(key)
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

        tabs = (padded(self.cols), padded(self.thrs, np.inf),
                padded(self.path_node), padded(self.path_dir, False),
                padded(self.path_ok, False), padded(self.path_slot),
                padded(self.slot_feat), padded(self.slot_zero, 1.0),
                padded(self.leaf_udep), padded(self.leaf_val),
                padded(self.expval))
        if self.n_cat_nodes:
            tabs += (padded(self.cat_idx, self.max_nodes),
                     padded(self.cat_cols), padded(self.cat_words))
        dev = tuple(jnp.asarray(t.reshape((C, Tc) + t.shape[1:]))
                    for t in tabs)
        self._dev[key] = dev
        while len(self._dev) > 4:
            self._dev.popitem(last=False)
        return dev


def _shap_paths(t: Tree):
    """Per model leaf id: the root-to-leaf path as a list of
    (node, went_left, feature, zero_fraction) tuples.  Iterative DFS —
    chain trees exceed Python's recursion limit."""
    L = max(t.num_leaves, 1)
    out: List[list] = [[] for _ in range(L)]
    if t.num_leaves <= 1:
        return out
    stack = [(0, [])]
    while stack:
        node, path = stack.pop()
        if node < 0:
            out[~node] = path
            continue
        nc = float(t.internal_count[node]) or 1.0
        f = int(t.split_feature[node])
        left, right = int(t.left_child[node]), int(t.right_child[node])

        def cc(c):
            return float(t.leaf_count[~c] if c < 0 else
                         t.internal_count[c])

        stack.append((right, path + [(node, False, f, cc(right) / nc)]))
        stack.append((left, path + [(node, True, f, cc(left) / nc)]))
    return out


def _leaf_slots(path):
    """Merge a path's duplicate features into unique slots the way the
    reference recursion does: the combined zero fraction multiplies
    later covers onto the earlier product, and the final slot order is
    the order of each feature's LAST occurrence (UnwindPath removes
    the old entry and ExtendPath re-appends at the end)."""
    zacc: Dict[int, float] = {}
    order: List[int] = []
    for _node, _left, f, z in path:
        if f in zacc:
            zacc[f] = z * zacc[f]
            order.remove(f)
        else:
            zacc[f] = z
        order.append(f)
    return order, zacc


def flatten_forest_shap(models: List[Tree],
                        num_tree_per_iteration: int = 1) -> ShapForest:
    """Pack ``models`` into the explanation engine's SoA tables (the
    cold host walk — boosters cache the result until the model
    mutates, the serve registry pins it per published fingerprint)."""
    from .predict import flatten_one_tree, _CAT_VARIANT, N_VARIANTS
    _tele_counters.incr("shap_flatten_builds")
    T = len(models)
    k = max(num_tree_per_iteration, 1)
    tflats = [flatten_one_tree(t) for t in models]
    tpaths = [_shap_paths(t) for t in models]
    tslots = [[_leaf_slots(p) for p in paths] for paths in tpaths]

    M = max([max(f.ni, 1) for f in tflats] or [1])
    Lm = max([f.num_leaves for f in tflats] or [1])
    P = max([len(p) for paths in tpaths for p in paths] or [1])
    P = max(P, 1)
    D = max([len(o) for slots in tslots for o, _ in slots] or [1])
    D = max(D, 1)
    # pad the layout dims to power-of-two buckets (floor 8): the
    # kernel masks every padded node / path position / slot / leaf
    # (``path_ok`` / ``udep`` / ``svalid``), so real-leaf arithmetic
    # is bitwise unchanged while near-identical forests — e.g. two
    # swap targets trained with the same hyper-parameters — land on
    # ONE compile key and hot-swaps stay compile-flat (pinned by
    # ``tests/test_serve.py``)
    M, Lm, P, D = (_pow2_dim(v) for v in (M, Lm, P, D))
    Mc = max([len(f.cat_nodes) for f in tflats] or [0])
    nw64 = max([len(w) for f in tflats for w in f.cat_words] or [1])

    used = set()
    num_features = 1
    requires_features = 0
    for f in tflats:
        if f.ni:
            num_features = max(num_features, f.max_feature)
            requires_features = num_features
            used.update(int(v) for v in np.unique(f.var[~f.is_cat]))
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

    cols = np.zeros((T, M), np.int32)
    thrs = np.full((T, M), np.inf, np.float64)
    cat_idx = np.full((T, max(Mc, 1)), M, np.int32)
    cat_cols = np.zeros((T, max(Mc, 1)), np.int32)
    cat_words = np.zeros((T, max(Mc, 1), nw64), np.int64)
    path_node = np.zeros((T, Lm, P), np.int32)
    path_dir = np.zeros((T, Lm, P), bool)
    path_ok = np.zeros((T, Lm, P), bool)
    path_slot = np.zeros((T, Lm, P), np.int32)
    slot_feat = np.zeros((T, Lm, D), np.int32)
    slot_zero = np.ones((T, Lm, D), np.float64)
    leaf_udep = np.zeros((T, Lm), np.int32)
    leaf_val = np.zeros((T, Lm), np.float64)
    expval = np.zeros(T, np.float64)

    for i, (f, paths, slots) in enumerate(zip(tflats, tpaths, tslots)):
        t = models[i]
        expval[i] = _expected_value(t)
        L = t.num_leaves
        leaf_val[i, :max(L, 1)] = np.asarray(t.leaf_value[:max(L, 1)],
                                             np.float64)
        if f.ni:
            num = ~f.is_cat
            cols[i, :f.ni] = np.where(num, base_lut[f.var] + f.feats, 0)
            thrs[i, :f.ni][num] = f.thrs[num]
            for j, nd in enumerate(f.cat_nodes):
                cat_idx[i, j] = nd
                cat_cols[i, j] = base_lut[_CAT_VARIANT] + f.feats[nd]
                w64 = np.zeros(nw64, np.uint64)
                w64[:len(f.cat_words[j])] = f.cat_words[j]
                cat_words[i, j] = w64.view(np.int64)
        for leaf, (path, (order, zacc)) in enumerate(zip(paths, slots)):
            slot_of = {fe: s for s, fe in enumerate(order)}
            leaf_udep[i, leaf] = len(order)
            for s, fe in enumerate(order):
                slot_feat[i, leaf, s] = fe
                slot_zero[i, leaf, s] = zacc[fe]
            for p, (node, left, fe, _z) in enumerate(path):
                path_node[i, leaf, p] = node
                path_dir[i, leaf, p] = left
                path_ok[i, leaf, p] = True
                path_slot[i, leaf, p] = slot_of[fe]

    return ShapForest(
        n_trees=T, k=k, num_features=num_features, max_nodes=M,
        max_leaves=Lm, max_path=P, max_unique=D, n_cat_nodes=Mc,
        n_cat_words=nw64, used_variants=used_variants, cols=cols,
        thrs=thrs, cat_idx=cat_idx, cat_cols=cat_cols,
        cat_words=cat_words, path_node=path_node, path_dir=path_dir,
        path_ok=path_ok, path_slot=path_slot, slot_feat=slot_feat,
        slot_zero=slot_zero, leaf_udep=leaf_udep, leaf_val=leaf_val,
        expval=expval, requires_features=requires_features)


def _make_contrib_kernel(st):
    """Jitted (k, F+1, B) contribution kernel for one static layout.

    ``st`` is the static key tuple — see :meth:`ShapEngine._key`.
    Arithmetic mirrors the host reference's evaluation order (the
    EXTEND recurrence and UNWOUND-sum loops use the same operand
    grouping), so duplicate-free paths reproduce the host bitwise;
    leaf/chunk accumulation order differs only by commutative adds.
    """
    import jax
    import jax.numpy as jnp

    (B, C, Tc, M, Mc, P, D, Lm, nw64, k, used, F) = st

    def contrib_fn(xmat, tabs):
        global TRACE_COUNT
        TRACE_COUNT += 1
        tarange = jnp.arange(Tc)[:, None]
        jv = jnp.arange(D + 1, dtype=jnp.float64)

        def chunk_fn(carry, x):
            (ncols, nthrs, pnode, pdir, pok, pslot, sfeat, szero,
             udep, lval, expv) = x[:11]
            # decision bits ("row goes left") at every internal node
            dec = xmat[ncols] <= nthrs[:, :, None]         # (Tc, M, B)
            if Mc:
                cat_i, cat_c, cat_w = x[11], x[12], x[13]
                ic = xmat[cat_c].astype(jnp.int64)         # (Tc, Mc, B)
                widx = ic >> 6
                word = jnp.zeros(ic.shape, jnp.int64)
                for wj in range(nw64):
                    word = jnp.where(widx == wj, cat_w[:, :, wj, None],
                                     word)
                cdec = ((word >> (ic & 63)) & 1) == 1
                dec = dec.at[tarange, cat_i, :].set(cdec, mode="drop")

            def leaf_fn(phi, lx):
                pn, pd_, pv, ps, sf, sz, ud, lv = lx
                # one fraction per unique slot: every path position of
                # the slot's feature must go the way the path went
                fol = jnp.take_along_axis(
                    dec, pn[:, :, None].astype(jnp.int32), axis=1)
                bad = jnp.where(pv[:, :, None],
                                (fol != pd_[:, :, None]).astype(
                                    jnp.float64), 0.0)
                badc = jnp.zeros((Tc, D, B)).at[tarange, ps, :].add(
                    bad, mode="drop")
                one = (badc == 0.0).astype(jnp.float64)    # (Tc, D, B)
                udn = ud[:, None, None]
                udf = ud.astype(jnp.float64)[:, None, None]
                # EXTEND: pweight DP, vectorized over the pweight
                # index; same operand grouping as the host _extend
                p = jnp.zeros((Tc, D + 1, B)).at[:, 0, :].set(1.0)
                for i in range(1, D + 1):
                    z = sz[:, i - 1][:, None, None]
                    o = one[:, i - 1][:, None, :]
                    psh = jnp.concatenate(
                        [jnp.zeros((Tc, 1, B)), p[:, :-1, :]], axis=1)
                    pn_ = (o * psh * jv[None, :, None]) / float(i + 1) \
                        + (z * p * (float(i) - jv)[None, :, None]) / \
                        float(i + 1)
                    p = jnp.where(i <= udn, pn_, p)
                # UNWOUND sums for all slots at once (the host loops
                # j from unique_depth-1 down to 0 per slot; the o/z
                # branch is slot-constant, so it vectorizes)
                pU = jnp.take_along_axis(p, udn.astype(jnp.int32),
                                         axis=1)
                n = jnp.broadcast_to(pU, (Tc, D, B))
                tot = jnp.zeros((Tc, D, B))
                svalid = jnp.arange(1, D + 1)[None, :, None] <= udn
                sz3 = sz[:, :, None]
                for j in range(D - 1, -1, -1):
                    live = (j < udn) & svalid
                    pj = p[:, j, :][:, None, :]
                    t_ = (n * (udf + 1.0)) / (float(j + 1) * one)
                    tz = (pj * (udf + 1.0)) / (sz3 * (udf - float(j)))
                    tot = tot + jnp.where(
                        live, jnp.where(one == 1.0, t_, tz), 0.0)
                    n = jnp.where(
                        live & (one == 1.0),
                        pj - ((t_ * sz3) * (udf - float(j))) /
                        (udf + 1.0), n)
                w = jnp.where(svalid, tot, 0.0)
                d = (w * (one - sz3)) * lv[:, None, None]
                phi = phi.at[tarange, sf, :].add(
                    jnp.where(svalid, d, 0.0), mode="drop")
                return phi, None

            lxs = (pnode.swapaxes(0, 1), pdir.swapaxes(0, 1),
                   pok.swapaxes(0, 1), pslot.swapaxes(0, 1),
                   sfeat.swapaxes(0, 1), szero.swapaxes(0, 1),
                   udep.swapaxes(0, 1), lval.swapaxes(0, 1))
            phi = jnp.zeros((Tc, F, B))
            phi, _ = jax.lax.scan(leaf_fn, phi, lxs)
            out_phi, out_bias = carry
            contrib = phi.reshape(Tc // k, k, F, B).sum(axis=0)
            bias = expv.reshape(Tc // k, k).sum(axis=0)
            return (out_phi + contrib, out_bias + bias), None

        carry = (jnp.zeros((k, F, B)), jnp.zeros((k,)))
        (phi, bias), _ = jax.lax.scan(chunk_fn, carry, tabs)
        return jnp.concatenate(
            [phi, jnp.broadcast_to(bias[:, None, None], (k, 1, B))],
            axis=1)

    return jax.jit(contrib_fn)


class ShapEngine:
    """Shape-bucketed compile cache + host-side row chunking around the
    flattened contribution kernel — :class:`~.predict.PredictEngine`'s
    discipline applied to explanations."""

    def __init__(self, chunk_rows: int = _SHAP_CHUNK_ROWS,
                 tree_chunk: int = _SHAP_TREE_CHUNK,
                 cache_size: int = 16):
        self.chunk_rows = int(chunk_rows)
        self.tree_chunk = int(tree_chunk)
        self.cache_size = int(cache_size)
        self._cache: "OrderedDict[tuple, object]" = OrderedDict()
        self._cache_lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    # -- cache ---------------------------------------------------------
    def _compiled(self, key):
        with self._cache_lock:
            hit = self._cache.get(key)
            if hit is not None:
                self._cache.move_to_end(key)
                self.hits += 1
                _tele_counters.incr("shap_cache_hits")
                return hit
            self.misses += 1
            _tele_counters.incr("shap_cache_misses")
            kern = _make_contrib_kernel(key)
            self._cache[key] = kern
            while len(self._cache) > self.cache_size:
                self._cache.popitem(last=False)
                self.evictions += 1
                _tele_counters.incr("shap_cache_evictions")
            return kern

    def set_cache_size(self, n: int) -> None:
        n = max(int(n), 1)
        with self._cache_lock:
            self.cache_size = n
            while len(self._cache) > n:
                self._cache.popitem(last=False)
                self.evictions += 1
                _tele_counters.incr("shap_cache_evictions")

    def cache_info(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions,
                "entries": len(self._cache),
                "capacity": self.cache_size, "traces": TRACE_COUNT}

    # -- bucketing -----------------------------------------------------
    def _tree_chunk_for(self, flat: ShapForest) -> int:
        return max(self.tree_chunk // flat.k, 1) * flat.k

    def _max_chunk(self, flat: ShapForest,
                   chunk_rows: Optional[int] = None) -> int:
        Tc = self._tree_chunk_for(flat)
        per_row = 8 * (len(flat.used_variants) * flat.num_features
                       + Tc * (3 * (flat.max_unique + 1)
                               + flat.num_features)) \
            + Tc * (flat.max_nodes + flat.max_path)
        cap = _SHAP_BYTES_CAP // max(per_row, 1)
        cap = max(_SHAP_MIN_BUCKET,
                  1 << max(int(cap).bit_length() - 1, 0))
        return max(_SHAP_MIN_BUCKET,
                   min(chunk_rows or self.chunk_rows, cap))

    @staticmethod
    def _buckets(n: int, max_chunk: int):
        """(start, rows, padded_bucket) row chunks: full ``max_chunk``
        chunks, then one power-of-two remainder bucket."""
        pos = 0
        while n - pos >= max_chunk:
            yield pos, max_chunk, max_chunk
            pos += max_chunk
        if n - pos:
            rem = n - pos
            b = 1 << (rem - 1).bit_length()
            yield pos, rem, min(max(b, _SHAP_MIN_BUCKET), max_chunk)

    def bucket_set(self, flat: ShapForest,
                   chunk_rows: Optional[int] = None) -> List[int]:
        """Every padded row-bucket size an explain request can hit for
        this layout; the serve layer warms exactly this set so
        steady-state explains never compile."""
        mx = self._max_chunk(flat, chunk_rows)
        out = []
        b = _SHAP_MIN_BUCKET
        while b < mx:
            out.append(b)
            b <<= 1
        out.append(mx)
        return out

    def padded_rows(self, flat: ShapForest, n: int,
                    chunk_rows: Optional[int] = None) -> int:
        mx = self._max_chunk(flat, chunk_rows)
        return sum(b for _, _, b in self._buckets(n, mx))

    def _key(self, flat: ShapForest, B: int, n_trees: int, Tc: int):
        C = max((n_trees + Tc - 1) // Tc, 1)
        return (B, C, Tc, flat.max_nodes, flat.n_cat_nodes,
                flat.max_path, flat.max_unique, flat.max_leaves,
                flat.n_cat_words, flat.k, flat.used_variants,
                flat.num_features)

    # -- execution -----------------------------------------------------
    def predict_contrib(self, flat: ShapForest, X: np.ndarray,
                        n_trees: Optional[int] = None,
                        chunk_rows: Optional[int] = None) -> np.ndarray:
        """Per-row contributions, shape (k, num_features+1, rows) f64
        (last feature column is the bias/expected-value term)."""
        import jax
        import jax.numpy as jnp
        from .predict import _xmat_compiled, engine_device

        n_trees = flat.n_trees if n_trees is None else n_trees
        n = X.shape[0]
        if n_trees <= 0 or n == 0:
            return np.zeros((flat.k, flat.num_features + 1, n))
        if X.shape[1] < flat.requires_features:
            raise ValueError(
                f"input has {X.shape[1]} features but the model "
                f"references feature {flat.requires_features - 1}")
        Tc = self._tree_chunk_for(flat)
        max_chunk = self._max_chunk(flat, chunk_rows)
        outs = []
        with jax.default_device(engine_device()), jax.enable_x64(True):
            tabs = flat.device_tables(n_trees, Tc)
            xmat_fn = _xmat_compiled()
            for start, rows, B in self._buckets(n, max_chunk):
                key = self._key(flat, B, n_trees, Tc)
                kern = self._compiled(key)
                blk = X[start:start + rows, :flat.num_features]
                if rows != B or blk.shape[1] != flat.num_features:
                    pad = np.zeros((B, flat.num_features))
                    pad[:rows, :blk.shape[1]] = blk
                    blk = pad
                xt = jnp.asarray(np.ascontiguousarray(blk.T))
                xmat = xmat_fn(xt, flat.used_variants)
                # full padded output + host-side slice, same contract
                # as PredictEngine._run (device-side slicing compiles
                # per request size)
                r = np.asarray(kern(xmat, tabs))
                outs.append(r[:, :, :rows])
        return np.concatenate(outs, axis=2)


_SHAP_ENGINE: Optional[ShapEngine] = None


def get_shap_engine() -> ShapEngine:
    """Process-wide explanation engine (compile cache shared across
    boosters with identical layouts, like :func:`~.predict.get_engine`)."""
    global _SHAP_ENGINE
    if _SHAP_ENGINE is None:
        _SHAP_ENGINE = ShapEngine()
    return _SHAP_ENGINE
