"""The plain reference: histogram gradient boosting in numpy float64
and the C loops of ref_kernels.c, for the objective of the file under
``harness/objectives/`` that lists the trainer's ``objective``
(``cells.objective``): its ``init_score``, its float64 ``gradients``
and, where it has one, its ``loss``.  Query groups, where the data have
them, go to the objective.

It imports nothing of lightgbm_tpu and takes nothing the trainer made
but its answers (trees, training score), which it judges.  The same
grower, with its gradients cut to a lower precision or with a fault
planted, is put in the trainer's place as the control.

What is compared (``compare``), for each of the trainer's first steps:
the reference routes every raw row through the trainer's tree, recounts
its leaves, recomputes the root's hessian sum and each leaf's hessian
sum and Newton step from its own float64 gradients at the state the
trainer's earlier trees give, and grows its own best-first tree from
that same state to see what loss decrease a sound step buys (where the
objective has no loss, what decrease of its second-order model).  After
the window it re-scores a sample of rows through every tree and holds
the trainer's device score to that."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from . import cells, native

BIN_SAMPLE_ROWS = 200_000
SCORE_SAMPLE_ROWS = 65_536
_LEVELS = {"int8": 127, "int4": 7}


@dataclass
class TreeArrays:
    """One tree as plain arrays.  Internal node i splits on
    ``feature[i]`` at raw ``threshold[i]`` (<= goes left); a child
    below 0 is the leaf ``~child``."""
    feature: np.ndarray
    threshold: np.ndarray
    left: np.ndarray
    right: np.ndarray
    leaf_value: np.ndarray
    leaf_weight: np.ndarray
    leaf_count: np.ndarray
    root_weight: float          # the hessian sum the root was given

    @property
    def num_leaves(self) -> int:
        return len(self.leaf_value)

    def route(self, x: np.ndarray) -> np.ndarray:
        return native.route_rows(x, self.feature, self.threshold,
                                 self.left, self.right)


@dataclass
class Produced:
    """What a trainer hands over: its trees in order, and its training
    score at the rows asked for (raw margin, one per row)."""
    trees: List[TreeArrays]
    score: np.ndarray           # full training score, length N
    rows: int                   # rows the trainer says it trained on


@dataclass
class GrowParams:
    num_leaves: int
    learning_rate: float
    min_data_in_leaf: int
    min_sum_hessian_in_leaf: float
    lambda_l2: float
    max_bin: int

    @classmethod
    def from_config(cls, params: Dict) -> "GrowParams":
        return cls(int(params["num_leaves"]), float(params["learning_rate"]),
                   int(params.get("min_data_in_leaf", 20)),
                   float(params.get("min_sum_hessian_in_leaf", 1e-3)),
                   float(params.get("lambda_l2", 0.0)),
                   int(params.get("max_bin", 255)))


# ----------------------------------------------------------------------
# binning, precision
# ----------------------------------------------------------------------
def make_bins(x: np.ndarray, max_bin: int, seed: int):
    """Quantile bins from a seeded sample: (uppers (F, 256) padded with
    +inf, nbins (F,)).  A column with few distinct values gets a bin a
    value."""
    n, f = x.shape
    rng = np.random.default_rng([seed, 0xB1])
    take = np.sort(rng.choice(n, min(n, BIN_SAMPLE_ROWS), replace=False))
    sample = x[take]
    uppers = np.full((f, 256), np.inf)
    nbins = np.ones(f, np.int32)
    for j in range(f):
        vals, counts = np.unique(sample[:, j], return_counts=True)
        vals = vals.astype(np.float64)
        if len(vals) <= max_bin:
            cut = np.arange(len(vals) - 1)
        else:
            cum = np.cumsum(counts)
            want = cum[-1] * np.arange(1, max_bin) / max_bin
            cut = np.unique(np.minimum(np.searchsorted(cum, want),
                                       len(vals) - 2))
        mids = (vals[cut] + vals[cut + 1]) / 2.0
        uppers[j, :len(mids)] = mids
        nbins[j] = len(mids) + 1
    return uppers, nbins


def _to_bf16(a: np.ndarray) -> np.ndarray:
    u = a.astype(np.float32).view(np.uint32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & np.uint32(0xFFFF0000)
    return u.view(np.float32).astype(np.float64)


def cut_precision(g, h, precision: str, rng):
    """Gradients as a lower precision would hand them to the histograms."""
    if precision == "float64":
        return g, h
    if precision == "float32":
        return (g.astype(np.float32).astype(np.float64),
                h.astype(np.float32).astype(np.float64))
    if precision == "bfloat16":
        return _to_bf16(g), _to_bf16(h)
    levels = _LEVELS[precision]
    out = []
    for a in (g, h):
        scale = float(np.abs(a).max()) / levels or 1.0
        out.append(np.floor(a / scale + rng.random(len(a))) * scale)
    return out[0], out[1]


# ----------------------------------------------------------------------
# one tree, best first
# ----------------------------------------------------------------------
def _best_split(hist, nbins, p: GrowParams):
    tot = hist[0].sum(axis=0)
    G, H, n = tot
    c = np.cumsum(hist, axis=1)
    GL, HL, NL = c[..., 0], c[..., 1], c[..., 2]
    GR, HR, NR = G - GL, H - HL, n - NL
    ok = ((NL >= p.min_data_in_leaf) & (NR >= p.min_data_in_leaf)
          & (HL >= p.min_sum_hessian_in_leaf)
          & (HR >= p.min_sum_hessian_in_leaf)
          & (np.arange(256)[None, :] < nbins[:, None] - 1))
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = (GL * GL / (HL + p.lambda_l2) + GR * GR / (HR + p.lambda_l2)
                - G * G / (H + p.lambda_l2))
    gain = np.where(ok, gain, -np.inf)
    k = int(np.argmax(gain))
    f, b = divmod(k, 256)
    return float(gain[f, b]), f, b


def grow_tree(bins, uppers, nbins, g_hist, h_hist, g_leaf, h_leaf,
              p: GrowParams, rows: Optional[np.ndarray] = None):
    """Best-first growth with histogram subtraction.  Histograms sum
    (g_hist, h_hist); leaf values come from (g_leaf, h_leaf).  Returns
    (TreeArrays with values -lr G/(H + l2), leaf index of every row in
    ``rows``, -1 elsewhere)."""
    n_all = bins.shape[0]
    idx0 = (np.arange(n_all, dtype=np.int32) if rows is None
            else np.ascontiguousarray(rows, np.int32))
    hist0 = native.hist_rows(bins, idx0, g_hist, h_hist)
    leaves = [{"idx": idx0, "hist": hist0,
               "best": _best_split(hist0, nbins, p), "parent": -1}]
    feature, threshold, left, right = [], [], [], []
    while len(leaves) < p.num_leaves:
        k = max(range(len(leaves)), key=lambda i: leaves[i]["best"][0])
        gain, f, b = leaves[k]["best"]
        if not gain > 0.0:
            break
        leaf = leaves[k]
        li, ri = native.split_rows(bins, leaf["idx"], f, b)
        if len(li) <= len(ri):
            hl = native.hist_rows(bins, li, g_hist, h_hist)
            hr = leaf["hist"] - hl
        else:
            hr = native.hist_rows(bins, ri, g_hist, h_hist)
            hl = leaf["hist"] - hr
        node = len(feature)
        feature.append(f)
        threshold.append(uppers[f, b])
        left.append(~k)
        right.append(~len(leaves))
        par = leaf["parent"]
        if par >= 0:
            if left[par] == ~k:
                left[par] = node
            else:
                right[par] = node
        leaves[k] = {"idx": li, "hist": hl,
                     "best": _best_split(hl, nbins, p), "parent": node}
        leaves.append({"idx": ri, "hist": hr,
                       "best": _best_split(hr, nbins, p), "parent": node})
    leaf_of = np.full(n_all, -1, np.int32)
    for k, leaf in enumerate(leaves):
        leaf_of[leaf["idx"]] = k
    sel = leaf_of >= 0
    L = len(leaves)
    G = np.bincount(leaf_of[sel], g_leaf[sel], L)
    H = np.bincount(leaf_of[sel], h_leaf[sel], L)
    cnt = np.bincount(leaf_of[sel], minlength=L)
    tree = TreeArrays(np.array(feature, np.int32),
                      np.array(threshold, np.float64),
                      np.array(left, np.int32), np.array(right, np.int32),
                      -p.learning_rate * G / (H + p.lambda_l2), H, cnt,
                      float(hist0[0, :, 1].sum()))
    return tree, leaf_of


# ----------------------------------------------------------------------
# the reference in the trainer's place: the control and the faults
# ----------------------------------------------------------------------
def train_in_place(x, y, params: Dict, steps: int, seed: int,
                   hist_precision: str = "float64",
                   leaf_precision: str = "float64",
                   fault: Optional[str] = None,
                   group: Optional[np.ndarray] = None,
                   root: str = cells.ROOT) -> Produced:
    """The reference run as if it were the trainer, for ``steps``
    iterations.  ``fault`` is None, ``state_unchanged`` (the score is
    not updated after a step), ``half_batch`` (the second half of the
    rows, or of the queries where there are ``group``s, is left out) or
    ``altered_answer`` (one leaf value of the second tree is negated
    where it is produced)."""
    obj = cells.objective(params.get("objective"), root)
    p = GrowParams.from_config(params)
    uppers, nbins = make_bins(x, p.max_bin, seed)
    bins = native.bin_rows(x, uppers, nbins)
    n = len(y)
    # half_batch: the trees see the first half of the rows (of whole
    # queries) only; the trainer still says it trained on all of them
    # and scores them all
    rows, kept = None, group
    if fault == "half_batch":
        if group is not None:
            kept = group[:len(group) // 2]
        rows = np.arange(n // 2 if group is None else int(kept.sum()),
                         dtype=np.int32)
    bias = obj.init_score(y if rows is None else y[rows], kept, params)
    score = np.full(n, bias)
    rng = np.random.default_rng([seed, 0xC7])
    trees = []
    for k in range(steps):
        g, h = obj.gradients(score, y, group, params)
        gq, hq = cut_precision(g, h, hist_precision, rng)
        gl, hl = cut_precision(g, h, leaf_precision, rng)
        tree, leaf_of = grow_tree(bins, uppers, nbins, gq, hq, gl, hl, p,
                                  rows)
        if fault == "altered_answer" and k == 1:
            j = int(np.argmax(np.abs(tree.leaf_value)))
            tree.leaf_value[j] = -tree.leaf_value[j]
        if fault != "state_unchanged":
            if rows is not None:
                leaf_of = tree.route(x)
            score = score + tree.leaf_value[leaf_of]
        if k == 0:
            tree.leaf_value = tree.leaf_value + bias
        trees.append(tree)
    return Produced(trees, score.astype(np.float32), n)


# ----------------------------------------------------------------------
# the comparison
# ----------------------------------------------------------------------
def _worst_leaf(got, want) -> float:
    """Largest gap over the leaves, each against its own size or the
    median leaf's, whichever is larger."""
    want = np.asarray(want, np.float64)
    floor = float(np.median(np.abs(want)))
    denom = np.maximum(np.abs(want), floor)
    denom = np.where(denom > 0, denom, 1.0)
    return float(np.max(np.abs(np.asarray(got, np.float64) - want) / denom))


def _second_order(leaf_of, g, h, value) -> float:
    """The change sum_l (G_l v_l + H_l v_l^2 / 2) that leaf values v on
    a partition make to a loss of gradients g and hessians h."""
    L = len(value)
    return float(np.sum(np.bincount(leaf_of, g, L) * value
                        + 0.5 * np.bincount(leaf_of, h, L) * value * value))


def compare(produced: Produced, x, y, params: Dict, seed: int,
            steps: int, log=lambda s: None,
            group: Optional[np.ndarray] = None,
            root: str = cells.ROOT) -> Dict[str, float]:
    """The numbers that decide ``correct`` (module docstring).  Every
    one is 0 for a trainer that agrees with the reference exactly."""
    obj = cells.objective(params.get("objective"), root)
    loss = getattr(obj, "loss", None)
    p = GrowParams.from_config(params)
    n = len(y)
    out = {"rows_gap": abs(produced.rows - n) / n,
           "count_gap": 0.0, "root_hess_gap": 0.0, "leaf_value_gap": 0.0,
           "leaf_hess_gap": 0.0, "step_gain_gap": 0.0,
           "score_gap": float("inf")}
    if produced.rows != n or len(produced.score) != n \
            or len(produced.trees) < steps:
        # nothing below can be laid row against row
        out.update(count_gap=float("inf"), root_hess_gap=float("inf"),
                   leaf_value_gap=float("inf"), leaf_hess_gap=float("inf"),
                   step_gain_gap=float("inf"))
        return out
    uppers, nbins = make_bins(x, p.max_bin, seed)
    bins = native.bin_rows(x, uppers, nbins)
    bias = obj.init_score(y, group, params)
    score = np.full(n, bias)
    for k in range(steps):
        tree = produced.trees[k]
        off = bias if k == 0 else 0.0
        g, h = obj.gradients(score, y, group, params)
        leaf_of = tree.route(x)
        L = tree.num_leaves
        cnt = np.bincount(leaf_of, minlength=L)
        G = np.bincount(leaf_of, g, L)
        H = np.bincount(leaf_of, h, L)
        out["count_gap"] = max(
            out["count_gap"],
            float(np.abs(cnt - tree.leaf_count).sum()) / n)
        out["root_hess_gap"] = max(
            out["root_hess_gap"],
            abs(tree.root_weight - float(H.sum())) / float(H.sum()))
        want = -p.learning_rate * G / (H + p.lambda_l2)
        out["leaf_value_gap"] = max(out["leaf_value_gap"],
                                    _worst_leaf(tree.leaf_value - off, want))
        out["leaf_hess_gap"] = max(out["leaf_hess_gap"],
                                   _worst_leaf(tree.leaf_weight, H))
        after = score + (tree.leaf_value - off)[leaf_of]
        own, own_leaf = grow_tree(bins, uppers, nbins, g, h, g, h, p)
        if loss is not None:
            loss0 = loss(score, y, group, params)
            loss_p = loss(after, y, group, params)
            loss_r = loss(score + own.leaf_value[own_leaf], y, group,
                          params)
            gap = abs(loss_p - loss_r) / (loss0 - loss_r)
            log(f"reference step {k}: loss before {loss0:.9f}, trainer's "
                f"{loss_p:.9f}, reference's {loss_r:.9f} "
                f"(leaves {L} vs {own.num_leaves})")
        else:
            # no loss: each tree's own values on its own partition, in
            # the second-order model of the reference's gradients
            q_p = _second_order(leaf_of, g, h, tree.leaf_value - off)
            q_r = _second_order(own_leaf, g, h, own.leaf_value)
            gap = abs(q_p - q_r) / -q_r
            log(f"reference step {k}: second-order change, trainer's "
                f"{q_p:.9g}, reference's {q_r:.9g} "
                f"(leaves {L} vs {own.num_leaves})")
        out["step_gain_gap"] = max(out["step_gain_gap"], gap)
        score = after
    # the device's score against every tree the trainer produced
    rng = np.random.default_rng([seed, 0x5C])
    rows = np.sort(rng.choice(n, min(n, SCORE_SAMPLE_ROWS), replace=False))
    xs = np.ascontiguousarray(x[rows])
    model = np.zeros(len(rows))
    for tree in produced.trees:
        model += tree.leaf_value[tree.route(xs)]
    spread = float(np.sqrt(np.mean((model - bias) ** 2))) or 1.0
    got = np.asarray(produced.score, np.float64)[rows]
    out["score_gap"] = float(np.max(np.abs(got - model))) / spread
    return out
