"""Plain reference for histogram-GBDT training, in float64 NumPy.

It imports nothing of the program and takes from it only what the timed
path *produced*: the dumped trees (public ``Booster.dump_model()``) and, in
a cell with a held-out set, the recorded metric values.  From the raw float
matrix and the labels it then recomputes, tree by tree, what a correct
trainer must have produced, following its own scores:

- rows are routed by the tree's real-valued thresholds on the raw matrix, so
  a leaf's row count checks binning, threshold conversion and the partition
  update together;
- gradients and hessians of the binary log loss are taken from the
  reference's own scores, summed per leaf in float64, and give the leaf
  value ``-G / (H + lambda_l2) * learning_rate`` and each split's gain
  ``G_L^2/H_L + G_R^2/H_R - G_P^2/H_P``;
- the reference's scores advance by its *own* leaf values, so a program whose
  score update is wrong grows its next tree on other gradients and shows as a
  gap there;
- at the nodes nearest the root the reference searches splits itself
  (``best_exact_gains``): over every real threshold of a set of candidate
  features, from its own float64 gradients.  It has no bin table (that is
  the program's), so the search is one-sided: the program's split, whose
  gain the reference recomputes, may fall short of the best exact split by
  what 63 quantile bins lose and by no more.  A search that takes a weaker
  feature or the wrong end of a feature shows as a shortfall.

The control (``accumuland="bfloat16"``) is this same computation with every
gradient and hessian rounded to bfloat16 before it is summed: the nearest
precision below the float32 histograms the configuration states.
"""

from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

ROUTE_BLOCK = 1 << 20
ROUTE_THREADS = 8


@dataclass
class FlatTree:
    """One dumped tree as flat arrays; children < 0 are ``~leaf``."""
    split_feature: np.ndarray
    threshold: np.ndarray
    left: np.ndarray
    right: np.ndarray
    split_gain: np.ndarray
    internal_count: np.ndarray
    leaf_value: np.ndarray
    leaf_count: np.ndarray
    shrinkage: float

    @property
    def num_leaves(self) -> int:
        return len(self.leaf_value)


def flatten_tree(tree_info: dict) -> FlatTree:
    """A ``dump_model()["tree_info"][i]`` entry as a :class:`FlatTree`."""
    n_leaves = int(tree_info["num_leaves"])
    n_int = max(n_leaves - 1, 0)
    sf = np.zeros(n_int, np.int64)
    thr = np.zeros(n_int, np.float64)
    left = np.zeros(n_int, np.int64)
    right = np.zeros(n_int, np.int64)
    gain = np.zeros(n_int, np.float64)
    icount = np.zeros(n_int, np.int64)
    lv = np.zeros(n_leaves, np.float64)
    lc = np.zeros(n_leaves, np.int64)

    def ref(node: dict) -> int:
        return ~int(node["leaf_index"]) if "leaf_index" in node \
            else int(node["split_index"])

    stack = [tree_info["tree_structure"]]
    while stack:
        node = stack.pop()
        if "split_index" not in node:
            leaf = int(node.get("leaf_index", 0))
            lv[leaf] = node["leaf_value"]
            lc[leaf] = node.get("leaf_count", 0)
            continue
        if node["decision_type"] != "<=":
            raise ValueError("the reference routes numerical splits only")
        i = int(node["split_index"])
        sf[i], thr[i] = node["split_feature"], node["threshold"]
        gain[i], icount[i] = node["split_gain"], node["internal_count"]
        left[i], right[i] = ref(node["left_child"]), ref(node["right_child"])
        stack += [node["left_child"], node["right_child"]]
    return FlatTree(sf, thr, left, right, gain, icount, lv, lc,
                    float(tree_info.get("shrinkage", 1.0)))


def route(tree: FlatTree, x: np.ndarray, rows=None) -> np.ndarray:
    """Leaf index of every row of ``x``, or of its rows ``rows`` in their
    order (no missing values)."""
    n = len(x) if rows is None else len(rows)
    out = np.zeros(n, np.int32)
    if tree.num_leaves <= 1:
        return out
    flat = x.reshape(-1)
    f = x.shape[1]

    def block(lo: int) -> None:
        hi = min(n, lo + ROUTE_BLOCK)
        rows_ = np.arange(lo, hi, dtype=np.int64) if rows is None \
            else np.asarray(rows[lo:hi], np.int64)
        node = np.zeros(hi - lo, np.int64)
        live = np.arange(hi - lo)
        while len(live):
            nd = node[live]
            v = flat[rows_[live] * f + tree.split_feature[nd]]
            nxt = np.where(v <= tree.threshold[nd], tree.left[nd],
                           tree.right[nd])
            node[live] = nxt
            live = live[nxt >= 0]
        out[lo:hi] = ~node

    with ThreadPoolExecutor(ROUTE_THREADS) as pool:
        list(pool.map(block, range(0, n, ROUTE_BLOCK)))
    return out


def round_bfloat16(a: np.ndarray) -> np.ndarray:
    """Round float values to the nearest bfloat16 (ties to even)."""
    bits = np.asarray(a, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


def binary_grad_hess(score: np.ndarray, y: np.ndarray):
    p = 1.0 / (1.0 + np.exp(-score))
    return p - y, p * (1.0 - p)


def init_score(y: np.ndarray) -> float:
    """``boost_from_average`` of the binary objective: the log odds."""
    p = float(np.mean(y, dtype=np.float64))
    return float(np.log(p / (1.0 - p)))


def auc(y: np.ndarray, score: np.ndarray) -> float:
    """Rank-sum AUC in float64, ties given their mean rank."""
    order = np.argsort(score, kind="stable")
    s = score[order]
    starts = np.flatnonzero(np.r_[True, s[1:] != s[:-1]])
    ends = np.r_[starts[1:], len(s)]
    mean_rank = (starts + 1 + ends) / 2.0
    rank = np.repeat(mean_rank, ends - starts)
    pos = y[order] > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return float((rank[pos].sum() - n_pos * (n_pos + 1) / 2.0)
                 / (n_pos * n_neg))


def _node_sums(tree: FlatTree, leaf_sum: np.ndarray) -> np.ndarray:
    """Per-internal-node (left, right) sums from per-leaf sums.  A split's
    children are numbered after it, so one pass from the last node up."""
    n_int = len(tree.left)
    out = np.zeros((n_int, 2), np.float64)
    for i in range(n_int - 1, -1, -1):
        for side, kid in enumerate((int(tree.left[i]), int(tree.right[i]))):
            if kid >= 0 and kid <= i:
                raise ValueError("child numbered before its parent")
            out[i, side] = leaf_sum[~kid] if kid < 0 else out[kid].sum()
    return out


def rel_gap(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """``|got - want|`` against ``|want|`` or the median ``|want|``,
    whichever is larger: some leaves' values are all but zero."""
    want = np.asarray(want, np.float64)
    scale = np.maximum(np.abs(want), np.median(np.abs(want)))
    return np.abs(np.asarray(got, np.float64) - want) / scale


def _node_leaves(tree: FlatTree) -> np.ndarray:
    """``[internal node, leaf]`` mask of the leaves under each node."""
    n_int = len(tree.left)
    under = np.zeros((n_int, tree.num_leaves), bool)
    for i in range(n_int - 1, -1, -1):
        for kid in (int(tree.left[i]), int(tree.right[i])):
            if kid < 0:
                under[i, ~kid] = True
            else:
                under[i] |= under[kid]
    return under


def node_depths(tree: FlatTree) -> np.ndarray:
    """Depth of every internal node; the root (node 0) has depth 0."""
    depth = np.zeros(len(tree.left), np.int64)
    for i in range(len(tree.left)):
        for kid in (int(tree.left[i]), int(tree.right[i])):
            if kid >= 0:
                depth[kid] = depth[i] + 1
    return depth


def best_exact_gains(x, rows, g, h, features, *, min_hess: float,
                     min_data: int, lambda_l2: float = 0.0) -> np.ndarray:
    """For each of ``features`` the best gain of a split ``value <= t`` of
    the node that holds ``rows`` of ``x``, over every real threshold between
    two distinct values, with both children holding at least ``min_hess``
    of hessian and ``min_data`` rows.  ``g`` and ``h`` are the node's rows'
    float64 gradients and hessians, in the order of ``rows``.  ``-inf``
    where a feature admits no split."""
    m = len(rows)
    base = np.asarray(rows, np.int64) * x.shape[1]
    flat = x.reshape(-1)
    gt, ht = float(g.sum()), float(h.sum())
    parent = gt * gt / (ht + lambda_l2)

    def one(f: int) -> float:
        v = flat[base + int(f)]
        order = np.argsort(v, kind="stable")
        vs = v[order]
        cut = np.flatnonzero(vs[1:] != vs[:-1])     # left holds cut + 1 rows
        if not len(cut):
            return -np.inf
        gl, hl = np.cumsum(g[order])[cut], np.cumsum(h[order])[cut]
        gr, hr = gt - gl, ht - hl
        ok = (hl >= min_hess) & (hr >= min_hess) \
            & (cut + 1 >= min_data) & (m - cut - 1 >= min_data)
        if not ok.any():
            return -np.inf
        gain = gl[ok] ** 2 / (hl[ok] + lambda_l2) \
            + gr[ok] ** 2 / (hr[ok] + lambda_l2) - parent
        return float(gain.max())

    with ThreadPoolExecutor(ROUTE_THREADS) as pool:
        return np.array(list(pool.map(one, features)), np.float64)


def follow(trees, x, y, *, learning_rate: float, lambda_l2: float = 0.0,
           rows=None, x_valid=None, y_valid=None, valid_rows=None,
           accumuland: str = "float64", split_check=None):
    """Follow ``trees`` (a list of :class:`FlatTree`) from iteration 0 on
    the rows ``rows`` of ``x`` and ``y`` (all of them where ``rows`` is None).

    Returns one dict per tree with the reference's own leaf counts, leaf
    values and split gains beside the gaps of the dumped ones, and, with a
    held-out set (``x_valid``, ``y_valid`` and the rows ``valid_rows`` of
    them), the reference's AUC after that tree.  With
    ``accumuland="bfloat16"`` the returned ``value`` (a leaf's value without
    the first tree's bias) and ``gain`` are the control's; the caller measures
    them against a float64 follow.

    ``split_check`` (``{"depth", "features", "min_hess", "min_data"}``) adds
    ``split_shortfall``: at every split no deeper than ``depth``, the share
    by which the dumped split's gain, as the reference recomputes it, falls
    short of the best exact split among ``features`` and the split's own
    feature; and ``runner_up``, the same share for the best *other* feature
    (what a search that took the second-best feature would read).
    """
    y_all = np.asarray(y)
    y64 = (y_all if rows is None else y_all[rows]).astype(np.float64)
    n = len(y64)
    rows_ = np.arange(n, dtype=np.int64) if rows is None \
        else np.asarray(rows, np.int64)
    s0 = init_score(y64)
    score = np.full(n, s0, np.float64)
    v_score = yv64 = None
    if x_valid is not None:
        yv = np.asarray(y_valid)
        yv64 = (yv if valid_rows is None else yv[valid_rows]) \
            .astype(np.float64)
        v_score = np.full(len(yv64), s0, np.float64)
    out = []
    for t, tree in enumerate(trees):
        g, h = binary_grad_hess(score, y64)
        if accumuland == "bfloat16":
            g, h = round_bfloat16(g), round_bfloat16(h)
        elif accumuland != "float64":
            raise ValueError(accumuland)
        leaf = route(tree, x, rows)
        nl = tree.num_leaves
        count = np.bincount(leaf, minlength=nl)
        gs = np.bincount(leaf, weights=g, minlength=nl)
        hs = np.bincount(leaf, weights=h, minlength=nl)
        value = -gs / (hs + lambda_l2) * learning_rate
        g_lr, h_lr = _node_sums(tree, gs), _node_sums(tree, hs)
        gp, hp = g_lr.sum(1), h_lr.sum(1)
        gain = (g_lr ** 2 / (h_lr + lambda_l2)).sum(1) \
            - gp ** 2 / (hp + lambda_l2)
        bias = s0 if t == 0 else 0.0      # Tree::AddBias on the first tree
        rec = {"leaf_count": count, "value": value, "gain": gain,
               "count_gap": float(np.abs(count - tree.leaf_count).max()),
               "leaf_gap": rel_gap(tree.leaf_value - bias, value),
               "gain_gap": rel_gap(tree.split_gain, gain)}
        if split_check is not None and len(tree.left):
            under = _node_leaves(tree)
            short, runner = [], []
            for i in np.flatnonzero(node_depths(tree)
                                    <= split_check["depth"]):
                own = int(tree.split_feature[i])
                feats = np.union1d(split_check["features"], [own])
                at = np.flatnonzero(under[i][leaf])
                best = best_exact_gains(
                    x, rows_[at], g[at], h[at], feats,
                    min_hess=split_check["min_hess"],
                    min_data=split_check["min_data"], lambda_l2=lambda_l2)
                top = float(best.max())
                short.append((top - gain[i]) / top)
                runner.append((top - float(best[feats != own].max())) / top)
            rec["split_shortfall"] = np.array(short)
            rec["runner_up"] = np.array(runner)
        score += value[leaf]
        if v_score is not None:
            v_score += value[route(tree, x_valid, valid_rows)]
            rec["auc"] = auc(yv64, v_score)
        out.append(rec)
    return out
