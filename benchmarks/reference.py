"""Plain reference for histogram-GBDT training, in float64 NumPy.

It imports nothing of the program and takes from it only what the timed
path *produced*: the dumped trees (public ``Booster.dump_model()``) and, in
a cell with a held-out set, the recorded metric values.  From the raw float
matrix and the labels it then recomputes, tree by tree, what a correct
trainer must have produced, following its own scores:

- rows are routed by the tree's real-valued thresholds on the raw matrix, and
  a row whose value is missing (NaN) by what each node's dump says of it
  (``missing_type`` and ``default_left``: upstream's ``NumericalDecision``),
  so a leaf's row count checks binning, threshold conversion, the default
  directions and the partition update together;
- gradients and hessians of the binary log loss are taken from the
  reference's own scores, summed per leaf in float64, and give the leaf
  value ``-G / (H + lambda_l2) * learning_rate`` and each split's gain
  ``G_L^2/H_L + G_R^2/H_R - G_P^2/H_P``;
- the reference's scores advance by its *own* leaf values, so a program whose
  score update is wrong grows its next tree on other gradients and shows as a
  gap there;
- at the nodes nearest the root the reference searches splits itself
  (``best_exact_gains``): over every real threshold of a set of candidate
  features, with a node's missing rows placed on either side, from its own
  float64 gradients.  It has no bin table (that is the program's), so the
  search is one-sided: the program's split, whose gain the reference
  recomputes under the node's own direction, may fall short of the best
  exact split by what 63 quantile bins lose and by no more.  A search that
  takes a weaker feature, the wrong end of a feature or the wrong side for
  its missing rows shows as a shortfall.

Nothing selects any of this: the trees say how they route, the data says
whether it has holes, and a table without NaN takes the same arithmetic
with or without them.

The control (``accumuland="bfloat16"``) is this same computation with every
gradient and hessian rounded to bfloat16 before it is summed: the nearest
precision below the float32 histograms the configuration states.
"""

import threading
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

# A split whose gain, by the reference's own float64 sums, is under this share
# of its parent's term G_P^2 / H_P is nought to rounding: every row of the node
# has all but the same gradient (a node of one class under a rare label), no
# split of it gains anything, and a float32 program's recorded gain there is
# the rounding of three terms that cancel.  Such a split is followed (its
# rows, its leaves' values) and left out of the gains compared.
NOUGHT = 1e-6
ROUTE_BLOCK = 1 << 20
ROUTE_THREADS = 8
SEARCH_THREADS = 8      # over the nodes searched at once, by their rows


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
    default_left: np.ndarray    # bool a node: its missing rows go left
    missing_type: np.ndarray    # "NaN" a node that routes them so, or "None"

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
    dleft = np.zeros(n_int, bool)
    missing = np.full(n_int, "None", "<U4")
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
        if node["missing_type"] not in ("None", "NaN"):
            raise ValueError("the reference routes missing values of the "
                             f"types None and NaN, not {node['missing_type']}")
        i = int(node["split_index"])
        dleft[i], missing[i] = node["default_left"], node["missing_type"]
        sf[i], thr[i] = node["split_feature"], node["threshold"]
        gain[i], icount[i] = node["split_gain"], node["internal_count"]
        left[i], right[i] = ref(node["left_child"]), ref(node["right_child"])
        stack += [node["left_child"], node["right_child"]]
    return FlatTree(sf, thr, left, right, gain, icount, lv, lc,
                    float(tree_info.get("shrinkage", 1.0)), dleft, missing)


def route(tree: FlatTree, x: np.ndarray, rows=None) -> np.ndarray:
    """Leaf index of every row of ``x``, or of its rows ``rows`` in their
    order, as upstream's ``NumericalDecision`` decides it: ``value <=
    threshold`` goes left; a NaN goes where the node's ``default_left`` says
    at a node of ``missing_type`` "NaN", and is taken as 0.0 at one of
    "None"."""
    n = len(x) if rows is None else len(rows)
    out = np.zeros(n, np.int32)
    if tree.num_leaves <= 1:
        return out
    flat = x.reshape(-1)
    f = x.shape[1]
    nan_left = np.where(tree.missing_type == "NaN", tree.default_left,
                        0.0 <= tree.threshold)

    def block(lo: int) -> None:
        hi = min(n, lo + ROUTE_BLOCK)
        rows_ = np.arange(lo, hi, dtype=np.int64) if rows is None \
            else np.asarray(rows[lo:hi], np.int64)
        node = np.zeros(hi - lo, np.int64)
        live = np.arange(hi - lo)
        while len(live):
            nd = node[live]
            v = flat[rows_[live] * f + tree.split_feature[nd]]
            go_left = v <= tree.threshold[nd]
            nan = np.isnan(v)
            if nan.any():
                go_left[nan] = nan_left[nd[nan]]
            nxt = np.where(go_left, tree.left[nd], tree.right[nd])
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
    if not len(want):
        return np.zeros(0)
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


def exact_gains(x, rows, g, h, features, *, min_hess: float, min_data: int,
                lambda_l2: float = 0.0,
                threads: int = ROUTE_THREADS) -> np.ndarray:
    """For each of ``features`` the best gain of a split ``value <= t`` of
    the node that holds ``rows`` of ``x``, over every real threshold between
    two distinct values, with both children holding at least ``min_hess``
    of hessian and ``min_data`` rows: ``[feature, 0]`` with the node's
    missing rows (NaN) placed right, where the cut after the last real value
    sets all real rows against the missing ones, and ``[feature, 1]`` with
    them placed left.  ``g`` and ``h`` are the node's rows' float64
    gradients and hessians, in the order of ``rows``.  ``-inf`` where a
    feature admits no split under a placement, and under the left one where
    the node has no missing row: it is the right one's splits over again."""
    m = len(rows)
    rows = np.asarray(rows, np.int64)
    gt, ht = float(g.sum()), float(h.sum())
    parent = gt * gt / (ht + lambda_l2)
    if m < 2:
        return np.full((len(features), 2), -np.inf)
    iota = np.arange(m, dtype=np.uint64)
    local = threading.local()

    def best_cut(vs, gl, hl, first: int, last: int) -> float:
        """The best gain over the cuts after sorted positions ``first <= i
        < last`` that part distinct values and leave ``min_hess`` on both
        sides; ``gl`` and ``hl`` hold the left side's sums at every cut and
        are written over."""
        gain, ok, also = local.buffers[6:9]
        np.not_equal(vs[1:], vs[:-1], out=ok)
        ok &= np.greater_equal(hl[:-1], min_hess, out=also)
        ok &= np.less_equal(hl[:-1], ht - min_hess, out=also)
        ok[:max(0, first)] = False
        ok[max(0, last):] = False
        if not ok.any():
            return -np.inf
        with np.errstate(divide="ignore", invalid="ignore"):
            np.add(hl, lambda_l2, out=hl)
            np.multiply(gl, gl, out=gain)
            np.divide(gain, hl, out=gain)
            np.subtract(gl, gt, out=gl)               # minus the right's sum
            np.multiply(gl, gl, out=gl)
            np.subtract(ht + 2 * lambda_l2, hl, out=hl)   # hr + lambda_l2
            np.divide(gl, hl, out=gl)
            np.add(gain, gl, out=gain)
        np.putmask(gain[:-1], np.logical_not(ok, out=ok), -np.inf)
        return float(gain[:-1].max() - parent)

    def one(f: int):
        # every array is one of the thread's own buffers, made once and
        # written over for each column: at 8.4M rows a column's dozen
        # temporaries are 30 to 70 MB each, and the chip's machine takes
        # memory back far more slowly than a dozen threads map and unmap it
        if not hasattr(local, "buffers"):
            local.buffers = (
                np.empty(m, np.float32), np.empty(m, np.float32),
                np.empty(m, np.uint64), np.empty(m, np.int64),
                np.empty(m), np.empty(m), np.empty(m),
                np.empty(m - 1, bool), np.empty(m - 1, bool),
                np.empty(m, bool))
        v, vs, key, idx, gl, hl, _, _, _, nan = local.buffers
        np.take(x[:, int(f)], rows, out=v, mode="clip")
        # the missing rows are made the order's tail: a NaN with its sign
        # bit clear sorts past +inf (one with it set, before -inf)
        k = int(np.count_nonzero(np.isnan(v, out=nan)))
        if k:
            np.putmask(v, nan, np.float32(np.nan))
        # sorted in place: the value's bits made monotone in the high half
        # of a key, the row's position in the low half (any order among
        # equal values gives the same sums at the cuts between distinct ones)
        bits = idx.view(np.uint64)
        np.copyto(key, v.view(np.uint32))
        np.right_shift(key, 31, out=bits)
        np.multiply(bits, 0x7FFFFFFF, out=bits)
        np.bitwise_or(bits, 0x80000000, out=bits)
        np.bitwise_xor(key, bits, out=key)
        np.left_shift(key, 32, out=key)
        np.bitwise_or(key, iota, out=key)
        key.sort()
        np.bitwise_and(key, 0xFFFFFFFF, out=bits)     # idx: the sorted order
        np.take(v, idx, out=vs, mode="clip")
        np.take(g, idx, out=gl, mode="clip")
        np.take(h, idx, out=hl, mode="clip")
        real = m - k
        missing = (float(gl[real:].sum()), float(hl[real:].sum()))
        np.cumsum(gl, out=gl)
        np.cumsum(hl, out=hl)
        # a cut after sorted position i leaves i + 1 rows on the left, and
        # with the k missing rows placed there i + 1 + k, of real - 1 - i
        placed_left = -np.inf
        if k:
            if not hasattr(local, "more"):
                local.more = (np.empty(m), np.empty(m))
            placed_left = best_cut(
                vs, np.add(gl, missing[0], out=local.more[0]),
                np.add(hl, missing[1], out=local.more[1]),
                min_data - 1 - k, min(real - min_data, real - 1))
        return best_cut(vs, gl, hl, min_data - 1,
                        min(m - min_data, real)), placed_left

    with ThreadPoolExecutor(threads) as pool:
        return np.array(list(pool.map(one, features)),
                        np.float64).reshape(len(features), 2)


def best_exact_gains(x, rows, g, h, features, **kw) -> np.ndarray:
    """For each of ``features`` the best gain of :func:`exact_gains` under
    either placement of the node's missing rows."""
    return exact_gains(x, rows, g, h, features, **kw).max(axis=1)


def follow(trees, x, y, *, learning_rate: float, lambda_l2: float = 0.0,
           rows=None, x_valid=None, y_valid=None, valid_rows=None,
           accumuland: str = "float64", split_check=None):
    """Follow ``trees`` (a list of :class:`FlatTree`) from iteration 0 on
    the rows ``rows`` of ``x`` and ``y`` (all of them where ``rows`` is None).

    Returns one dict per tree with the reference's own leaf counts, leaf
    values and split gains beside the gaps of the dumped ones (the gains'
    over the splits ``gains_compared``: those that gain more than ``NOUGHT``
    of their parent's term), and, with a held-out set (``x_valid``,
    ``y_valid`` and the rows ``valid_rows`` of them), the reference's AUC
    after that tree.  With
    ``accumuland="bfloat16"`` the returned ``value`` (a leaf's value without
    the first tree's bias) and ``gain`` are the control's; the caller measures
    them against a float64 follow.

    ``split_check`` (``{"depth", "features", "min_hess", "min_data"}``) adds
    ``split_shortfall``: at every split no deeper than ``depth``, the share
    by which the dumped split's gain, as the reference recomputes it, falls
    short of the best exact split among ``features`` and the split's own
    feature; ``runner_up``, the same share for the best *other* feature
    (what a search that took the second-best feature would read); and
    ``missing_right_only``, the same share for the best split with the node's
    missing rows placed right (what a search that never placed them left
    would read; 0 at a node without a missing row).  The dumped split's gain
    is recomputed under the node's own direction, so the shortfall covers
    the choice of feature, threshold and direction.
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
        nought = NOUGHT * gp ** 2 / (hp + lambda_l2)
        some = np.abs(gain) > nought
        rec = {"leaf_count": count, "value": value, "gain": gain,
               "gains_compared": some,
               "count_gap": float(np.abs(count - tree.leaf_count).max()),
               "leaf_gap": rel_gap(tree.leaf_value - bias, value),
               "gain_gap": rel_gap(tree.split_gain[some], gain[some])}
        nodes = np.flatnonzero(node_depths(tree) <= split_check["depth"]) \
            if split_check is not None else []
        if len(nodes):
            under = _node_leaves(tree)
            at = [np.flatnonzero(under[i][leaf]) for i in nodes]

            def search(k: int):
                """Node ``nodes[k]``'s shortfall, its runner-up's and that
                of the best split that places the missing rows right; None
                where no split of the node gains more than rounding."""
                i, own = nodes[k], int(tree.split_feature[nodes[k]])
                feats = np.union1d(split_check["features"], [own])
                placed = exact_gains(
                    x, rows_[at[k]], g[at[k]], h[at[k]], feats,
                    min_hess=split_check["min_hess"],
                    min_data=split_check["min_data"], lambda_l2=lambda_l2,
                    threads=max(1, round(SEARCH_THREADS * len(at[k])
                                         / sum(map(len, at)))))
                best = placed.max(axis=1)
                top = float(best.max())
                if not top > nought[i]:
                    return None
                return ((top - gain[i]) / top,
                        (top - float(best[feats != own].max())) / top,
                        (top - float(placed[:, 0].max())) / top)

            # the nodes side by side, each with threads by its share of
            # the rows: a column's sort and sums take a thread ~2 s at 8.4M
            # rows, and what they hold at once is by the threads, not the
            # nodes
            with ThreadPoolExecutor(len(nodes)) as pool:
                found = [at_node for at_node in pool.map(
                    search, range(len(nodes))) if at_node is not None]
            for j, name in enumerate(("split_shortfall", "runner_up",
                                      "missing_right_only")):
                rec[name] = np.array([at_node[j] for at_node in found])
        score += value[leaf]
        if v_score is not None:
            v_score += value[route(tree, x_valid, valid_rows)]
            rec["auc"] = auc(yv64, v_score)
        out.append(rec)
    return out
