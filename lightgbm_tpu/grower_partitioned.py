"""Partitioned leaf-wise grower: the single-chip performance learner.

Where grower.py's fully-jitted program pays a full-N masked histogram pass
per split, this learner keeps the reference's work complexity — histogram
work proportional to the SMALLER child (serial_tree_learner.cpp:283-323
smaller/larger leaf logic + subtraction trick), via:

- a device-resident row-permutation ``order`` grouped by leaf — the
  ``DataPartition::indices_`` analog (data_partition.hpp:161), repartitioned
  in place per split with an O(P) cumsum scatter (the CUDA learner's
  prefix-sum pipeline, cuda_data_partition.cu:288);
- host-orchestrated per-split loop (one tiny D2H of the two child split
  records per split — the same sync the CUDA learner does,
  cuda_single_gpu_tree_learner.cpp:118-228) with power-of-2 size bucketing
  so every jitted kernel has a static shape (~log2(N) compile variants);
- gathered-row histogram construction on the MXU (ops/histogram.py).

Output matches grower.py's TreeArrays bit-for-bit in structure; tests
assert equivalence between the two learners.
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .grower import RUNGS, TreeArrays
from .ops.histogram import compute_histogram
from .ops.split import (SplitParams, SplitResult, dequantize_hist,
                        find_best_split, leaf_output,
                        monotone_penalty_factor)


def _quantize_vals(vals, rng_iter, *, spec):
    """Per-iteration quantization for the partitioned learner: shared
    per-channel scales + iteration-keyed stochastic rounding
    (ops/quantize.py; single-chip, so global row id == row index)."""
    from .ops.quantize import quant_scales, quantize_stack
    scales = quant_scales(vals, spec.qmax)
    return quantize_stack(vals, scales, spec, rng_iter, 0), scales


def _pow2(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


@functools.partial(jax.jit, static_argnames=("p", "num_bins", "block_rows"))
def _hist_segment(order, binned, vals, begin, count, *, p, num_bins,
                  block_rows=0):
    """Histogram over rows order[begin:begin+count], padded to p."""
    n = order.shape[0]
    pos = begin + jnp.arange(p, dtype=jnp.int32)
    idx = order[jnp.clip(pos, 0, n - 1)]
    rows = jnp.take(binned, idx, axis=0)
    mask = (jnp.arange(p) < count).astype(vals.dtype)
    v = jnp.take(vals, idx, axis=0) * mask[:, None]
    return compute_histogram(rows, v, num_bins=num_bins,
                             block_rows=block_rows)


@functools.partial(jax.jit, static_argnames=("p",))
def _partition_segment(order, binned, col, nb, goff, nbm1, thr, dleft, icat,
                       rank_vec, begin, count, *, p):
    """Stable in-place partition of order[begin:begin+count] by the split
    predicate (left block first).  Returns (order, left_count).
    ``rank_vec`` [B] is the decision rank (iota for numerical splits);
    ``col`` is the binned-matrix column (the EFB group for bundled
    features), ``nb`` the feature's NaN bin, ``goff``/``nbm1`` the bundle
    offset (-1 = identity) and num_bin-1 for group-bin unmapping."""
    n = order.shape[0]
    pos = begin + jnp.arange(p, dtype=jnp.int32)
    cpos = jnp.clip(pos, 0, n - 1)
    idx = order[cpos]
    gcol = binned[idx, col].astype(jnp.int32)
    fcol = jnp.where(goff < 0, gcol,
                     jnp.where((gcol >= goff) & (gcol < goff + nbm1),
                               gcol - goff + 1, 0))
    is_na = (nb >= 0) & (fcol == nb) & (~icat)
    valid = jnp.arange(p) < count
    go_left = jnp.where(is_na, dleft, rank_vec[fcol] <= thr) & valid
    go_right = (~go_left) & valid
    cl = go_left.sum()
    # O(p) stable partition via cumsum ranks (no sort)
    left_rank = jnp.cumsum(go_left) - 1
    right_rank = cl + jnp.cumsum(go_right) - 1
    inv_rank = count + jnp.cumsum(~valid) - 1
    dest = jnp.where(go_left, left_rank,
                     jnp.where(go_right, right_rank, inv_rank))
    dest_pos = begin + dest.astype(jnp.int32)
    dest_pos = jnp.where(pos < n, dest_pos, n)  # out-of-range -> dropped
    new_order = order.at[dest_pos].set(idx, mode="drop")
    return new_order, cl


@functools.partial(jax.jit, static_argnames=("num_leaves",))
def _leaf_of_row(order, seg_begins, seg_leafs, *, num_leaves):
    """Reconstruct row->leaf from the order permutation + host segment map."""
    n = order.shape[0]
    seg = jnp.searchsorted(seg_begins, jnp.arange(n, dtype=jnp.int32),
                           side="right") - 1
    leaf_by_pos = seg_leafs[seg]
    return jnp.zeros(n, jnp.int32).at[order].set(leaf_by_pos)


class _HostSplit(NamedTuple):
    gain: float
    feature: int
    threshold: int
    default_left: bool
    left_sum: np.ndarray
    right_sum: np.ndarray
    left_output: float
    right_output: float
    is_cat: bool
    bin_rank: np.ndarray


def _pull(res: SplitResult) -> _HostSplit:
    """Convert a (device or already-fetched) SplitResult to host scalars.

    Callers batching several results should jax.device_get the whole tuple
    first — one transfer instead of ~10 blocking scalar reads per result
    (each read is a sync that drains the dispatch queue)."""
    return _HostSplit(
        gain=float(res.gain), feature=int(res.feature),
        threshold=int(res.threshold), default_left=bool(res.default_left),
        left_sum=np.asarray(res.left_sum), right_sum=np.asarray(res.right_sum),
        left_output=float(res.left_output), right_output=float(res.right_output),
        is_cat=bool(res.is_cat), bin_rank=np.asarray(res.bin_rank))


class CEGBState(NamedTuple):
    """Cost-effective gradient boosting penalties
    (cost_effective_gradient_boosting.hpp:22-160): per-split data-acquisition
    cost + per-feature coupled (once per model) and lazy (per data point,
    approximated here by leaf size) penalties, scaled by cegb_tradeoff and
    subtracted from candidate gains.  ``used`` persists across trees."""
    tradeoff: float
    penalty_split: float
    coupled: Optional[np.ndarray]     # [F] or None
    lazy: Optional[np.ndarray]        # [F] or None
    used: np.ndarray                  # [F] bool, mutated in place

    def penalty_vector(self, num_data_in_leaf: float) -> np.ndarray:
        f = len(self.used)
        pen = np.full(f, self.tradeoff * self.penalty_split
                      * float(num_data_in_leaf), np.float32)
        if self.coupled is not None:
            pen += self.tradeoff * self.coupled * (~self.used)
        if self.lazy is not None:
            pen += self.tradeoff * self.lazy * float(num_data_in_leaf)
        return pen

    def mark_used(self, feature: int) -> None:
        self.used[feature] = True

    @property
    def active(self) -> bool:
        return (self.penalty_split > 0 or self.coupled is not None
                or self.lazy is not None)


class PartitionedGrower:
    """Host-orchestrated device-resident leaf-wise learner.

    Optional per-node controls (host bookkeeping, device search):
    - ``mono``: [F] -1/0/+1 monotone constraints ('basic' range method,
      monotone_constraints.hpp BasicLeafConstraints analog);
    - ``interaction_groups``: [G, F] bool constraint-group matrix — a leaf
      may split on its branch features plus the union of the groups that
      contain the WHOLE branch set (ColSampler GetByNode subset
      containment, col_sampler.hpp:91-111; overlapping groups make the
      progressive-intersection shortcut wrong), and the root is limited
      to the union of all groups;
    - ``bynode_frac`` < 1: feature_fraction_bynode re-sampling per node.
    """

    def __init__(self, *, num_leaves: int, num_bins: int, params: SplitParams,
                 max_depth: int = -1, block_rows: int = 0,
                 mono: Optional[np.ndarray] = None,
                 mono_method: str = "basic", mono_penalty: float = 0.0,
                 interaction_groups: Optional[np.ndarray] = None,
                 bynode_frac: float = 1.0, bynode_seed: int = 0,
                 efb=None, pool_entries: int = 0,
                 feature_contri: Optional[np.ndarray] = None,
                 extra_trees: bool = False, extra_seed: int = 6,
                 quant=None):
        self.L = int(num_leaves)
        self.B = int(num_bins)
        self.params = params
        self.max_depth = max_depth
        self.block_rows = block_rows
        self.mono = None if mono is None or not np.any(mono) else \
            jnp.asarray(mono, jnp.int32)
        # 'basic' = midpoint range splitting (BasicLeafConstraints);
        # 'intermediate' = constraints from actual opposite-subtree
        # outputs, refreshed across the whole frontier after each split
        # (IntermediateLeafConstraints, monotone_constraints.hpp:514);
        # 'advanced' = per-THRESHOLD constraint refinement
        # (AdvancedLeafConstraints, monotone_constraints.hpp:856): a
        # candidate split is only constrained by leaves whose region
        # actually overlaps the resulting child's region.  Implemented
        # from leaf bounding boxes (_leaf_boxes/_advanced_bounds): exact
        # per-(feature, bin) neighbor bounds rather than the reference's
        # incremental up-walk bookkeeping — at least as tight, and
        # recomputed per frontier refresh like the intermediate mode.
        self.mono_method = mono_method
        self.mono_penalty = float(mono_penalty)
        self.interaction_groups = None if interaction_groups is None \
            else np.asarray(interaction_groups, bool)
        self.bynode_frac = bynode_frac
        self._bynode_rng = np.random.RandomState(bynode_seed)
        # feature_contri (per-feature gain scale, feature_histogram.hpp) —
        # composed multiplicatively with the monotone penalty below
        self.feature_contri = None if feature_contri is None else \
            jnp.asarray(feature_contri, jnp.float32)
        self.extra_trees = bool(extra_trees)
        self._extra_rng = np.random.RandomState(extra_seed)
        # quantized training (ops/quantize.py): vals are packed once per
        # grow() call (= per iteration) on device, the per-segment
        # histograms accumulate exact int32 (subtraction included), and
        # _find_leaf dequantizes at scan time — the same contract as the
        # masked grower, on the host-orchestrated loop
        self.quant = quant
        if quant is not None:
            self._quantize = jax.jit(functools.partial(
                _quantize_vals, spec=quant))
        # this learner keeps its histograms [F, B, 3], as compute_histogram
        # hands them over; the scan takes them channel-major
        self._find = jax.jit(
            lambda hist, *args, **kw: find_best_split(
                jnp.moveaxis(hist, -1, 0), *args, params=params, **kw))
        # HistogramPool analog (feature_histogram.hpp:1095,
        # histogram_pool_size): cap the number of device-resident per-leaf
        # histograms; evicted leaves are reconstructed on demand (the
        # reference recomputes on pool miss the same way,
        # serial_tree_learner.cpp:283-323 slot juggling).  0 = unbounded.
        self.pool_entries = max(2, int(pool_entries)) if pool_entries > 0 \
            else 0
        self.efb = efb  # EFBDevice (efb.py) or None
        # histogram axis: group bins when bundled, feature bins otherwise
        self.BH = efb.group_bins if efb is not None else self.B
        if efb is not None:
            from .efb import expand_group_hist
            self._expand = jax.jit(functools.partial(
                expand_group_hist, group_of_feat=efb.group_of_feat,
                col_idx=efb.col_idx, fix0=efb.fix0))

    def grow(self, binned, vals, feature_mask, num_bin, na_bin,
             is_cat=None, forced=None,
             cegb_state: Optional[CEGBState] = None,
             rng_iter=None) -> TreeArrays:
        L, B = self.L, self.B
        n = binned.shape[0]
        p_full = _pow2(n)
        order = jnp.arange(n, dtype=jnp.int32)
        nb_host = np.asarray(num_bin)
        na_host = np.asarray(na_bin)

        scales = None
        if self.quant is not None:
            # pack once per tree; every segment histogram below is then
            # an exact int32 accumulation, dequantized only at scan time
            vals, scales = self._quantize(
                jnp.asarray(vals),
                jnp.int32(0 if rng_iter is None else rng_iter))

        # root histogram + split (over EFB groups when bundled)
        hist0 = _hist_segment(order, binned, vals, jnp.int32(0), jnp.int32(n),
                              p=p_full, num_bins=self.BH,
                              block_rows=self.block_rows)
        total0_dev = hist0[0].sum(axis=0)
        if scales is not None:
            total0_dev = dequantize_hist(total0_dev, scales)
        root_out_dev = leaf_output(total0_dev[0], total0_dev[1], self.params)
        total0, root_out = jax.device_get((total0_dev, root_out_dev))
        total0 = np.asarray(total0)
        root_out = float(root_out)
        base_mask = np.asarray(feature_mask, bool)
        if self.interaction_groups is not None:
            # GetByNode (col_sampler.hpp:91-111): per-leaf branch sets;
            # allowed = branch ∪ (groups that contain the whole branch).
            # Root branch is empty -> union of all groups.
            def _inter_allowed(branch):
                g = self.interaction_groups
                contains = (g | ~branch[None, :]).all(axis=1)
                return (g & contains[:, None]).any(axis=0) | branch
            leaf_branch = {0: np.zeros(base_mask.shape[0], bool)}
            leaf_mask = {0: base_mask & _inter_allowed(leaf_branch[0])}
        else:
            leaf_mask = {0: base_mask}
        inf = np.float32(np.finfo(np.float32).max)
        leaf_lo = {0: -inf}
        leaf_hi = {0: inf}
        use_advanced = self.mono is not None \
            and self.mono_method == "advanced"
        adv_bounds: dict = {}
        adv_prev_boxes: list = [None]
        if use_advanced:
            nf_adv = len(np.asarray(num_bin))
            adv_bounds[0] = (np.full((nf_adv, B), -np.inf, np.float32),
                             np.full((nf_adv, B), np.inf, np.float32),
                             np.full((nf_adv, B), -np.inf, np.float32),
                             np.full((nf_adv, B), np.inf, np.float32))

        def _node_mask(mask: np.ndarray) -> jax.Array:
            if self.bynode_frac < 1.0:
                f_all = len(mask)
                k = max(1, int(round(mask.sum() * self.bynode_frac)))
                on = np.nonzero(mask)[0]
                keep = self._bynode_rng.choice(on, size=min(k, len(on)),
                                               replace=False)
                m = np.zeros(f_all, bool)
                m[keep] = True
                return jnp.asarray(m)
            return jnp.asarray(mask)

        def _find_leaf(hist, total, pout, leaf):
            if scales is not None:
                # quantized training: dequantize AT SCAN TIME only
                # (ops/split.py dequantize_hist) — int32 everywhere else
                hist = dequantize_hist(hist, scales)
            kw = {}
            if self.mono is not None:
                kw = dict(mono=self.mono,
                          out_lo=jnp.float32(leaf_lo[leaf]),
                          out_hi=jnp.float32(leaf_hi[leaf]))
                if use_advanced:
                    kw["mono_bounds"] = tuple(
                        jnp.asarray(a) for a in adv_bounds[leaf])
                if self.mono_penalty > 0.0:
                    factor = monotone_penalty_factor(self.mono_penalty,
                                                     depth.get(leaf, 0))
                    kw["gain_scale"] = jnp.where(
                        self.mono != 0, factor.astype(jnp.float32),
                        jnp.float32(1.0))
            if cegb_state is not None and cegb_state.active:
                kw["gain_penalty"] = jnp.asarray(
                    cegb_state.penalty_vector(total[2]))
            if self.feature_contri is not None:
                gs = kw.get("gain_scale")
                kw["gain_scale"] = self.feature_contri if gs is None \
                    else gs * self.feature_contri
            if self.extra_trees:
                # one random threshold bin per feature per candidate-leaf
                # evaluation (extremely randomized trees; host RNG since
                # this learner is host-orchestrated anyway)
                nb_host = np.asarray(num_bin)
                u = self._extra_rng.rand(len(nb_host))
                kw["rand_bin"] = jnp.asarray(
                    np.minimum((u * np.maximum(nb_host - 1, 1)).astype(np.int32),
                               nb_host - 2), jnp.int32)
            if self.efb is not None:
                hist = self._expand(hist, jnp.asarray(total, jnp.float32))
            return self._find(hist, jnp.asarray(total, jnp.float32),
                              num_bin, na_bin, _node_mask(leaf_mask[leaf]),
                              parent_output=jnp.float32(pout),
                              is_cat=is_cat, **kw)

        depth = {0: 0}
        hists = {0: hist0}
        lru: List[int] = [0]

        def _store(l: int, h) -> None:
            hists[l] = h
            if self.pool_entries <= 0:
                return
            if l in lru:
                lru.remove(l)
            lru.append(l)
            live = [k for k in lru if hists.get(k) is not None]
            while len(live) > self.pool_entries:
                victim = live.pop(0)
                hists[victim] = None
                lru.remove(victim)

        def _get_hist(l: int):
            """Pool fetch; evicted leaves rebuilt from their row segment."""
            h = hists.get(l)
            if h is None:
                p_l = min(_pow2(max(counts[l], 1)), p_full)
                h = _hist_segment(order_box[0], binned, vals,
                                  jnp.int32(begins[l]), jnp.int32(counts[l]),
                                  p=p_l, num_bins=self.BH,
                                  block_rows=self.block_rows)
            _store(l, h)
            return h

        cand = {0: _pull(_find_leaf(hist0, total0, root_out, 0))}
        totals = {0: total0}
        parent_out = {0: root_out}

        # host tree state
        begins = {0: 0}
        counts = {0: n}
        leaf_parent = {0: -1}
        split_feature = np.zeros(L - 1, np.int32)
        threshold_bin = np.zeros(L - 1, np.int32)
        default_left = np.zeros(L - 1, bool)
        left_child = np.zeros(L - 1, np.int32)
        right_child = np.zeros(L - 1, np.int32)
        split_gain = np.zeros(L - 1, np.float32)
        leaf_value = np.zeros(L, np.float32)
        leaf_weight = np.zeros(L, np.float32)
        leaf_count = np.zeros(L, np.float32)
        internal_value = np.zeros(L - 1, np.float32)
        internal_weight = np.zeros(L - 1, np.float32)
        internal_count = np.zeros(L - 1, np.float32)
        leaf_depth_arr = np.zeros(L, np.int32)
        is_cat_node = np.zeros(L - 1, bool)
        cat_rank = np.broadcast_to(np.arange(B, dtype=np.int32)[None],
                                   (L - 1, B)).copy()
        leaf_value[0] = root_out
        leaf_weight[0] = total0[1]
        leaf_count[0] = total0[2]

        num_leaves = 1
        order_box = [order]

        def apply_split(i: int, leaf: int, rec: _HostSplit) -> None:
            nonlocal num_leaves
            order = order_box[0]
            new = num_leaves

            # tree bookkeeping (Tree::Split)
            parent = leaf_parent[leaf]
            if parent >= 0:
                if left_child[parent] == ~leaf:
                    left_child[parent] = i
                else:
                    right_child[parent] = i
            left_child[i] = ~leaf
            right_child[i] = ~new
            split_feature[i] = rec.feature
            threshold_bin[i] = rec.threshold
            default_left[i] = rec.default_left
            split_gain[i] = rec.gain
            internal_value[i] = leaf_value[leaf]
            internal_weight[i] = leaf_weight[leaf]
            internal_count[i] = leaf_count[leaf]
            leaf_parent[leaf] = i
            leaf_parent[new] = i
            is_cat_node[i] = rec.is_cat
            cat_rank[i] = rec.bin_rank

            # partition the leaf's segment
            begin, cnt = begins[leaf], counts[leaf]
            p_seg = min(_pow2(max(cnt, 1)), p_full)
            if self.efb is not None:
                col = int(self.efb.group_host[rec.feature])
                goff = int(self.efb.off_host[rec.feature])
            else:
                col, goff = rec.feature, -1
            order, cl_dev = _partition_segment(
                order, binned, jnp.int32(col),
                jnp.int32(na_host[rec.feature]), jnp.int32(goff),
                jnp.int32(nb_host[rec.feature] - 1),
                jnp.int32(rec.threshold), jnp.bool_(rec.default_left),
                jnp.bool_(rec.is_cat), jnp.asarray(rec.bin_rank),
                jnp.int32(begin), jnp.int32(cnt), p=p_seg)
            # actual moved-row count (with bagging, out-of-bag rows follow
            # the split too, so segment size != in-bag left_sum count).
            # this is the split's one unavoidable host sync (the CUDA
            # learner's D2H of the split description,
            # cuda_single_gpu_tree_learner.cpp:118-228)
            cl = int(cl_dev)
            cr = cnt - cl
            begins[leaf], counts[leaf] = begin, cl
            begins[new], counts[new] = begin + cl, cr
            d = depth[leaf] + 1
            depth[leaf] = d
            depth[new] = d
            leaf_value[leaf] = rec.left_output
            leaf_value[new] = rec.right_output
            leaf_weight[leaf] = rec.left_sum[1]
            leaf_weight[new] = rec.right_sum[1]
            leaf_count[leaf] = rec.left_sum[2]
            leaf_count[new] = rec.right_sum[2]
            leaf_depth_arr[leaf] = d
            leaf_depth_arr[new] = d

            # histogram: smaller child constructed, larger by subtraction
            # (falls back to direct construction on a histogram-pool miss —
            # the parent's rows are already re-partitioned by now)
            sm, lg = (leaf, new) if cl <= cr else (new, leaf)
            parent_hist = hists.get(leaf)
            p_sm = min(_pow2(max(counts[sm], 1)), p_full)
            hist_sm = _hist_segment(order, binned, vals,
                                    jnp.int32(begins[sm]),
                                    jnp.int32(counts[sm]), p=p_sm,
                                    num_bins=self.BH,
                                    block_rows=self.block_rows)
            if parent_hist is not None:
                hist_lg = parent_hist - hist_sm
            else:
                p_lg = min(_pow2(max(counts[lg], 1)), p_full)
                hist_lg = _hist_segment(order, binned, vals,
                                        jnp.int32(begins[lg]),
                                        jnp.int32(counts[lg]), p=p_lg,
                                        num_bins=self.BH,
                                        block_rows=self.block_rows)
            _store(sm, hist_sm)
            _store(lg, hist_lg)
            totals[leaf] = rec.left_sum
            totals[new] = rec.right_sum
            parent_out[leaf] = rec.left_output
            parent_out[new] = rec.right_output

            # constraint propagation to children
            if self.interaction_groups is not None:
                child_branch = leaf_branch[leaf].copy()
                child_branch[rec.feature] = True
                leaf_branch[leaf] = leaf_branch[new] = child_branch
                child_mask = base_mask & _inter_allowed(child_branch)
            else:
                child_mask = leaf_mask[leaf]
            leaf_mask[leaf] = child_mask
            leaf_mask[new] = child_mask
            lo_p, hi_p = leaf_lo[leaf], leaf_hi[leaf]
            mc = 0 if self.mono is None else int(np.asarray(self.mono)[rec.feature])
            use_intermediate = (self.mono is not None
                                and self.mono_method == "intermediate")
            refresh = []
            if use_advanced:
                # recompute per-threshold bounds ONLY for leaves this
                # split can affect: a leaf's bounds depend on boxes and
                # outputs of its monotone neighbors, and the only changed
                # regions are the split leaf's old box and the two child
                # boxes — any other leaf keeps its cached bounds (the
                # AdvancedLeafConstraints GoUpToFindLeavesToUpdate role,
                # as a box-overlap filter instead of a tree up-walk)
                num_leaves_next = new + 1
                boxes_int, boxes_wide = self._leaf_boxes(
                    num_leaves_next, split_feature, threshold_bin,
                    left_child, right_child, is_cat_node,
                    np.asarray(num_bin), default_left=default_left,
                    na_host=na_host)
                mono_np = np.asarray(self.mono)
                cand_boxes = [boxes_wide[leaf], boxes_wide[new]]
                if adv_prev_boxes[0] is not None \
                        and leaf < len(adv_prev_boxes[0]):
                    cand_boxes.append(adv_prev_boxes[0][leaf])

                # a changed box can constrain leaf l iff l's box overlaps
                # it in every dim except possibly ONE monotone feature
                # (the neighbor relation AdvancedLeafConstraints walks).
                # Vectorized over all leaves at once: the old per-leaf
                # Python loop was O(M^2*F) per split and walled out at
                # 255 leaves (VERDICT r3 weak 6); this is O(M*F) numpy.
                mono_mask = mono_np != 0
                could = np.zeros(num_leaves_next, bool)
                bw = boxes_wide[:num_leaves_next]
                for cb in cand_boxes:
                    nonov = ~((cb[None, :, 0] <= bw[:, :, 1])
                              & (bw[:, :, 0] <= cb[None, :, 1]))  # [M, F]
                    cnt = nonov.sum(axis=1)
                    mono_nonov = (nonov & mono_mask[None, :]).sum(axis=1)
                    could |= (cnt == 0) | ((cnt == 1) & (mono_nonov == 1))

                for l in range(num_leaves_next):
                    if l in (leaf, new) or l not in adv_bounds \
                            or could[l]:
                        nbnd = self._advanced_bounds(
                            boxes_int, boxes_wide, leaf_value, l, B,
                            na_host=na_host)
                        old = adv_bounds.get(l)
                        if l not in (leaf, new) and (
                                old is None or any(
                                    not np.array_equal(a, b)
                                    for a, b in zip(old, nbnd))):
                            refresh.append(l)
                        adv_bounds[l] = nbnd
                    # scalar range is unused under advanced (the per-bin
                    # bounds replace it) but must exist for _find_leaf
                    leaf_lo.setdefault(l, -inf)
                    leaf_hi.setdefault(l, inf)
                adv_prev_boxes[0] = boxes_wide
            elif use_intermediate:
                # recompute the whole frontier's intervals from the actual
                # opposite-subtree outputs (IntermediateLeafConstraints
                # UpdateConstraintsWithOutputs + GoUpToFindLeavesToUpdate,
                # monotone_constraints.hpp:543-587 — here a full host-side
                # refresh instead of the reference's up-walk bookkeeping)
                num_leaves_next = new + 1
                iv = self._mono_intervals(
                    num_leaves_next, split_feature, left_child, right_child,
                    leaf_value, is_cat_node)
                for l in range(num_leaves_next):
                    lo2, hi2 = iv[l]
                    if l not in (leaf, new) and (
                            abs(lo2 - leaf_lo.get(l, -inf)) > 1e-12
                            or abs(hi2 - leaf_hi.get(l, inf)) > 1e-12):
                        refresh.append(l)
                    leaf_lo[l], leaf_hi[l] = lo2, hi2
            elif mc != 0 and not rec.is_cat:
                mid = 0.5 * (rec.left_output + rec.right_output)
                if mc > 0:   # left (smaller values) must output <= right
                    leaf_lo[leaf], leaf_hi[leaf] = lo_p, min(hi_p, mid)
                    leaf_lo[new], leaf_hi[new] = max(lo_p, mid), hi_p
                else:
                    leaf_lo[leaf], leaf_hi[leaf] = max(lo_p, mid), hi_p
                    leaf_lo[new], leaf_hi[new] = lo_p, min(hi_p, mid)
            else:
                leaf_lo[new], leaf_hi[new] = lo_p, hi_p

            # new candidates for both children; dispatches are async, then
            # ONE batched device_get for everything this split needs on host
            r_l = _find_leaf(hists[leaf], totals[leaf], parent_out[leaf], leaf)
            r_r = _find_leaf(hists[new], totals[new], parent_out[new], new)
            r_refresh = [_find_leaf(_get_hist(l), totals[l], parent_out[l], l)
                         for l in refresh]
            got = jax.device_get((r_l, r_r, r_refresh))
            cand[leaf] = _pull(got[0])
            cand[new] = _pull(got[1])
            for l, r in zip(refresh, got[2]):
                cand[l] = _pull(r)
            num_leaves = new + 1
            order_box[0] = order

        # forced splits pre-pass (ForceSplits, serial_tree_learner.cpp:455):
        # apply the forced tree top regardless of gain, in BFS order
        node_budget = L - 1
        next_node = 0
        if forced is not None:
            queue = [(forced, 0)]
            while queue and next_node < node_budget:
                spec, leaf = queue.pop(0)
                ph = _get_hist(leaf)
                if scales is not None:
                    ph = dequantize_hist(ph, scales)
                fh = ph if self.efb is None else self._expand(
                    ph, jnp.asarray(totals[leaf], jnp.float32))
                rec = self._forced_record(spec, fh, totals[leaf],
                                          parent_out[leaf], B)
                if rec is None:
                    continue
                new = num_leaves
                apply_split(next_node, leaf, rec)
                next_node += 1
                if isinstance(spec.get("left"), dict):
                    queue.append((spec["left"], leaf))
                if isinstance(spec.get("right"), dict):
                    queue.append((spec["right"], new))

        for i in range(next_node, L - 1):
            # pick best leaf (host argmax — the per-leaf candidates are here)
            ok = [l for l in range(num_leaves)
                  if cand[l].gain > 0
                  and (self.max_depth <= 0 or depth[l] < self.max_depth)]
            if not ok:
                break
            leaf = max(ok, key=lambda l: cand[l].gain)
            if cegb_state is not None:
                cegb_state.mark_used(cand[leaf].feature)
            apply_split(i, leaf, cand[leaf])

        order = order_box[0]
        # reconstruct leaf_of_row from segments
        seg = sorted(((begins[l], l) for l in range(num_leaves)))
        seg_begins = jnp.asarray([s[0] for s in seg], jnp.int32)
        seg_leafs = jnp.asarray([s[1] for s in seg], jnp.int32)
        lor = _leaf_of_row(order, seg_begins, seg_leafs, num_leaves=L)

        return TreeArrays(
            num_leaves=jnp.int32(num_leaves),
            split_feature=jnp.asarray(split_feature),
            threshold_bin=jnp.asarray(threshold_bin),
            default_left=jnp.asarray(default_left),
            left_child=jnp.asarray(left_child),
            right_child=jnp.asarray(right_child),
            split_gain=jnp.asarray(split_gain),
            leaf_value=jnp.asarray(leaf_value),
            leaf_weight=jnp.asarray(leaf_weight),
            leaf_count=jnp.asarray(leaf_count),
            internal_value=jnp.asarray(internal_value),
            internal_weight=jnp.asarray(internal_weight),
            internal_count=jnp.asarray(internal_count),
            leaf_depth=jnp.asarray(leaf_depth_arr),
            leaf_of_row=lor,
            is_cat_node=jnp.asarray(is_cat_node),
            cat_rank=jnp.asarray(cat_rank),
            n_steps=jnp.int32(num_leaves - 1),
            # this learner contracts row ranges of its own: no bucket
            rung_steps=jnp.zeros(RUNGS, jnp.int32),
        )

    @staticmethod
    def _leaf_boxes(num_leaves, split_feature, threshold_bin, left_child,
                    right_child, is_cat_node, nb_host, default_left=None,
                    na_host=None):
        """Per-leaf bin-range boxes from the numerical split structure,
        as TWO [M, F, 2] arrays:

        - ``box_int``: the pure interval part (may be empty, lo > hi, for
          a child whose only rows are NA-routed).  Used for ORDERING
          along a monotone feature — NaN values are unordered, so only
          interval parts create left-of/right-of relations.
        - ``box_wide``: widened over the NaN bin for the child that
          receives NA rows by default_left, and over the full range for
          categorical splits — used for region-OVERLAP tests, where
          over-approximation can only ADD constraints (safe)."""
        nf = len(nb_host)
        box_i = np.zeros((num_leaves, nf, 2), np.int32)
        box_w = np.zeros((num_leaves, nf, 2), np.int32)
        lo0 = np.zeros(nf, np.int32)
        hi0 = np.asarray(nb_host, np.int32) - 1
        if num_leaves <= 1:
            for b in (box_i, box_w):
                b[0, :, 0], b[0, :, 1] = lo0, hi0
            return box_i, box_w
        stack = [(0, lo0, hi0, lo0, hi0)]
        while stack:
            node, lo, hi, wlo, whi = stack.pop()
            f = int(split_feature[node])
            t = int(threshold_bin[node])
            na = -1 if na_host is None else int(na_host[f])
            dl = bool(default_left[node]) if default_left is not None \
                else False
            for child, is_left in ((int(left_child[node]), True),
                                   (int(right_child[node]), False)):
                l2, h2, wl2, wh2 = lo, hi, wlo, whi
                if not is_cat_node[node]:
                    if is_left:
                        h2, wh2 = hi.copy(), whi.copy()
                        h2[f] = min(h2[f], t)
                        wh2[f] = min(wh2[f], t)
                    else:
                        l2, wl2 = lo.copy(), wlo.copy()
                        l2[f] = max(l2[f], t + 1)
                        wl2[f] = max(wl2[f], t + 1)
                    if na >= 0 and (dl == is_left):
                        wl2 = wl2.copy()
                        wh2 = wh2.copy()
                        wl2[f] = min(wl2[f], na)
                        wh2[f] = max(wh2[f], na)
                if child < 0:
                    box_i[~child, :, 0], box_i[~child, :, 1] = l2, h2
                    box_w[~child, :, 0], box_w[~child, :, 1] = wl2, wh2
                else:
                    stack.append((child, l2, h2, wl2, wh2))
        return box_i, box_w

    def _advanced_bounds(self, boxes_int, boxes_wide, leaf_value, y,
                         num_bins_total, na_host=None):
        """Per-(candidate-feature s, threshold-bin b) allowed output
        ranges of the two children of leaf ``y`` ('advanced' method).

        A leaf L' constrains a child C through monotone feature f iff
        their regions overlap in every dim except f (then point pairs
        differing only in f exist across them).  C's box equals y's box
        except in the split feature s, so the qualification is
        b-dependent exactly when s != f; because tree leaves partition
        the space, qualifying leaves' interval parts are f-disjoint from
        y's, making the s == f contribution b-independent.

        Ordering along f uses INTERVAL boxes (NaN is unordered, so only
        finite f-ranges create left-of/right-of relations; leaves whose
        f-interval is empty impose nothing through f), while every
        overlap test uses the NA-WIDENED boxes, plus an escape that keeps
        a constraint active at all thresholds of s when both regions
        cover s's NaN bin (NA rows follow default_left regardless of the
        threshold).  MissingType.Zero gets the same treatment on purpose:
        the model ROUTES zeros by default_left exactly like NaN
        (tree.h NumericalDecision), so zeros sit outside the ordered
        threshold geometry — matching the reference, whose monotone
        constraints also do not order the missing-routed branch."""
        nf, B = boxes_int.shape[1], int(num_bins_total)
        mono_np = np.asarray(self.mono)
        neg, pos = -np.inf, np.inf
        lo_l = np.full((nf, B), neg, np.float32)
        lo_r = np.full((nf, B), neg, np.float32)
        hi_l = np.full((nf, B), pos, np.float32)
        hi_r = np.full((nf, B), pos, np.float32)
        m = boxes_int.shape[0]
        if m <= 1:
            return lo_l, hi_l, lo_r, hi_r
        ybi, ybw = boxes_int[y], boxes_wide[y]
        ov = (boxes_wide[:, :, 0] <= ybw[None, :, 1]) \
            & (ybw[None, :, 0] <= boxes_wide[:, :, 1])    # [M, F]
        ids = np.arange(m)
        bgrid = np.arange(B)
        vals_all = np.asarray(leaf_value[:m], np.float64)
        if na_host is not None:
            na_s = np.asarray(na_host)
            cov_nb = (na_s[None, :] >= 0) \
                & (boxes_wide[:, :, 0] <= na_s[None, :]) \
                & (na_s[None, :] <= boxes_wide[:, :, 1])  # [M, F]
            cov_y = (na_s >= 0) & (ybw[:, 0] <= na_s) & (na_s <= ybw[:, 1])
            na_escape = cov_nb & cov_y[None, :]
        else:
            na_escape = np.zeros((m, nf), bool)
        for f in np.nonzero(mono_np != 0)[0]:
            mc = int(mono_np[f])
            q = (ov | (np.arange(nf) == f)[None, :]).all(axis=1) \
                & (ids != y)
            nonempty = boxes_int[:, f, 0] <= boxes_int[:, f, 1]
            right_nb = q & nonempty & (boxes_int[:, f, 0] > ybi[f, 1])
            left_nb = q & nonempty & (boxes_int[:, f, 1] < ybi[f, 0])
            ub_nb, lb_nb = (right_nb, left_nb) if mc > 0 \
                else (left_nb, right_nb)
            for nb_mask, is_min in ((ub_nb, True), (lb_nb, False)):
                vals = vals_all[nb_mask]
                if vals.size == 0:
                    continue
                sb = boxes_wide[nb_mask]
                ext = vals.min() if is_min else vals.max()
                fill = pos if is_min else neg
                # broadcast pass over (s, b):
                # left child's s-range is [y.lo_s, b] -> L' overlaps iff
                # L'.lo_s <= b; right child's is [b+1, y.hi_s] -> iff
                # L'.hi_s >= b+1.  Masked extremum over the K neighbors,
                # chunked over the s axis so the [K, s_chunk, B]
                # temporaries stay bounded (~8 MB) at wide/high-bin
                # shapes instead of multi-GB churn.
                k_nb = len(vals)
                vb = vals.astype(np.float32)[:, None, None]
                esc_all = na_escape[nb_mask]
                c_l = np.empty((nf, B), np.float32)
                c_r = np.empty((nf, B), np.float32)
                s_chunk = max(1, (1 << 21) // max(k_nb * B, 1))
                for s0 in range(0, nf, s_chunk):
                    sl = slice(s0, min(s0 + s_chunk, nf))
                    m_l = sb[:, sl, 0][:, :, None] <= bgrid[None, None, :]
                    m_r = sb[:, sl, 1][:, :, None] \
                        >= (bgrid + 1)[None, None, :]
                    esc = esc_all[:, sl, None]
                    m_l = m_l | esc
                    m_r = m_r | esc
                    if is_min:
                        c_l[sl] = np.where(m_l, vb, fill).min(axis=0)
                        c_r[sl] = np.where(m_r, vb, fill).min(axis=0)
                    else:
                        c_l[sl] = np.where(m_l, vb, fill).max(axis=0)
                        c_r[sl] = np.where(m_r, vb, fill).max(axis=0)
                # splits ON f itself: qualifying leaves are f-disjoint
                # from y, so the bound is b-independent for both children
                c_l[f, :] = ext
                c_r[f, :] = ext
                if is_min:
                    hi_l = np.minimum(hi_l, c_l)
                    hi_r = np.minimum(hi_r, c_r)
                else:
                    lo_l = np.maximum(lo_l, c_l)
                    lo_r = np.maximum(lo_r, c_r)
        return lo_l, hi_l, lo_r, hi_r

    def _mono_intervals(self, num_leaves, split_feature, left_child,
                        right_child, leaf_value, is_cat_node):
        """Per-leaf allowed output intervals from the current tree shape
        ('intermediate' method): walking root->leaf, a monotone split bounds
        the leaf by the extremum of the *opposite* subtree's current leaf
        outputs (tighter than the 'basic' midpoint; the analog of
        IntermediateLeafConstraints keeping constraints equal to actual
        sibling outputs, monotone_constraints.hpp:543-556)."""
        inf = float(np.finfo(np.float32).max)
        mono_np = np.asarray(self.mono)
        iv = {l: (-inf, inf) for l in range(num_leaves)}
        if num_leaves <= 1:
            return iv
        minmax_cache = {}

        def subtree_minmax(child):
            if child in minmax_cache:
                return minmax_cache[child]
            if child < 0:
                v = float(leaf_value[~child])
                r = (v, v)
            else:
                l0, l1 = subtree_minmax(int(left_child[child]))
                r0, r1 = subtree_minmax(int(right_child[child]))
                r = (min(l0, r0), max(l1, r1))
            minmax_cache[child] = r
            return r

        stack = [(0, -inf, inf)]
        while stack:
            node, lo, hi = stack.pop()
            lc, rc = int(left_child[node]), int(right_child[node])
            mc = 0 if is_cat_node[node] else \
                int(mono_np[int(split_feature[node])])
            llo, lhi, rlo, rhi = lo, hi, lo, hi
            if mc > 0:
                lhi = min(lhi, subtree_minmax(rc)[0])
                rlo = max(rlo, subtree_minmax(lc)[1])
            elif mc < 0:
                llo = max(llo, subtree_minmax(rc)[1])
                rhi = min(rhi, subtree_minmax(lc)[0])
            for child, clo, chi in ((lc, llo, lhi), (rc, rlo, rhi)):
                if child < 0:
                    iv[~child] = (clo, chi)
                else:
                    stack.append((child, clo, chi))
        return iv

    def _forced_record(self, spec, hist, total, pout, B) -> Optional[_HostSplit]:
        """Build a split record for a forced (feature, threshold) node
        (forcedsplits_filename, serial_tree_learner.cpp ForceSplits)."""
        f = int(spec["feature"])
        t = int(spec["threshold_bin"])
        h = np.asarray(hist[f])                         # [B, 3]
        lsum = h[:t + 1].sum(axis=0)
        rsum = np.asarray(total, np.float64) - lsum
        if lsum[2] < 1 or rsum[2] < 1:
            return None
        p = self.params

        def out(s):
            g, hh = float(s[0]), float(s[1])
            tl1 = np.sign(g) * max(0.0, abs(g) - p.lambda_l1) \
                if p.lambda_l1 > 0 else g
            o = -tl1 / (hh + p.lambda_l2 + 1e-15)
            if p.max_delta_step > 0:
                o = float(np.clip(o, -p.max_delta_step, p.max_delta_step))
            return float(o)

        return _HostSplit(
            gain=0.0, feature=f, threshold=t, default_left=False,
            left_sum=lsum.astype(np.float32), right_sum=rsum.astype(np.float32),
            left_output=out(lsum), right_output=out(rsum),
            is_cat=False, bin_rank=np.arange(B, dtype=np.int32))

    def __call__(self, binned, vals, feature_mask, num_bin, na_bin,
                 is_cat=None, **kw):
        return self.grow(binned, vals, feature_mask, num_bin, na_bin,
                         is_cat, **kw)
