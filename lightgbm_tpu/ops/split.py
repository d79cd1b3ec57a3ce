"""Vectorized best-split search over histograms.

Replaces the reference's per-feature sequential threshold scan
``FeatureHistogram::FindBestThresholdSequentially``
(/root/reference/src/treelearner/feature_histogram.hpp:856-1050) and the CUDA
``FindBestSplitsForLeafKernel``
(/root/reference/src/treelearner/cuda/cuda_best_split_finder.cu:603): the
two directional scans (missing->right / missing->left) become cumulative
sums + masked argmax over a ``[2, F, B]`` gain tensor — branchless, all
features at once on the VPU.

Gain / leaf-output math follows feature_histogram.hpp:737-854
(``ThresholdL1``, ``CalculateSplittedLeafOutput``, ``GetSplitGains``) with
lambda_l1 / lambda_l2 / max_delta_step / path_smooth.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

kEpsilon = 1e-15
kMinScore = -jnp.inf


def dequantize_hist(hist: jax.Array, scales: jax.Array,
                    axis: int = -1) -> jax.Array:
    """Quantized-training dequantization AT SPLIT-SCAN TIME: an exact
    int32 histogram (or [3] leaf-total vector) whose ``axis`` is the
    (grad, hess, count) channel block (the trailing one of a ``[F, B, 3]``
    histogram and of the totals, the leading one of the masked grower's
    channel-major ``[3, F, B]``) becomes the real-valued f32 tensor the
    gain/leaf-value math below consumes.

    The int32 accumulation (ops/histogram.py integer path) is exact, so
    this one widening multiply is the ONLY place quantization noise
    enters the split scan — totals and every cumsum derived from them
    are deterministic integers times the iteration's shared scale, and
    split selection is bit-reproducible across serial and every
    sharded learner (the f32 path only guarantees that per compiled
    program).  ``scales`` [3] broadcasts over a 3-channel axis and tiles
    over the split_batch 3K channel blocks.

    A trace-time flop/byte note (obs/flops.py "dequant") is recorded by
    the grower at its call sites, not here — this helper also runs on
    tiny [3] totals where a per-call note would misattribute shapes.
    """
    axis = axis % hist.ndim
    c = hist.shape[axis]
    s = scales
    if c != s.shape[-1]:            # split_batch: 3K channels tile [3]
        s = jnp.tile(s, c // s.shape[-1])
    return hist.astype(jnp.float32) \
        * s.reshape((c,) + (1,) * (hist.ndim - 1 - axis))


class SplitParams(NamedTuple):
    """Static split hyperparameters (hashable; closed over at jit time)."""
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    max_delta_step: float = 0.0
    path_smooth: float = 0.0
    # categorical (feature_histogram.hpp:278 FindBestThresholdCategoricalInner)
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_threshold: int = 32
    max_cat_to_onehot: int = 4
    min_data_per_group: int = 100


class SplitResult(NamedTuple):
    """Per-leaf best split (SplitInfo analog, split_info.hpp:55).

    The decision is uniformly "go left iff bin_rank[bin] <= threshold":
    numerical splits use the identity rank (bin order), categorical splits
    the gradient-ratio ordering of the chosen subset — one partition
    predicate serves both (tree.h Numerical/CategoricalDecision collapse).
    """
    gain: jax.Array          # f32; <=0 / -inf when invalid
    feature: jax.Array       # int32 (used-feature slot)
    threshold: jax.Array     # int32 rank threshold
    default_left: jax.Array  # bool
    left_sum: jax.Array      # [3] (g, h, count)
    right_sum: jax.Array     # [3]
    left_output: jax.Array   # f32 leaf output
    right_output: jax.Array  # f32
    is_cat: jax.Array        # bool
    bin_rank: jax.Array      # [B] int32 rank of each bin in the decision order


def globalize_feature(res: SplitResult, gfid: jax.Array) -> SplitResult:
    """Map a chunk-local winning feature slot back to its GLOBAL feature
    id via the owner-shard slot map ``gfid`` [f_local] (-1 = padding).

    Used by the sharded learners (feature-parallel contiguous slices map
    with an offset instead; the data-parallel owner-shard chunks are
    non-contiguous under EFB, hence the explicit map).  A pad slot can
    only win when every candidate is invalid (gain -inf), in which case
    the serial scan's argmax also degenerates to slot 0 — clamping to
    feature 0 keeps the two bit-identical."""
    return res._replace(feature=jnp.maximum(jnp.take(gfid, res.feature), 0))


def gather_best(res: SplitResult, axis_name: str) -> SplitResult:
    """``SyncUpGlobalBestSplit`` (parallel_tree_learner.h:191): allgather
    each shard's best candidate over ``axis_name`` and keep the winner.
    This is the entire cross-shard communication of a split decision — a
    few scalars plus the [B] decision-rank vector, never a histogram.
    ``res.feature`` must already be a GLOBAL feature id (see
    ``globalize_feature``).

    Exact-gain ties across shards break toward the LOWEST GLOBAL FEATURE
    ID, matching the serial scan's flat argmax — lowest-shard-index would
    instead follow EFB group order, which need not follow feature order
    (duplicated columns bundled into different groups would then split on
    a different feature than serial).  Within a shard the local argmax
    already reproduces serial's (dir, feature, bin) order.

    Under the device scope ``lgbtpu.sync``: what a trace books there is
    the exchange and the choice among the gathered candidates, the one
    part of a sharded grower's step that waits on the other chips."""
    with jax.named_scope("lgbtpu.sync"):
        g = lax.all_gather(res, axis_name)   # one collective: pytree [S, ...]
        tie = g.gain == jnp.max(g.gain)
        win = jnp.argmin(jnp.where(tie, g.feature, jnp.int32(2 ** 30)))
        return jax.tree.map(lambda a: a[win], g)


def threshold_l1(s: jax.Array, l1: float) -> jax.Array:
    """ThresholdL1 (feature_histogram.hpp:751)."""
    if l1 <= 0.0:
        return s
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def monotone_penalty_factor(penalty: float, depth):
    """ComputeMonotoneSplitGainPenalty (monotone_constraints.hpp:355):
    depth-based gain de-rating applied to monotone features.  ``depth``
    may be a traced array or a host scalar; the single definition keeps
    the masked and partitioned learners bit-consistent."""
    pen = float(penalty)
    d = jnp.asarray(depth, jnp.float32)
    return jnp.where(
        pen >= d + 1.0, 1e-15,
        jnp.where(pen <= 1.0, 1.0 - pen / (2.0 ** d) + 1e-15,
                  1.0 - 2.0 ** (pen - 1.0 - d) + 1e-15))


def leaf_output(sum_g, sum_h, p: SplitParams, parent_output=None,
                count=None):
    """CalculateSplittedLeafOutput (feature_histogram.hpp:742-764): raw
    Newton step -> L1 threshold -> max_delta_step clamp -> path smoothing
    (in the reference's order: the clamp applies to the RAW output, then
    the smoothed blend may exceed it toward the parent).

    Path smoothing blends with the leaf's DATA COUNT ``count``
    (feature_histogram.hpp:760-761 ``num_data``), not its hessian weight —
    they differ for every non-unit-hessian objective."""
    num = -threshold_l1(sum_g, p.lambda_l1)
    denom = sum_h + p.lambda_l2
    out = num / jnp.maximum(denom, kEpsilon)
    if p.max_delta_step > 0.0:
        out = jnp.clip(out, -p.max_delta_step, p.max_delta_step)
    if p.path_smooth > 0.0 and parent_output is not None:
        # ret * (n/s)/(n/s + 1) + parent/(n/s + 1)
        n_data = sum_h if count is None else count
        smooth_w = n_data / (n_data + p.path_smooth)
        out = out * smooth_w + parent_output * (1.0 - smooth_w)
    return out


def leaf_gain(sum_g, sum_h, p: SplitParams, parent_output=None, count=None):
    """GetLeafGain (feature_histogram.hpp:790-820): gain of a leaf with the
    (possibly clipped/smoothed) optimal output."""
    if p.max_delta_step <= 0.0 and p.path_smooth <= 0.0:
        t = threshold_l1(sum_g, p.lambda_l1)
        return t * t / jnp.maximum(sum_h + p.lambda_l2, kEpsilon)
    out = leaf_output(sum_g, sum_h, p, parent_output, count)
    tg = threshold_l1(sum_g, p.lambda_l1)
    # GetLeafGainGivenOutput: -(2*G̃*w + (H+λ2)*w²)
    return -(2.0 * tg * out + (sum_h + p.lambda_l2) * out * out)


# lanes of a TPU vector register: the longest run the TPU's compiler scans
# as one piece
LANES = 128


def prefix_sums(hist):
    """Inclusive prefix sums along the bin axis, the last.

    Of more than 128 bins on a TPU: the 128-lane pieces' own prefix sums and
    the carry of the pieces before, written out.  ``jnp.cumsum`` there is one
    ``reduce_window`` over the whole axis that the TPU's compiler rewrites
    into just these pieces, as a pad, a reshape, copies and an add that carry
    no scope of the program's: at 2,000 features x 255 bins they were 0.94 s
    of a traced job under no ``lgbtpu.`` scope, and with the rest 7.6% of the
    device's busy time once the contraction had shrunk (PERF.md §6, PR 35).  The rule reads the
    backend and the shape; elsewhere, and up to 128 bins, ``jnp.cumsum``."""
    b = hist.shape[-1]
    if jax.default_backend() != "tpu" or b <= LANES:
        return jnp.cumsum(hist, axis=-1)
    pieces = -(-b // LANES)
    padded = jnp.pad(hist, [(0, 0)] * (hist.ndim - 1)
                     + [(0, pieces * LANES - b)])
    cum = jnp.cumsum(padded.reshape(hist.shape[:-1] + (pieces, LANES)),
                     axis=-1)
    ends = cum[..., -1]                                  # [..., pieces]
    carry = jnp.cumsum(ends, axis=-1) - ends             # of the pieces before
    return (cum + carry[..., None]).reshape(
        hist.shape[:-1] + (pieces * LANES,))[..., :b]


def _numerical_candidates(hist, total, num_bin, na_bin, feature_mask,
                          params: SplitParams, parent_out, rand_bin=None):
    """Gain tensor [2, F, B] over (missing-direction, feature, threshold)
    and the left sums [2, 3, F, B] behind it, from a channel-major
    histogram [3, F, B].

    rand_bin: [F] int32 or None — extra_trees mode (extremely randomized
    trees, feature_histogram.hpp:116): each feature is only allowed to
    split at its one pre-drawn random threshold bin.
    """
    _, f, b = hist.shape
    cum = prefix_sums(hist)                             # [3, F, B] inclusive
    bins = jnp.arange(b, dtype=jnp.int32)

    has_na = (na_bin >= 0)
    na_idx = jnp.broadcast_to(jnp.maximum(na_bin, 0)[None, :, None],
                              (3, f, 1))
    na_vals = jnp.where(has_na[None, :, None],
                        jnp.take_along_axis(hist, na_idx, axis=2),
                        0.0)                            # [3, F, 1]

    # dir 0: missing -> right. left(b) = cum[b]  (na bin == last, never left)
    # dir 1: missing -> left.  left(b) = cum[b] + hist[na]
    lefts = jnp.stack([cum, cum + na_vals], axis=0)     # [2, 3, F, B]
    rights = total[None, :, None, None] - lefts

    gl, hl, cl = lefts[:, 0], lefts[:, 1], lefts[:, 2]
    gr, hr, cr = rights[:, 0], rights[:, 1], rights[:, 2]

    gain_l = leaf_gain(gl, hl, params, parent_out, cl)
    gain_r = leaf_gain(gr, hr, params, parent_out, cr)
    # gain_shift smooths too (BeforeNumercal, feature_histogram.hpp:104-105
    # passes num_data + parent_output into the leaf's own GetLeafGain)
    gain_shift = leaf_gain(total[0], total[1], params, parent_out, total[2])
    split_gain = gain_l + gain_r - (gain_shift + params.min_gain_to_split)

    # validity masks (FindBestThresholdSequentially early-continue conditions)
    md = float(params.min_data_in_leaf) - 0.5
    mh = params.min_sum_hessian_in_leaf
    # threshold range: b <= num_bin - 2 excluding the NaN bin from the scan
    max_t = jnp.where(has_na, num_bin - 2, num_bin - 2)  # na bin = num_bin-1
    valid = (bins[None, None, :] <= max_t[None, :, None])
    valid &= feature_mask[None, :, None]
    valid &= (cl >= md) & (cr >= md)
    valid &= (hl >= mh) & (hr >= mh)
    valid &= split_gain > kEpsilon
    # dir-1 scan only exists for features with a NaN bin
    valid &= jnp.stack([jnp.ones((f, b), bool),
                        jnp.broadcast_to(has_na[:, None], (f, b))], axis=0)
    if rand_bin is not None:
        valid &= (bins[None, None, :] == rand_bin[None, :, None])

    gains = jnp.where(valid, split_gain, kMinScore)     # [2, F, B]
    return gains, lefts


def _categorical_candidates(hist, total, num_bin, cat_mask,
                            params: SplitParams, parent_out):
    """Categorical subset candidates (FindBestThresholdCategoricalInner,
    feature_histogram.hpp:278): one-vs-rest when few categories, else a
    two-direction scan over bins sorted by grad/hess ratio.

    Returns (gains [3, F, B], lefts [3, 3, F, B], orders [3, F, B]):
    scan modes = (one-vs-rest, ratio-ascending, ratio-descending), then the
    channel for ``lefts``; ``orders`` maps scan position -> bin id.
    """
    _, f, b = hist.shape
    pcat = params._replace(lambda_l2=params.lambda_l2 + params.cat_l2)
    g, h, c = hist[0], hist[1], hist[2]
    used = c >= max(0.5, float(params.min_data_per_group) - 0.5)
    n_used = used.sum(axis=1)                            # [F]
    positions = jnp.arange(b, dtype=jnp.int32)

    # ratio ordering (cat_smooth regularized), unused bins pushed last
    ratio = g / (h + params.cat_smooth)
    big = jnp.float32(1e30)
    key_asc = jnp.where(used, ratio, big)
    order_asc = jnp.argsort(key_asc, axis=1).astype(jnp.int32)    # [F, B]
    key_desc = jnp.where(used, -ratio, big)
    order_desc = jnp.argsort(key_desc, axis=1).astype(jnp.int32)
    order_ovr = jnp.broadcast_to(positions[None, :], (f, b)).astype(jnp.int32)
    orders = jnp.stack([order_ovr, order_asc, order_desc])         # [3, F, B]

    hist3 = jnp.broadcast_to(hist[None], (3, 3, f, b))
    sorted_hist = jnp.take_along_axis(
        hist3, jnp.broadcast_to(orders[:, None], (3, 3, f, b)), axis=3)
    cum = jnp.cumsum(sorted_hist, axis=3)                # [3, 3, F, B]
    # mode 0 = one-vs-rest: left = single bin at this position
    lefts = cum.at[0].set(sorted_hist[0])
    rights = total[None, :, None, None] - lefts

    gl, hl, cl = lefts[:, 0], lefts[:, 1], lefts[:, 2]
    gr, hr, cr = rights[:, 0], rights[:, 1], rights[:, 2]
    gain_l = leaf_gain(gl, hl, pcat, parent_out, cl)
    gain_r = leaf_gain(gr, hr, pcat, parent_out, cr)
    gain_shift = leaf_gain(total[0], total[1], pcat, parent_out, total[2])
    split_gain = gain_l + gain_r - (gain_shift + params.min_gain_to_split)

    md = float(params.min_data_in_leaf) - 0.5
    mh = params.min_sum_hessian_in_leaf
    pos = positions[None, None, :]
    few = (n_used <= params.max_cat_to_onehot)[None, :, None]      # [1, F, 1]
    # mode 0 valid at positions whose bin is used; modes 1-2 at prefix
    # lengths 1..min(max_cat_threshold, n_used-1)
    used3 = jnp.take_along_axis(jnp.broadcast_to(used[None], (3, f, b)),
                                orders, axis=2)
    valid = jnp.zeros((3, f, b), bool)
    valid = valid.at[0].set(few[0] & used3[0])
    k_max = jnp.minimum(params.max_cat_threshold,
                        n_used - 1)[None, :, None]                 # prefix cap
    prefix_ok = (pos < k_max) & (~few)
    valid = valid.at[1].set(prefix_ok[0] & used3[1])
    valid = valid.at[2].set(prefix_ok[0] & used3[2])
    valid &= cat_mask[None, :, None]
    valid &= (cl >= md) & (cr >= md)
    valid &= (hl >= mh) & (hr >= mh)
    valid &= split_gain > kEpsilon

    gains = jnp.where(valid, split_gain, kMinScore)
    return gains, lefts, orders


def _monotone_adjust(gains, lefts, total, mono, out_lo, out_hi, dir_axis,
                     params: SplitParams, parent_out, mono_bounds=None):
    """Monotone-constraint filter ('basic' method,
    monotone_constraints.hpp BasicLeafConstraints): clamp candidate child
    outputs to the leaf's allowed range, recompute gains with the clamped
    outputs (GetLeafGainGivenOutput), and invalidate splits whose direction
    violates the feature's monotonicity.

    mono_bounds ('advanced' method, AdvancedLeafConstraints analog):
    optional (lo_l, hi_l, lo_r, hi_r) per-(feature, threshold-bin) [F, B]
    bound tensors — the allowed range of each CHILD as a function of the
    candidate threshold, so a split is only constrained by opposite
    leaves whose region actually overlaps that child's region."""
    rights = total[None, :, None, None] - lefts         # [2, 3, F, B]
    out_l = leaf_output(lefts[:, 0], lefts[:, 1], params, parent_out,
                        lefts[:, 2])
    out_r = leaf_output(rights[:, 0], rights[:, 1], params, parent_out,
                        rights[:, 2])
    if mono_bounds is not None:
        lo_l, hi_l, lo_r, hi_r = (b[None] for b in mono_bounds)  # [1,F,B]
        cl_l = jnp.clip(out_l, lo_l, hi_l)
        cl_r = jnp.clip(out_r, lo_r, hi_r)
    else:
        cl_l = jnp.clip(out_l, out_lo, out_hi)
        cl_r = jnp.clip(out_r, out_lo, out_hi)

    def gain_given(sums, out):
        tg = threshold_l1(sums[:, 0], params.lambda_l1)
        return -(2.0 * tg * out + (sums[:, 1] + params.lambda_l2) * out * out)

    mono_f = mono[None, :, None]                       # broadcast over dirs/bins
    was_valid = gains > kMinScore
    clamped = (cl_l != out_l) | (cl_r != out_r)
    new_gain = (gain_given(lefts, cl_l) + gain_given(rights, cl_r)
                - (leaf_gain(total[0], total[1], params)
                   + params.min_gain_to_split))
    gains = jnp.where(was_valid & clamped, new_gain, gains)
    ok = jnp.where(mono_f > 0, cl_l <= cl_r,
                   jnp.where(mono_f < 0, cl_l >= cl_r, True))
    return jnp.where(was_valid & ok & (gains > kEpsilon), gains, kMinScore)


def _take_sums(lefts, d, f, b):
    """``lefts[d, :, f, b]`` of ``[D, 3, F, B]`` as three gathers of one
    scalar each.  One gather of the 3-channel slice makes the TPU's
    compiler lay the whole operand out with the channels minor, padded
    3 -> 128: at 16 slots x 2,000 x 63 bins a copy of 4.2 GB, 1.23 s a job
    (``copy.97``, PERF.md §5 at PR 28), and of 15.6 GB at 255 bins."""
    return jnp.stack([lefts[d, c, f, b] for c in range(lefts.shape[1])])


@jax.named_scope("lgbtpu.split")
def find_best_split(hist: jax.Array, total: jax.Array, num_bin: jax.Array,
                    na_bin: jax.Array, feature_mask: jax.Array,
                    params: SplitParams, parent_output: jax.Array = None,
                    is_cat: jax.Array = None, mono: jax.Array = None,
                    out_lo: jax.Array = None, out_hi: jax.Array = None,
                    gain_penalty: jax.Array = None,
                    gain_scale: jax.Array = None,
                    rand_bin: jax.Array = None,
                    mono_bounds=None) -> SplitResult:
    """Best split for one leaf across numerical and categorical features.

    hist:         [3, F, B] f32 — per-feature histograms, channel-major:
                  (g, h, count) lead, bins are the minor axis.  No array
                  of the scan has the 3 channels minor: the TPU's compiler
                  pads such an axis to 128 lanes wherever it tiles it
    total:        [3] parent aggregates
    num_bin:      [F] int32 valid bin count per feature
    na_bin:       [F] int32 NaN-bin index or -1
    feature_mask: [F] bool — feature_fraction / interaction constraint mask
    is_cat:       [F] bool — categorical feature flags (None = none)
    mono:         [F] int32 — monotone constraints -1/0/+1 (None = none)
    out_lo/out_hi: scalar allowed output range of this leaf (monotone)
    """
    _, f, b = hist.shape
    # static FLOP/byte note from the traced shapes (obs/flops.py): one
    # candidate leaf's scan — fires at trace time only; under the
    # grower's vmap the recorded unit is the per-leaf scan
    from ..obs.flops import note_traced, split_scan_flops_bytes
    note_traced("split_scan", *split_scan_flops_bytes(f, b, n_leaves=1),
                phase="grow")
    parent_out = leaf_output(total[0], total[1], params) \
        if parent_output is None else parent_output

    num_mask = feature_mask if is_cat is None else (feature_mask & (~is_cat))
    ngains, nlefts = _numerical_candidates(hist, total, num_bin, na_bin,
                                           num_mask, params, parent_out,
                                           rand_bin)
    if mono is not None:
        ngains = _monotone_adjust(ngains, nlefts, total, mono, out_lo, out_hi,
                                  0, params, parent_out, mono_bounds)
    if gain_scale is not None:
        # per-feature multiplicative gain scale: monotone_penalty
        # (ComputeMonotoneSplitGainPenalty, monotone_constraints.hpp:355)
        # and/or feature_contri (feature_histogram.hpp gain *= contri)
        ngains = jnp.where(ngains > kMinScore,
                           ngains * gain_scale[None, :, None], ngains)
    if gain_penalty is not None:
        # CEGB per-feature acquisition penalty subtracted from candidate
        # gains (cost_effective_gradient_boosting.hpp:70-78 DeltaGain)
        pen = gain_penalty[None, :, None]
        ngains = jnp.where(ngains > kMinScore,
                           jnp.where(ngains - pen > kEpsilon,
                                     ngains - pen, kMinScore), ngains)
    nflat = ngains.reshape(-1)
    nbest = jnp.argmax(nflat)
    nbest_gain = nflat[nbest]

    if is_cat is not None:
        cat_mask = feature_mask & is_cat
        cgains, clefts, corders = _categorical_candidates(
            hist, total, num_bin, cat_mask, params, parent_out)
        if gain_scale is not None:
            cgains = jnp.where(cgains > kMinScore,
                               cgains * gain_scale[None, :, None], cgains)
        if gain_penalty is not None:
            cpen = gain_penalty[None, :, None]
            cgains = jnp.where(cgains > kMinScore,
                               jnp.where(cgains - cpen > kEpsilon,
                                         cgains - cpen, kMinScore), cgains)
        cflat = cgains.reshape(-1)
        cbest = jnp.argmax(cflat)
        cbest_gain = cflat[cbest]
    else:
        cbest_gain = jnp.float32(kMinScore)

    use_cat = (is_cat is not None) and True
    iota_rank = jnp.arange(b, dtype=jnp.int32)

    def build_numerical():
        best_dir = nbest // (f * b)
        rem = nbest % (f * b)
        best_f = (rem // b).astype(jnp.int32)
        best_b = (rem % b).astype(jnp.int32)
        left_sum = _take_sums(nlefts, best_dir, best_f, best_b)
        return (nbest_gain, best_f, best_b, best_dir == 1, left_sum,
                jnp.bool_(False), iota_rank)

    if is_cat is None:
        g_, f_, t_, d_, ls_, ic_, rank_ = build_numerical()
    else:
        def build_categorical():
            mode = cbest // (f * b)
            rem = cbest % (f * b)
            best_f = (rem // b).astype(jnp.int32)
            pos = (rem % b).astype(jnp.int32)
            left_sum = _take_sums(clefts, mode, best_f, pos)
            order = corders[mode, best_f]                 # [B] pos -> bin
            rank = jnp.argsort(order).astype(jnp.int32)   # bin -> pos
            # one-vs-rest: single bin at `pos` goes left -> rank 0 only
            rank_ovr = jnp.where(iota_rank == order[pos], 0, b).astype(jnp.int32)
            rank = jnp.where(mode == 0, rank_ovr, rank)
            thr = jnp.where(mode == 0, 0, pos).astype(jnp.int32)
            return (cbest_gain, best_f, thr, jnp.bool_(False), left_sum,
                    jnp.bool_(True), rank)

        take_num = nbest_gain >= cbest_gain
        nvals = build_numerical()
        cvals = build_categorical()
        g_, f_, t_, d_, ls_, ic_, rank_ = jax.tree.map(
            lambda a, c: jnp.where(take_num, a, c), nvals, cvals)

    right_sum = total - ls_
    # categorical splits regularize leaf outputs with l2 + cat_l2
    pcat = params._replace(lambda_l2=params.lambda_l2 + params.cat_l2)
    lo = jnp.where(ic_,
                   leaf_output(ls_[0], ls_[1], pcat, parent_out, ls_[2]),
                   leaf_output(ls_[0], ls_[1], params, parent_out, ls_[2]))
    ro = jnp.where(ic_,
                   leaf_output(right_sum[0], right_sum[1], pcat, parent_out,
                               right_sum[2]),
                   leaf_output(right_sum[0], right_sum[1], params, parent_out,
                               right_sum[2]))
    if mono is not None:
        if mono_bounds is not None:
            lo_l, hi_l, lo_r, hi_r = mono_bounds
            # categorical winners: t_ is a category rank, not an interval
            # threshold, and the children are not f_-intervals — clamp
            # with the tightest bound over ALL thresholds of the feature
            # (conservative).  If that intersection is empty (mutually
            # contradictory neighbor bounds), no output satisfies every
            # constraint; keep the interval well-ordered so clip stays
            # deterministic (lower bound wins) instead of returning the
            # violated hi.
            l_lo = jnp.where(ic_, jnp.max(lo_l[f_]), lo_l[f_, t_])
            l_hi = jnp.where(ic_,
                             jnp.maximum(jnp.min(hi_l[f_]), jnp.max(lo_l[f_])),
                             hi_l[f_, t_])
            r_lo = jnp.where(ic_, jnp.max(lo_r[f_]), lo_r[f_, t_])
            r_hi = jnp.where(ic_,
                             jnp.maximum(jnp.min(hi_r[f_]), jnp.max(lo_r[f_])),
                             hi_r[f_, t_])
            lo = jnp.clip(lo, l_lo, l_hi)
            ro = jnp.clip(ro, r_lo, r_hi)
        else:
            lo = jnp.clip(lo, out_lo, out_hi)
            ro = jnp.clip(ro, out_lo, out_hi)
    return SplitResult(
        gain=g_, feature=f_.astype(jnp.int32),
        threshold=t_.astype(jnp.int32), default_left=d_,
        left_sum=ls_, right_sum=right_sum,
        left_output=lo.astype(jnp.float32),
        right_output=ro.astype(jnp.float32),
        is_cat=ic_, bin_rank=rank_.astype(jnp.int32),
    )
