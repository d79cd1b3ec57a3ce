"""Device-side tree traversal over binned data.

Walks trees that are already finished (the reference's
``ScoreUpdater::AddScore(tree)`` path, score_updater.hpp:21-128): a valid
set's replay of the trees grown before it was added, rollback and dropped
iterations, DART's drop-and-rescale, batched leaf prediction and serving.
A new tree's validation-set score update takes this walk only where the
grower cannot carry the held-out rows through its own partition
(``models/gbdt._followers``: the partitioned and the sharded learners,
sparse-binned valid sets, linear trees).  The traversal is a fixed-depth
``fori_loop`` of vectorized gathers: every row walks one level per step;
finished rows carry their (negative-encoded) leaf id unchanged — static
shapes, no divergence.

Numerical and categorical decisions share one predicate: per-node
``cat_rank`` maps bin -> decision rank (identity for numerical nodes), go
left iff rank <= threshold (see ops/split.py SplitResult).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .utils.compile_cache import trace_event
from .utils.shapes import round_up_pow2  # noqa: F401  (shared policy;
#                                          re-exported for existing users)


@functools.partial(jax.jit, static_argnames=("steps",))
@jax.named_scope("lgbtpu.walk")
def traverse_tree_binned(binned, split_feature, threshold_bin, default_left,
                         left_child, right_child, na_bin, is_cat_node,
                         cat_rank, efb_maps=None, *, steps: int):
    """Return the leaf index for every row of ``binned`` [N, F].

    ``efb_maps``: optional (group_of_feat, off_of_feat, nbm1_of_feat) device
    arrays when ``binned`` is the EFB-grouped matrix [N, G] (efb.py) — the
    gathered group bin is unmapped to the feature's own bin space."""
    trace_event("traverse_tree")
    n = binned.shape[0]
    from .obs.flops import note_traced, traverse_flops_bytes
    note_traced("traverse_tree", *traverse_flops_bytes(
        n, 1, steps, binned.shape[1],
        binned_itemsize=getattr(binned.dtype, "itemsize", 1)),
        phase="score", cadence="iter")
    node = jnp.zeros(n, jnp.int32)

    def body(_, node):
        internal = node >= 0
        nid = jnp.maximum(node, 0)
        f = split_feature[nid]
        if efb_maps is None:
            col = f
        else:
            col = efb_maps[0][f]
        v = jnp.take_along_axis(binned, col[:, None].astype(jnp.int32),
                                axis=1)[:, 0].astype(jnp.int32)
        if efb_maps is not None:
            off, nbm1 = efb_maps[1][f], efb_maps[2][f]
            v = jnp.where(off < 0, v,
                          jnp.where((v >= off) & (v < off + nbm1),
                                    v - off + 1, 0))
        nb = na_bin[f]
        is_na = (nb >= 0) & (v == nb) & (~is_cat_node[nid])
        rank = cat_rank[nid, v]
        go_left = jnp.where(is_na, default_left[nid], rank <= threshold_bin[nid])
        nxt = jnp.where(go_left, left_child[nid], right_child[nid])
        return jnp.where(internal, nxt, node)

    node = lax.fori_loop(0, steps, body, node)
    return (~node).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("steps",))
def add_tree_score(score, binned, split_feature, threshold_bin, default_left,
                   left_child, right_child, na_bin, is_cat_node, cat_rank,
                   leaf_value, weight, efb_maps=None, *, steps: int):
    """score += weight * tree(binned) — incremental ScoreUpdater step."""
    trace_event("add_tree_score")
    leaf = traverse_tree_binned(binned, split_feature, threshold_bin,
                                default_left, left_child, right_child,
                                na_bin, is_cat_node, cat_rank, efb_maps,
                                steps=steps)
    with jax.named_scope("lgbtpu.score"):
        return score + weight * jnp.take(leaf_value, leaf)


@jax.jit
@jax.named_scope("lgbtpu.score")
def leaf_values_of_rows(leaf_value, leaf_of_row):
    """A tree's score delta of rows whose leaves are known: the training
    rows' out of the grower's partition, and the held-out rows' where the
    grower carried them through it (``models/gbdt._followers``).  One
    program under the score's scope, so that a device trace books the
    per-iteration loop's look-up to ``lgbtpu.score`` as the scan's."""
    return jnp.take(leaf_value, leaf_of_row)


# (round_up_pow2 moved to utils/shapes.py — the ONE bucketing policy
# shared by serving batches, validation rows and the grower leaf budget
# — and re-imported above so existing callers keep working.)


# ---------------------------------------------------------------------------
# Whole-ensemble traversal (serving / bucketed Booster.predict)
# ---------------------------------------------------------------------------

# traces of the forest-traversal program, incremented while TRACING only
# (the increment is a Python side effect, so it runs once per new jit
# cache entry, never per execution).  tests/test_serve.py reads this to
# prove the bucketed compile cache bounds XLA compiles.
_FOREST_TRACES = [0]


def forest_trace_count() -> int:
    """Number of times ``traverse_forest_binned`` has been traced (==
    compiled) in this process."""
    return _FOREST_TRACES[0]


def _forest_walk(binned, split_feature, threshold_bin, default_left,
                 left_child, right_child, na_bin, is_cat_node, cat_index,
                 cat_table, steps: int):
    """Shared traced body of the whole-forest traversal (no counters —
    callers own trace accounting).  The node tables may arrive in
    PACKED narrow dtypes (serve/engine.py ``serve_packed_tables``:
    thresholds uint8/uint16 by bin count, children int8/int16 by node
    count); every gathered value is widened to int32 before compare /
    index use, so packing shrinks HBM traffic without touching the
    decision arithmetic."""
    n = binned.shape[0]
    t = split_feature.shape[0]
    node = jnp.zeros((n, t), jnp.int32)
    tree_ids = jnp.arange(t, dtype=jnp.int32)[None, :]

    def body(_, node):
        internal = node >= 0
        nid = jnp.maximum(node, 0)
        f = split_feature[tree_ids, nid].astype(jnp.int32)     # [N, T]
        v = jnp.take_along_axis(binned, f, axis=1) \
            .astype(jnp.int32)                                 # [N, T]
        cat = is_cat_node[tree_ids, nid]
        nb = na_bin[f]
        is_na = (nb >= 0) & (v == nb) & (~cat)
        ci = cat_index[tree_ids, nid].astype(jnp.int32)
        rank = jnp.where(cat, cat_table[ci, v].astype(jnp.int32), v)
        go_left = jnp.where(
            is_na, default_left[tree_ids, nid],
            rank <= threshold_bin[tree_ids, nid].astype(jnp.int32))
        nxt = jnp.where(go_left,
                        left_child[tree_ids, nid].astype(jnp.int32),
                        right_child[tree_ids, nid].astype(jnp.int32))
        return jnp.where(internal, nxt, node)

    node = lax.fori_loop(0, steps, body, node)
    return (~node).astype(jnp.int32)


def traverse_forest_binned(binned, split_feature, threshold_bin,
                           default_left, left_child, right_child, na_bin,
                           is_cat_node, cat_index, cat_table, *, steps: int):
    """Leaf index for every (row, tree) pair: ``binned`` [N, F] ->
    [N, T] int32.

    The whole-ensemble counterpart of :func:`traverse_tree_binned` used
    by ``serve/engine.py``: per-node arrays are stacked [T, M] (M = max
    nodes per tree, padded), every row walks all T trees one level per
    step, finished rows carry their ~leaf id unchanged.  Categorical
    decisions go through a compact rank table — ``cat_index`` maps a
    node to its row of ``cat_table`` [C, B] (0 = category in the node's
    left set, 1 = not), numerical nodes use the bin id itself as the
    rank (model-derived binning makes ``bin(x) <= threshold_bin`` exact,
    see serve/engine.py).  Call under ``jax.jit`` with ``steps`` static;
    a module-level trace counter records each compilation.
    """
    _FOREST_TRACES[0] += 1
    trace_event("forest")
    n = binned.shape[0]
    t = split_feature.shape[0]
    from .obs.flops import note_traced, traverse_flops_bytes
    note_traced("forest", *traverse_flops_bytes(
        n, t, steps, binned.shape[1],
        binned_itemsize=getattr(binned.dtype, "itemsize", 1)),
        phase="serve", cadence="iter")
    return _forest_walk(binned, split_feature, threshold_bin,
                        default_left, left_child, right_child, na_bin,
                        is_cat_node, cat_index, cat_table, steps)


def bin_rows_device(x, thresholds, na_bin, zero_bin):
    """On-device model-derived binning of raw NUMERICAL rows (f32).

    ``thresholds`` [F, B] is each feature's sorted split-threshold table
    padded with +inf; the bin id is the count of thresholds < x, i.e.
    ``searchsorted(T_f, x, 'left')`` as a comparison-sum.  NaNs map to
    ``na_bin[f]`` when the feature reserves one (missing-type NaN nodes)
    and to ``zero_bin[f]`` (the bin of 0.0) otherwise — the reference
    Predictor's NaN->0 conversion.  f32 comparisons: rows whose value
    ties a threshold within f32 rounding may bin differently from the
    exact host (f64) path — this feeds the opt-in approximate
    ``serve_device_binning`` mode only (docs/Serving.md)."""
    xf = x.astype(jnp.float32)
    isnan = jnp.isnan(xf)
    bins = jnp.sum(xf[:, :, None] > thresholds[None, :, :],
                   axis=-1).astype(jnp.int32)
    fallback = jnp.where(na_bin >= 0, na_bin, zero_bin)[None, :]
    return jnp.where(isnan, fallback, bins)


def bin_rows_device_full(x, thresholds, na_bin, zero_bin, cat_values,
                         cat_len):
    """On-device model-derived binning covering BOTH feature kinds.

    Numerical features bin exactly like :func:`bin_rows_device`.
    Categorical features (``cat_len[f] > 0``) reproduce the host
    ``engine.bin_rows`` mapping in integer-exact arithmetic:
    ``iv = trunc(x)`` (NaN/inf -> -1, the reference
    CategoricalDecision input mapping), position = count of known
    categories < iv, and the position is kept only when the category
    at it matches ``iv`` — otherwise the unseen-category sentinel bin
    ``cat_len[f]``.  ``cat_values`` [F, C] holds each categorical
    feature's sorted known categories as f32 (padded +inf; exact for
    |category| < 2^24 — the engine refuses device binning beyond
    that).  f32 rounding can only move a NUMERICAL threshold tie; the
    categorical compare is integer-exact."""
    xf = x.astype(jnp.float32)
    isnan = jnp.isnan(xf)
    bins = jnp.sum(xf[:, :, None] > thresholds[None, :, :],
                   axis=-1).astype(jnp.int32)
    fallback = jnp.where(na_bin >= 0, na_bin, zero_bin)[None, :]
    bins = jnp.where(isnan, fallback, bins)
    if cat_values.shape[1] > 0:
        iv = jnp.where(jnp.isfinite(xf), jnp.trunc(xf), -1.0)
        pos = jnp.sum(cat_values[None, :, :] < iv[:, :, None],
                      axis=-1).astype(jnp.int32)
        posc = jnp.clip(pos, 0, jnp.maximum(cat_len - 1, 0)[None, :])
        feat_ids = jnp.arange(xf.shape[1], dtype=jnp.int32)[None, :]
        hit = cat_values[feat_ids, posc]                    # [N, F]
        cat_bin = jnp.where(hit == iv, posc, cat_len[None, :])
        bins = jnp.where((cat_len > 0)[None, :], cat_bin, bins)
    return bins


# ---------------------------------------------------------------------------
# Fused device-resident serve path (one jit: bin -> traverse -> accumulate
# -> transform; serve/engine.py fused_predict)
# ---------------------------------------------------------------------------

# traces of the fused serve program, counted at trace time like
# _FOREST_TRACES — tests and tools/check_retraces.py pin the budget
_FUSED_TRACES = [0]


def fused_trace_count() -> int:
    """Number of times ``fused_forest_predict`` has been traced (==
    compiled) in this process."""
    return _FUSED_TRACES[0]


def fused_forest_predict(x, thresholds, na_bin, zero_bin, cat_values,
                         cat_len, split_feature, threshold_bin,
                         default_left, left_child, right_child,
                         is_cat_node, cat_index, cat_table, leaf_value,
                         tree_weight, avg_denom, *, steps: int,
                         num_class: int, transform):
    """The device-resident serve fast path: raw rows [N, F] -> final
    scores, ONE program.

    Bins on device (:func:`bin_rows_device_full`, f32), walks the whole
    forest (:func:`_forest_walk` over the packed SoA tables), gathers
    each tree's leaf value (``leaf_value`` [T, L] f32), multiplies by
    ``tree_weight`` [T] (DART/RF weights), and accumulates per class
    IN TREE ORDER with a sequential ``fori_loop`` — the accumulation
    order is part of the path's parity contract (serve/engine.py
    ``_fused_reference`` recomputes exactly these f32 ops on the host
    for the self-check).  ``avg_denom`` (f32 scalar, 1.0 when not
    averaging) applies RF output averaging; ``transform`` (static; a
    shared per-objective-config callable, None = raw) applies the
    objective's output conversion.  The caller fetches ONLY the
    returned [N] / [N, num_class] scores — the single host<->device
    sync of a fused serve batch (tools/sync_allowlist.txt)."""
    _FUSED_TRACES[0] += 1
    trace_event("serve_fused")
    n, f = x.shape
    t = split_feature.shape[0]
    from .obs.flops import fused_forest_flops_bytes, note_traced
    note_traced("serve_fused", *fused_forest_flops_bytes(
        n, t, steps, f, thresholds.shape[1], num_class,
        table_itemsize=getattr(threshold_bin.dtype, "itemsize", 4)),
        phase="serve", cadence="iter")
    binned = bin_rows_device_full(x, thresholds, na_bin, zero_bin,
                                  cat_values, cat_len)
    leaves = _forest_walk(binned, split_feature, threshold_bin,
                          default_left, left_child, right_child, na_bin,
                          is_cat_node, cat_index, cat_table, steps)
    tree_ids = jnp.arange(t, dtype=jnp.int32)[None, :]
    vals = leaf_value[tree_ids, leaves]                        # [N, T]
    # barrier: keep the weight multiply a distinct op from the loop's
    # adds so XLA cannot FMA-contract across them — the host oracle
    # recomputes mul-then-add as separate IEEE f32 ops
    prods = lax.optimization_barrier(vals * tree_weight[None, :])
    k = max(1, int(num_class))
    score = jnp.zeros((n, k), jnp.float32)

    def body(ti, s):
        return s.at[:, ti % k].add(prods[:, ti])

    score = lax.fori_loop(0, t, body, score)
    score = score / avg_denom
    out = score if k > 1 else score[:, 0]
    if transform is not None:
        out = transform(out)
    return out
