"""Shared shape-bucketing policy for trace-relevant static dimensions.

Every distinct static shape that reaches a jitted program is a fresh
XLA trace + compile, and compile before the first training iteration
can rival the steady-state work of a short run.  ``serve/engine.py`` already proved the fix for the
serving batch axis: round the dimension up to a power-of-two bucket so
one trace covers a family of sizes.  This module is that policy
extracted so every layer buckets the same way:

- **rows** (serve batches, validation sets): power-of-two with a floor,
  so tiny sizes share one shape instead of one per pow2 below it
  (:func:`bucket_rows`).
- **leaf budget** (the grower's ``num_leaves``): power-of-two with a
  floor of ``LEAF_BUCKET_FLOOR`` — the grower's ``lax.while_loop``
  exits on the *actual* budget (a traced scalar), so ``num_leaves``
  31 / 40 / 63 all run the same ``L=64``-shaped program with
  bit-identical output (:func:`bucket_leaves`, grower.py).
- **split_batch**: pinned to the shipped ``{1, 8, 16, 32, 64}`` set
  (:func:`snap_split_batch`) — the auto-tuner (ops/hist_tune.py) only
  ever picks from it, and snapping explicit odd values keeps the
  super-step trace family closed (K is a structural constant of the
  trace, it cannot be made dynamic the way the leaf budget can).
- **histogram channel axis** (the contraction's slot-expanded C = 3·K
  channels): widths past the shipped C=48 ceiling pad to MXU lane
  multiples of 128 (:func:`bucket_channels`) so the ``[block, C]``
  accumuland operand lands on full 128-lane tiles — padded channels
  belong to slots no row carries, accumulate exact zeros, and are
  sliced off inside the kernel (ops/histogram.py), so the pad costs
  MXU cycles only, never numerics.
- **serve SoA dimensions** (node slots, leaf slots, traversal steps):
  power-of-two with floors (:func:`bucket_nodes`,
  :func:`bucket_leaf_slots`, :func:`bucket_steps`) so two co-hosted
  model versions of one family (hot-swap / shadow, serve/registry.py)
  land on IDENTICAL SoA shapes and share every compiled serve trace —
  a retrained model whose deepest tree moved from 13 to 15 nodes must
  not re-trace the fused serve program.  Node/leaf padding costs
  memory only (padded slots are never gathered); the steps floor costs
  up to ``floor - 1`` no-op level walks for very shallow forests
  (:func:`bucket_steps` documents the tradeoff).

The retrace-budget lint (tools/check_retraces.py) pins the trace
counts this policy produces; changing a bucket boundary is a conscious
act that updates tools/retrace_budget.txt.
"""

from __future__ import annotations

# floor of the leaf-budget bucket: the common LightGBM budgets 31..63
# (default 31) collapse onto one L=64 trace; 127 -> 128, 255 -> 256.
# Below the floor the padded state costs (hist [L, 3, F, B] carry) stay
# small in absolute terms while the trace family shrinks drastically.
LEAF_BUCKET_FLOOR = 64

# the shipped split_batch widths (grower super-step K): 1 = strict
# leaf-wise reference growth, 8/16 = the measured MXU-sublane sweet
# spots (models/gbdt.py auto-selection), 32/64 = the
# lane-padded wide widths (ROADMAP item 1: C = 3K channels bucket to
# 128-lane tiles, ops/histogram.py) the on-device autotuner
# (ops/hist_tune.py) selects from by measured ms/pass
SPLIT_BATCH_SET = (1, 8, 16, 32, 64)

# channel widths up to the pre-widening ceiling (C = 3·16 = 48, the
# largest shipped slot expansion before K ∈ {32, 64} existed) keep
# their exact un-padded shapes: their histograms are regression-pinned
# byte-identical, and at ≤ 48 channels the sublane mapping measured
# fine (ops/histogram.py orientation note)
HIST_CHANNEL_EXACT_MAX = 48
# MXU lane width the wide channel axis pads to
HIST_CHANNEL_LANE = 128


def round_up_pow2(x: int) -> int:
    """Smallest power of two >= x (>= 1)."""
    p = 1
    while p < x:
        p *= 2
    return p


def _pow2_floor(n: int, floor: int) -> int:
    """THE bucketing rule every dimension policy below delegates to:
    pow2 with a floor.  Change it here, nowhere else."""
    return max(int(floor), round_up_pow2(max(int(n), 1)))


def bucket_rows(n: int, min_bucket: int = 16, cap: int | None = None) -> int:
    """Pow2 row bucket with a floor (and an optional pow2'd cap) —
    the serve/engine.py batch policy, shared."""
    b = _pow2_floor(n, min_bucket)
    if cap is not None:
        b = min(b, round_up_pow2(int(cap)))
    return b


def bucket_leaves(num_leaves: int, floor: int = LEAF_BUCKET_FLOOR) -> int:
    """Padded leaf budget covering ``num_leaves``: pow2 with a floor.

    31 / 40 / 63 -> 64; 127 -> 128; 255 -> 256.  The grower exits its
    while_loop on the ACTUAL budget, so the padded slots only cost
    state memory, never semantics (grower.py ``max_leaves``)."""
    return _pow2_floor(num_leaves, floor)


def bucket_nodes(n: int, floor: int = 16) -> int:
    """Padded per-tree node-slot count for the serve SoA tables: pow2
    with a floor.  Padded node rows are never reached by traversal
    (children pad to -1), so the cost is table memory only."""
    return _pow2_floor(n, floor)


def bucket_leaf_slots(n: int, floor: int = 8) -> int:
    """Padded per-tree leaf-slot count for the serve leaf-value table:
    pow2 with a floor; padded slots hold 0.0 and are never gathered."""
    return _pow2_floor(n, floor)


def bucket_bins(n: int, floor: int = 16) -> int:
    """Padded device bin-table width (per-feature threshold slots /
    known-category slots, serve/engine.py ``_device_bin_tables``): pow2
    with a floor.  Pad slots hold +inf, so every comparison against
    them is false — a retrained co-hosted version whose threshold
    count moved from 40 to 55 must not re-trace the fused serve
    program."""
    return _pow2_floor(n, floor)


def bucket_steps(depth: int, floor: int = 8) -> int:
    """Padded traversal step count (forest max depth): pow2 with a
    floor.  Finished rows carry their leaf id unchanged through the
    padded levels, so extra steps change cost, never results.  The
    floor keeps co-hosted versions whose depths jitter in the common
    shallow range (3..8) on ONE trace; the price is up to ``floor - 1``
    no-op level walks for very shallow forests (a depth-2 forest walks
    8 levels instead of 2) — accepted because sub-floor forests are
    tiny workloads and the trace-sharing win compounds per version."""
    return _pow2_floor(depth, floor)


def traversal_steps(max_depth: int, leaf_budget: int) -> int:
    """Static per-tree traversal step budget for the fused super-epoch
    (models/gbdt.py train_superepoch): the in-scan valid-set traversal
    cannot size its fori_loop from the grown tree's ACTUAL depth (a
    traced value), so it walks a config-derived worst case — max_depth
    when bounded, else ``leaf_budget - 1`` (a leaf-wise tree with L
    leaves is at most L-1 deep).  Finished rows carry their leaf id
    unchanged through the surplus levels
    (predict_device.traverse_tree_binned), so padding costs cycles
    only, never numerics; bounding max_depth is the perf lever when
    the leaf budget is large."""
    cap = int(max_depth) if int(max_depth) > 0 else max(int(leaf_budget) - 1, 1)
    return round_up_pow2(max(cap, 1))


def bucket_channels(c: int) -> int:
    """Padded histogram-contraction channel width for a slot-expanded
    C = cv·K axis: exact up to ``HIST_CHANNEL_EXACT_MAX`` (the shipped
    pre-widening widths stay byte-identical down to the trace shape),
    then the next ``HIST_CHANNEL_LANE`` multiple — K=32 (C=96) pads to
    128, K=64 (C=192) to 256.  The pad columns are zero (no slot maps
    to them) and sliced off in-kernel; obs/flops.py excludes their
    FLOPs from MFU accounting (they are not useful work) while the
    autotuner measures their real cost."""
    c = int(c)
    if c <= HIST_CHANNEL_EXACT_MAX:
        return c
    return -(-c // HIST_CHANNEL_LANE) * HIST_CHANNEL_LANE


def snap_split_batch(k: int) -> int:
    """Nearest shipped super-step width >= the request (capped at the
    largest shipped width); 0/1 pass through untouched."""
    k = int(k)
    if k <= 1:
        return k
    for s in SPLIT_BATCH_SET:
        if k <= s:
            return s
    return SPLIT_BATCH_SET[-1]


def fit_split_batch(k: int, num_leaves: int) -> int:
    """Snap a super-step width into the shipped set AND under the leaf
    budget: the grower can never split more than ``num_leaves - 1``
    leaves in one step, so a width past the budget steps DOWN the set
    (num_leaves=31 at K=32 runs K=16) instead of clamping to an
    off-set width that would open a private trace family — K is a
    structural constant of the grower trace, and leaf-budget padding
    must never change it (padded and exact-shape growers of one config
    train byte-identical trees)."""
    k = snap_split_batch(k)
    cap = int(num_leaves) - 1
    if k <= cap:
        return k
    fit = 1
    for s in SPLIT_BATCH_SET:
        if s <= cap:
            fit = s
    return fit
