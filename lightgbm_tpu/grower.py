"""Leaf-wise tree grower: a fully device-resident JAX program.

TPU-native re-design of the reference's device learner
(/root/reference/src/treelearner/cuda/cuda_single_gpu_tree_learner.cpp:108-232
and serial_tree_learner.cpp:159-210): the whole tree build is ONE jitted
``lax.fori_loop`` with ``num_leaves-1`` trip count (static shapes — SURVEY.md
§7 "hard parts").  Design translations:

- ``DataPartition``'s permuted index array (data_partition.hpp:161) becomes a
  row->leaf index vector (``leaf_of_row``), exactly like the CUDA learner's
  ``data_index_to_leaf_index`` (cuda_data_partition.cu:111) — no reordering,
  per-leaf work masks by leaf id.
- Histogram **subtraction trick** (serial_tree_learner.cpp:423-425): only the
  smaller child's histogram is constructed (masked MXU pass); the sibling is
  parent - smaller.
- Split search: vectorized scans over ``[2, F, B]`` (ops/split.py).
- Distributed: a ``hist_reduce`` hook (identity | ``lax.psum`` over the mesh
  row axis) makes the same program the data-parallel learner
  (data_parallel_tree_learner.cpp:174-186's ReduceScatter collapses onto an
  XLA collective; split decisions are then replicated).
"""

from __future__ import annotations

import functools
import threading
from collections import OrderedDict
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .ops.histogram import compute_histogram
from . import sparse_data as _spd
# LANES, a TPU vector register's: a matrix narrower than this has all its
# columns in every tile of its row-major form
from .ops.split import (LANES, SplitParams, SplitResult, find_best_split,
                        leaf_output, monotone_penalty_factor)
from .utils.compile_cache import trace_event


def grower_trace_count() -> int:
    """Number of times a grower program has been traced (== compiled,
    modulo persistent-cache hits) in this process — the ``grower``
    entry of ``utils/compile_cache.trace_counts()``, counted by the
    ``trace_event`` call inside the traced function bodies (a Python
    side effect: once per new jit cache entry, never per execution).
    tests/test_compile_cache.py and tools/check_retraces.py read this
    to prove the leaf-budget bucketing bounds XLA compiles (one L=64
    trace covers num_leaves 31/40/63)."""
    from .utils.compile_cache import trace_counts
    return trace_counts().get("grower", 0)


# process-level grower sharing: two Boosters whose grower CONFIG matches
# (after leaf-budget bucketing the common num_leaves sweep collapses
# onto one config) reuse the same jitted callable — and therefore the
# same trace.  Keyed on every closure input of make_grower; skipped
# whenever a distribution hook (an unkeyable callable) is present.
# Bounded LRU: evicting an entry only drops the SHARED handle — live
# Boosters keep their reference, exactly like the pre-memo behavior.
_SHARED_GROWERS: "OrderedDict[tuple, Callable]" = OrderedDict()
_SHARED_GROWERS_MAX = 64
_SHARED_GROWERS_LOCK = threading.Lock()
# what the memo answered, over the process: hit (an earlier booster's
# jitted grower is reused), miss (a new one is kept), unkeyable (a hook
# or an object-valued argument: this grower jits privately)
_MEMO_COUNTS = {"hit": 0, "miss": 0, "unkeyable": 0}


def grower_memo_counts() -> dict:
    """Copy of the memo's hit / miss / unkeyable counts (telemetry's
    ``grower.memo{result=}`` is the difference around one
    ``make_grower`` call)."""
    with _SHARED_GROWERS_LOCK:
        return dict(_MEMO_COUNTS)


# XLA's memory analysis of the executable a jitted grower runs, by grower
# and argument shapes; the grower is held with its answer, so that its id
# stays its own.  Bounded like the memo of growers.
_GROWER_MEMORY: "OrderedDict[tuple, tuple]" = OrderedDict()


def compiled_grower_temp_bytes(grow, args, kwargs) -> Optional[int]:
    """Bytes of temporaries in the executable that the jitted grower ``grow``
    runs on these arguments, from XLA's own memory analysis: what the
    compiler laid out, padding included, which no count of logical shapes
    gives (at 2,000 features x 255 bins one padded copy was 15.6 GB of a
    state of 1.7).  None where ``grow`` is no jitted function or the backend
    has no analysis.

    Lowering reuses the trace of the call that ran; compiling reads the
    persistent cache where that call wrote there, and compiles a second
    time where it did not.  So the answer is remembered process-wide, a
    booster asks once, and only telemetry asks at all (``grower.temp_bytes``,
    docs/Observability.md)."""
    if not hasattr(grow, "lower"):
        return None
    leaves = jax.tree_util.tree_leaves((args, kwargs))
    key = (id(grow),) + tuple((tuple(a.shape), str(a.dtype))
                              for a in leaves if hasattr(a, "shape"))
    with _SHARED_GROWERS_LOCK:
        if key in _GROWER_MEMORY:
            return _GROWER_MEMORY[key][1]
    try:
        out = int(grow.lower(*args, **kwargs).compile()
                  .memory_analysis().temp_size_in_bytes)
    except Exception:           # no analysis on this backend: not a fault
        out = None
    with _SHARED_GROWERS_LOCK:
        _GROWER_MEMORY[key] = (grow, out)
        while len(_GROWER_MEMORY) > _SHARED_GROWERS_MAX:
            _GROWER_MEMORY.popitem(last=False)
    return out


def slot_histograms(h: jax.Array, nslots: int) -> jax.Array:
    """A step's contraction ``[3·nslots, F, B]`` (channel ``c·nslots +
    slot``, ops/histogram.py) as one histogram a slot, ``[nslots, 3, F,
    B]``: what the state holds a leaf (``_GrowState.hist``) and the split
    scan takes.  Only major axes move."""
    return h.reshape((3, nslots) + h.shape[1:]).swapaxes(0, 1)


# -- the rows a contraction is handed (PERF.md §6, PR 35) -------------------
# A step's contraction needs the rows of its target leaves (the smaller
# child of each split), a tenth of the rows in most steps of a 255-leaf
# tree.  Before the pass they are compacted into a row bucket, the smallest
# of a static ladder of capacities that holds them: rung r holds
# ``ceil(N / 2**r)`` rows, rung 0 is the pass over all N rows.  Which rungs
# a shape gets follows from what a row costs, in nanoseconds on the v5e
# (the builder's chip readings at 320,000 x 2,000 and 8.4M x 28 uint8,
# PERF.md §6, PR 35):

# to contract: every weight tile of a row block's one-hot (features x padded
# bins / 128 lanes) takes the block's 3 x padded channels of accumuland
# pieces through one of 4 MXUs at 1.5 GHz, 128 rows a block
# (``ops/hist_kernel.py``; PERF.md §6, PR 27: the kernel runs within 4-15%
# of it, 845 / 209 / 10.9 ns a row at 2,000 x 255, 2,000 x 63 and 28 x 255)
MXU_NS_PER_TILE_ROW = 1.0 / (4 * 1.5 * 128)
# to make a bucket's row indices, a row of N: one sort of the rows' numbers
# (0.4 ms at 320,000 rows and 18 at 8.4M inside the grower; alone 1.0 and
# 19.7, ``nonzero`` 3.5 and 76.5, a cumulative sum and a scatter 2.2 and 52.1)
INDEX_NS_PER_ROW = 2.5
# to gather a row into the bucket: its accumulands and slot, and its bins by
# the byte (inside the grower 23 ns a row of 28 bytes, 42 a row of 2,000)
GATHER_NS_PER_ROW = 25.0
GATHER_NS_PER_BYTE = 0.01
# a rung is worth its branch where it saves a tenth of the full pass
LEAST_SAVING = 0.1
# rungs are counted in a vector of this length a tree (``rung_steps``)
RUNGS = 8
# under this many rows a pass is one grid step or two of the kernel
LEAST_BUCKET_ROWS = 2048


def rung_capacity(n: int, rung: int) -> int:
    """Rows that rung ``rung`` of the ladder holds: all ``n`` at rung 0."""
    return -(-int(n) // 2 ** rung)


def rows_contracted(n: int, rung_steps) -> int:
    """Rows a tree's contractions were handed: ``rung_steps[r]`` passes at
    rung r of a grower over ``n`` rows (``TreeArrays.rung_steps``)."""
    return sum(int(c) * rung_capacity(n, r) for r, c in enumerate(rung_steps))


def contract_ns_per_row(features: int, num_bins: int, channels: int) -> float:
    """What the one-hot contraction costs a row by ``tile_plan``'s own
    arithmetic: weight tiles times streamed accumuland rows."""
    from .obs.flops import padded_bins
    cp = -(-int(channels) // 16) * 16
    return features * padded_bins(num_bins) / 128 * 3 * cp \
        * MXU_NS_PER_TILE_ROW


def compact_ladder(n: int, features: int, row_bytes: int, num_bins: int,
                   channels: int) -> tuple:
    """The rungs below the full pass that ``[n, features]`` bins of
    ``row_bytes`` a row get, as halvings of ``n`` in rising order: rung r is
    there where making the indices over all rows, gathering its ``n / 2**r``
    and contracting them costs at least ``LEAST_SAVING`` less than
    contracting all ``n``, and its bucket is no smaller than
    ``LEAST_BUCKET_ROWS``.  Short or empty where a row costs little more to
    contract than to gather (a narrow table)."""
    contract = contract_ns_per_row(features, num_bins, channels)
    a_row = GATHER_NS_PER_ROW + GATHER_NS_PER_BYTE * row_bytes + contract
    return tuple(
        r for r in range(1, RUNGS)
        if rung_capacity(n, r) >= LEAST_BUCKET_ROWS
        and INDEX_NS_PER_ROW + a_row / 2 ** r
        < (1.0 - LEAST_SAVING) * contract)


def pick_rung(count, caps):
    """Index into ``[full pass] + caps`` (``caps`` falling) of the smallest
    bucket that holds ``count`` rows: a bucket is chosen only where ``count
    <= cap`` was seen to hold, so it can never be too small."""
    return functools.reduce(
        jnp.add, [(count <= c).astype(jnp.int32) for c in caps],
        jnp.int32(0))


def row_reader(binned_view) -> Callable:
    """``rows(idx) -> [len(idx), F]`` of the dense binned matrix for rising
    row numbers ``idx`` (one past the end reads the last row).  Call it once
    a tree, outside the grow loop, as ``column_reader``.

    A matrix narrower than a vector register's lanes is turned once, so
    that a row is gathered lane by lane from a copy that is the gather's
    alone.  Gathered from the matrix itself, the TPU's compiler lays the
    matrix out that way for the whole loop and turns it back for the kernel
    at every pass over all rows (3 ms of 8.4M x 28 a step, under no scope;
    PERF.md §6, PR 35).  A wide one is gathered as it lies."""
    take = functools.partial(jnp.take, mode="clip", indices_are_sorted=True)
    if binned_view.shape[1] >= LANES:
        return lambda idx: take(binned_view, idx, axis=0)
    with jax.named_scope("lgbtpu.hist.compact"):
        by_column = binned_view.T
    return lambda idx: take(by_column, idx, axis=1).T


def contract_compacted(contract, binned_view, vals, tslot, rungs,
                       rows: Optional[Callable] = None):
    """``contract(binned_view, vals, tslot)`` over the target rows alone
    (``tslot >= 0``; the rest add nothing to it), and the rung of the row
    bucket it was handed, 0 for all rows.  ``rows`` is the matrix's
    ``row_reader``, made outside the loop this is called in.

    The target rows are compacted into the smallest bucket of ``rungs``
    that holds them: their indices in rising order (a sort of the rows'
    numbers with the others' set past the end: the rows keep their relative
    order), then one ``take`` each of the bins, the accumulands and the
    slots, under ``lgbtpu.hist.compact``; the bucket's unused rows carry
    slot -1, which a contraction meets with no row of its one-hot.
    The rungs are the branches of one ``switch``, chosen by ``pick_rung``
    from the count of the very mask that is compacted; more rows than the
    largest bucket holds, or no rung at all, is the pass over all rows."""
    if not rungs:
        return contract(binned_view, vals, tslot), jnp.int32(0)
    n = vals.shape[0]
    caps = [rung_capacity(n, r) for r in rungs]
    rows = rows or row_reader(binned_view)
    with jax.named_scope("lgbtpu.hist.compact"):
        target = tslot >= 0
        which = pick_rung(jnp.sum(target, dtype=jnp.int32), caps)

    def bucket(cap):
        def f():
            with jax.named_scope("lgbtpu.hist.compact"):
                # the bucket's unused places read ``n``, which every
                # ``take`` clips to the last row
                idx = jnp.sort(jnp.where(
                    target, jnp.arange(n, dtype=jnp.int32), n))[:cap]
                take = functools.partial(
                    jnp.take, indices=idx, axis=0, mode="clip",
                    indices_are_sorted=True)
                s = jnp.where(idx < n, take(tslot), -1)
                b, v = rows(idx), take(vals)
            return contract(b, v, s)
        return f

    hist = lax.switch(
        which, [lambda: contract(binned_view, vals, tslot)]
        + [bucket(c) for c in caps])
    return hist, jnp.asarray((0,) + tuple(rungs), jnp.int32)[which]


def column_reader(binned) -> Optional[Callable]:
    """``columns(col_k) -> K arrays [N]`` for ``[K]`` traced column ids of
    the dense binned matrix: K dynamic slices, no gather.  Call it once a
    tree, outside the grow loop.  ``None`` for a ``SparseBinned`` matrix,
    which has no columns to slice.

    Of a matrix of 128 columns or more a column slice reads the one lane
    tile that holds it, N x 128 bytes (2.7 ms a step of 16 at 320,000 x
    2,000).  A narrower one has every column in every tile, whichever way
    the TPU's compiler lays it out (28 columns padded to 128 lanes, or
    column-major with 32 columns to a tile), so each slice reads all of it,
    16 times a step (7.6 ms at 8.4M x 28): that one is turned once a tree
    into one run of N bytes a column, end to end, N x F bytes in all, and
    a column is a contiguous slice that the selects read in place (PERF.md
    §6, PR 31).  Turning a wide one too is faster still (0.2 ms a step and
    2.7 ms a tree at 320,000 x 2,000) but holds a second copy of the matrix
    while a tree grows.  The runs are addressed in int32, so a narrow matrix
    of 2**31 cells or more is sliced as a wide one is."""
    if isinstance(binned, _spd.SparseBinned):
        return None
    n, f = binned.shape
    turned = f < LANES and n * f < 2 ** 31
    by_column = binned.T.reshape(-1) if turned else binned

    def columns(col_k):
        if turned:
            return [lax.dynamic_slice_in_dim(by_column, col_k[k] * n, n)
                    for k in range(col_k.shape[0])]
        return [lax.dynamic_index_in_dim(by_column, col_k[k], axis=1,
                                         keepdims=False)
                for k in range(col_k.shape[0])]
    return columns


class _Unkeyable(Exception):
    pass


def _key_part(x):
    if x is None or isinstance(x, (bool, int, float, str)):
        return x
    if isinstance(x, (tuple, list)):
        return tuple(_key_part(v) for v in x)
    try:
        a = np.asarray(x)
    except Exception:
        raise _Unkeyable
    if a.dtype == object:
        # np.asarray(<arbitrary object>).tobytes() is the raw CPython
        # POINTER — address reuse after GC would alias two different
        # configs onto one cached grower.  Unkeyable -> private jit.
        raise _Unkeyable
    return (str(a.dtype), a.shape, a.tobytes())


def _grower_key(kw: dict):
    try:
        return tuple((k, _key_part(v)) for k, v in sorted(kw.items()))
    except _Unkeyable:
        return None


class TreeArrays(NamedTuple):
    """Array-encoded tree (include/LightGBM/tree.h:25 analog).

    Internal nodes are 0..num_leaves-2; a child pointer < 0 encodes leaf
    ``~child`` (tree.h leaf encoding).
    """
    num_leaves: jax.Array        # scalar int32, actual number of leaves
    split_feature: jax.Array     # [L-1] int32 (used-feature slot)
    threshold_bin: jax.Array     # [L-1] int32
    default_left: jax.Array      # [L-1] bool
    left_child: jax.Array        # [L-1] int32
    right_child: jax.Array       # [L-1] int32
    split_gain: jax.Array        # [L-1] f32
    leaf_value: jax.Array        # [L] f32
    leaf_weight: jax.Array       # [L] f32 (sum hessian)
    leaf_count: jax.Array        # [L] f32
    internal_value: jax.Array    # [L-1] f32
    internal_weight: jax.Array   # [L-1] f32
    internal_count: jax.Array    # [L-1] f32
    leaf_depth: jax.Array        # [L] int32
    leaf_of_row: jax.Array       # [N] int32 — final row -> leaf assignment
    is_cat_node: jax.Array       # [L-1] bool — categorical split flags
    cat_rank: jax.Array          # [L-1, B] int32 — per-node bin decision rank
    n_steps: jax.Array           # scalar int32 — grower loop steps taken
    #                              (== splits for strict leaf-wise; < splits
    #                              for split_batch>1 super-steps) — perf
    #                              observability, not part of the model
    rung_steps: jax.Array        # [RUNGS] int32 — contractions of the tree
    #                              by the rung of the row bucket they were
    #                              handed (0: all rows; ``rows_contracted``)


class _GrowState(NamedTuple):
    leaf_of_row: jax.Array
    hist: jax.Array              # [L, 3, F, B], channel-major
    # per-leaf allowed output range (monotone 'basic' method; ±inf w/o)
    olo: jax.Array               # [L] f32
    ohi: jax.Array               # [L] f32
    # per-leaf BRANCH feature sets (interaction constraints; [1,1] w/o) —
    # the allowed mask is derived per step by subset containment against
    # the constraint groups (col_sampler.hpp:91-111 GetByNode)
    fallow: jax.Array            # [L, F] bool (or [L, 1] placeholder)
    # features already split on (CEGB coupled penalties; [1] w/o)
    cuse: jax.Array              # [F] bool (or [1] placeholder)
    # per-leaf best-split candidates
    bg: jax.Array                # [L] gain
    bf: jax.Array                # [L] feature
    bt: jax.Array                # [L] threshold
    bdl: jax.Array               # [L] default_left
    bls: jax.Array               # [L, 3] left sums
    brs: jax.Array               # [L, 3] right sums
    blo: jax.Array               # [L] left output
    bro: jax.Array               # [L] right output
    bic: jax.Array               # [L] bool is-categorical
    brank: jax.Array             # [L, B] decision rank vector
    # tree arrays under construction
    split_feature: jax.Array
    threshold_bin: jax.Array
    default_left: jax.Array
    left_child: jax.Array
    right_child: jax.Array
    split_gain: jax.Array
    leaf_value: jax.Array
    leaf_weight: jax.Array
    leaf_count: jax.Array
    internal_value: jax.Array
    internal_weight: jax.Array
    internal_count: jax.Array
    leaf_depth: jax.Array
    leaf_parent: jax.Array       # [L] int32
    num_leaves: jax.Array        # scalar int32
    done: jax.Array              # scalar bool
    is_cat_node: jax.Array       # [L-1] bool
    cat_rank: jax.Array          # [L-1, B] int32


def make_grower(*, num_leaves: int, num_bins: int, params: SplitParams,
                max_depth: int = -1, block_rows: int = 0,
                hist_reduce: Optional[Callable] = None,
                hist_view: Optional[Callable] = None,
                hist_expand: Optional[Callable] = None,
                select_best: Optional[Callable] = None,
                mono_view: Optional[Callable] = None,
                subtract: bool = True,
                sum_reduce: Optional[Callable] = None,
                efb=None,
                gain_scale=None,
                extra_trees: bool = False, extra_seed: int = 6,
                split_batch: int = 1,
                hist_overlap: bool = False,
                mono=None, mono_penalty: float = 0.0,
                interaction_groups=None,
                bynode_frac: float = 1.0, bynode_seed: int = 0,
                cegb=None,
                padded_leaves: Optional[int] = None,
                quant=None,
                scale_reduce: Optional[Callable] = None,
                row_offset: Optional[Callable] = None,
                vmapped: bool = False,
                jit: bool = True):
    """Build a jitted ``grow_tree(binned, vals, feature_mask, num_bin, na_bin,
    na_bin_part=None)``.

    ``padded_leaves``: leaf-budget bucketing (utils/shapes.bucket_leaves)
    — state arrays are sized to this PADDED budget while the grow loop
    exits on the ACTUAL budget, which the caller must then pass per call
    as the traced ``max_leaves`` scalar.  One padded trace covers every
    ``num_leaves`` in its bucket (31/40/63 share L=64) with
    bit-identical trees: padded leaf slots start at -inf cached gain so
    argmax/top_k never select them, and the host side slices all tree
    arrays by the returned ``num_leaves``.

    vals: [N, 3] f32 = (grad, hess, in-bag weight); out-of-bag rows zeroed.

    Parallelism hooks (SURVEY.md §2.6 strategies map onto one program);
    every histogram a hook takes or returns is channel-major,
    ``[C, F|G, B|Bg]`` (``_hist`` below):
    - hist_reduce: reduce local histograms across the mesh row axis
      (data-parallel psum; identity for serial).  The hook may SHRINK the
      feature axis: the owner-shard data-parallel learner reduce-scatters
      a feature-chunked layout (``lax.psum_scatter``) so each shard's
      histogram carry holds only its owned chunk of the GLOBAL
      histograms — the carry and every child histogram follow the
      reduced shape, never the local-view width.
    - hist_expand: maps the (possibly owner-chunked) reduced histogram
      plus the leaf totals into the SPLIT-SCAN feature space — replaces
      the built-in EFB group->feature expansion when the scan space is a
      per-shard slice (owner-shard dp; identity slicing without EFB).
      ``num_bin``/``na_bin``/``feature_mask``/``is_cat`` must then be the
      scan-space slices, while ``na_bin_part``/``num_bin_part`` carry the
      global arrays for row partitioning.
    - mono_view: maps the global [F] monotone-constraint vector into the
      split-scan feature space (owner-shard dp); partitioning keeps the
      global vector (the winning feature id is global).
    - hist_view:   restrict the binned matrix to this shard's feature slice
      before histogram work (feature-parallel; identity for serial).
      ``feature_mask``/``num_bin``/``na_bin`` must then be the local slices,
      while ``na_bin_part`` carries the global array for row partitioning.
    - select_best: cross-shard reduction of a SplitResult (feature-parallel
      argmax + feature-index globalization; identity for serial).
    - vmapped: the caller runs the body under ``jax.vmap`` (the fleet's
      member axis).  Not a choice of the user's: a fact about the caller
      that the body cannot see, and the one exclusion of the row buckets
      below that it must be told.
    - efb: an ``EFBDevice`` — ``binned`` is then the BUNDLED group matrix
      [N, G] (dataset.cpp:239 FastFeatureBundling); histograms are built
      and subtracted in the narrow group space (the HBM-bandwidth win) and
      expanded to feature space only for split search, with the leaf's
      totals reconstructing the shared default bin (FixHistogram,
      dataset.cpp:1292).  Row partitioning decodes the winning feature's
      bins from its group column.
    - interaction_groups: [G, F] bool constraint-group matrix (ColSampler
      / col_sampler.hpp:91-111 GetByNode): per-leaf BRANCH feature sets
      are tracked on device ([L, F] state); a leaf may split on its
      branch features plus the union of the groups that contain the
      whole branch set (subset containment — progressive intersection
      diverges for overlapping groups), and the root is restricted to
      the union of all groups.
    - bynode_frac/bynode_seed: feature_fraction_bynode — every candidate
      leaf evaluation draws its own random feature subset in-graph
      (keyed by iteration/step/child so the fused scan reproduces the
      per-iteration stream).
    - cegb: a ``CEGBState`` (cost_effective_gradient_boosting.hpp):
      per-candidate acquisition penalties subtracted from gains in-graph;
      within-tree feature usage is tracked as an [F] bool state vector,
      and cross-tree usage comes in through ``grow(..., cegb_used=...)``
      (the caller derives the update from the returned split features).
    - mono/mono_penalty: [F] -1/0/+1 monotone constraints, 'basic' method
      (monotone_constraints.hpp BasicLeafConstraints): per-leaf allowed
      output ranges tracked ON DEVICE ([L] lo/hi vectors in the grow
      state), split candidates clamped+filtered in the split scan, child
      ranges bounded by the split midpoint.  Works under hist_reduce
      (data-parallel monotone, which the reference supports in all
      parallel learners) because ranges derive from replicated split
      decisions.  mono_penalty applies the depth-based gain de-rating
      (ComputeMonotoneSplitGainPenalty, monotone_constraints.hpp:355).
    - quant: a ``QuantSpec`` (ops/quantize.py) — quantized training:
      the (grad, hess, weight) stack is packed to int8/int16 with one
      shared per-channel scale per call (= per boosting iteration) and
      iteration-keyed stochastic rounding (``rng_iter`` keys the
      counter-based stream, so resume stays byte-identical), histograms
      accumulate exact int32 through the same one-hot contraction (the
      carry, the subtraction trick and any ``hist_reduce`` collective
      all run on int32), and dequantization happens only at split-scan
      time (ops/split.py ``dequantize_hist``).  Hooks for the sharded
      learners: ``scale_reduce`` maxes the [3] scale vector across the
      mesh so every shard quantizes with the GLOBAL scale, and
      ``row_offset(n_local)`` returns this shard's global row offset so
      the rounding stream is keyed by GLOBAL row ids — together they
      make the int32 reduce bitwise dp==serial.
    - split_batch=K>1: grow K leaves per super-step instead of strictly
      one.  Each step picks the top-K leaves by cached best gain, applies
      all K splits in one row-partition pass, and builds all K smaller
      children's histograms in ONE one-hot contraction with C=3K channels.
      A pass costs what its one-hot costs whatever the channels carry:
      the TPU's kernel streams 3·16·⌈C/16⌉ accumuland rows through every
      weight tile of the one-hot (ops/hist_kernel.py; its MXU floor,
      PERF.md §6), so one pass for K leaves costs little more than one
      pass for one and the per-split cost drops toward 1/K.  Trees differ
      slightly from strict leaf-wise
      growth (between LightGBM's leaf-wise and XGBoost's depth-wise);
      K=1 keeps exact reference semantics and is the default.  Widths
      are snapped into ``utils/shapes.SPLIT_BATCH_SET`` (and fitted
      under the leaf budget) by the driver; the wide widths (32/64)
      lane-pad their C=3K channel axis to MXU 128-multiples inside the
      contraction (ops/histogram.py) — exact zeros, sliced off,
      excluded from MFU accounting (obs/flops.py ``hist_pad``).
    - hist_overlap: route the STRICT (K=1) grower's masked smaller-child
      pass through the same per-row slot mechanism the batched grower
      uses (``slot = 0 if in_child else -1``, num_slots=1) instead of
      materializing a fresh ``vals * mask`` [N, 3] scan operand per
      split.  The slot one-hot multiplies the identical 0/1 factors
      inside the row-block scan, so the histogram — and the trained
      model — is BYTE-IDENTICAL to the serialized masked baseline
      (tests/test_hist_width.py pins it), while the per-split scan
      operand shrinks to one [N] int32 slot vector and the strict path
      shares the contraction form (and the autotuner's block_rows
      choice, ops/hist_tune.py) with the batched super-step.
      Sparse-binned data keeps the masked form (its per-slot total
      reduction has a different summation order).

    **The rows a contraction is handed.**  A step's target rows (the
    smaller child of each split) are compacted into the smallest row bucket
    of ``compact_ladder``'s that holds them, and the contraction runs over
    the bucket (``_contractor``, scope ``lgbtpu.hist.compact`` for the index
    making and the gather); the ladder follows from the shapes alone and
    may be empty.  Every product and every float32 sum of the full pass is
    made, in another order of the blocks.  It applies to this function's
    own body over dense rows that every worker holds whole, as the
    followers do (``gbdt._followers``): the one-chip grower and the
    feature-sharded one, the strict and the batched.  A ``hist_reduce``
    hook (the row-sharded learners, whose shards would each pick a bucket
    of their own around a collective), integer accumulands (``quant``), a
    ``SparseBinned`` matrix and a ``vmapped`` body (a ``switch`` under
    ``vmap`` runs every branch) keep the pass over all rows.  The tree
    says what it was handed: ``TreeArrays.rung_steps``.
    """
    L_req = int(num_leaves)
    L = int(padded_leaves) if padded_leaves and int(padded_leaves) > L_req \
        else L_req
    padded = L != L_req
    B = int(num_bins)
    use_quant = quant is not None
    if use_quant:
        from .ops.quantize import quant_scales, quantize_stack
        from .ops.split import dequantize_hist
    reduce_fn = hist_reduce or (lambda h, scales=None: h)
    view_fn = hist_view or (lambda b: b)
    select_fn = select_best or (lambda r: r)
    use_subtraction = subtract
    Bh = int(efb.group_bins) if efb is not None else B   # histogram bin axis
    if efb is not None:
        efb_off_dev = jnp.asarray(efb.off_host)
    if hist_expand is not None:
        # owner-shard distribution: the reduced histogram is this shard's
        # chunk of the global one; the hook views it in scan space
        # (including the EFB group->feature expansion, done per shard)
        _expand = hist_expand
    elif efb is not None:
        from .efb import expand_group_hist

        def _expand(gh, total):
            # the EFB expansion keeps the [G, Bg, C] layout it shares with
            # the partitioned learner; it sees a turned view
            return jnp.moveaxis(expand_group_hist(
                jnp.moveaxis(gh, 0, -1), total, efb.group_of_feat,
                efb.col_idx, efb.fix0), -1, 0)
    else:
        def _expand(gh, total):
            return gh

    def _hist(binned_view, vals, slot=None, nslots=1, scales=None):
        """Reduced histogram, channel-major ``[C, F|G, B|Bg]`` (C = 3, or
        3·nslots with channel ``c * nslots + slot``): the layout of the
        grower's state, of every hook and of the split scan.  The 3
        channels are never an array's minor axis here: the TPU's compiler
        pads such an axis to 128 lanes where it tiles it, and at 2,000
        features x 255 bins one padded copy was 15.6 GB (PERF.md §6, PR
        30).  With ``slot`` a per-slot multi-histogram
        (split_batch) whose vals ⊗ onehot(slot) expansion happens inside
        the scan (ops/histogram.py), never as an [N, 3*K] HBM buffer.
        Sparse-binned data takes the O(nnz) segment-sum formulation
        (sparse_data.py) instead of the one-hot contraction.  Under
        quantized training the hook receives the iteration's scales as
        a second argument (voting's gain-statistic vote needs real
        values; the reduce itself stays int32)."""
        if isinstance(binned_view, _spd.SparseBinned):
            h = jnp.moveaxis(_spd.histogram(
                binned_view, vals, num_bins=Bh, slot=slot,
                num_slots=nslots), -1, 0)
        else:
            h = compute_histogram(binned_view, vals, num_bins=Bh,
                                  block_rows=block_rows, slot=slot,
                                  num_slots=nslots, channel_major=True)
        return reduce_fn(h, scales) if use_quant else reduce_fn(h)

    def _quant_prepare(n, vals, feature_mask, rng_iter, n_leaves,
                       quant_seed=None):
        """Shared quantized-training entry for the strict and batched
        growers: trace-time flop/byte notes, the per-iteration GLOBAL
        scales, and the iteration-keyed stochastic quantization of the
        grad/hess/weight stack (ops/quantize.py).  One definition so
        the rounding key and scale reduction can never diverge between
        the two paths — the fused==per-iter and dp==serial bitwise
        contracts hang off them.  Returns (vals, scales, scan_expand);
        ``n_leaves`` sizes the dequant ledger note (2 children per
        split, 2K under a K-way super-step)."""
        from .obs.flops import (dequant_flops_bytes, note_traced,
                                quantize_flops_bytes)
        note_traced("quantize", *quantize_flops_bytes(
            n, quant.itemsize), phase="grow", cadence="iter")
        note_traced("dequant", *dequant_flops_bytes(
            feature_mask.shape[0], B, n_leaves=n_leaves), phase="grow")
        scales = quant_scales(vals, quant.qmax)
        if scale_reduce is not None:
            scales = scale_reduce(scales)
        off = row_offset(n) if row_offset is not None else 0
        ikey = jnp.int32(0) if rng_iter is None \
            else jnp.asarray(rng_iter, jnp.int32)
        vals = quantize_stack(vals, scales, quant, ikey, off,
                              seed=quant_seed)

        def scan_expand(h, t):
            return _expand(dequantize_hist(h, scales, axis=0), t)
        return vals, scales, scan_expand

    compacts = hist_reduce is None and not use_quant and not vmapped

    def _contractor(binned_view, vals, nslots, scales=None, masked=False):
        """``contract(tslot) -> (histograms, rung)`` for one tree, made once
        outside its grow loop: the histograms of the rows whose ``tslot`` is
        not negative, by slot, as ``_hist`` returns them, and the rung of
        the row bucket the contraction was handed (0: all rows;
        ``contract_compacted`` over the ladder that the rule above and
        ``compact_ladder`` give these shapes).  ``masked`` is the strict
        grower's form without a slot: the other rows' accumulands zeroed."""
        def pass_(b, v, s):
            if masked:
                return _hist(b, v * (s >= 0).astype(v.dtype)[:, None],
                             scales=scales)
            return _hist(b, v, s, nslots, scales=scales)

        rungs, rows = (), None
        if compacts and not isinstance(binned_view, _spd.SparseBinned):
            f = binned_view.shape[1]
            rungs = compact_ladder(
                vals.shape[0], f, f * binned_view.dtype.itemsize, Bh,
                vals.shape[1] * nslots)
            rows = row_reader(binned_view) if rungs else None
        return lambda tslot: contract_compacted(
            pass_, binned_view, vals, tslot, rungs, rows)

    def _child_contractor(binned_view, vals, scales=None):
        """The strict grower's ``child_hist(leaf_of_row, child_id) ->
        (histogram, rung)``, made once a tree.  With ``hist_overlap`` the
        mask rides as a 1-slot id, so the 0/1 multiply happens INSIDE the
        row-block scan — byte-identical products, but the per-split scan
        operand is one [N] int32 vector instead of a fresh [N, 3] masked
        temp (see make_grower doc)."""
        contract = _contractor(
            binned_view, vals, 1, scales,
            masked=not hist_overlap
            or isinstance(binned_view, _spd.SparseBinned))
        return lambda leaf_of_row, child_id: contract(jnp.where(
            leaf_of_row == child_id, jnp.int32(0), jnp.int32(-1)))

    def _partition_rows(binned, columns, leaf_of_row, is_cat, na_bin_part,
                        num_bin_part, leaf_k, new_leaf_k, feat_k, thr_k,
                        dleft_k, icat_k, rank_k, tleft_k=None,
                        tright_k=None):
        """The row partition of one step, the strict grower's (one slot)
        and the batched one's (K): a row of leaf ``leaf_k[k]`` stays if
        the rank of its bin in column ``feat_k[k]`` is at most
        ``thr_k[k]`` (its missing-value bin goes where ``dleft_k[k]``
        says) and moves to ``new_leaf_k[k]`` otherwise.  Returns the new
        ``leaf_of_row`` and, where ``tleft_k`` / ``tright_k`` name the
        contraction slot of each side's rows (-1: none), the rows' ``tslot``.

        What a row needs of its slot is K scalars, not a table: every
        per-slot value reaches the rows by K compares and selects, fused
        into one pass, where a look-up by the row's slot was an ``[N]``
        gather that the TPU runs element by element (8-12 ns a row each,
        ten of them a step: three fifths of HIGGS's device time, PERF.md
        §6, PR 31).  The K split columns are K dynamic slices of the
        dense matrix (``columns``, the tree's ``column_reader(binned)``).
        The rule follows what the trace sees of its input, and
        ``grower.partition_rule{rule=}`` says which it took:

        - ``select``: dense matrix, ``is_cat is None``.  The split scan
          then only ever returns the identity rank (ops/split.py
          ``iota_rank``), so the rank of a bin is the bin: no ``[N]``
          gather is left.
        - ``select+rank``: a categorical feature is present; the one
          look-up ``rank_k[slot, bin]`` stays.
        - ``sparse``: a ``SparseBinned`` matrix has no columns to slice;
          its rows' bins come from ``column_per_row`` as before (one
          look-up of the absent value's bin), the rank's as above.

        ``na_bin_part`` / ``num_bin_part`` are the global arrays under the
        feature-parallel and owner-shard learners, indexed by the global
        ``feat_k``."""
        nslots = leaf_k.shape[0]
        sparse = columns is None
        hit = [leaf_of_row == leaf_k[k] for k in range(nslots)]

        def of_slot(v, none):
            """``v[k]`` for the rows of slot k, ``none`` for the rest; a
            row is in at most one slot."""
            out = none
            for k in range(nslots):
                out = jnp.where(hit[k], v[k], out)
            return out

        if sparse:
            raw = _spd.column(binned, feat_k[0]) if nslots == 1 else \
                _spd.column_per_row(binned, of_slot(feat_k, jnp.int32(0)))
        else:
            col_k = feat_k if efb is None else efb.group_of_feat[feat_k]
            cols = columns(col_k)
            raw = of_slot(cols, jnp.zeros((), cols[0].dtype)) \
                .astype(jnp.int32)
        if efb is None:
            fcol = raw
        else:
            # decode the feature's bins from its bundle column
            # (SubFeatureIterator analog, feature_group.h)
            off = of_slot(efb_off_dev[feat_k], jnp.int32(-1))
            nbp = of_slot(num_bin_part[feat_k], jnp.int32(0))
            in_range = (raw >= off) & (raw < off + nbp - 1)
            fcol = jnp.where(off < 0, raw,
                             jnp.where(in_range, raw - off + 1, 0))
        nb = of_slot(na_bin_part[feat_k], jnp.int32(-1))
        is_na = (nb >= 0) & (fcol == nb)
        # decision rank unifies numerical (iota rank) and categorical
        # (ratio-order rank) predicates
        if is_cat is None:
            rv = fcol
        else:
            is_na = is_na & ~of_slot(icat_k, jnp.bool_(False))
            rv = rank_k[of_slot(jnp.arange(nslots, dtype=jnp.int32),
                                jnp.int32(0)), fcol]
        go_left = jnp.where(is_na, of_slot(dleft_k, jnp.bool_(False)),
                            rv <= of_slot(thr_k, jnp.int32(0)))
        moved = functools.reduce(jnp.logical_or, hit) & ~go_left
        new_leaf_of_row = jnp.where(
            moved, of_slot(new_leaf_k, jnp.int32(0)), leaf_of_row)
        if tleft_k is None:
            return new_leaf_of_row, None
        return new_leaf_of_row, jnp.where(
            go_left, of_slot(tleft_k, jnp.int32(-1)),
            of_slot(tright_k, jnp.int32(-1)))

    def _note_partition_rule(columns, is_cat, nslots):
        """Count the rule that ``_partition_rows`` takes over the training
        rows, once a trace (``grower.partition_rule{rule=}``)."""
        from .obs.flops import note_partition_rule
        sparse = columns is None
        note_partition_rule(
            "sparse" if sparse else
            "select" if is_cat is None else "select+rank",
            row_gathers=int(sparse and nslots > 1) + int(is_cat is not None))

    def _followers_at_root(followers):
        """``(columns, leaves)`` of the followers before the first split:
        one ``column_reader`` a follower, made once a tree beside the
        training matrix's, and every row at the root.  Followers are row
        sets that take no part in the tree (the held-out sets of a booster,
        ``[Nv, F|G]`` binned like the training matrix): the grower only
        carries them through every split it makes, so that their leaves are
        known when the tree is."""
        with jax.named_scope("lgbtpu.walk"):
            return (tuple(column_reader(f) for f in followers),
                    tuple(jnp.zeros(f.shape[0], jnp.int32)
                          for f in followers))

    def _follow(followers, fcolumns, fleaves, *step):
        """The step's partition (``step``: ``is_cat`` ... ``rank_k`` as
        ``_partition_rows`` takes them, no contraction slots) on every
        follower's ``leaf_of_row``.  Under ``lgbtpu.walk``, the scope of
        the held-out rows' way through a new tree: inside ``lgbtpu.grow``
        the innermost scope is the one an operation is booked to."""
        with jax.named_scope("lgbtpu.walk"):
            return tuple(
                _partition_rows(f, c, lor, *step)[0]
                for f, c, lor in zip(followers, fcolumns, fleaves))

    gscale = None if gain_scale is None else jnp.asarray(gain_scale,
                                                         jnp.float32)
    mono_dev = None if mono is None else jnp.asarray(mono, jnp.int32)
    use_mono = mono_dev is not None

    def _scan_mono():
        """Monotone vector in SPLIT-SCAN feature space: owner-shard
        learners scan only their owned feature chunk (mono_view gathers
        the slice in-graph); identity otherwise.  Partitioning and child
        range propagation keep indexing the GLOBAL ``mono_dev`` — the
        winning feature id is global after select_best."""
        return mono_dev if mono_view is None else mono_view(mono_dev)
    inter_dev = None if interaction_groups is None \
        else jnp.asarray(interaction_groups, bool)     # [G, F]
    use_inter = inter_dev is not None

    def _inter_allowed(branch):
        """GetByNode: branch ∪ (∪ groups that contain the whole branch).
        ``branch`` [F] bool -> allowed [F] bool.  An empty branch is a
        subset of every group -> union of all groups (root case)."""
        contains = (inter_dev | ~branch[None, :]).all(axis=1)      # [G]
        return (inter_dev & contains[:, None]).any(axis=0) | branch
    use_bynode = 0.0 < float(bynode_frac) < 1.0
    use_cegb = cegb is not None and cegb.active
    if use_cegb:
        nf_c = len(cegb.used)
        lazy = cegb.lazy if cegb.lazy is not None else np.zeros(nf_c)
        # per-count slope and coupled once-per-model components of
        # CEGBState.penalty_vector, as device constants
        cegb_slope = jnp.asarray(
            cegb.tradeoff * (cegb.penalty_split + lazy), jnp.float32)
        cegb_coupled = None if cegb.coupled is None else \
            jnp.asarray(cegb.tradeoff * cegb.coupled, jnp.float32)

    def _cegb_penalty(count, cuse):
        pen = cegb_slope * count
        if cegb_coupled is not None:
            pen = pen + cegb_coupled * (~cuse)
        return pen
    # per-leaf feature masks are threaded through _best2 whenever EITHER
    # mechanism is active (they compose by &)
    per_leaf_mask = use_inter or use_bynode

    def _bynode_mask(key, base):
        """One random feature subset (ColSampler bynode): keep
        ceil(frac * |valid|) features sampled FROM the valid set ``base``
        (reference semantics, col_sampler.hpp — sampling from the full
        axis and intersecting could leave a constrained branch with an
        empty candidate set).  Always keeps >= 1 valid feature."""
        nf = base.shape[0]
        nvalid = base.sum()
        k = jnp.maximum(1, jnp.ceil(
            nvalid.astype(jnp.float32) * bynode_frac)).astype(jnp.int32)
        u = jnp.where(base, jax.random.uniform(key, (nf,)), jnp.inf)
        rank = jnp.argsort(jnp.argsort(u))
        return base & (rank < k)

    def _rand_bins(key, shape, num_bin):
        """extra_trees (feature_histogram.hpp:116): one random threshold
        bin per feature, uniform over the feature's valid range."""
        u = jax.random.uniform(key, shape)
        span = jnp.maximum(num_bin - 1, 1).astype(jnp.float32)
        return jnp.minimum((u * span).astype(jnp.int32), num_bin - 2)

    def _mono_gain_scale(depth):
        """Per-feature [F] penalty scale on monotone features, composed
        with ``gain_scale`` (shared formula: ops/split.py
        monotone_penalty_factor); scan-space under owner sharding."""
        factor = monotone_penalty_factor(mono_penalty, depth)
        gs = jnp.where(_scan_mono() != 0, factor, 1.0).astype(jnp.float32)
        return gs if gscale is None else gs * gscale

    def _best2(hist2, totals2, num_bin, na_bin, fmask, parent_out2, is_cat,
               rand2=None, lo2=None, hi2=None, depth2=None, fmask2=None,
               cuse_cur=None):
        """Vmapped best-split over a batch of candidate leaves; optional
        per-leaf extra_trees random bins, monotone output ranges, and
        per-leaf feature masks (interaction constraints / bynode)."""
        extras, axes = [], []
        if rand2 is not None:
            extras.append(rand2)
            axes.append(0)
        if use_mono:
            extras += [lo2, hi2, depth2]
            axes += [0, 0, 0]
        if fmask2 is not None:
            extras.append(fmask2)
            axes.append(0)

        def one(h, t, po, *rest):
            i = 0
            kw = {}
            if rand2 is not None:
                kw["rand_bin"] = rest[i]
                i += 1
            if use_mono:
                lo, hi, d = rest[i], rest[i + 1], rest[i + 2]
                i += 3
                kw.update(mono=_scan_mono(), out_lo=lo, out_hi=hi)
                kw["gain_scale"] = _mono_gain_scale(d) \
                    if mono_penalty > 0.0 else gscale
            else:
                kw["gain_scale"] = gscale
            fm = rest[i] if fmask2 is not None else fmask
            if use_cegb:
                # cuse_cur is shared by all children of this step (the
                # vmap closes over it); the penalty's count term is the
                # candidate leaf's own row count
                kw["gain_penalty"] = _cegb_penalty(t[2], cuse_cur)
            return select_fn(find_best_split(h, t, num_bin, na_bin, fm,
                                             params, po, is_cat, **kw))

        return jax.vmap(one, in_axes=(0, 0, 0) + tuple(axes))(
            hist2, totals2, parent_out2, *extras)

    def _child_ranges(lo_p, hi_p, mc, icat, mid):
        """BasicLeafConstraints child range propagation: a +1 split caps
        the left child at the midpoint and floors the right child (and
        mirrored for -1); categorical or unconstrained splits inherit."""
        apply = (mc != 0) & (~icat)
        up = mc > 0
        l_lo = jnp.where(apply & (~up), jnp.maximum(lo_p, mid), lo_p)
        l_hi = jnp.where(apply & up, jnp.minimum(hi_p, mid), hi_p)
        r_lo = jnp.where(apply & up, jnp.maximum(lo_p, mid), lo_p)
        r_hi = jnp.where(apply & (~up), jnp.minimum(hi_p, mid), hi_p)
        return l_lo, l_hi, r_lo, r_hi

    def _root_eval(binned_view, vals, feature_mask, num_bin, na_bin,
                   is_cat, rng_iter, cuse0=None, expand=None,
                   scales=None):
        """Root histogram + aggregates + best split; shared by the strict
        and batched growers.  ``expand``/``scales``: quantized training
        — ``vals`` is already the int stack, ``expand`` dequantizes
        before the scan-space view, and the root aggregates come from
        exact int32 sums dequantized by the shared scales."""
        expand = _expand if expand is None else expand
        hist0 = _hist(binned_view, vals, scales=scales)  # [3, F|G, B|Bg]
        # root aggregates from vals directly, NOT from feature 0's: a filtering
        # hist_reduce (voting's top-k zeroing) may have dropped feature 0's
        # histogram, and this is also one less reduction of a big tensor
        if scales is not None:
            # int32 sums are exact; cross-shard sum_reduce (psum) runs
            # on the integers so the dequantized totals are bitwise
            # identical between serial and every sharded learner
            if sum_reduce is not None:
                ti = sum_reduce(vals.astype(jnp.int32).sum(axis=0))
            elif hist_reduce is not None:
                ti = hist0[:, 0].sum(axis=1)
            else:
                ti = vals.astype(jnp.int32).sum(axis=0)
            total0 = dequantize_hist(ti, scales)
        elif sum_reduce is not None:
            total0 = sum_reduce(vals.sum(axis=0))
        elif hist_reduce is not None:
            # caller-supplied reduce hook without a sum_reduce: derive the
            # totals from the reduced histogram so cross-shard hooks keep
            # seeing globally-reduced root aggregates
            total0 = hist0[:, 0].sum(axis=1)
        else:
            total0 = vals.sum(axis=0)
        root_out = leaf_output(total0[0], total0[1], params)
        rb0 = None
        et_key = None
        if extra_trees:
            # key = (extra_seed, iteration, split index): without the
            # iteration fold every TREE would redraw identical thresholds
            # and the ExtraTrees decorrelation would be lost entirely
            et_key = jax.random.PRNGKey(extra_seed)
            if rng_iter is not None:
                et_key = jax.random.fold_in(et_key, rng_iter)
            # the split search runs in (possibly EFB-expanded) feature
            # space = feature_mask's axis, not binned_view's column count
            rb0 = _rand_bins(jax.random.fold_in(et_key, 0),
                             (feature_mask.shape[0],), num_bin)
        bn_key = None
        fmask_root = feature_mask
        if use_inter:
            # root branch is empty -> only the union of all groups is
            # splittable (col_sampler.hpp:99-100)
            fmask_root = fmask_root & _inter_allowed(
                jnp.zeros(feature_mask.shape[0], bool))
        if use_bynode:
            bn_key = jax.random.PRNGKey(bynode_seed)
            if rng_iter is not None:
                bn_key = jax.random.fold_in(bn_key, rng_iter)
            fmask_root = _bynode_mask(jax.random.fold_in(bn_key, 0),
                                      fmask_root)
        kw = {"gain_scale": gscale, "rand_bin": rb0}
        if use_mono:
            kw.update(mono=_scan_mono(), out_lo=jnp.float32(-jnp.inf),
                      out_hi=jnp.float32(jnp.inf))
            if mono_penalty > 0.0:
                kw["gain_scale"] = _mono_gain_scale(jnp.int32(0))
        if use_cegb:
            kw["gain_penalty"] = _cegb_penalty(total0[2], cuse0)
        res0 = select_fn(find_best_split(expand(hist0, total0), total0,
                                         num_bin, na_bin, fmask_root,
                                         params, root_out, is_cat, **kw))
        return hist0, total0, root_out, res0, et_key, bn_key

    def _init_state(n, nleaf, nnode, nf, hist0, total0, root_out,
                    res0, cuse0=None) -> _GrowState:
        """Fresh grow state with ``nleaf`` leaf slots / ``nnode`` node
        slots (== L/L-1 strict; +K scratch slots batched)."""
        neg_inf = jnp.float32(-jnp.inf)
        return _GrowState(
            leaf_of_row=jnp.zeros(n, jnp.int32),
            # quantized training carries the histogram state as exact
            # int32 (dtype follows the root pass); subtraction and the
            # reduce collectives stay integer, dequantized only at scan.
            # The carry follows the REDUCED histogram's feature axis, not
            # the binned view's: an owner-shard hist_reduce leaves each
            # shard with only its chunk of the global histograms
            # ([L, 3, F/n, B])
            hist=jnp.zeros((nleaf,) + hist0.shape,
                           hist0.dtype).at[0].set(hist0),
            olo=jnp.full(nleaf, neg_inf),
            ohi=jnp.full(nleaf, jnp.inf),
            # branch sets start empty (root has no ancestors)
            fallow=jnp.zeros((nleaf, nf if use_inter else 1), bool),
            cuse=cuse0 if cuse0 is not None else jnp.zeros(1, bool),
            bg=jnp.full(nleaf, neg_inf).at[0].set(res0.gain),
            bf=jnp.zeros(nleaf, jnp.int32).at[0].set(res0.feature),
            bt=jnp.zeros(nleaf, jnp.int32).at[0].set(res0.threshold),
            bdl=jnp.zeros(nleaf, bool).at[0].set(res0.default_left),
            bls=jnp.zeros((nleaf, 3)).at[0].set(res0.left_sum),
            brs=jnp.zeros((nleaf, 3)).at[0].set(res0.right_sum),
            blo=jnp.zeros(nleaf).at[0].set(res0.left_output),
            bro=jnp.zeros(nleaf).at[0].set(res0.right_output),
            bic=jnp.zeros(nleaf, bool).at[0].set(res0.is_cat),
            brank=jnp.zeros((nleaf, B), jnp.int32).at[0].set(res0.bin_rank),
            split_feature=jnp.zeros(nnode, jnp.int32),
            threshold_bin=jnp.zeros(nnode, jnp.int32),
            default_left=jnp.zeros(nnode, bool),
            left_child=jnp.zeros(nnode, jnp.int32),
            right_child=jnp.zeros(nnode, jnp.int32),
            split_gain=jnp.zeros(nnode, jnp.float32),
            leaf_value=jnp.zeros(nleaf, jnp.float32).at[0].set(root_out),
            leaf_weight=jnp.zeros(nleaf, jnp.float32).at[0].set(total0[1]),
            leaf_count=jnp.zeros(nleaf, jnp.float32).at[0].set(total0[2]),
            internal_value=jnp.zeros(nnode, jnp.float32),
            internal_weight=jnp.zeros(nnode, jnp.float32),
            internal_count=jnp.zeros(nnode, jnp.float32),
            leaf_depth=jnp.zeros(nleaf, jnp.int32),
            leaf_parent=jnp.full(nleaf, -1, jnp.int32),
            num_leaves=jnp.int32(1),
            done=jnp.bool_(False),
            is_cat_node=jnp.zeros(nnode, bool),
            cat_rank=jnp.broadcast_to(
                jnp.arange(B, dtype=jnp.int32)[None], (nnode, B)) + 0,
        )

    def _root_pass():
        """A tree's ``rung_steps`` before its first split: the root's pass."""
        return jnp.zeros(RUNGS, jnp.int32).at[0].set(1)

    # every operation of a grower carries the scope ``lgbtpu.grow`` and,
    # inside it, its phase's (partition, hist.onehot, hist.contract,
    # hist.state, split): the device trace is folded by the innermost
    @jax.named_scope("lgbtpu.grow")
    def grow_tree(binned, vals, feature_mask, num_bin, na_bin,
                  na_bin_part=None, is_cat=None,
                  rng_iter=None, cegb_used=None,
                  num_bin_part=None, max_leaves=None,
                  quant_seed=None, followers=None):
        """``followers``: an optional tuple of binned matrices whose rows
        the grower carries through its splits without their touching a
        histogram, a count or a gain (``_follow``); the return value is
        then ``(tree, leaves)`` with one final ``leaf_of_row`` a follower,
        what a walk of the finished tree over those rows gives."""
        trace_event("grower")
        if max_leaves is None:
            if padded:
                raise ValueError(
                    "a leaf-padded grower needs the actual budget per "
                    "call: pass max_leaves=<num_leaves>")
            limit = jnp.int32(L)
        else:
            limit = jnp.asarray(max_leaves, jnp.int32)
        n, _f_global = binned.shape
        binned_view = view_fn(binned)
        with jax.named_scope("lgbtpu.partition"):
            columns = column_reader(binned)
        _note_partition_rule(columns, is_cat, 1)
        fcolumns, fleaves0 = _followers_at_root(followers or ())
        scales = None
        scan_expand = _expand
        if use_quant:
            vals, scales, scan_expand = _quant_prepare(
                n, vals, feature_mask, rng_iter, n_leaves=2,
                quant_seed=quant_seed)
        child_hist = _child_contractor(binned_view, vals, scales)
        if na_bin_part is None:
            na_bin_part = na_bin
        if num_bin_part is None:
            num_bin_part = num_bin
        cuse0 = None
        if use_cegb:
            cuse0 = cegb_used if cegb_used is not None \
                else jnp.zeros(feature_mask.shape[0], bool)

        hist0, total0, root_out, res0, et_key, bn_key = _root_eval(
            binned_view, vals, feature_mask, num_bin, na_bin, is_cat,
            rng_iter, cuse0, expand=scan_expand, scales=scales)
        st = _init_state(n, L, L - 1, feature_mask.shape[0], hist0, total0,
                         root_out, res0, cuse0)

        def split_step(carry):
            st = carry[0]
            # one split per step, so the node id IS the split count so far
            i = st.num_leaves - 1
            leaf = jnp.argmax(st.bg).astype(jnp.int32)
            can_split = (st.bg[leaf] > 0.0) & (~st.done)

            def do_split(carry):
                st, fleaves, rung_steps = carry
                # partition-site static accounting (obs/flops.py): a
                # trace-time Python side effect, zero runtime cost
                from .obs.flops import note_traced, partition_flops_bytes
                note_traced("partition", *partition_flops_bytes(n),
                            phase="grow")
                new_leaf = (i + 1).astype(jnp.int32)
                feat, thr = st.bf[leaf], st.bt[leaf]
                dleft = st.bdl[leaf]
                lsum, rsum = st.bls[leaf], st.brs[leaf]
                icat, rank_vec = st.bic[leaf], st.brank[leaf]

                # --- tree bookkeeping (Tree::Split, src/io/tree.cpp) ------
                parent = st.leaf_parent[leaf]
                node_ids = jnp.arange(L - 1, dtype=jnp.int32)
                fix_l = (node_ids == parent) & (st.left_child == ~leaf)
                fix_r = (node_ids == parent) & (st.right_child == ~leaf)
                lc = jnp.where(fix_l, i, st.left_child).at[i].set(~leaf)
                rc = jnp.where(fix_r, i, st.right_child).at[i].set(~new_leaf)

                # --- partition rows (CUDADataPartition::Split analog) -----
                step = (is_cat, na_bin_part, num_bin_part, leaf[None],
                        new_leaf[None], feat[None], thr[None], dleft[None],
                        icat[None], rank_vec[None])
                with jax.named_scope("lgbtpu.partition"):
                    leaf_of_row, _ = _partition_rows(
                        binned, columns, st.leaf_of_row, *step)
                fleaves = _follow(followers or (), fcolumns, fleaves, *step)

                # --- histograms: smaller child + subtraction --------------
                smaller_left = lsum[2] <= rsum[2]
                smaller_id = jnp.where(smaller_left, leaf, new_leaf)
                hist_small, rung = child_hist(leaf_of_row, smaller_id)
                rung_steps = rung_steps.at[rung].add(1)
                if use_subtraction:
                    with jax.named_scope("lgbtpu.hist.state"):
                        hist_large = st.hist[leaf] - hist_small
                else:
                    # voting-parallel: per-split feature votes make the
                    # reduced hist feature sets differ between parent and
                    # children, so the larger child is constructed too
                    larger_id = jnp.where(smaller_left, new_leaf, leaf)
                    hist_large, rung = child_hist(leaf_of_row, larger_id)
                    rung_steps = rung_steps.at[rung].add(1)
                with jax.named_scope("lgbtpu.hist.state"):
                    hl_leaf = jnp.where(smaller_left, hist_small, hist_large)
                    hl_new = jnp.where(smaller_left, hist_large, hist_small)
                    hist = st.hist.at[leaf].set(hl_leaf) \
                                  .at[new_leaf].set(hl_new)

                # --- leaf stats -------------------------------------------
                d = st.leaf_depth[leaf] + 1
                lv = st.leaf_value.at[leaf].set(st.blo[leaf]) \
                                  .at[new_leaf].set(st.bro[leaf])
                lw = st.leaf_weight.at[leaf].set(lsum[1]).at[new_leaf].set(rsum[1])
                lcnt = st.leaf_count.at[leaf].set(lsum[2]).at[new_leaf].set(rsum[2])
                ld = st.leaf_depth.at[leaf].set(d).at[new_leaf].set(d)

                # --- monotone range propagation (basic) -------------------
                lo2 = hi2 = depth2 = None
                olo, ohi = st.olo, st.ohi
                if use_mono:
                    mid = 0.5 * (st.blo[leaf] + st.bro[leaf])
                    l_lo, l_hi, r_lo, r_hi = _child_ranges(
                        st.olo[leaf], st.ohi[leaf], mono_dev[feat], icat,
                        mid)
                    olo = st.olo.at[leaf].set(l_lo).at[new_leaf].set(r_lo)
                    ohi = st.ohi.at[leaf].set(l_hi).at[new_leaf].set(r_hi)
                    lo2 = jnp.stack([l_lo, r_lo])
                    hi2 = jnp.stack([l_hi, r_hi])
                    depth2 = jnp.stack([d, d])

                # --- per-leaf feature masks (interaction / bynode) --------
                fmask2 = None
                fallow = st.fallow
                if per_leaf_mask:
                    nf = feature_mask.shape[0]
                    if use_inter:
                        child_branch = st.fallow[leaf] | (
                            jnp.arange(nf, dtype=jnp.int32) == feat)
                        fallow = st.fallow.at[leaf].set(child_branch) \
                                          .at[new_leaf].set(child_branch)
                        base = _inter_allowed(child_branch) & feature_mask
                    else:
                        base = feature_mask
                    if use_bynode:
                        kL = jax.random.fold_in(bn_key, 2 * (i + 1))
                        kR = jax.random.fold_in(bn_key, 2 * (i + 1) + 1)
                        m_l = _bynode_mask(kL, base)
                        m_r = _bynode_mask(kR, base)
                    else:
                        m_l = m_r = base
                    fmask2 = jnp.stack([m_l, m_r])

                # --- new best splits for both children (batched) ----------
                hist2 = jnp.stack([hl_leaf, hl_new])
                tot2 = jnp.stack([lsum, rsum])
                po2 = jnp.stack([st.blo[leaf], st.bro[leaf]])
                rand2 = None
                if extra_trees:
                    rand2 = _rand_bins(jax.random.fold_in(et_key, i + 1),
                                       (2, feature_mask.shape[0]), num_bin)
                cuse = st.cuse
                if use_cegb:
                    cuse = st.cuse | (
                        jnp.arange(st.cuse.shape[0], dtype=jnp.int32)
                        == feat)
                r2 = _best2(jax.vmap(scan_expand)(hist2, tot2), tot2,
                            num_bin, na_bin, feature_mask, po2, is_cat,
                            rand2, lo2, hi2, depth2, fmask2, cuse)
                depth_ok = (max_depth <= 0) | (d < max_depth)
                g2 = jnp.where(depth_ok, r2.gain, -jnp.inf)

                return st._replace(
                    leaf_of_row=leaf_of_row,
                    hist=hist,
                    olo=olo, ohi=ohi, fallow=fallow, cuse=cuse,
                    bg=st.bg.at[leaf].set(g2[0]).at[new_leaf].set(g2[1]),
                    bf=st.bf.at[leaf].set(r2.feature[0]).at[new_leaf].set(r2.feature[1]),
                    bt=st.bt.at[leaf].set(r2.threshold[0]).at[new_leaf].set(r2.threshold[1]),
                    bdl=st.bdl.at[leaf].set(r2.default_left[0]).at[new_leaf].set(r2.default_left[1]),
                    bls=st.bls.at[leaf].set(r2.left_sum[0]).at[new_leaf].set(r2.left_sum[1]),
                    brs=st.brs.at[leaf].set(r2.right_sum[0]).at[new_leaf].set(r2.right_sum[1]),
                    blo=st.blo.at[leaf].set(r2.left_output[0]).at[new_leaf].set(r2.left_output[1]),
                    bro=st.bro.at[leaf].set(r2.right_output[0]).at[new_leaf].set(r2.right_output[1]),
                    bic=st.bic.at[leaf].set(r2.is_cat[0]).at[new_leaf].set(r2.is_cat[1]),
                    brank=st.brank.at[leaf].set(r2.bin_rank[0]).at[new_leaf].set(r2.bin_rank[1]),
                    split_feature=st.split_feature.at[i].set(feat),
                    threshold_bin=st.threshold_bin.at[i].set(thr),
                    default_left=st.default_left.at[i].set(dleft),
                    left_child=lc,
                    right_child=rc,
                    split_gain=st.split_gain.at[i].set(st.bg[leaf]),
                    leaf_value=lv, leaf_weight=lw, leaf_count=lcnt,
                    internal_value=st.internal_value.at[i].set(st.leaf_value[leaf]),
                    internal_weight=st.internal_weight.at[i].set(st.leaf_weight[leaf]),
                    internal_count=st.internal_count.at[i].set(st.leaf_count[leaf]),
                    leaf_depth=ld,
                    leaf_parent=st.leaf_parent.at[leaf].set(i).at[new_leaf].set(i),
                    num_leaves=new_leaf + 1,
                    done=st.done,
                    is_cat_node=st.is_cat_node.at[i].set(icat),
                    cat_rank=st.cat_rank.at[i].set(rank_vec),
                ), fleaves, rung_steps

            return lax.cond(
                can_split, do_split,
                lambda c: (c[0]._replace(done=jnp.bool_(True)),) + c[1:],
                carry)

        # while_loop, not a fixed L-1 fori_loop: a tree that stops early
        # (no positive gain) exits instead of running no-op tail steps —
        # with 255-leaf budgets those dead steps used to dominate small
        # trees' device time (each one still copies the multi-MB carried
        # state through the cond).  The exit bound is the TRACED actual
        # budget ``limit`` (== L unless leaf-padded), which is what lets
        # one padded trace serve a whole num_leaves bucket.
        st, fleaves, rung_steps = lax.while_loop(
            lambda c: (~c[0].done) & (c[0].num_leaves < limit), split_step,
            (st, fleaves0, _root_pass()))
        tree = TreeArrays(
            num_leaves=st.num_leaves,
            split_feature=st.split_feature,
            threshold_bin=st.threshold_bin,
            default_left=st.default_left,
            left_child=st.left_child,
            right_child=st.right_child,
            split_gain=st.split_gain,
            leaf_value=st.leaf_value,
            leaf_weight=st.leaf_weight,
            leaf_count=st.leaf_count,
            internal_value=st.internal_value,
            internal_weight=st.internal_weight,
            internal_count=st.internal_count,
            leaf_depth=st.leaf_depth,
            leaf_of_row=st.leaf_of_row,
            is_cat_node=st.is_cat_node,
            cat_rank=st.cat_rank,
            n_steps=st.num_leaves - 1,
            rung_steps=rung_steps,
        )
        return tree if followers is None else (tree, fleaves)

    # K clamps against the ACTUAL budget, not the padded one: the
    # super-step width is baked into RNG streams (bynode/extra_trees key
    # schedules) and tree shape, so padding must never change it
    K = max(1, min(int(split_batch), L_req - 1)) if L_req > 1 else 1

    @jax.named_scope("lgbtpu.grow")
    def grow_tree_batched(binned, vals, feature_mask, num_bin, na_bin,
                          na_bin_part=None, is_cat=None,
                          rng_iter=None, cegb_used=None,
                          num_bin_part=None, max_leaves=None,
                          quant_seed=None, followers=None):
        """K-splits-per-super-step grower (split_batch above);
        ``followers`` and the return value as ``grow_tree``'s.

        Per-leaf state arrays carry K scratch slots past the real range
        (leaves ``L..L+K-1``, nodes ``L-1..L-2+K``): slots of the top-K
        batch whose cached gain is non-positive (or past the leaf budget)
        are redirected there, so every step runs the same fixed-shape
        program and the scratch writes are sliced off at the end."""
        trace_event("grower")
        if max_leaves is None:
            if padded:
                raise ValueError(
                    "a leaf-padded grower needs the actual budget per "
                    "call: pass max_leaves=<num_leaves>")
            limit = jnp.int32(L)
        else:
            limit = jnp.asarray(max_leaves, jnp.int32)
        n, _f_global = binned.shape
        binned_view = view_fn(binned)
        with jax.named_scope("lgbtpu.partition"):
            columns = column_reader(binned)
        _note_partition_rule(columns, is_cat, K)
        fcolumns, fleaves0 = _followers_at_root(followers or ())
        scales = None
        scan_expand = _expand
        if use_quant:
            vals, scales, scan_expand = _quant_prepare(
                n, vals, feature_mask, rng_iter, n_leaves=2 * K,
                quant_seed=quant_seed)
        if na_bin_part is None:
            na_bin_part = na_bin
        if num_bin_part is None:
            num_bin_part = num_bin
        LP, NP = L + K, (L - 1) + K
        cuse0 = None
        if use_cegb:
            cuse0 = cegb_used if cegb_used is not None \
                else jnp.zeros(feature_mask.shape[0], bool)

        hist0, total0, root_out, res0, et_key, bn_key = _root_eval(
            binned_view, vals, feature_mask, num_bin, na_bin, is_cat,
            rng_iter, cuse0, expand=scan_expand, scales=scales)
        st = _init_state(n, LP, NP, feature_mask.shape[0], hist0,
                         total0, root_out, res0, cuse0)

        neg_inf = jnp.float32(-jnp.inf)
        kidx = jnp.arange(K, dtype=jnp.int32)
        nC = K if use_subtraction else 2 * K
        contract = _contractor(binned_view, vals, nC, scales)

        def super_step(carry):
            s, st = carry[:2]
            gains, leaves = lax.top_k(lax.slice_in_dim(st.bg, 0, L), K)
            num_nodes = st.num_leaves - 1
            budget = (limit - 1) - num_nodes
            # gains sorted desc and budget a prefix: valid slots are a
            # prefix, so node/leaf id assignment below stays contiguous
            valid = (gains > 0.0) & (kidx < budget) & (~st.done)
            can_split = valid[0]

            def do_split(carry):
                st, fleaves, rung_steps = carry
                # one partition pass serves all K splits of the super-
                # step (trace-time note; obs/flops.py)
                from .obs.flops import note_traced, partition_flops_bytes
                note_traced("partition",
                            *partition_flops_bytes(n, slots=K), phase="grow")
                leaf_sel = jnp.where(valid, leaves, L + kidx)
                node_sel = jnp.where(valid, num_nodes + kidx,
                                     jnp.int32(L - 1) + kidx)
                new_leaf_sel = jnp.where(valid, st.num_leaves + kidx,
                                         L + kidx)

                feat_k = st.bf[leaf_sel]
                thr_k = st.bt[leaf_sel]
                dleft_k = st.bdl[leaf_sel]
                icat_k = st.bic[leaf_sel]
                lsum_k, rsum_k = st.bls[leaf_sel], st.brs[leaf_sel]
                rank_k = st.brank[leaf_sel]          # [K, B]
                blo_k, bro_k = st.blo[leaf_sel], st.bro[leaf_sel]
                parent_k = st.leaf_parent[leaf_sel]

                # --- partition rows: ONE pass for all K splits ------------
                # the contraction slot of a row comes out of the same pass:
                # slot k takes the smaller child of split k (both children,
                # k and K + k, where nothing is subtracted); the scratch
                # leaves ``L + k`` of the invalid slots hold no row
                smaller_left = lsum_k[:, 2] <= rsum_k[:, 2]      # [K]
                if use_subtraction:
                    tleft_k = jnp.where(smaller_left, kidx, -1)
                    tright_k = jnp.where(smaller_left, -1, kidx)
                else:
                    tleft_k, tright_k = kidx, K + kidx
                step = (is_cat, na_bin_part, num_bin_part, leaf_sel,
                        new_leaf_sel, feat_k, thr_k, dleft_k, icat_k, rank_k)
                with jax.named_scope("lgbtpu.partition"):
                    leaf_of_row, tslot = _partition_rows(
                        binned, columns, st.leaf_of_row, *step, tleft_k,
                        tright_k)
                fleaves = _follow(followers or (), fcolumns, fleaves, *step)

                # --- batched child histograms: one C=3K contraction -------
                hist_c, rung = contract(tslot)               # [3nC, Fh, Bh]
                rung_steps = rung_steps.at[rung].add(1)
                with jax.named_scope("lgbtpu.hist.state"):
                    hist_c = slot_histograms(hist_c, nC)     # [nC, 3, Fh, Bh]
                    if use_subtraction:
                        hist_small = hist_c
                        hist_large = st.hist[leaf_sel] - hist_small
                        sel = smaller_left[:, None, None, None]
                        hl_leaf = jnp.where(sel, hist_small, hist_large)
                        hl_new = jnp.where(sel, hist_large, hist_small)
                    else:
                        hl_leaf, hl_new = hist_c[:K], hist_c[K:]
                    hist = st.hist.at[leaf_sel].set(hl_leaf) \
                                  .at[new_leaf_sel].set(hl_new)

                # --- leaf stats -------------------------------------------
                d_k = st.leaf_depth[leaf_sel] + 1
                lv = st.leaf_value.at[leaf_sel].set(blo_k) \
                                  .at[new_leaf_sel].set(bro_k)
                lw = st.leaf_weight.at[leaf_sel].set(lsum_k[:, 1]) \
                                   .at[new_leaf_sel].set(rsum_k[:, 1])
                lcnt = st.leaf_count.at[leaf_sel].set(lsum_k[:, 2]) \
                                    .at[new_leaf_sel].set(rsum_k[:, 2])
                ld = st.leaf_depth.at[leaf_sel].set(d_k) \
                                  .at[new_leaf_sel].set(d_k)

                # --- monotone range propagation (basic, ×K) ---------------
                lo2 = hi2 = depth2 = None
                olo, ohi = st.olo, st.ohi
                if use_mono:
                    mid_k = 0.5 * (blo_k + bro_k)
                    l_lo, l_hi, r_lo, r_hi = _child_ranges(
                        st.olo[leaf_sel], st.ohi[leaf_sel],
                        mono_dev[feat_k], icat_k, mid_k)
                    olo = st.olo.at[leaf_sel].set(l_lo) \
                                .at[new_leaf_sel].set(r_lo)
                    ohi = st.ohi.at[leaf_sel].set(l_hi) \
                                .at[new_leaf_sel].set(r_hi)
                    lo2 = jnp.concatenate([l_lo, r_lo])
                    hi2 = jnp.concatenate([l_hi, r_hi])
                    depth2 = jnp.concatenate([d_k, d_k])

                # --- per-leaf feature masks (interaction / bynode, ×K) ----
                fmask2 = None
                fallow = st.fallow
                if per_leaf_mask:
                    nf = feature_mask.shape[0]
                    if use_inter:
                        child_branch = st.fallow[leaf_sel] | (
                            jnp.arange(nf, dtype=jnp.int32)[None]
                            == feat_k[:, None])              # [K, F]
                        fallow = st.fallow.at[leaf_sel].set(child_branch) \
                                          .at[new_leaf_sel].set(child_branch)
                        base = jax.vmap(_inter_allowed)(child_branch) \
                            & feature_mask[None]
                    else:
                        base = jnp.broadcast_to(feature_mask[None],
                                                (K, nf))
                    if use_bynode:
                        ids = (s + 1) * 2 * K \
                            + jnp.arange(2 * K, dtype=jnp.int32)
                        keys = jax.vmap(
                            lambda j: jax.random.fold_in(bn_key, j))(ids)
                        fmask2 = jax.vmap(_bynode_mask)(
                            keys, jnp.concatenate([base, base]))
                    else:
                        fmask2 = jnp.concatenate([base, base])

                # --- best splits for all 2K children (batched) ------------
                hist2 = jnp.concatenate([hl_leaf, hl_new])   # [2K, ...]
                tot2 = jnp.concatenate([lsum_k, rsum_k])
                po2 = jnp.concatenate([blo_k, bro_k])
                rand2 = None
                if extra_trees:
                    rand2 = _rand_bins(jax.random.fold_in(et_key, s + 1),
                                       (2 * K, feature_mask.shape[0]),
                                       num_bin)
                cuse = st.cuse
                if use_cegb:
                    marks = jnp.zeros(st.cuse.shape[0], jnp.int32) \
                        .at[feat_k].add(valid.astype(jnp.int32))
                    cuse = st.cuse | (marks > 0)
                r2 = _best2(jax.vmap(scan_expand)(hist2, tot2), tot2,
                            num_bin, na_bin, feature_mask, po2, is_cat,
                            rand2, lo2, hi2, depth2, fmask2, cuse)
                d2 = jnp.concatenate([d_k, d_k])
                depth_ok = (max_depth <= 0) | (d2 < max_depth)
                valid2 = jnp.concatenate([valid, valid])
                g2 = jnp.where(depth_ok & valid2, r2.gain, neg_inf)
                idx2 = jnp.concatenate([leaf_sel, new_leaf_sel])

                # --- tree bookkeeping (Tree::Split ×K) --------------------
                node_ids = jnp.arange(NP, dtype=jnp.int32)
                lc, rc = st.left_child, st.right_child
                for j in range(K):       # static unroll over tiny arrays
                    fix_l = (node_ids == parent_k[j]) \
                        & (lc == ~leaf_sel[j])
                    fix_r = (node_ids == parent_k[j]) \
                        & (rc == ~leaf_sel[j])
                    lc = jnp.where(fix_l, node_sel[j], lc)
                    rc = jnp.where(fix_r, node_sel[j], rc)
                lc = lc.at[node_sel].set(~leaf_sel)
                rc = rc.at[node_sel].set(~new_leaf_sel)

                return st._replace(
                    leaf_of_row=leaf_of_row,
                    hist=hist,
                    olo=olo, ohi=ohi, fallow=fallow, cuse=cuse,
                    bg=st.bg.at[idx2].set(g2),
                    bf=st.bf.at[idx2].set(r2.feature),
                    bt=st.bt.at[idx2].set(r2.threshold),
                    bdl=st.bdl.at[idx2].set(r2.default_left),
                    bls=st.bls.at[idx2].set(r2.left_sum),
                    brs=st.brs.at[idx2].set(r2.right_sum),
                    blo=st.blo.at[idx2].set(r2.left_output),
                    bro=st.bro.at[idx2].set(r2.right_output),
                    bic=st.bic.at[idx2].set(r2.is_cat),
                    brank=st.brank.at[idx2].set(r2.bin_rank),
                    split_feature=st.split_feature.at[node_sel].set(feat_k),
                    threshold_bin=st.threshold_bin.at[node_sel].set(thr_k),
                    default_left=st.default_left.at[node_sel].set(dleft_k),
                    left_child=lc,
                    right_child=rc,
                    split_gain=st.split_gain.at[node_sel].set(
                        jnp.where(valid, gains, 0.0)),
                    leaf_value=lv, leaf_weight=lw, leaf_count=lcnt,
                    internal_value=st.internal_value.at[node_sel].set(
                        st.leaf_value[leaf_sel]),
                    internal_weight=st.internal_weight.at[node_sel].set(
                        st.leaf_weight[leaf_sel]),
                    internal_count=st.internal_count.at[node_sel].set(
                        st.leaf_count[leaf_sel]),
                    leaf_depth=ld,
                    leaf_parent=st.leaf_parent.at[leaf_sel].set(node_sel)
                                              .at[new_leaf_sel].set(node_sel),
                    num_leaves=st.num_leaves
                    + valid.sum().astype(jnp.int32),
                    done=st.done,
                    is_cat_node=st.is_cat_node.at[node_sel].set(icat_k),
                    cat_rank=st.cat_rank.at[node_sel].set(rank_k),
                ), fleaves, rung_steps

            return (s + 1,) + lax.cond(
                can_split, do_split,
                lambda c: (c[0]._replace(done=jnp.bool_(True)),) + c[1:],
                carry[1:])

        # while_loop, not a fixed trip count: a super-step splits only the
        # leaves that HAVE positive gain (chain-shaped trees take 1 split
        # per step, balanced trees ~K), so no static count below L-1 is
        # safe — and a fixed L-1 count makes balanced 255-leaf trees pay
        # ~(L-1)(1-1/K) dead steps, each copying the multi-MB carried
        # state through the cond's no-op branch.  The loop exits the
        # moment the budget is exhausted or no leaf can split; the step
        # counter ``s`` is carried for the bynode RNG stream.  As in the
        # strict grower, the bound is the TRACED actual budget.
        s_final, st, fleaves, rung_steps = lax.while_loop(
            lambda c: (~c[1].done) & (c[1].num_leaves < limit), super_step,
            (jnp.int32(0), st, fleaves0, _root_pass()))
        tree = TreeArrays(
            num_leaves=st.num_leaves,
            split_feature=st.split_feature[:L - 1],
            threshold_bin=st.threshold_bin[:L - 1],
            default_left=st.default_left[:L - 1],
            left_child=st.left_child[:L - 1],
            right_child=st.right_child[:L - 1],
            split_gain=st.split_gain[:L - 1],
            leaf_value=st.leaf_value[:L],
            leaf_weight=st.leaf_weight[:L],
            leaf_count=st.leaf_count[:L],
            internal_value=st.internal_value[:L - 1],
            internal_weight=st.internal_weight[:L - 1],
            internal_count=st.internal_count[:L - 1],
            leaf_depth=st.leaf_depth[:L],
            leaf_of_row=st.leaf_of_row,
            is_cat_node=st.is_cat_node[:L - 1],
            cat_rank=st.cat_rank[:L - 1],
            n_steps=s_final,
            rung_steps=rung_steps,
        )
        return tree if followers is None else (tree, fleaves)

    fn = grow_tree_batched if K > 1 else grow_tree
    if not jit:
        return fn
    # process-level sharing: identical configs (common after leaf-budget
    # bucketing) reuse ONE jitted callable, so a num_leaves sweep inside
    # a bucket traces the grower exactly once per process.  Distribution
    # hooks are callables (unkeyable) -> those growers jit privately.
    key = None
    if all(h is None for h in (hist_reduce, hist_view, hist_expand,
                               select_best, mono_view, sum_reduce,
                               scale_reduce, row_offset)):
        key = _grower_key(dict(
            L=L, B=B, K=K, padded=padded, params=params,
            hist_overlap=hist_overlap,
            max_depth=max_depth, block_rows=block_rows, subtract=subtract,
            vmapped=vmapped, efb=efb,
            gain_scale=gain_scale, extra_trees=extra_trees,
            extra_seed=extra_seed, mono=mono, mono_penalty=mono_penalty,
            interaction_groups=interaction_groups, bynode_frac=bynode_frac,
            bynode_seed=bynode_seed, cegb=cegb, quant=quant,
            # unpadded growers bake the budget as the default limit, so
            # the key must carry it; padded ones take it per call
            L_default=None if padded else L_req))
    if key is None:
        with _SHARED_GROWERS_LOCK:
            _MEMO_COUNTS["unkeyable"] += 1
        return jax.jit(fn)
    with _SHARED_GROWERS_LOCK:
        shared = _SHARED_GROWERS.get(key)
        _MEMO_COUNTS["miss" if shared is None else "hit"] += 1
        if shared is None:
            shared = jax.jit(fn)
            _SHARED_GROWERS[key] = shared
            while len(_SHARED_GROWERS) > _SHARED_GROWERS_MAX:
                _SHARED_GROWERS.popitem(last=False)
        else:
            _SHARED_GROWERS.move_to_end(key)
    return shared


def make_shadow_grower(**kwargs):
    """An INDEPENDENTLY-jitted twin of ``make_grower(**kwargs)`` for the
    computation-integrity layer (lightgbm_tpu/integrity.py): same
    logical math, but a separate ``jax.jit`` wrapper that deliberately
    bypasses the ``_SHARED_GROWERS`` memo — so the shadow program is a
    second trace AND a second compiled executable, and a silently wrong
    answer must reproduce across two distinct programs to evade the
    compare.  The extra trace is intentional and accounted in
    tools/retrace_budget (sites fire only when integrity_check_freq>0).
    """
    return jax.jit(make_grower(**dict(kwargs, jit=False)))
