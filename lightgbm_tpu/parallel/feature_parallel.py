"""Feature-parallel tree learner: split search sharded over features.

TPU-native redesign of the reference FeatureParallelTreeLearner
(/root/reference/src/treelearner/feature_parallel_tree_learner.cpp:13-83):
data is REPLICATED on every shard; each shard builds histograms and scans
thresholds only for its own feature slice; the winning split is agreed via
an all-gather + argmax (the reference's 2-SplitInfo ``SyncUpGlobalBestSplit``
allreduce, parallel_tree_learner.h:191); every shard then applies the split
locally — no row data ever moves.

Implemented as hooks into the shared grower program (grower.py):
``hist_view`` slices this shard's columns, ``select_best`` globalizes the
feature index and reduces candidates across the mesh axis.

Quantized training (``quant``) threads straight through: rows are
replicated, so every shard computes the IDENTICAL per-iteration scale
and rounding stream with no extra collective (global row id == local
row id, ops/quantize.py).

Leaf-budget trace sharing (ROADMAP item 1 remainder): ``padded_leaves``
+ per-call traced ``max_leaves`` + a process-level memo of the jitted
shard_map program, so a ``num_leaves`` sweep inside one bucket runs ONE
feature-parallel grower trace (pinned by tools/check_retraces.py).

Rows are replicated, so a booster's held-out matrices ride the grower's
partition here as they do on one chip (``followers``, grower.py
``_follow``): every worker carries every follower's ``leaf_of_row``
through the same global splits and hands the same leaves back.

What a worker holds (the cell ``epsilon-b255-fp4.cv5`` measures it on four
v5e chips, PERF.md section 4): every row of the binned matrix and of each
follower, and ``1/n`` of the columns' histograms, per-leaf state and split
search.  What crosses chips is ``gather_best``'s one all-gather of the
candidates, under the device scope ``lgbtpu.sync``.
"""

from __future__ import annotations

import threading
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..grower import make_grower
from ..obs.comm import CommLedger
from ..ops.split import SplitParams, SplitResult, gather_best
from ..utils.memo import memo_get_or_build

# process-level memo of jitted feature-parallel growers (the voting
# learner's pattern; see parallel/voting_parallel.py)
_SHARED: "OrderedDict[tuple, tuple]" = OrderedDict()
_SHARED_MAX = 16
_SHARED_LOCK = threading.Lock()


def make_fp_grower(mesh: Mesh, *, num_features: int, num_leaves: int,
                   num_bins: int, params: SplitParams, max_depth: int = -1,
                   block_rows: int = 0, axis: str = "feature",
                   split_batch: int = 1, hist_overlap: bool = False,
                   padded_leaves=None, quant=None):
    """Jitted feature-parallel ``grow_tree``.

    Inputs: binned [N, F] and vals replicated; feature metadata arrays
    (feature_mask, num_bin, na_bin) sharded over the feature axis by
    shard_map; ``na_bin_part`` replicated for row partitioning.
    ``num_features`` must be a multiple of the axis size (pad with masked
    dummy features).
    """
    n_shards = mesh.shape[axis]
    if num_features % n_shards != 0:
        raise ValueError(f"num_features {num_features} must divide over "
                         f"{n_shards} shards (pad with masked features)")
    key = (tuple(int(d.id) for d in np.ravel(mesh.devices)), axis,
           int(num_features),
           int(padded_leaves) if padded_leaves else None,
           None if padded_leaves else int(num_leaves),
           int(num_bins), params, int(max_depth), int(block_rows),
           int(split_batch), bool(hist_overlap), quant)
    jitted, ledger = memo_get_or_build(
        _SHARED, _SHARED_LOCK, _SHARED_MAX, key,
        lambda: _build(mesh, num_features=num_features,
                       num_leaves=num_leaves, num_bins=num_bins,
                       params=params, max_depth=max_depth,
                       block_rows=block_rows, axis=axis,
                       split_batch=split_batch,
                       hist_overlap=hist_overlap,
                       padded_leaves=padded_leaves, quant=quant))

    def _args(binned, vals, feature_mask, num_bin, na_bin, na_bin_part=None,
              is_cat=None, max_leaves=None, rng_iter=None, followers=None):
        if na_bin_part is None:
            na_bin_part = na_bin
        ml = jnp.int32(num_leaves if max_leaves is None else max_leaves)
        ri = jnp.int32(0 if rng_iter is None else rng_iter)
        # ``is_cat`` stays None where no feature is categorical, as on one
        # chip: the partition then takes no per-row look-up of a rank
        # (grower.py ``_partition_rows``, rule ``select``)
        return (binned, vals, feature_mask, num_bin, na_bin, na_bin_part,
                is_cat, ml, ri, followers)

    def grow(*args, **kwargs):
        """The tree, replicated; with ``followers`` (replicated binned
        matrices) ``(tree, their leaf_of_row)``, as ``make_grower``'s."""
        return jitted(*_args(*args, **kwargs))

    grow.lower = lambda *args, **kwargs: jitted.lower(*_args(*args, **kwargs))
    grow.comm = ledger
    # columns of the per-leaf histogram state that one worker holds
    grow.state_columns = num_features // n_shards
    return grow


def _build(mesh: Mesh, *, num_features, num_leaves, num_bins, params,
           max_depth, block_rows, axis, split_batch, hist_overlap=False,
           padded_leaves=None,
           quant=None):
    n_shards = mesh.shape[axis]
    f_local = num_features // n_shards
    ledger = CommLedger(n_shards)     # static comm-bytes sites (obs/comm)

    def hist_view(binned):
        idx = lax.axis_index(axis)
        return lax.dynamic_slice_in_dim(binned, idx * f_local, f_local,
                                        axis=1)

    # candidates a step's exchange carries: both children of each of the
    # step's K splits (make_grower's own clamp of K)
    k = max(1, min(int(split_batch), int(num_leaves) - 1))

    def select_best(res: SplitResult) -> SplitResult:
        # contiguous slices globalize by offset; the winner sync is the
        # shared SyncUpGlobalBestSplit allgather (ops/split.gather_best)
        idx = lax.axis_index(axis)
        res = res._replace(feature=res.feature + idx * f_local)
        # the hook runs once for the root and, under vmap, once for a
        # step's 2K children: vmap hides the batch from the traced shapes,
        # so the step's payload is the candidate's times 2K
        ledger.note_all_gather(res, site="fp.root_split", cadence="tree")
        ledger.note_all_gather(res, site="fp.best_split", copies=2 * k)
        return gather_best(res, axis)

    inner = make_grower(
        num_leaves=num_leaves, num_bins=num_bins, params=params,
        max_depth=max_depth, block_rows=block_rows,
        hist_view=hist_view, select_best=select_best,
        split_batch=split_batch, hist_overlap=hist_overlap,
        padded_leaves=padded_leaves,
        # rows replicated: identical scales/rounding on every shard —
        # no scale pmax or row offset needed (module docstring)
        quant=quant, jit=False)

    def wrapped(binned, vals, fm, nb, na, nabp, ic, ml, ri, followers):
        return inner(binned, vals, fm, nb, na, nabp, ic, rng_iter=ri,
                     max_leaves=ml, followers=followers)

    # a spec stands for its argument's whole subtree: ``is_cat`` may be
    # None and ``followers`` None or a tuple of matrices; the result is the
    # tree, or the tree and the followers' leaves, replicated either way
    f = jax.shard_map(
        wrapped, mesh=mesh,
        in_specs=(P(None, None), P(None, None), P(axis), P(axis), P(axis),
                  P(None), P(axis), P(), P(), P()),
        out_specs=P(), check_vma=False)

    return jax.jit(f), ledger
