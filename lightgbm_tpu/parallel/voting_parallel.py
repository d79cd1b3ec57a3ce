"""Voting-parallel learner: communication-compressed data parallelism.

TPU-native redesign of the reference VotingParallelTreeLearner (PV-tree,
/root/reference/src/treelearner/voting_parallel_tree_learner.cpp:15-507):
rows are sharded like data-parallel, but instead of reducing histograms for
ALL features, each shard votes its local top-k features (by local split
gain), the global vote selects the top-2k (``GlobalVoting``,
voting_parallel_tree_learner.cpp:150-181), and only those features'
histograms cross the interconnect.

Implementation: the psum hook zeroes non-voted features before reducing —
a zero histogram can never produce a valid split (count constraints), so
no separate search mask is needed.  Because the voted feature set changes
per split, the subtraction trick is disabled (both children constructed),
matching the reference's CopyLocalHistogram behavior of syncing both.

Quantized training (``quant``): the vote statistic needs real-valued
gains, so the hook dequantizes its LOCAL int32 histogram with the
iteration's shared scales (grower.py passes them to the reduce hook) —
the reduced tensor itself stays exact int32 (an integer psum, bitwise
order-independent).

Leaf-budget trace sharing (ROADMAP item 1 remainder): ``padded_leaves``
threads through to the shared grower, the actual budget rides per call
as the traced ``max_leaves`` scalar, and the jitted shard_map program is
memoized process-wide — a ``num_leaves`` sweep inside one bucket runs
ONE voting-grower trace (pinned by tools/check_retraces.py).
"""

from __future__ import annotations

import threading
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..grower import TreeArrays, make_grower
from ..obs.comm import CommLedger
from ..ops.split import SplitParams, dequantize_hist
from ..utils.memo import memo_get_or_build

# process-level memo of jitted voting growers (same role as grower.py's
# _SHARED_GROWERS): keyed on devices + every trace-relevant static, so
# a leaf sweep inside one padded bucket shares ONE shard_map trace.
_SHARED: "OrderedDict[tuple, tuple]" = OrderedDict()
_SHARED_MAX = 16
_SHARED_LOCK = threading.Lock()


def _local_feature_gains(h: jax.Array, params: SplitParams,
                         n_shards: int) -> jax.Array:
    """Per-feature best LOCAL split gain from a local histogram [3, F, B]
    — the vote statistic.  Matches the reference's local search setup:
    L1/L2-regularized gains with the per-rank constraint rescale
    ``min_data_in_leaf /= num_machines`` / ``min_sum_hessian_in_leaf /=
    num_machines`` (voting_parallel_tree_learner.cpp:61-63 — a shard
    only sees ~1/M of any leaf's rows, so unscaled constraints would
    veto splits the GLOBAL histogram easily clears)."""
    md = max(float(params.min_data_in_leaf) / n_shards, 1.0) - 0.5
    mh = float(params.min_sum_hessian_in_leaf) / n_shards
    l1, l2 = float(params.lambda_l1), float(params.lambda_l2)
    eps = 1e-10
    cum = jnp.cumsum(h, axis=2)
    total = cum[:, :, -1:]
    gl, hl = cum[0], cum[1]
    gr = total[0] - cum[0]
    hr = total[1] - cum[1]
    cl, cr = cum[2], total[2] - cum[2]

    def tl1(g):
        if l1 <= 0.0:
            return g
        return jnp.sign(g) * jnp.maximum(jnp.abs(g) - l1, 0.0)

    gains = (tl1(gl) ** 2 / (hl + l2 + eps)
             + tl1(gr) ** 2 / (hr + l2 + eps))
    valid = (cl >= md) & (cr >= md) & (hl >= mh) & (hr >= mh)
    gains = jnp.where(valid, gains, -jnp.inf)
    return jnp.max(gains, axis=1)                       # [F]


def make_voting_grower(mesh: Mesh, *, num_leaves: int, num_bins: int,
                       params: SplitParams, top_k: int = 20,
                       max_depth: int = -1, block_rows: int = 0,
                       axis: str = "data", padded_leaves=None,
                       quant=None):
    """Jitted voting-parallel ``grow_tree`` over ``mesh`` (rows sharded)."""

    key = (tuple(int(d.id) for d in np.ravel(mesh.devices)), axis,
           int(padded_leaves) if padded_leaves else None,
           None if padded_leaves else int(num_leaves),
           int(num_bins), params, int(top_k), int(max_depth),
           int(block_rows), quant)
    jitted, ledger = memo_get_or_build(
        _SHARED, _SHARED_LOCK, _SHARED_MAX, key,
        lambda: _build(mesh, num_leaves=num_leaves, num_bins=num_bins,
                       params=params, top_k=top_k, max_depth=max_depth,
                       block_rows=block_rows, axis=axis,
                       padded_leaves=padded_leaves, quant=quant))

    def grow(binned, vals, feature_mask, num_bin, na_bin, is_cat=None,
             max_leaves=None, rng_iter=None):
        if is_cat is None:
            is_cat = jnp.zeros(num_bin.shape[0], bool)
        ml = jnp.int32(num_leaves if max_leaves is None else max_leaves)
        ri = jnp.int32(0 if rng_iter is None else rng_iter)
        return jitted(binned, vals, feature_mask, num_bin, na_bin, na_bin,
                      is_cat, ml, ri)

    grow.comm = ledger
    return grow


def _build(mesh: Mesh, *, num_leaves, num_bins, params, top_k, max_depth,
           block_rows, axis, padded_leaves, quant):
    n_shards = mesh.shape[axis]
    ledger = CommLedger(n_shards)     # static comm-bytes sites (obs/comm)

    def vote_reduce(h, scales=None):
        f = h.shape[1]
        k = min(top_k, f)
        # quantized training: the vote statistic needs real values;
        # the LOCAL dequantization is scan-shaped work, the reduced
        # tensor stays exact int32
        h_stat = h if scales is None else dequantize_hist(h, scales, axis=0)
        gains = _local_feature_gains(h_stat, params, n_shards)
        _, local_top = lax.top_k(gains, k)              # [k]
        onehot = jnp.zeros(f, jnp.float32).at[local_top].add(1.0)
        votes = ledger.psum(onehot, axis,
                            site="voting.votes")        # [F] vote counts
        # global top-2k by votes (ties: summed local gains)
        gain_sum = ledger.psum(jnp.where(jnp.isfinite(gains), gains, 0.0),
                               axis, site="voting.gains")
        score = votes * 1e12 + gain_sum
        k2 = min(2 * k, f)
        _, selected = lax.top_k(score, k2)
        sel_mask = jnp.zeros(f, bool).at[selected].set(True)
        # the ledger records the full zero-masked [3, F, B] payload —
        # the tensor XLA actually reduces; the reference's
        # CopyLocalHistogram would ship only the voted k2/F slice.
        # jnp.where (not *) keeps the int32 dtype under quant
        return ledger.psum(jnp.where(sel_mask[None, :, None], h,
                                     jnp.zeros((), h.dtype)), axis,
                           site="voting.hist")

    from .data_parallel import _quant_hooks
    inner = make_grower(
        num_leaves=num_leaves, num_bins=num_bins, params=params,
        max_depth=max_depth, block_rows=block_rows,
        hist_reduce=vote_reduce, subtract=False,
        # root totals must NOT come through the vote-filtered histogram
        sum_reduce=lambda t: ledger.psum(t, axis, site="voting.root_sum",
                                         cadence="tree"),
        padded_leaves=padded_leaves,
        **_quant_hooks(axis, ledger, quant, site="voting.quant_scale"),
        jit=False)

    out_specs = TreeArrays(
        num_leaves=P(), split_feature=P(), threshold_bin=P(),
        default_left=P(), left_child=P(), right_child=P(), split_gain=P(),
        leaf_value=P(), leaf_weight=P(), leaf_count=P(), internal_value=P(),
        internal_weight=P(), internal_count=P(), leaf_depth=P(),
        leaf_of_row=P(axis), is_cat_node=P(), cat_rank=P(), n_steps=P(),
        rung_steps=P())

    def wrapped(binned, vals, fm, nb, na, nabp, ic, ml, ri):
        return inner(binned, vals, fm, nb, na, nabp, ic, rng_iter=ri,
                     max_leaves=ml)

    f = jax.shard_map(
        wrapped, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(), P(), P(), P(), P(),
                  P(), P()),
        out_specs=out_specs, check_vma=False)

    return jax.jit(f), ledger
