"""Data-parallel tree learner: rows sharded over the mesh ``data`` axis.

TPU-native redesign of the reference DataParallelTreeLearner
(/root/reference/src/treelearner/data_parallel_tree_learner.cpp:13-283):

- rows live sharded; every shard builds LOCAL histograms for all features;
- the reference's ``Network::ReduceScatter(hists, HistogramSumReducer)``
  (:185) is a real ``lax.psum_scatter`` over a feature-chunked histogram
  layout: the feature-group axis is padded to ``n_shards`` equal chunks
  and reduce-scattered, so each shard ends up holding only ITS chunk of
  the GLOBAL histograms — the grower's per-shard histogram carry is
  ``[L, 3, G/n_shards, B]`` and per-chip histogram state stops scaling
  with the global feature width (the owner-shard memory shape the
  reference gets from ReduceScatter; arXiv:1611.01276's communication
  pattern for distributed tree induction);
- the split scan (ops/split.py) runs on the owned slice only; the
  per-shard best ``SplitResult`` is globalized back to global feature ids
  and allgathered (``SyncUpGlobalBestSplit``, parallel_tree_learner.h:191)
  — a few scalars plus the [B] rank vector per leaf cross the
  interconnect, never a histogram tensor;
- the histogram subtraction trick runs POST-scatter, on owned features
  only (parent chunk - smaller-child chunk);
- the root Σgrad/Σhess allreduce (:126-152) stays one tiny [3] psum;
- row partition stays local (no row data ever moves, like the reference).

``owner_shard=False`` restores the previous design — ONE full-tensor
``lax.psum`` of ``[3, F, B]`` with the split decision recomputed
replicated on every shard — kept for A/B comparison and as a config
escape hatch (``dp_owner_shard=false``).

The same grower program (grower.py) is used for both — distribution is a
``shard_map`` wrapper plus reduce/expand/select hooks, not a separate
learner implementation.  With ``efb`` the chunked axis is the BUNDLED
group axis — exactly where the reference bundles before its
reduce-scatter (dataset.cpp:239; data_parallel_tree_learner.cpp:174-186).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..grower import TreeArrays, make_grower
from ..obs.comm import CommLedger
from ..ops.histogram import pad_feature_axis
from ..ops.split import (SplitParams, SplitResult, gather_best,
                         globalize_feature)
from ..utils.memo import memo_get_or_build
from .mesh import owner_shard_plan

# process-level memo of built dp growers (the voting/feature builders'
# _SHARED pattern, utils/memo.py): a leaf sweep inside one padded
# bucket — and every Booster the elastic recovery ladder constructs on
# the SAME topology while retrying a rung — shares one jitted program
# per (mesh, config family) instead of re-tracing per Booster.  Keyed
# through grower._grower_key so unkeyable configs simply build private
# programs (never a correctness risk).
import threading
from collections import OrderedDict

_SHARED: "OrderedDict[tuple, object]" = OrderedDict()
_SHARED_LOCK = threading.Lock()
_SHARED_MAX = 32


def pad_to_multiple(n: int, k: int) -> int:
    return (n + k - 1) // k * k


def shard_rows(mesh: Mesh, arr, axis: str = "data"):
    """Place a row-major array sharded over the mesh data axis (rows padded
    by the caller to a multiple of the axis size).

    Multi-process (one controller per host, the TPU-pod topology): ``arr``
    is each process's LOCAL rows and the global array is assembled with
    ``make_array_from_process_local_data`` — ``device_put`` of a global
    value is single-controller-only (every process would need the whole
    array, and JAX asserts the values match across processes).  The
    caller must have padded every process to the same local row count."""
    spec = P(axis, *([None] * (np.ndim(arr) - 1)))
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() > 1:
        return jax.make_array_from_process_local_data(sharding,
                                                      np.asarray(arr))
    return jax.device_put(jnp.asarray(arr), sharding)


def _dp_out_specs(axis: str) -> TreeArrays:
    """Tree fields replicated, the row->leaf vector row-sharded."""
    return TreeArrays(
        num_leaves=P(), split_feature=P(), threshold_bin=P(),
        default_left=P(), left_child=P(), right_child=P(), split_gain=P(),
        leaf_value=P(), leaf_weight=P(), leaf_count=P(), internal_value=P(),
        internal_weight=P(), internal_count=P(), leaf_depth=P(),
        leaf_of_row=P(axis), is_cat_node=P(), cat_rank=P(), n_steps=P(),
        rung_steps=P())


def owner_hist_reduce(axis: str, n_shards: int, chunk: int,
                      ledger: CommLedger = None):
    """The ReduceScatter hook: pad the histogram's feature-group axis to
    ``n_shards * chunk`` rows and ``psum_scatter`` it, leaving each shard
    with its owned ``[C, chunk, B]`` slice of the GLOBAL histograms
    (data_parallel_tree_learner.cpp:185's communication shape; XLA
    lowers this to a true reduce-scatter over ICI, moving 1/n_shards of
    the bytes a full psum replicates to every chip).  ``ledger`` records
    the payload statically at trace time (obs/comm.py) — dtype-aware,
    so quantized training's int32 payload (exact integer reduce, half
    the reference's f64 ReduceScatter wire format) is accounted at its
    real width.  ``scales`` is the quant hook contract (grower.py
    ``_hist``); the reduce itself never needs it."""
    total = n_shards * chunk

    def hist_reduce(h, scales=None):
        h = pad_feature_axis(h, total)
        if ledger is not None:
            return ledger.psum_scatter(h, axis, site="dp.hist_reduce",
                                       scatter_dimension=1, tiled=True)
        return lax.psum_scatter(h, axis, scatter_dimension=1, tiled=True)

    return hist_reduce


def make_dp_grower(mesh: Mesh, *, num_leaves: int, num_bins: int,
                   params: SplitParams, max_depth: int = -1,
                   block_rows: int = 0, axis: str = "data", efb=None,
                   split_batch: int = 1, hist_overlap: bool = False,
                   mono=None,
                   mono_penalty: float = 0.0, sparse: bool = False,
                   owner_shard: bool = True,
                   padded_leaves=None, quant=None):
    """Jitted data-parallel ``grow_tree`` over ``mesh``.

    Inputs: binned [N, F] (or the bundled [N, G] group matrix when ``efb``
    is set) and vals [N, 3] sharded on rows; feature metadata replicated.
    Output tree arrays are replicated; ``leaf_of_row`` stays row-sharded.
    Child histograms use the masked full pass (gather tiers measured slower
    on TPU), which also keeps every shard's collective
    schedule trivially congruent.

    owner_shard=True (default): reduce-scatter + owned-slice split scan +
    best-split allgather (module docstring).  False: the legacy full
    ``lax.psum`` with replicated split decisions.
    """
    kw = dict(num_leaves=num_leaves, num_bins=num_bins, params=params,
              max_depth=max_depth, block_rows=block_rows, axis=axis,
              efb=efb, split_batch=split_batch,
              hist_overlap=hist_overlap, mono=mono,
              mono_penalty=mono_penalty, sparse=sparse,
              padded_leaves=padded_leaves, quant=quant)
    build = (lambda: _make_dp_owner_grower(mesh, **kw)) if owner_shard \
        else (lambda: _make_dp_psum_grower(mesh, **kw))

    from ..grower import _grower_key
    kw_key = dict(kw)
    if padded_leaves:
        # the padded budget is the trace-relevant leaf dimension; the
        # actual num_leaves rides in as the traced max_leaves argument,
        # so 31/63 inside one bucket share the memo entry
        kw_key["num_leaves"] = None
    key_part = _grower_key(kw_key)
    if key_part is None:
        inner = build()
    else:
        key = (tuple(int(d.id) for d in np.ravel(mesh.devices)),
               bool(owner_shard), key_part)
        inner = memo_get_or_build(_SHARED, _SHARED_LOCK, _SHARED_MAX,
                                  key, build)
    return _CollectiveGate(inner)


class _CollectiveGate:
    """Callable pass-through hosting the 'collective' fault-injection
    site (utils/faultinject.py) at the dispatch of the cross-shard
    histogram reduction program — one dict-empty check when inactive.
    Attribute access (e.g. the owner-shard ``plan``, attached to the
    inner grower lazily at first trace) delegates to the wrapped
    grower."""

    def __init__(self, inner):
        self._inner = inner

    def __call__(self, *args, **kwargs):
        from ..utils import faultinject
        faultinject.check("collective")
        return self._inner(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _quant_hooks(axis: str, ledger: CommLedger, quant,
                 site: str = "dp.quant_scale"):
    """Quantized-training hooks for the row-sharded learners: the [3]
    scale vector pmaxes across the mesh so every shard quantizes with
    the GLOBAL per-iteration scale, and the stochastic-rounding stream
    is keyed by GLOBAL row ids via this shard's row offset — together
    they make the int32 histogram reduce bitwise dp==serial
    (ops/quantize.py module docstring).  ``site`` names the pmax in the
    comm ledger — the voting learner reuses these hooks under its own
    label."""
    if quant is None:
        return dict(quant=None)
    return dict(
        quant=quant,
        scale_reduce=lambda s: ledger.pmax(s, axis, site=site,
                                           cadence="tree"),
        row_offset=lambda n_local: lax.axis_index(axis) * n_local)


def _make_dp_owner_grower(mesh: Mesh, *, num_leaves, num_bins, params,
                          max_depth, block_rows, axis, efb, split_batch,
                          hist_overlap=False,
                          mono, mono_penalty, sparse, padded_leaves=None,
                          quant=None):
    """Owner-shard data-parallel grower (see module docstring)."""
    n_shards = mesh.shape[axis]
    out_specs = _dp_out_specs(axis)
    cache = {}
    ledger = CommLedger(n_shards)     # static comm-bytes sites (obs/comm)

    def _build(nf: int, sparse_key=None):
        group_of = np.asarray(efb.group_host) if efb is not None \
            else np.arange(nf)
        plan = owner_shard_plan(group_of, n_shards)
        sf_dev = jnp.asarray(plan.shard_feat)        # [S, fmax] global ids
        chunk, fmax = plan.chunk, plan.fmax
        hist_reduce = owner_hist_reduce(axis, n_shards, chunk, ledger)

        def _gfid():
            """This shard's scan-slot -> global-feature map (in-graph)."""
            return sf_dev[lax.axis_index(axis)]

        if efb is not None:
            # per-shard EFB expansion: owned-groups histogram
            # [C, chunk, Bg] -> scan feature space [C, fmax, B], with the
            # FixHistogram default-bin reconstruction (dataset.cpp:1292)
            # done from the leaf totals on owned features only; like the
            # serial expansion (efb.expand_group_hist) it works on a
            # turned [chunk, Bg, C] view
            bg = int(efb.group_bins)
            g_of = efb.group_of_feat

            def hist_expand(gh, total):
                gh = jnp.moveaxis(gh, 0, -1)
                idx = lax.axis_index(axis)
                gfid = sf_dev[idx]
                safe = jnp.maximum(gfid, 0)
                ok = gfid >= 0
                glocal = jnp.clip(jnp.take(g_of, safe) - idx * chunk,
                                  0, gh.shape[0] - 1)
                src = jnp.take(gh, glocal, axis=0)       # [fmax, Bg, C]
                ci = jnp.take(efb.col_idx, safe, axis=0)  # [fmax, B]
                fh = jnp.take_along_axis(
                    src, jnp.clip(ci, 0, bg - 1)[:, :, None], axis=1)
                fh = jnp.where((ok[:, None] & (ci >= 0))[:, :, None],
                               fh, 0.0)
                rest = fh[:, 1:, :].sum(axis=1)
                bin0 = jnp.where((jnp.take(efb.fix0, safe) & ok)[:, None],
                                 total[None, :] - rest, fh[:, 0, :])
                return jnp.moveaxis(fh.at[:, 0, :].set(bin0), -1, 0)
        else:
            # unbundled: group == feature, owned features are the
            # contiguous chunk — the scan view just trims reduce padding
            def hist_expand(h, total):
                return lax.slice_in_dim(h, 0, fmax, axis=1)

        def mono_view(m):
            gfid = _gfid()
            return jnp.where(gfid >= 0,
                             jnp.take(m, jnp.maximum(gfid, 0)), 0)

        def select_best(res: SplitResult) -> SplitResult:
            ledger.note_all_gather(res, site="dp.best_split")
            return gather_best(globalize_feature(res, _gfid()), axis)

        inner = make_grower(
            num_leaves=num_leaves, num_bins=num_bins, params=params,
            max_depth=max_depth, block_rows=block_rows,
            hist_reduce=hist_reduce,
            sum_reduce=lambda t: ledger.psum(t, axis, site="dp.root_sum",
                                             cadence="tree"),
            hist_expand=hist_expand, select_best=select_best,
            efb=efb, split_batch=split_batch,
            hist_overlap=hist_overlap, mono=mono,
            mono_view=None if mono is None else mono_view,
            mono_penalty=mono_penalty, padded_leaves=padded_leaves,
            **_quant_hooks(axis, ledger, quant),
            jit=False)

        def _localize(fmask, nb, na, ic):
            """Scan-space metadata slices for this shard's owned
            features; pad slots are masked (and given harmless bins)."""
            gfid = _gfid()
            safe = jnp.maximum(gfid, 0)
            ok = gfid >= 0
            return (fmask[safe] & ok,
                    jnp.where(ok, nb[safe], 2),
                    jnp.where(ok, na[safe], -1),
                    ic[safe] & ok)

        if sparse_key is not None:
            from ..sparse_data import SparseBinned
            stride, nfs = sparse_key

            def wrapped(flat, db, vals, fmask, nb, na, nabp, ic, ml, ri):
                fm_l, nb_l, na_l, ic_l = _localize(fmask, nb, na, ic)
                return inner(SparseBinned(flat, db, stride, nfs), vals,
                             fm_l, nb_l, na_l, nabp, ic_l, rng_iter=ri,
                             num_bin_part=nb, max_leaves=ml)

            in_specs = (P(axis, None), P(None), P(axis, None),
                        P(), P(), P(), P(), P(), P(), P())
        else:
            def wrapped(binned, vals, fmask, nb, na, nabp, ic, ml, ri):
                fm_l, nb_l, na_l, ic_l = _localize(fmask, nb, na, ic)
                return inner(binned, vals, fm_l, nb_l, na_l, nabp, ic_l,
                             rng_iter=ri, num_bin_part=nb, max_leaves=ml)

            in_specs = (P(axis, None), P(axis, None),
                        P(), P(), P(), P(), P(), P(), P())

        fn = jax.jit(jax.shard_map(wrapped, mesh=mesh, in_specs=in_specs,
                                   out_specs=out_specs, check_vma=False))
        return fn, plan

    def grow(binned, vals, feature_mask, num_bin, na_bin, is_cat=None,
             max_leaves=None, rng_iter=None):
        if is_cat is None:
            is_cat = jnp.zeros(num_bin.shape[0], bool)
        ml = jnp.int32(num_leaves if max_leaves is None else max_leaves)
        # always a traced argument (0 when unused) so the jit signature
        # is stable whether or not quantized rounding consumes it
        ri = jnp.int32(0 if rng_iter is None else rng_iter)
        nf = int(num_bin.shape[0])
        if sparse:
            key = (nf, binned.stride, binned.num_features)
            if key not in cache:
                cache[key] = _build(nf, (binned.stride,
                                         binned.num_features))
            fn, plan = cache[key]
            grow.plan = plan
            return fn(binned.flat, binned.default_bin, vals, feature_mask,
                      num_bin, na_bin, na_bin, is_cat, ml, ri)
        if nf not in cache:
            cache[nf] = _build(nf)
        fn, plan = cache[nf]
        grow.plan = plan
        return fn(binned, vals, feature_mask, num_bin, na_bin, na_bin,
                  is_cat, ml, ri)

    grow.owner_shard = True
    grow.comm = ledger
    if efb is not None:
        # bundle structure is static: expose the plan before the first call
        grow.plan = owner_shard_plan(np.asarray(efb.group_host), n_shards)
    return grow


def _make_dp_psum_grower(mesh: Mesh, *, num_leaves, num_bins, params,
                         max_depth, block_rows, axis, efb, split_batch,
                         hist_overlap=False,
                         mono, mono_penalty, sparse, padded_leaves=None,
                         quant=None):
    """Legacy full-psum data-parallel grower: every shard receives ALL
    global histograms and recomputes the split decision replicated (no
    separate best-split sync needed — but per-chip histogram state scales
    with the full feature width; see the owner-shard default)."""
    ledger = CommLedger(mesh.shape[axis])
    inner = make_grower(
        num_leaves=num_leaves, num_bins=num_bins, params=params,
        max_depth=max_depth, block_rows=block_rows,
        hist_reduce=lambda h, scales=None: ledger.psum(
            h, axis, site="dp.hist_psum"),
        sum_reduce=lambda t: ledger.psum(t, axis, site="dp.root_sum",
                                         cadence="tree"),
        efb=efb,
        split_batch=split_batch, hist_overlap=hist_overlap,
        mono=mono, mono_penalty=mono_penalty,
        padded_leaves=padded_leaves,
        **_quant_hooks(axis, ledger, quant), jit=False)

    out_specs = _dp_out_specs(axis)

    if sparse:
        # SparseBinned pytree (sparse_data.py): the flat [N, K] entry
        # matrix shards on rows while the [F] default_bin vector is
        # replicated — a single prefix spec cannot describe both leaves,
        # so the wrapper ships the leaves as separate shard_map arguments
        # and rebuilds the pytree inside (stride/F are static aux, cached
        # per shape).
        from ..sparse_data import SparseBinned
        cache = {}

        def _sparse_fn(stride: int, nf: int):
            def wrapped(flat, db, vals, fm, nb, nab, nabp, ic, ml, ri):
                return inner(SparseBinned(flat, db, stride, nf), vals,
                             fm, nb, nab, nabp, ic, rng_iter=ri,
                             max_leaves=ml)
            return jax.shard_map(
                wrapped, mesh=mesh,
                in_specs=(P(axis, None), P(None), P(axis, None),
                          P(), P(), P(), P(), P(), P(), P()),
                out_specs=out_specs, check_vma=False)

        def grow(binned, vals, feature_mask, num_bin, na_bin, is_cat=None,
                 max_leaves=None, rng_iter=None):
            if is_cat is None:
                is_cat = jnp.zeros(num_bin.shape[0], bool)
            ml = jnp.int32(num_leaves if max_leaves is None else max_leaves)
            ri = jnp.int32(0 if rng_iter is None else rng_iter)
            key = (binned.stride, binned.num_features)
            if key not in cache:
                cache[key] = jax.jit(_sparse_fn(*key))
            return cache[key](binned.flat, binned.default_bin, vals,
                              feature_mask, num_bin, na_bin, na_bin,
                              is_cat, ml, ri)

        grow.owner_shard = False
        grow.comm = ledger
        return grow

    def _dense(b, v, fm, nb, na, ic, ml, ri):
        # na doubles as na_bin_part (the old outside-the-shard_map
        # duplication, folded in), so _dense has 8 params — in_specs
        # must match that arity, not inner's
        return inner(b, v, fm, nb, na, na, ic, rng_iter=ri, max_leaves=ml)

    f = jax.shard_map(
        _dense, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(), P(), P(), P(), P(),
                  P()),
        out_specs=out_specs, check_vma=False)

    jitted = jax.jit(f)

    def grow(binned, vals, feature_mask, num_bin, na_bin, is_cat=None,
             max_leaves=None, rng_iter=None):
        if is_cat is None:
            is_cat = jnp.zeros(num_bin.shape[0], bool)
        ml = jnp.int32(num_leaves if max_leaves is None else max_leaves)
        ri = jnp.int32(0 if rng_iter is None else rng_iter)
        return jitted(binned, vals, feature_mask, num_bin, na_bin, is_cat,
                      ml, ri)

    grow.owner_shard = False
    grow.comm = ledger
    return grow
