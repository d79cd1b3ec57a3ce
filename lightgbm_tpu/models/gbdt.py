"""GBDT boosting driver.

TPU-native analog of the reference GBDT
(/root/reference/src/boosting/gbdt.cpp): iteration loop of
gradient computation -> (bagging | GOSS sampling) -> per-class tree growth
on device -> leaf renewal -> shrinkage -> score update (gbdt.cpp:371-449
``TrainOneIter``).  Scores for train data are updated via the grower's
row->leaf vector (no traversal); validation scores via device traversal
(predict_device.py).  Model state (host ``Tree`` list) is serialized in the
reference text format by the Booster layer.
"""

from __future__ import annotations

import copy
import math
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..dataset import Dataset
from ..grower import make_grower, TreeArrays
from ..objectives import ObjectiveFunction
from ..ops.split import SplitParams
from ..predict_device import (add_tree_score, leaf_values_of_rows,
                              round_up_pow2, traverse_tree_binned)
from ..tree_model import Tree

# finite_check_policy=clamp replaces non-finite gradients/hessians/leaf
# outputs with 0 (NaN) or ±this bound (infinities) — large enough not to
# distort healthy training, small enough that squares stay in f32 range
_FINITE_CLAMP = 1e30

# process-level super-epoch program sharing (the grower._SHARED_GROWERS
# pattern one layer up): the jitted k-iteration scan closes over NO
# data-derived device arrays — binned matrices, bin metadata, objective
# arrays and valid-set operands all ride in as ARGUMENTS — so two
# boosters whose configs match (31/63 num_leaves collapse onto one
# L=64 leaf bucket) reuse ONE compiled super-epoch.  Keyed on the full
# config plus every shape-/semantics-relevant static; any unkeyable
# state (EFB bundles, categorical flags, CEGB, monotone/interaction
# constraints, multi-process meshes) falls back to a private per-model
# jit in ``self._fused_cache`` — correct, just not shared.
_SE_CACHE: "OrderedDict[tuple, object]" = OrderedDict()
_SE_CACHE_MAX = 8
_SE_CACHE_LOCK = threading.Lock()


class _DeviceTree:
    """Per-tree device arrays for fast binned traversal."""

    __slots__ = ("split_feature", "threshold_bin", "default_left",
                 "left_child", "right_child", "is_cat_node", "cat_rank",
                 "leaf_value", "steps")

    def __init__(self, arrays: TreeArrays, leaf_value: np.ndarray, steps: int):
        self.split_feature = arrays.split_feature
        self.threshold_bin = arrays.threshold_bin
        self.default_left = arrays.default_left
        self.left_child = arrays.left_child
        self.right_child = arrays.right_child
        self.is_cat_node = arrays.is_cat_node
        self.cat_rank = arrays.cat_rank
        self.leaf_value = jnp.asarray(leaf_value, jnp.float32)
        self.steps = steps


def _apply_tree(score_vec, binned, dt: _DeviceTree, na_bin, weight: float,
                efb_maps=None):
    """score_vec += weight * tree(binned) — dense or sparse-binned rows."""
    from ..sparse_data import SparseBinned, add_tree_score_sparse
    if isinstance(binned, SparseBinned):
        return add_tree_score_sparse(
            score_vec, binned, dt.split_feature, dt.threshold_bin,
            dt.default_left, dt.left_child, dt.right_child, na_bin,
            dt.is_cat_node, dt.cat_rank, dt.leaf_value,
            jnp.float32(weight), steps=dt.steps)
    return add_tree_score(
        score_vec, binned, dt.split_feature, dt.threshold_bin,
        dt.default_left, dt.left_child, dt.right_child, na_bin,
        dt.is_cat_node, dt.cat_rank, dt.leaf_value, jnp.float32(weight),
        efb_maps, steps=dt.steps)


def _tree_leaves(binned, dt: _DeviceTree, na_bin, efb_maps=None):
    """Leaf id per row — dense or sparse-binned rows."""
    from ..sparse_data import SparseBinned, traverse_tree_sparse
    if isinstance(binned, SparseBinned):
        return traverse_tree_sparse(
            binned, dt.split_feature, dt.threshold_bin, dt.default_left,
            dt.left_child, dt.right_child, na_bin, dt.is_cat_node,
            dt.cat_rank, steps=dt.steps)
    return traverse_tree_binned(
        binned, dt.split_feature, dt.threshold_bin, dt.default_left,
        dt.left_child, dt.right_child, na_bin, dt.is_cat_node,
        dt.cat_rank, efb_maps, steps=dt.steps)


def _followers(grower_follows: bool, linear: bool, matrices):
    """The held-out matrices as the grower's followers, or None where a new
    tree's held-out leaves come from a walk of its node tables.

    The rule of both training loops, from what the booster is and holds:
    the grower is ``make_grower``'s own body over rows that every worker
    holds whole (``grower_follows``: the one-chip masked grower, or the
    feature-sharded one, whose rows are replicated and whose splits carry
    global feature ids; not the partitioned learner, not a row-sharded one,
    whose rows and hooks are theirs, no caller's reduce hook), every
    held-out matrix is dense (a ``SparseBinned`` has no columns to slice)
    and the trees are not linear (their leaves are fitted on the host from
    raw values).  The grower then partitions the held-out rows beside the
    training rows, step by step, and their leaves are ready when the tree
    is, with no per-row look-up of a node table (PERF.md section 6, PR 33).
    Finished trees are still walked: replay, rollback, DART, predict."""
    from ..sparse_data import SparseBinned
    if not (grower_follows and matrices) or linear \
            or any(isinstance(m, SparseBinned) for m in matrices):
        return None
    return tuple(matrices)


class GBDTModel:
    """Boosting state machine (boosting.h:27-319 interface analog)."""

    def __init__(self, config: Config, train_set: Dataset,
                 objective: Optional[ObjectiveFunction],
                 hist_reduce=None, obs=None):
        self.config = config
        # telemetry (obs/): None when telemetry=false — the hot paths
        # below only ever test this for None, so the default adds zero
        # host syncs and no per-iteration allocation beyond the branch.
        # First, so that the session sees this constructor's own work;
        # ``obs`` is a session the caller already opened spans on
        from ..obs import maybe_session
        if obs is None:
            obs = maybe_session(config)
        self._obs = obs
        self.train_set = train_set.construct(config)
        self.objective = objective
        self.num_class = config.num_model_per_iteration
        self.learning_rate = config.learning_rate
        self.iter_ = 0
        # iteration-keyed RNG/guard streams (bagging epochs, GOSS keys,
        # extra_trees/bynode draws, finite-check cadence) run on
        # iter_ + this offset so a crash+resume run replays the SAME
        # per-iteration randomness as the straight run (snapshot resume,
        # engine.py; set via set_resume_state)
        self._iter_rng_offset = 0

        ds = self.train_set
        self.num_data = ds.num_data
        self.num_features = ds.num_features
        if self.num_features == 0:
            raise ValueError("Dataset has no usable (non-trivial) features")
        import jax as _jax
        self._pc = _jax.process_count()   # >1 = one controller per host

        # elastic liveness layer (parallel/elastic.py): when enabled,
        # the per-iteration host fetch runs under the collective
        # deadline and peers are heartbeat-checked each iteration.
        # Disabled (default) costs one None test per fetch — every
        # path stays byte-identical to before
        self._elastic = None
        self._elastic_timeout = 0.0
        if getattr(config, "elastic_enable", False):
            from ..parallel import elastic as _elastic
            self._elastic = _elastic
            self._elastic_timeout = float(
                config.elastic_collective_timeout_s)
        self._global_fp = None      # cached global data fingerprint

        # learner selection (the device_type axis, tree_learner.cpp:16-64):
        # - partitioned: host-orchestrated, histogram work ∝ smaller child —
        #   wins when dispatch is cheap (CPU) or trees are huge
        # - masked: ONE jitted program per tree (the cuda_exp stance,
        #   cuda_single_gpu_tree_learner.cpp) — wins on accelerators where
        #   per-split host round-trips dominate
        learner = config.tpu_learner
        if learner == "auto":
            import jax
            learner = "partitioned" if jax.default_backend() == "cpu" \
                else "masked"
        if ds.binned_sparse is not None:
            # sparse k-hot storage (sparse_data.py) is consumed by the
            # one-program masked grower; the partitioned learner works on
            # host-dense arrays, which would defeat the memory budget
            if learner == "partitioned" and config.tpu_learner != "auto":
                from ..utils.log import Log
                Log.warning(
                    "tpu_learner=partitioned overridden to masked: the "
                    "dataset chose sparse binned storage (pass "
                    "enable_sparse=false to keep the partitioned learner)")
            learner = "masked"

        self.split_params = SplitParams(
            lambda_l1=config.lambda_l1,
            lambda_l2=config.lambda_l2,
            min_data_in_leaf=config.min_data_in_leaf,
            min_sum_hessian_in_leaf=config.min_sum_hessian_in_leaf,
            min_gain_to_split=config.min_gain_to_split,
            max_delta_step=config.max_delta_step,
            path_smooth=config.path_smooth,
            cat_l2=config.cat_l2,
            cat_smooth=config.cat_smooth,
            max_cat_threshold=config.max_cat_threshold,
            max_cat_to_onehot=config.max_cat_to_onehot,
            min_data_per_group=config.min_data_per_group,
        )
        mono = None
        if config.monotone_constraints:
            mc_full = np.zeros(ds.num_total_features, np.int32)
            mc_in = np.asarray(config.monotone_constraints, np.int32)
            mc_full[:len(mc_in)] = mc_in
            mono = mc_full[np.asarray(ds.used_features)]
        inter = self._interaction_allow(config, ds)
        self._cegb_state = self._make_cegb(config, ds)
        self._forced_spec = self._load_forced(config, ds)
        # feature_contri: per-feature split-gain scale over used slots
        # (feature_histogram.hpp; config.h feature_contri)
        contri = None
        if config.feature_contri:
            fc = np.ones(ds.num_total_features, np.float32)
            vals_in = np.asarray(config.feature_contri, np.float32)
            fc[:len(vals_in)] = vals_in
            contri = fc[np.asarray(ds.used_features)]
        self._feature_contri = contri
        self._extra_trees = bool(config.extra_trees)
        mono_active = mono is not None and np.any(mono)
        # monotone 'basic' lives in the one-program masked grower too
        # (device-resident [L] lo/hi range vectors, grower.py), so it no
        # longer forces the host-orchestrated path and is supported under
        # the data-parallel learner like the reference's parallel learners
        # (monotone_constraints.hpp works under all of them).
        # 'intermediate'/'advanced' recompute the whole frontier's
        # intervals from sibling subtrees — still host bookkeeping.
        mono_masked_ok = mono_active \
            and config.monotone_constraints_method == "basic"
        self._mono = mono if mono_active else None
        self._inter = inter
        # interaction constraints and bynode sampling also run in the
        # masked grower now (per-leaf [L, F] feature-mask state / in-graph
        # subset draws, grower.py) — only CEGB, forced splits and the
        # non-basic monotone methods still need host orchestration
        self._bynode_masked = config.feature_fraction_bynode < 1.0
        has_node_controls = (mono_active and not mono_masked_ok) \
            or self._forced_spec is not None

        if has_node_controls and ds.binned_sparse is not None:
            raise ValueError(
                "forced splits and monotone intermediate/advanced need the "
                "host-orchestrated learner, which requires dense binned "
                "storage; construct the Dataset with enable_sparse=false")
        if has_node_controls and learner != "partitioned" \
                and config.tpu_learner == "auto":
            # node-level controls are host bookkeeping -> partitioned only
            # (auto falls back silently; explicit masked still errors below)
            learner = "partitioned"

        # distributed learner selection (tree_learner.cpp:16-64 factory;
        # config auto-promotes serial->data when num_machines>1).  The
        # distributed growers are shard_map wrappers around the masked
        # one-program grower (parallel/{data,feature,voting}_parallel.py).
        dist = config.tree_learner \
            if config.tree_learner in ("data", "feature", "voting") else None
        if dist is not None and hist_reduce is not None:
            # can't raise: num_machines>1 auto-promotes serial->data in
            # Config, so multi-host callers using the hook pattern never
            # asked for a distributed learner explicitly — warn and keep
            # the (previously silent) hook path
            from ..utils.log import Log
            Log.warning(
                f"ignoring tree_learner={dist}: a caller-supplied "
                "hist_reduce hook takes over cross-shard reduction")
        self._custom_hist_reduce = hist_reduce is not None
        # the learner one device would grow this model with (a sharded
        # learner is the masked one whatever this says): the leaf values'
        # shrinkage of the feature-sharded learner follows it,
        # _shrinks_in_float32
        self._serial_kind = learner
        self._fused_cache: Dict[str, object] = {}
        self._mesh = None
        self._row_pad = 0
        self._feat_pad = 0
        self._global_counts = None
        self._dist_axis = "feature" if dist == "feature" else "data"
        if dist is not None and hist_reduce is None:
            if obs is not None:
                _sp = obs.span("booster.mesh", learner=dist)
            self._mesh = self._resolve_mesh(config, self._dist_axis)
            if obs is not None:
                obs.end_setup(_sp, devices=0 if self._mesh is None
                              else int(self._mesh.size))
            if self._mesh is None:
                dist = None             # single device -> serial (warned)
            elif has_node_controls or inter is not None \
                    or self._bynode_masked or self._cegb_state is not None:
                raise ValueError(
                    "monotone intermediate/advanced, interaction "
                    "constraints, CEGB, forced splits and "
                    "feature_fraction_bynode are not supported with "
                    f"tree_learner={dist} (they require a single-chip "
                    "learner); monotone basic IS supported")
            elif contri is not None or self._extra_trees:
                raise ValueError(
                    "feature_contri and extra_trees are not yet supported "
                    f"with tree_learner={dist}")
            elif mono_masked_ok and dist in ("feature", "voting"):
                raise ValueError(
                    f"monotone constraints with tree_learner={dist} are "
                    "not supported (the [F] constraint vector would need "
                    "feature-axis sharding); use tree_learner=data")
            else:
                learner = "masked"
        else:
            dist = None
        self._dist = dist
        self._learner_kind = learner

        # device-resident binned matrix + per-feature bin metadata.
        # EFB (efb.py): the grouped layout is used by the single-chip
        # learners AND the data-parallel learner, where it shrinks the
        # histogram reduce-scatter payload and the owner-shard chunk axis
        # (dataset.cpp:239 bundles before the reduce-scatter,
        # data_parallel_tree_learner.cpp:174-186).
        # Feature-parallel shards the feature axis (bundles would straddle
        # shards) and voting votes per feature, so both keep flat layout.
        self._use_efb = (ds.efb is not None and hist_reduce is None
                         and learner in ("partitioned", "masked")
                         and dist in (None, "data"))
        # sparse k-hot storage rides the masked serial/data-parallel paths
        # natively; feature/voting shard or vote per flat feature column,
        # so they fall back to densified flat layout (feature_binned warns)
        self._sparse = (ds.binned_sparse is not None and learner == "masked"
                        and dist in (None, "data"))
        if self._pc > 1 and dist == "data":
            # each process chose its binned layout (sparse k-hot vs
            # dense, EFB bundles vs flat, entry width K) from its LOCAL
            # rows; the jitted SPMD program needs ONE layout across the
            # pod.  Consensus: any dense rank demotes everyone to dense
            # (it means dense was viable there); dense ranks keep EFB
            # only when EVERY rank holds the IDENTICAL bundle structure
            # (bundles are fitted on per-rank samples, so shards can
            # disagree, and a sparse-chooser dropped its bundles
            # outright) — otherwise the whole pod uses the flat [N, F]
            # layout; all-sparse pods pad the entry axis to the max K.
            from jax.experimental import multihost_utils
            efb_sig = 0
            if self._use_efb:
                import hashlib
                hsh = hashlib.sha256()
                for a in (ds.efb.group_of_feat, ds.efb.off_of_feat,
                          ds.efb.group_num_bin,
                          [len(g) for g in ds.efb.groups],
                          [j for g in ds.efb.groups for j in g]):
                    hsh.update(np.asarray(a, np.int64).tobytes())
                efb_sig = int.from_bytes(hsh.digest()[:7], "big")
            mine = np.asarray([1 if self._sparse else 0,
                               ds.binned_sparse.k
                               if ds.binned_sparse is not None else 0,
                               efb_sig], np.int64)
            allinfo = np.asarray(multihost_utils.process_allgather(mine))
            if self._sparse and int(allinfo[:, 0].min()) == 0:
                from ..utils.log import Log
                Log.info("sparse binned storage demoted to dense: another "
                         "process's shard kept the dense layout")
                self._sparse = False
            elif self._sparse:
                kmax = int(allinfo[:, 1].max())
                sp = ds.binned_sparse
                if sp.k < kmax:
                    sp.flat = np.concatenate(
                        [sp.flat, np.full((sp.flat.shape[0],
                                           kmax - sp.k), -1, np.int32)],
                        axis=1)
            if not self._sparse:
                sigs = allinfo[:, 2]
                if self._use_efb and not (sigs == sigs[0]).all():
                    from ..utils.log import Log
                    Log.info("EFB bundles dropped pod-wide: processes "
                             "disagree on the bundle structure (per-rank "
                             "sample bundling); using the flat layout")
                if not (sigs == sigs[0]).all() or int(sigs[0]) == 0:
                    self._use_efb = False
        # quantized training (ROADMAP item 3, docs/Quantized-Training.md):
        # one QuantSpec threads through every learner family below —
        # masked (strict/batched/scanned), partitioned, and all
        # three distributed growers
        self._quant = None
        if config.quant_train:
            if self._sparse:
                raise ValueError(
                    "quant_train requires dense binned storage (the "
                    "sparse k-hot segment-sum histogram has no integer "
                    "formulation yet); construct the Dataset with "
                    "enable_sparse=false")
            from ..ops.quantize import QuantSpec
            self._quant = QuantSpec(
                bits=int(config.quant_bits),
                stochastic=(config.quant_round == "stochastic"),
                seed=int(config.seed))

        if self._sparse:
            feat_binned = ds.binned_sparse.flat
        elif self._use_efb:
            feat_binned = ds.binned
        else:
            feat_binned = ds.feature_binned()
        num_bin = np.asarray([ds.bin_mappers[f].num_bin for f in ds.used_features],
                             np.int32)
        na_bin = np.asarray([ds.bin_mappers[f].na_bin for f in ds.used_features],
                            np.int32)
        self.num_bin_dev = jnp.asarray(num_bin)
        self.na_bin_dev = jnp.asarray(na_bin)
        from ..binning import BinType
        is_cat = np.asarray([ds.bin_mappers[f].bin_type == BinType.CATEGORICAL
                             for f in ds.used_features], bool)
        self.is_cat_dev = jnp.asarray(is_cat) if is_cat.any() else None
        self.max_bin = int(num_bin.max())
        if self._use_efb:
            from ..efb import make_device_efb
            self.efb_dev = make_device_efb(ds.efb, num_bin, self.max_bin)
            self.efb_maps = (self.efb_dev.group_of_feat,
                             jnp.asarray(ds.efb.off_of_feat),
                             jnp.asarray(num_bin - 1))
        else:
            self.efb_dev = None
            self.efb_maps = None

        if obs is not None:
            _sp = obs.span("booster.to_device", what="binned")
        # grower-facing bin metadata (== the user-facing arrays unless the
        # feature axis is padded for feature-parallel sharding)
        self._nb_grow = self.num_bin_dev
        self._na_grow = self.na_bin_dev
        self._ic_grow = self.is_cat_dev
        if dist in ("data", "voting"):
            from ..parallel.data_parallel import shard_rows
            n_sh = self._mesh.shape[self._dist_axis]
            if self._pc > 1:
                # multi-process (one controller per host): each process
                # holds only ITS rows; all processes must contribute the
                # same local row count to the global array, so pad to the
                # allgathered max rounded up to the local device count
                from jax.experimental import multihost_utils
                counts = np.asarray(multihost_utils.process_allgather(
                    np.asarray(self.num_data)))
                # unpadded per-process row counts: global GOSS needs the
                # true global N and this process's global row offset
                self._global_counts = counts
                ldev = max(n_sh // self._pc, 1)
                target = -(-int(counts.max()) // ldev) * ldev
                self._row_pad = target - self.num_data
            else:
                self._row_pad = (-self.num_data) % n_sh
            if self._row_pad:
                # sparse k-hot pads with -1 (no stored entries; the pad
                # rows' vals are zeroed so the default-bin fix adds 0)
                fill = -1 if self._sparse else 0
                feat_binned = np.concatenate(
                    [feat_binned, np.full((self._row_pad,
                                           feat_binned.shape[1]), fill,
                                          feat_binned.dtype)], axis=0)
            self.binned_dev = shard_rows(self._mesh, feat_binned,
                                         self._dist_axis)
        elif dist == "feature":
            n_sh = self._mesh.shape[self._dist_axis]
            self._feat_pad = (-self.num_features) % n_sh
            if self._feat_pad:
                feat_binned = np.concatenate(
                    [feat_binned, np.zeros((feat_binned.shape[0],
                                            self._feat_pad),
                                           feat_binned.dtype)], axis=1)
                pad_i = np.full(self._feat_pad, 2, np.int32)
                self._nb_grow = jnp.asarray(np.concatenate([num_bin, pad_i]))
                self._na_grow = jnp.asarray(np.concatenate(
                    [na_bin, np.full(self._feat_pad, -1, np.int32)]))
                if self.is_cat_dev is not None:
                    self._ic_grow = jnp.asarray(np.concatenate(
                        [is_cat, np.zeros(self._feat_pad, bool)]))
            # every worker holds every row: the matrix goes to the mesh
            # once, replicated and committed, and the columns' metadata as
            # the grower's shard_map takes it.  Left on one device, jit
            # sends the matrix to the others at every tree
            self.binned_dev = self._on_mesh(feat_binned)
            self._na_part = self._on_mesh(self._na_grow)
            self._nb_grow = self._on_mesh(self._nb_grow, self._dist_axis)
            self._na_grow = self._on_mesh(self._na_grow, self._dist_axis)
            if self._ic_grow is not None:
                self._ic_grow = self._on_mesh(self._ic_grow, self._dist_axis)
        else:
            self.binned_dev = jnp.asarray(feat_binned)
        if self._sparse:
            # wrap the (possibly sharded) flat entry matrix as the pytree
            # the grower/traversal paths dispatch on
            from ..sparse_data import SparseBinned
            self.binned_dev = SparseBinned(
                self.binned_dev, jnp.asarray(ds.binned_sparse.default_bin),
                ds.binned_sparse.stride, self.num_features)
        if obs is not None:
            obs.end_to_device(_sp, self.binned_dev)

        # split_batch resolution (config.py): 0 = auto -> strict leaf-wise
        # below 64 leaves, K-way super-steps above (the
        # histogram contraction is sublane-bound at M=3; batching K leaves
        # is the only way to raise that ceiling — M=3K of the MXU's 128
        # rows, so K=16 at 255 leaves lifts utilization to ~37% where K=8
        # sat at ~18%).  Voting stays strict: its per-split top-k feature
        # votes are per-histogram-pass.
        sb = config.split_batch
        self._split_batch = sb if sb >= 1 else \
            (16 if config.num_leaves >= 128 else
             8 if config.num_leaves >= 64 else 1)
        if dist == "voting":
            self._split_batch = 1
        if sb < 1 and self._split_batch > 1:
            from ..utils.log import Log
            Log.info(
                f"num_leaves={config.num_leaves} auto-selects "
                f"split_batch={self._split_batch} (top-K batched growth; "
                "trees differ slightly from strict leaf-wise order — set "
                "split_batch=1 for exact reference growth)")

        # on-device contraction autotuner (ops/hist_tune.py): under
        # hist_tune=on the FIRST fit per (platform, shape bucket)
        # sweeps the eligible (K, block_rows) grid by measured ms per
        # leaf slot and persists the winner next to the compile cache;
        # later fits — including other processes — reuse it (zero
        # re-tune, zero re-compile).  The tuner engages ONLY when
        # split_batch is on auto (an explicit width is the user's
        # choice, and applying the winner's paired block_rows to a
        # different K would both mis-tune and re-partition the f32
        # scan against the explicit-width byte pins); the tuned
        # block_rows fills rows_per_block=0.  Budgets that admit only
        # strict growth (num_leaves <= 8: no set width fits) have
        # nothing to tune and skip the sweep entirely.
        self._block_rows = config.rows_per_block
        self._hist_tuned = None
        if getattr(config, "hist_tune", "off") == "on" and sb < 1 \
                and learner == "masked" and dist != "voting" \
                and not self._sparse:
            from ..utils.shapes import SPLIT_BATCH_SET as _SBS
            from ..utils.shapes import fit_split_batch
            kmax = fit_split_batch(_SBS[-1], config.num_leaves)
            if kmax > 1:
                try:
                    from ..ops.hist_tune import ensure as _tune_ensure
                    # the contraction's column/bin axes: the binned
                    # matrix as built (EFB bundles -> group columns at
                    # group-bin width; dense otherwise)
                    t_cols = int(self.binned_dev.shape[1])
                    t_bins = (int(self.efb_dev.group_bins)
                              if self._use_efb else self.max_bin)
                    n_global = (int(self._global_counts.sum())
                                if self._global_counts is not None
                                else self.num_data)
                    rec = self._hist_tuned = _tune_ensure(
                        n_global, t_cols, t_bins,
                        itemsize=(self._quant.itemsize
                                  if self._quant is not None else 4),
                        kmax=kmax, config=config)
                    self._split_batch = rec["k"]
                    if config.rows_per_block <= 0:
                        self._block_rows = int(rec["block_rows"])
                    from ..utils.log import Log
                    Log.info(
                        f"hist_tune: measured choice K={rec['k']} "
                        f"block_rows={rec['block_rows']} "
                        f"({rec['ms_per_leaf']} ms/leaf-slot at "
                        f"{rec.get('sample_rows')} sampled rows)")
                except Exception as e:        # tuner is best-effort
                    from ..utils.log import Log
                    Log.warning(
                        f"hist_tune failed ({type(e).__name__}: {e}); "
                        "keeping untuned shapes")

        # trace-relevant static dims are bucketed (utils/shapes.py) so a
        # config sweep stays inside a bounded trace family; pinned by
        # tools/check_retraces.py.  trace_buckets=false restores exact
        # per-shape traces (A/B + escape hatch).
        from ..utils.shapes import (SPLIT_BATCH_SET, bucket_leaves,
                                    fit_split_batch, snap_split_batch)
        self._trace_buckets = bool(getattr(config, "trace_buckets", True))
        if self._trace_buckets and self._split_batch > 1:
            snapped = self._split_batch
            if snapped not in SPLIT_BATCH_SET:
                snapped = snap_split_batch(snapped)
            if snapped > 16:
                # the WIDE widths also fit under the leaf budget by
                # stepping DOWN the set (31 leaves at K=32 runs K=16)
                # so no off-set width ever opens a private trace
                # family; the shipped widths <= 16 keep their historic
                # clamp (grower.py K = min(K, num_leaves-1)) for
                # byte-identity with existing models
                snapped = fit_split_batch(snapped, config.num_leaves)
            if snapped != self._split_batch:
                from ..utils.log import Log
                Log.info(
                    f"split_batch={self._split_batch} snapped to the "
                    f"shipped super-step width {snapped} "
                    f"(trace_buckets=true pins the trace family to K in "
                    f"{SPLIT_BATCH_SET}, fitted under num_leaves="
                    f"{config.num_leaves}; set trace_buckets=false to "
                    "keep an off-set width)")
                self._split_batch = snapped
        # effective strict-overlap flag (grower.py hist_overlap):
        # masked growers only — voting keeps the masked pass (its
        # top-k vote is per histogram call either way), sparse-binned
        # data keeps its own total-reduction order, and the
        # partitioned learner has no slot path.  Threaded through the
        # serial, scanned, data- and feature-parallel builders;
        # the flop ledger accounts the 1-slot mask as the masked pass
        # it is byte-identical to (obs/flops.hist_flops_bytes).
        self._hist_overlap = (bool(getattr(config, "hist_overlap", True))
                              and learner == "masked"
                              and dist != "voting" and not self._sparse)
        # leaf-budget bucketing: every one-program (masked) grower takes
        # a traced budget — serial, data, and (since the ROADMAP item-1
        # remainder closed) the voting/feature growers too; only the
        # host-orchestrated partitioned learner keeps exact shapes
        self._leaf_pad = None
        self._grower_memory_noted = False
        if self._trace_buckets and learner == "masked":
            lp = bucket_leaves(config.num_leaves)
            # inflation cap: the grower carries a [L, 3, F, B] histogram
            # per leaf slot, so padding a tiny budget to the 64 floor
            # (e.g. num_leaves=4 -> 16x) could blow HBM on wide data;
            # past 4x the trace consolidation isn't worth the state.
            # The common sweep (31/40/63 -> 64) stays well inside.
            if config.num_leaves < lp <= 4 * config.num_leaves:
                self._leaf_pad = lp

        if self._quant is not None:
            # int32 accumulator headroom: every row contributes at most
            # qmax per channel to its bin, and a degenerate (constant or
            # NA-heavy) feature can put EVERY row in one bin — past
            # rows * qmax > 2^31-1 the histogram (and the dp psum over
            # shards, which sums to the same global totals) wraps
            # silently.  Same quant_bits + log2(rows) arithmetic that
            # rejected the 16-bit wire format
            # (docs/Quantized-Training.md).
            n_global = (int(self._global_counts.sum())
                        if self._global_counts is not None
                        else self.num_data)
            if n_global * self._quant.qmax > 2 ** 31 - 1:
                cap = (2 ** 31 - 1) // self._quant.qmax
                hint = "quant_bits=8 (bound ~16.9M rows) or " \
                    if self._quant.bits == 16 else ""
                raise ValueError(
                    f"quant_bits={self._quant.bits} can overflow the "
                    f"int32 histogram accumulator at {n_global} rows: "
                    f"a single bin may collect every row, so rows * "
                    f"qmax ({self._quant.qmax}) must stay under 2^31 "
                    f"(at most {cap} rows).  Use {hint}quant_train="
                    "false.")

        mg_kwargs = None   # set on the masked-learner path (integrity shadow)
        if obs is not None:
            from ..grower import grower_memo_counts
            _sp = obs.span("grower.make")
            memo0 = grower_memo_counts()
        if dist == "data":
            from ..parallel.data_parallel import make_dp_grower
            self.grower = make_dp_grower(
                self._mesh, num_leaves=config.num_leaves,
                num_bins=self.max_bin, params=self.split_params,
                max_depth=config.max_depth, block_rows=self._block_rows,
                efb=self.efb_dev if self._use_efb else None,
                split_batch=self._split_batch,
                hist_overlap=self._hist_overlap,
                mono=self._mono if mono_masked_ok else None,
                mono_penalty=config.monotone_penalty,
                sparse=self._sparse,
                padded_leaves=self._leaf_pad,
                quant=self._quant,
                # owner-shard reduce-scatter (dp_owner_shard=false falls
                # back to the full-psum reduction for A/B comparison)
                owner_shard=config.dp_owner_shard)
        elif dist == "voting":
            from ..parallel.voting_parallel import make_voting_grower
            self.grower = make_voting_grower(
                self._mesh, num_leaves=config.num_leaves,
                num_bins=self.max_bin, params=self.split_params,
                top_k=config.top_k, max_depth=config.max_depth,
                block_rows=self._block_rows,
                padded_leaves=self._leaf_pad, quant=self._quant)
        elif dist == "feature":
            from ..parallel.feature_parallel import make_fp_grower
            self.grower = make_fp_grower(
                self._mesh, num_features=self.num_features + self._feat_pad,
                num_leaves=config.num_leaves, num_bins=self.max_bin,
                params=self.split_params, max_depth=config.max_depth,
                block_rows=self._block_rows,
                split_batch=self._split_batch,
                hist_overlap=self._hist_overlap,
                padded_leaves=self._leaf_pad, quant=self._quant)
        elif hist_reduce is None and learner == "partitioned":
            # single-chip performance learner (grower_partitioned.py):
            # histogram work ∝ smaller child, like the reference
            from ..grower_partitioned import PartitionedGrower
            self.grower = PartitionedGrower(
                num_leaves=config.num_leaves, num_bins=self.max_bin,
                params=self.split_params, max_depth=config.max_depth,
                block_rows=self._block_rows, mono=mono,
                mono_method=config.monotone_constraints_method,
                mono_penalty=config.monotone_penalty,
                interaction_groups=inter,
                bynode_frac=config.feature_fraction_bynode,
                bynode_seed=config.feature_fraction_seed + 1,
                efb=self.efb_dev,
                pool_entries=self._pool_entries(config, ds),
                feature_contri=contri,
                extra_trees=self._extra_trees,
                extra_seed=config.extra_seed,
                quant=self._quant)
        else:
            if has_node_controls:
                raise ValueError(
                    "monotone intermediate/advanced and forced splits "
                    "currently require the partitioned learner "
                    "(tpu_learner=partitioned, single-chip); monotone "
                    "basic, interaction constraints, CEGB and "
                    "feature_fraction_bynode work on the masked learner")
            # a caller-supplied hist_reduce hook keeps its single-arg
            # contract; quantized growers call reduce hooks with the
            # iteration's scales as a second argument (grower.py _hist)
            if hist_reduce is not None and self._quant is not None:
                user_reduce = hist_reduce
                hist_reduce = lambda h, scales=None: user_reduce(h)  # noqa: E731
            # kwargs captured so the integrity layer can build an
            # independently-jitted shadow twin of this exact grower
            mg_kwargs = dict(
                num_leaves=config.num_leaves, num_bins=self.max_bin,
                params=self.split_params, max_depth=config.max_depth,
                block_rows=self._block_rows, hist_reduce=hist_reduce,
                quant=self._quant,
                efb=self.efb_dev if self._use_efb else None,
                gain_scale=contri, extra_trees=self._extra_trees,
                extra_seed=config.extra_seed,
                split_batch=self._split_batch,
                hist_overlap=self._hist_overlap,
                mono=self._mono if mono_masked_ok else None,
                mono_penalty=config.monotone_penalty,
                interaction_groups=inter,
                bynode_frac=config.feature_fraction_bynode,
                bynode_seed=config.feature_fraction_seed + 1,
                cegb=self._cegb_state,
                padded_leaves=self._leaf_pad)
            self.grower = make_grower(**mg_kwargs)
        # make_grower's own body over rows that every worker holds whole
        # carries follower row sets through its splits (_followers): the
        # one-chip grower and the feature-sharded one of one process
        self._grower_follows = (
            (mg_kwargs is not None or (dist == "feature" and self._pc == 1))
            and not self._custom_hist_reduce)
        if obs is not None:
            # what the process-wide memo of jitted growers answered
            # (grower.py _SHARED_GROWERS): hit = this booster runs a
            # program an earlier one traced
            memo = {r: n - memo0[r]
                    for r, n in grower_memo_counts().items() if n > memo0[r]}
            for result, n in memo.items():
                obs.metrics.counter("grower.memo", result=result).inc(n)
            obs.end_setup(_sp, memo="+".join(sorted(memo)) or "none")

        if config.linear_tree and config.boosting not in ("gbdt", "gbrt"):
            raise ValueError("linear_tree requires boosting=gbdt")

        if obs is not None:
            _sp = obs.span("booster.to_device", what="row_state")
        if self.objective is not None:
            self.objective.init(ds.metadata, self.num_data)

        # scores: [N, K] f32 on device
        init = np.zeros((self.num_data, self.num_class), np.float32)
        if ds.metadata.init_score is not None:
            s = np.asarray(ds.metadata.init_score, np.float32)
            init += s.reshape(self.num_data, -1)
        self.score = self._row_state(init)
        if dist == "feature" and self.objective is not None:
            # the row state lives where the grower's rows do: a label left
            # on one device is sent to the others at every gradient
            self.objective.place_row_state(self._on_mesh)
        self._init_applied = ds.metadata.init_score is not None
        if obs is not None:
            obs.end_to_device(_sp, (self.score, [
                getattr(self.objective, a, None)
                for a in ("label", "weight")]))

        # validation sets: (dataset, device binned, score)
        self.valid_sets: List[Tuple[Dataset, jax.Array, jax.Array]] = []
        # super-epoch traced early-stop vote state, carried ON DEVICE
        # across epochs: (best [E] f32, best_iter [E] i32, has-best [E]
        # bool, stop scalar bool) — see train_superepoch
        self._es_dev = None
        self._se_valid_cache: Dict[int, Tuple[jax.Array, jax.Array]] = {}

        self.models: List[Tree] = []          # host trees, grouped per iter
        self.device_trees: List[_DeviceTree] = []
        self.tree_weights: List[float] = []   # DART/RF reweighting
        self.step_counts: List[int] = []      # grower loop steps per tree
        self._rng_feat = np.random.RandomState(config.feature_fraction_seed)
        self._goss = config.data_sample_strategy == "goss"
        self._last_iter_state: Optional[dict] = None

        # computation-integrity layer (lightgbm_tpu/integrity.py): None
        # unless integrity_check_freq > 0 — the hot paths only test for
        # None, so the default adds zero work and zero syncs
        self._integrity = None
        if config.integrity_check_freq > 0:
            from ..integrity import IntegrityChecker
            if mg_kwargs is not None:
                # masked learner: a second trace of the same logical
                # math — jax.jit over the unjitted grower, deliberately
                # bypassing the shared-grower memo
                from ..grower import make_shadow_grower
                shadow, independent = make_shadow_grower(**mg_kwargs), True
            elif dist in ("data", "voting", "feature"):
                # distributed growers are built per-topology around
                # collectives: the shadow is the SAME program re-run —
                # a full redundant recompute rather than a second
                # trace (manifest records independent_trace=false)
                shadow, independent = self.grower, False
            else:
                raise ValueError(
                    "integrity_check_freq > 0 is unsupported with "
                    "tpu_learner=partitioned: its grower keeps host-side "
                    "pool/RNG state, so a shadow re-execution is not a "
                    "pure recompute.  Use the masked learner")
            self._integrity = IntegrityChecker(config, shadow, independent)

        self._flops = None
        if self._obs is not None:
            self._obs.adopt_construct_seconds(self.train_set)
            ledger = getattr(self.grower, "comm", None)
            if ledger is not None:
                self._obs.attach_comm_sites(ledger)
            # static compute ledger (obs/flops.py) from LOGICAL GLOBAL
            # shapes — identical between tree_learner=data and serial,
            # independent of jit-cache state.  Attached on process 0
            # only: the ledger accounts the global work, so a
            # per-process attach would multiply it by the process
            # count when snapshots aggregate.
            # peaks are process-independent (config override or the
            # device-kind table) — attached everywhere so every
            # process's perf.* join carries the same mfu/bound keys
            from ..obs.attrib import config_peaks
            self._obs.attach_peaks(*config_peaks(config))
            if _jax.process_index() == 0:
                from ..obs.flops import FlopLedger
                n_global = (int(self._global_counts.sum())
                            if self._global_counts is not None
                            else self.num_data)
                if self._sparse:
                    hist_cols, itemsize = self.num_features, 4
                else:
                    hist_cols = int(self.binned_dev.shape[1])
                    itemsize = int(self.binned_dev.dtype.itemsize)
                self._flops = FlopLedger.for_training(
                    n_rows=n_global, n_feat=self.num_features,
                    num_bins=self.max_bin,
                    split_batch=self._split_batch,
                    hist_cols=hist_cols,
                    hist_bins=(int(self.efb_dev.group_bins)
                               if self.efb_dev is not None
                               else self.max_bin),
                    binned_itemsize=itemsize,
                    num_class=self.num_class,
                    # per-dtype HBM accounting: the quantized passes
                    # read int8/int16 accumulands, and the quantize/
                    # dequant sites join the perf.* roofline so
                    # perf.hist.* shows the memory bound moving
                    vals_itemsize=(self._quant.itemsize
                                   if self._quant is not None else 4),
                    quant=self._quant is not None)
                self._obs.attach_flop_sites(self._flops)
        # flight recorder (obs/blackbox.py): None unless
        # telemetry_blackbox=true — zero ring allocation, no file
        from ..obs.blackbox import maybe_recorder
        self._bbox = maybe_recorder(
            config,
            default_path=((config.output_model + ".blackbox.jsonl")
                          if getattr(config, "output_model", "")
                          else "lgbtpu_blackbox.jsonl"),
            meta={"surface": "train", "objective": config.objective,
                  "num_leaves": config.num_leaves,
                  "tree_learner": config.tree_learner,
                  "learner": self._learner_kind,
                  "split_batch": self._split_batch})

    def _fit_linear_leaves(self, arrays: TreeArrays, ht: Tree, g, h, w,
                           shrinkage: float, bias: float) -> None:
        """Per-leaf linear models (LinearTreeLearner::CalculateLinear,
        linear_tree_learner.cpp): Newton-step ridge regression of the
        gradients on the leaf's path features; coefficients shrunk by the
        learning rate; constant = fitted intercept (+ iteration-0 bias)."""
        nl = int(arrays.num_leaves)
        raw = self.train_set.raw_data
        if nl <= 1 or raw is None:
            return
        lc = np.asarray(arrays.left_child)[:nl - 1]
        rc = np.asarray(arrays.right_child)[:nl - 1]
        sf = np.asarray(arrays.split_feature)[:nl - 1]
        icn = np.asarray(arrays.is_cat_node)[:nl - 1]
        lor = np.asarray(arrays.leaf_of_row)
        used = self.train_set.used_features

        paths: Dict[int, List[int]] = {}
        stack = [(0, [])]
        while stack:
            node, feats = stack.pop()
            if node < 0:
                paths[~node] = feats
                continue
            nf = feats if icn[node] else feats + [int(used[sf[node]])]
            stack.append((int(lc[node]), nf))
            stack.append((int(rc[node]), nf))

        g_np = np.asarray(g, np.float64)
        h_np = np.asarray(h, np.float64)
        w_np = np.asarray(w, np.float64)
        lam = self.config.linear_lambda
        ht.is_linear = True
        for leaf in range(nl):
            feats = list(dict.fromkeys(paths.get(leaf, [])))
            rows = np.nonzero((lor == leaf) & (w_np > 0))[0]
            ht.leaf_const[leaf] = ht.leaf_value[leaf]
            ht.leaf_features[leaf], ht.leaf_coeff[leaf] = [], []
            if not feats or len(rows) < len(feats) + 2:
                continue
            X = raw[np.ix_(rows, feats)].astype(np.float64)
            ok = ~np.isnan(X).any(axis=1)
            if ok.sum() < len(feats) + 2:
                continue
            # bagging/GOSS amplification weights scale g and h exactly as
            # in the histogram path (goss.hpp weight amplification)
            ww = w_np[rows][ok]
            X, gg, hh = X[ok], g_np[rows][ok] * ww, h_np[rows][ok] * ww
            Xt = np.column_stack([X, np.ones(len(X))])
            A = Xt.T @ (hh[:, None] * Xt)
            A[np.arange(len(feats)), np.arange(len(feats))] += lam
            A[np.arange(len(A)), np.arange(len(A))] += 1e-10
            b = -Xt.T @ gg
            try:
                beta = np.linalg.solve(A, b)
            except np.linalg.LinAlgError:
                continue
            if not np.isfinite(beta).all():
                continue
            ht.leaf_features[leaf] = feats
            ht.leaf_coeff[leaf] = (beta[:-1] * shrinkage).tolist()
            ht.leaf_const[leaf] = float(beta[-1] * shrinkage) + bias

    @staticmethod
    def _linear_outputs(ht: Tree, leaves: np.ndarray,
                        raw: np.ndarray) -> np.ndarray:
        """Per-row outputs of a linear tree given row->leaf assignment."""
        return ht.linear_leaf_outputs(leaves, raw)

    @staticmethod
    def _make_cegb(config: Config, ds: Dataset):
        """CEGB penalties over used-feature slots
        (cost_effective_gradient_boosting.hpp)."""
        coupled_in = config.cegb_penalty_feature_coupled
        lazy_in = config.cegb_penalty_feature_lazy
        if config.cegb_penalty_split <= 0 and not coupled_in and not lazy_in:
            return None
        from ..grower_partitioned import CEGBState
        nf = len(ds.used_features)

        def slot_array(vals):
            if not vals:
                return None
            full = np.zeros(ds.num_total_features, np.float32)
            full[:len(vals)] = np.asarray(vals, np.float32)
            return full[np.asarray(ds.used_features)]

        return CEGBState(
            tradeoff=config.cegb_tradeoff,
            penalty_split=config.cegb_penalty_split,
            coupled=slot_array(coupled_in),
            lazy=slot_array(lazy_in),
            used=np.zeros(nf, bool))

    @staticmethod
    def _load_forced(config: Config, ds: Dataset):
        """Parse forcedsplits_filename JSON into slot/bin space
        (forced splits file, serial_tree_learner.cpp:455)."""
        if not config.forcedsplits_filename:
            return None
        import json
        with open(config.forcedsplits_filename) as f:
            spec = json.load(f)
        slot_of_orig = {f: i for i, f in enumerate(ds.used_features)}

        def conv(node):
            if not isinstance(node, dict) or "feature" not in node:
                return None
            orig = int(node["feature"])
            if orig not in slot_of_orig:
                return None
            mapper = ds.bin_mappers[orig]
            thr_bin = int(mapper.value_to_bin(
                np.asarray([float(node["threshold"])]))[0])
            out = {"feature": slot_of_orig[orig], "threshold_bin": thr_bin}
            for side in ("left", "right"):
                c = conv(node.get(side))
                if c is not None:
                    out[side] = c
            return out

        return conv(spec)

    def _pool_entries(self, config: Config, ds: Dataset) -> int:
        """histogram_pool_size (MB, config.h) -> max cached per-leaf
        histograms for the HistogramPool analog (feature_histogram.hpp:1095;
        sizing logic mirrors serial_tree_learner.cpp:33-46)."""
        if config.histogram_pool_size <= 0:
            return 0
        cols = self.efb_dev.group_bins if self.efb_dev is not None \
            else self.max_bin
        nf = (int(self.efb_dev.group_host.max()) + 1
              if self.efb_dev is not None else self.num_features)
        # grower histograms are [F, B, 3] f32; under EFB the bin axis is the
        # max group-bin count
        bytes_per_leaf = max(nf, 1) * max(cols, 2) * 3 * 4
        return max(2, int(config.histogram_pool_size * 1024 * 1024
                          / bytes_per_leaf))

    @staticmethod
    def _resolve_mesh(config: Config, axis: str):
        """Device mesh for tree_learner=data|feature|voting
        (tree_learner.cpp:16-64 factory dispatch; the mesh replaces the
        reference's machine list, SURVEY.md §2.5).  Size precedence:
        ``mesh_shape`` > ``num_machines`` > all visible devices.  Returns
        None (serial fallback, with a warning) on a single device —
        the reference's num_machines=1 degenerate case.

        The device claim itself (jax backend init, which can hang when
        another process holds the chip) runs under the resilience layer:
        watchdog stack dumps at ``dist_init_timeout_s``, ``dist_init_retries``
        jittered-backoff retries for classified-transient errors, and an
        optional graceful degradation to the serial learner
        (``dist_fallback_serial``) when bring-up exhausts its retries."""
        import jax
        from ..parallel import make_mesh
        from ..utils import faultinject
        from ..utils.log import Log
        from ..utils.resilience import (RetryPolicy, Watchdog,
                                        WatchdogTimeout, retry_call)

        def _claim():
            faultinject.check("device_claim")
            faultinject.check("claim_wedge")
            return jax.devices()

        timeout = config.dist_init_timeout_s
        elastic = bool(getattr(config, "elastic_enable", False))
        policy = RetryPolicy.for_bringup(config.dist_init_retries, timeout)
        try:
            if elastic:
                # cancel-and-raise: a HUNG claim is abandoned at its
                # deadline slice
                # and becomes a retryable WatchdogTimeout.  The
                # per-attempt slice is timeout/attempts — a wedge
                # abandoned at the FULL timeout would exhaust
                # retry_call's deadline_s (== timeout) on the first
                # attempt and dist_init_retries would never fire.
                # Exhaustion surfaces as a classified ElasticFailure
                # for the recovery ladder
                per_attempt = timeout / max(1, policy.max_attempts)
                devs = retry_call(
                    lambda: Watchdog(per_attempt, label="device claim",
                                     on_timeout="raise").run(_claim),
                    policy=policy, label="device claim")
            else:
                with Watchdog(timeout, label="device claim"):
                    devs = retry_call(_claim, policy=policy,
                                      label="device claim")
        except Exception as e:
            fail = None
            if elastic and isinstance(e, WatchdogTimeout):
                # classify + record (elastic.* metrics, JSONL event,
                # blackbox dump) BEFORE the fallback decision — a wedge
                # must never be silent, even when dist_fallback_serial
                # then degrades it to the serial learner
                from ..parallel.elastic import ElasticFailure, _on_failure
                fail = ElasticFailure("claim_wedge", str(e))
                _on_failure(fail, site="device_claim")
            if config.dist_fallback_serial:
                Log.warning(
                    f"multi-chip bring-up failed after "
                    f"{policy.max_attempts} attempt(s) ({e}); falling back "
                    "to the serial learner (dist_fallback_serial=true)")
                return None
            if fail is not None:
                raise fail from e
            raise
        if elastic:
            # suspect-device quarantine (integrity.py sticky SDC): a
            # quarantined chip is excluded from the claimed list, so
            # the ladder's "sdc" rung runs mesh-minus-suspects.  Never
            # filter down to nothing — with every device suspect the
            # serial rung re-trusts the least-recently-accused
            from ..parallel import elastic as elastic_mod
            sus = elastic_mod.suspected_devices()
            if sus:
                keep = [d for d in devs
                        if getattr(d, "id", None) not in sus]
                if keep and len(keep) < len(devs):
                    Log.warning(
                        f"excluding {len(devs) - len(keep)} quarantined "
                        f"suspect device(s) {sorted(sus)} from the mesh")
                    devs = keep
        if config.mesh_shape and len(config.mesh_shape) > 1:
            # the tree learners shard exactly one axis (rows OR features);
            # a multi-dim mesh has no meaning here, so reject it loudly
            # rather than silently flattening
            raise ValueError(
                f"mesh_shape={config.mesh_shape}: tree_learner="
                f"{config.tree_learner} shards a single axis; pass a "
                "one-element mesh_shape (e.g. [8])")
        if config.mesh_shape:
            n = int(np.prod(config.mesh_shape))
        elif config.num_machines > 1:
            n = config.num_machines
        else:
            n = len(devs)
        if n > len(devs):
            raise ValueError(
                f"tree_learner={config.tree_learner} needs {n} devices "
                f"(mesh_shape/num_machines), only {len(devs)} visible")
        if n <= 1:
            Log.warning(
                f"tree_learner={config.tree_learner} requested but only one "
                "device is visible; training serially")
            return None
        return make_mesh((n,), (axis,), devs)

    def _eget(self, x, site: str = "fetch"):
        """The iteration's host fetch.  Under ``elastic_enable`` it runs
        inside the collective deadline (``parallel/elastic.guarded_get``:
        a hung collective materializes at this blocking fetch, gets
        stack-dumped, abandoned, and classified as an ElasticFailure
        instead of hanging the run); otherwise a plain device fetch."""
        if self._elastic is not None and self._elastic_timeout > 0:
            return self._elastic.guarded_get(x, self._elastic_timeout,
                                             site=site)
        return jax.device_get(x)

    def integrity_boundary_check(self) -> None:
        """Shadow-verify the newest committed tree right before a
        snapshot is written (engine.py calls this ahead of
        ``write_snapshot``), so the manifest's ``integrity`` stamp means
        'last check clean AT this snapshot'.  No-op when the integrity
        layer is off or the newest tree already passed a check.  Raises
        ``IntegrityFailure`` on a sticky boundary mismatch."""
        if self._integrity is not None:
            self._integrity.boundary_check(self)

    def integrity_manifest(self, iteration: int):
        """The snapshot manifest's ``integrity`` stamp dict, or None
        when the integrity layer is off (manifests stay byte-identical
        to pre-integrity ones at ``integrity_check_freq=0``)."""
        if self._integrity is None:
            return None
        return self._integrity.manifest(iteration)

    def snapshot_state(self):
        """``(score, fingerprint_override)`` for snapshot.write_snapshot.

        Default: this process's score and no override.  Under elastic
        MULTI-PROCESS row-sharded training the snapshot must instead
        carry GLOBAL state — the all-process score in global row order
        and the full-data fingerprint — so a shrunk (even
        single-process) relaunch over the full data can locate and
        resume it (docs/Fault-Tolerance.md "Elastic training")."""
        if not (self._elastic is not None and self._pc > 1
                and self._dist in ("data", "voting")
                and self._global_counts is not None):
            return np.asarray(self.score, np.float32), None
        from jax.experimental import multihost_utils

        def _allgather(arr, site):
            # the allgather is itself a collective: a peer that died
            # between the iteration's liveness check and this snapshot
            # write would wedge it forever — bound it by the same
            # elastic deadline as the training fetch so a snapshot
            # boundary can never reopen the silent-hang class
            return np.asarray(self._elastic.guarded_call(
                lambda: multihost_utils.process_allgather(arr),
                self._elastic_timeout, site))

        counts = self._global_counts
        tmax = int(counts.max())
        sc = np.asarray(self.score, np.float32)
        if sc.shape[0] < tmax:
            sc = np.concatenate(
                [sc, np.zeros((tmax - sc.shape[0], sc.shape[1]),
                              np.float32)])
        allsc = _allgather(sc, "snapshot_allgather")
        gscore = np.concatenate(
            [allsc[p, :int(counts[p])] for p in range(len(counts))])
        if self._global_fp is None:
            lab = np.asarray(self.train_set.metadata.label, np.float32)
            w = self.train_set.metadata.weight
            pad = tmax - len(lab)
            cols = [np.pad(lab, (0, pad))]
            if w is not None:
                cols.append(np.pad(np.asarray(w, np.float32), (0, pad)))
            g = _allgather(np.stack(cols), "snapshot_fp_allgather")
            glab = np.concatenate(
                [g[p, 0, :int(counts[p])] for p in range(len(counts))])
            gw = None
            if w is not None:
                gw = np.concatenate(
                    [g[p, 1, :int(counts[p])] for p in range(len(counts))])
            from ..dataset import fingerprint_arrays
            self._global_fp = fingerprint_arrays(glab, gw)
        return gscore, self._global_fp

    def _note_grower_memory(self, obs, args, kwargs) -> None:
        """Once a booster, where one jitted masked grower grows its trees:
        the bytes of temporaries XLA laid out for the executable the
        iteration just ran (``grower.temp_bytes``) and the logical bytes of
        its per-leaf histogram state (``grower.hist_state_bytes``: leaf
        slots x 3 x columns x bins), one observation each, so a reader of
        several boosters takes ``sum / count``.  Of a sharded grower both
        are one worker's share (XLA analyses the program of one device; a
        grower says what columns of the state a worker holds,
        ``state_columns``): one that says neither is left out."""
        self._grower_memory_noted = True
        from ..grower import compiled_grower_temp_bytes
        temp = compiled_grower_temp_bytes(self.grower, args, kwargs)
        if temp is None:
            return
        obs.metrics.histogram("grower.temp_bytes").observe(temp)
        # the batched grower keeps K scratch slots past the leaf budget
        k = min(self._split_batch, self.config.num_leaves - 1)
        slots = (self._leaf_pad or self.config.num_leaves) + (k if k > 1 else 0)
        if self._dist is not None:
            cols = getattr(self.grower, "state_columns", None)
            if cols is None:
                return
        else:
            cols = self.binned_dev.num_features if self._sparse \
                else self.binned_dev.shape[1]
        bins = int(self.efb_dev.group_bins) if self._use_efb else self.max_bin
        obs.metrics.histogram("grower.hist_state_bytes").observe(
            slots * 3 * int(cols) * bins * 4)

    def _prep_vals(self, vals: jax.Array) -> jax.Array:
        """Pad + row-shard the per-row (grad, hess, weight) stack for the
        row-sharded learners, replicate it for the feature-sharded one;
        identity otherwise.  Padded rows carry zero weight so they never
        contribute to histograms."""
        if self._dist == "feature":
            # the stack follows the score, which lies on the mesh from the
            # booster's construction; a caller's gradients (``fobj``) come
            # from one device, and jit traces the grower anew for another
            # placement: give every stack the one the program runs on
            return self._on_mesh(vals)
        if self._dist not in ("data", "voting"):
            return vals
        if self._row_pad:
            vals = jnp.concatenate(
                [vals, jnp.zeros((self._row_pad, vals.shape[1]), vals.dtype)])
        from ..parallel.data_parallel import shard_rows
        return shard_rows(self._mesh, vals, self._dist_axis)

    def _boost_from_score(self, class_id: int) -> float:
        """BoostFromScore with reference multi-machine semantics: the
        initial score comes from the GLOBAL label/weight statistics
        (binary_objective.hpp BoostFromScore runs after a network
        allreduce of suml/sumw), not this process's shard."""
        if self._pc <= 1 or self._dist is None or self._dist == "feature" \
                or getattr(self.objective, "is_ranking", False):
            # feature-parallel replicates the data: every process already
            # holds the GLOBAL metadata, and gathering would only
            # duplicate each row process_count times.  Ranking objectives
            # boost from 0 regardless of data (rank_objective.hpp), so
            # the gathered metadata — which would also need global query
            # boundaries — is never consulted.
            return self.objective.boost_from_score(class_id)
        from jax.experimental import multihost_utils
        obj = self.objective
        lab = np.asarray(self.train_set.metadata.label, np.float64)
        w = self.train_set.metadata.weight
        w = np.ones_like(lab) if w is None else np.asarray(w, np.float64)
        pad = self.num_data + self._row_pad - len(lab)
        stacked = np.stack([np.pad(lab, (0, pad)), np.pad(w, (0, pad))])
        g = np.asarray(multihost_utils.process_allgather(stacked))
        glab = g[:, 0].reshape(-1)
        gw = g[:, 1].reshape(-1)
        keep = gw > 0.0            # padded rows carry zero weight
        # a fresh instance init'd on the GLOBAL metadata: objectives
        # derive their boost statistics (label counts, means) in init()
        from ..dataset import Metadata
        md = Metadata(int(keep.sum()))
        md.label = glab[keep].astype(np.float32)
        if self.train_set.metadata.weight is not None:
            md.weight = gw[keep].astype(np.float32)
        gobj = type(obj)(self.config)
        gobj.init(md, md.num_data)
        return gobj.boost_from_score(class_id)

    def _localize_rows(self, global_arr: jax.Array) -> jax.Array:
        """This process's rows of a row-sharded global array, pad dropped
        (multi-process only; shards ordered by global row offset)."""
        shards = sorted(global_arr.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        parts = [np.asarray(s.data) for s in shards]
        local = parts[0] if len(parts) == 1 else np.concatenate(parts)
        return jnp.asarray(local[:self.num_data])

    def _prep_fmask(self, fmask: jax.Array) -> jax.Array:
        if self._feat_pad:
            fmask = jnp.concatenate([fmask, jnp.zeros(self._feat_pad, bool)])
        if self._dist == "feature":
            return self._on_mesh(fmask, self._dist_axis)
        return fmask

    def _row_state(self, x) -> jax.Array:
        """Per-row state (a score, a held-out matrix) where the grower's
        rows lie: on every device of the feature-sharded learner's mesh,
        else on the default device."""
        return self._on_mesh(x) if self._dist == "feature" \
            else jnp.asarray(x)

    def _on_mesh(self, x, axis: Optional[str] = None):
        """``x`` placed on the learner's mesh and committed there:
        replicated, or its leading axis split over ``axis``."""
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.device_put(
            x, NamedSharding(self._mesh, PartitionSpec(axis)))

    @staticmethod
    def _interaction_allow(config: Config, ds: Dataset):
        """Parse interaction_constraints ("[0,1],[2,3]" over original feature
        indices) into a [G, F] constraint-GROUP matrix over used-feature
        slots (ColSampler, col_sampler.hpp:91-111 GetByNode): a leaf's
        allowed features are its branch set plus the union of the groups
        that contain the WHOLE branch set — overlapping groups compose by
        subset containment, not by progressive intersection, and features
        in no group are unusable (an empty branch allows only the union
        of all groups)."""
        spec = config.interaction_constraints
        if not spec:
            return None
        groups: List[List[int]] = []
        for part in spec.replace(" ", "").strip("[]").split("],["):
            if part:
                groups.append([int(t) for t in part.split(",") if t != ""])
        if not groups:
            return None
        slot_of_orig = {f: i for i, f in enumerate(ds.used_features)}
        nf = len(ds.used_features)
        gm = np.zeros((len(groups), nf), bool)
        for gi, grp in enumerate(groups):
            for member in grp:
                if member in slot_of_orig:
                    gm[gi, slot_of_orig[member]] = True
        return gm

    # -- plumbing ----------------------------------------------------------
    def _valid_followers(self) -> Optional[tuple]:
        """This booster's held-out matrices where the grower follows them
        (``_followers``), else None."""
        return _followers(self._grower_follows, self.config.linear_tree,
                          [vb for _, vb, _ in self.valid_sets])

    def _count_valid_leaves(self, followed: bool, trees: int = 1) -> None:
        """``train.valid_leaves{source=partition|walk}``: one count a tree
        a held-out set, by where the tree's held-out leaves came from."""
        if self._obs is not None and self.valid_sets:
            self._obs.metrics.counter(
                "train.valid_leaves",
                source="partition" if followed else "walk").inc(
                    trees * len(self.valid_sets))

    def _note_contracted(self, rung_steps) -> None:
        """What a new tree's contractions were handed (``TreeArrays
        .rung_steps``): ``hist.rows_contracted``, the rows of all its
        passes, and ``hist.compact_steps{rung=}``, its passes over a row
        bucket by rung (``N/4``: a quarter of the grower's rows; grower.py
        ``compact_ladder``).  The partitioned learner reports no pass."""
        steps = np.asarray(rung_steps)
        if self._obs is None or not steps.any():
            return
        from ..grower import rows_contracted
        self._obs.metrics.histogram("hist.rows_contracted").observe(
            rows_contracted(self.binned_dev.shape[0], steps))
        for rung in np.flatnonzero(steps[1:]) + 1:
            self._obs.metrics.counter(
                "hist.compact_steps", rung=f"N/{2 ** rung}").inc(
                    int(steps[rung]))

    def add_valid_set(self, valid: Dataset) -> None:
        valid.construct(self.config)
        nv = valid.num_data
        pad = 0
        obs = self._obs
        if obs is not None:
            _sp = obs.span("booster.to_device", what="valid")
        if valid.binned_sparse is not None:
            binned = valid.binned_sparse.to_device()
        else:
            vb = valid.binned if self._use_efb else valid.feature_binned()
            if self._trace_buckets and nv <= (1 << 20):
                # row-bucket the valid set (utils/shapes.py pow2 policy)
                # so the per-iteration score-update traversal — and
                # therefore early stopping over differently-sized valid
                # sets — traces once per BUCKET, not once per size.
                # Padded rows are bin-0 and their scores are sliced off
                # in valid_score(); metrics are byte-identical.  Above
                # ~1M rows the up-to-2x recurring pad work outweighs the
                # one-time retrace, so huge valid sets keep exact shapes.
                from ..utils.shapes import bucket_rows
                pad = bucket_rows(nv, min_bucket=256) - nv
                if pad:
                    vb = np.concatenate(
                        [vb, np.zeros((pad, vb.shape[1]), vb.dtype)])
            # held-out rows lie where the training rows do (_followers)
            binned = self._row_state(vb)
        init = np.zeros((nv + pad, self.num_class), np.float32)
        if valid.metadata.init_score is not None:
            init[:nv] += np.asarray(valid.metadata.init_score, np.float32) \
                .reshape(nv, -1)
        # models without device copies (reset_training_data installed an
        # existing ensemble): fold their contribution in by host
        # prediction on the raw values; device_trees always corresponds
        # to the TAIL of models
        n_host_only = len(self.models) - len(self.device_trees)
        if n_host_only > 0:
            if valid.raw_data is None:
                raise ValueError(
                    "validation after reset_training_data needs the valid "
                    "set's raw values (free_raw_data=False)")
            raw = np.asarray(valid.raw_data, np.float64)
            for ti in range(n_host_only):
                k = ti % self.num_class
                init[:nv, k] += (self.tree_weights[ti]
                                 * self.models[ti].predict(raw))
        score = self._row_state(init)
        if obs is not None:
            obs.end_to_device(_sp, (binned, score))
        # replay existing device trees (continued training)
        for ti, dt in enumerate(self.device_trees):
            mi = n_host_only + ti
            k = mi % self.num_class
            ht = self.models[mi] if mi < len(self.models) else None
            if ht is not None and ht.is_linear:
                leaves = np.asarray(_tree_leaves(
                    binned, dt, self.na_bin_dev, self.efb_maps))[:nv]
                delta = self._linear_outputs(ht, leaves, valid.raw_data)
                if pad:
                    delta = np.pad(np.asarray(delta, np.float32), (0, pad))
                score = score.at[:, k].add(
                    self.tree_weights[mi] * jnp.asarray(delta, jnp.float32))
            else:
                score = score.at[:, k].set(_apply_tree(
                    score[:, k], binned, dt, self.na_bin_dev,
                    self.tree_weights[mi], self.efb_maps))
        self.valid_sets.append((valid, binned, score))

    # -- sampling (gbdt.cpp:230 Bagging + goss.hpp) ------------------------
    @property
    def _bagging_active(self) -> bool:
        cfg = self.config
        return cfg.bagging_freq > 0 and (
            cfg.bagging_fraction < 1.0 or cfg.pos_bagging_fraction < 1.0
            or cfg.neg_bagging_fraction < 1.0)

    def _bagging_w(self, it, seed=None) -> jax.Array:
        """In-graph bagging mask (gbdt.cpp:230-264 Bagging): the draw is
        keyed by the iteration's refresh epoch ``(it // freq) * freq`` so
        the mask is identical for ``bagging_freq`` consecutive iterations
        and identical between the per-iteration and scanned paths —
        ``it`` may be a traced scan index (the GOSS pattern).  Redrawing
        per iteration instead of caching costs one [N] uniform + compare,
        noise next to a histogram pass.  ``seed`` (optional, possibly a
        traced int32) overrides ``cfg.bagging_seed`` — the fleet trainer's
        per-member stream; PRNGKey on a traced seed stays in-graph."""
        cfg = self.config
        n = self.num_data
        epoch = (it // cfg.bagging_freq) * cfg.bagging_freq
        key = jax.random.fold_in(jax.random.PRNGKey(
            cfg.bagging_seed if seed is None else seed), epoch)
        if self._pc > 1 and self._dist != "feature":
            # per-host independent draws (the reference seeds its bagging
            # RNG per rank the same way, gbdt.cpp bagging_rand_).
            # feature-parallel replicates the rows, so every process MUST
            # draw the SAME mask or the pod's split statistics diverge.
            key = jax.random.fold_in(key, jax.process_index())
        u = jax.random.uniform(key, (n,))
        pos_f, neg_f = cfg.pos_bagging_fraction, cfg.neg_bagging_fraction
        if (pos_f < 1.0 or neg_f < 1.0) and self.objective is not None \
                and self.objective.name == "binary":
            lbl = jnp.asarray(
                np.asarray(self.train_set.metadata.label) > 0)
            mask = jnp.where(lbl, u < pos_f, u < neg_f)
        else:
            mask = u < cfg.bagging_fraction
        return mask.astype(jnp.float32)

    def _goss_vals(self, g: jax.Array, h: jax.Array,
                   it: Optional[jax.Array] = None,
                   seed=None) -> jax.Array:
        """GOSS (goss.hpp:20-188): keep top_rate by |grad|, sample
        other_rate of the rest, amplify their weight.  ``it`` may be a
        traced iteration index (the scan); defaults to the host
        counter so both paths draw identical per-iteration keys.
        ``seed`` (optional, possibly traced) overrides
        ``cfg.bagging_seed`` — the fleet trainer's per-member stream."""
        cfg = self.config
        multi = self._pc > 1 and self._global_counts is not None
        if multi:
            # GLOBAL semantics under multi-process data-parallel
            # (goss.hpp samples over the full data): the threshold is the
            # global top_k-th |g|h and the Bernoulli draw is keyed by the
            # row's GLOBAL index, so any process topology trains the same
            # trees as a single process over the concatenated rows.
            pidx = jax.process_index()
            n = int(self._global_counts.sum())
            offset = int(self._global_counts[:pidx].sum())
        else:
            n = self.num_data
            offset = 0
        top_k = max(1, int(n * cfg.top_rate))
        other_k = max(1, int(n * cfg.other_rate))
        amp = (1.0 - cfg.top_rate) / cfg.other_rate
        absg = jnp.abs(g) * h
        if multi:
            # the global top-k all lie inside the per-process local top-k:
            # allgather each process's top min(k, local_n) candidates and
            # take the k-th of the merged set
            from jax.experimental import multihost_utils
            cand = int(min(top_k, self.num_data))
            local_top = np.full(top_k, -np.inf, np.float32)
            local_top[:cand] = np.asarray(
                jax.lax.top_k(absg, cand)[0], np.float32)
            allc = np.asarray(multihost_utils.process_allgather(local_top))
            thresh = jnp.float32(np.partition(allc.ravel(), -top_k)[-top_k])
        else:
            thresh = -jnp.sort(-absg)[top_k - 1]
        is_top = absg >= thresh
        if it is None:
            it = self.iter_ + self._iter_rng_offset
        key = jax.random.PRNGKey(
            (cfg.bagging_seed if seed is None else seed) + it)
        if self._pc > 1 and not multi and self._dist != "feature":
            # multi-process WITHOUT the mesh data-parallel bookkeeping
            # (caller-supplied hist_reduce hook): keep per-rank independent
            # draws, matching _bagging_mask's fold-in.  feature-parallel
            # replicates the rows — identical draws on every process, so
            # the single-process sampling IS already global
            key = jax.random.fold_in(key, jax.process_index())
        u = jax.random.uniform(key, (n,))[offset:offset + self.num_data]
        p_other = other_k / jnp.maximum(n - top_k, 1)
        is_other = (~is_top) & (u < p_other)
        w = jnp.where(is_top, 1.0, jnp.where(is_other, amp, 0.0))
        return w.astype(jnp.float32)

    def _feature_mask(self) -> np.ndarray:
        frac = self.config.feature_fraction
        f = self.num_features
        if frac >= 1.0:
            return np.ones(f, bool)
        k = max(1, int(round(f * frac)))
        idx = self._rng_feat.choice(f, size=k, replace=False)
        mask = np.zeros(f, bool)
        mask[idx] = True
        return mask

    # -- training ----------------------------------------------------------
    _bias_in_every_tree = False   # RF overrides: init bias folded in each tree

    def _score_for_gradients(self) -> jax.Array:
        return self.score

    def set_resume_state(self, start_iteration: int) -> None:
        """Align all iteration-keyed state with a straight run that
        already trained ``start_iteration`` iterations (snapshot
        auto-resume, engine.py): iteration-indexed RNG keys (bagging
        epochs, GOSS, extra_trees/bynode, finite-check cadence) shift by
        the offset, and the stateful feature-fraction host RNG is
        fast-forwarded by redrawing the consumed masks — so crash+resume
        trains byte-identical trees to never-crashing."""
        self._iter_rng_offset = int(start_iteration)
        if self.config.feature_fraction < 1.0:
            for _ in range(int(start_iteration)):
                self._feature_mask()

    # -- scanned multi-iteration path (one host sync per epoch) -------------
    def _fusable_config(self, serial_kind: Optional[str] = None) -> bool:
        """Whether this model/objective/sampling combination has scan-path
        semantics (independent of whether the scan is enabled) — also gates
        the f32 leaf-shrinkage in train_one_iter so toggling ``fused_chunk``
        never changes the trained model.  ``serial_kind``: the same
        question of the serial learner of that kind on one device
        (``_shrinks_in_float32``)."""
        cfg = self.config
        return (type(self) is GBDTModel
                and self.objective is not None
                and not self.objective.need_renew_tree_output
                and not self.objective.host_state_per_iter
                and self.num_class == 1
                and not cfg.linear_tree
                and (serial_kind or self._learner_kind) == "masked"
                and (self._dist is None or serial_kind is not None)
                and not self._custom_hist_reduce
                and self._forced_spec is None)

    def _shrinks_in_float32(self) -> bool:
        """Whether ``train_one_iter`` shrinks a tree's leaf values as the
        scan does, in float32: where the scan could run this booster, and
        under the feature-sharded learner where it could run the serial
        learner this configuration picks on one device, whose trees the
        feature-sharded learner's are to the byte.  The row-sharded
        learners shrink in float64 as they did."""
        return self._fusable_config(
            self._serial_kind if self._dist == "feature" else None)

    def supports_fused(self) -> bool:
        """True when whole iterations can run fused on device via
        ``lax.scan``: pure-JAX gradients -> grow -> leaf-gather score
        update, with ONE host round trip per epoch instead of ~5 per
        iteration.  Every blocking fetch drains the dispatch queue, so
        the per-iteration path idles the device ~5 times per iteration;
        the reference's cuda_exp learner syncs once per TREE
        (cuda_single_gpu_tree_learner.cpp:108-232) — this syncs once per
        EPOCH of trees.

        Active fault injection (utils/faultinject.py) forces the
        per-iteration path: host-side injection sites cannot fire inside
        a fused device program.  Path choice only — numerics are still
        governed by ``_fusable_config``, so injected and clean runs train
        identical models.  The integrity layer likewise forces the
        per-iteration path: its shadow compares and transient re-runs
        are host-driven."""
        return (self._fusable_config() and not self._faults_active()
                and self._integrity is None)

    @staticmethod
    def _faults_active() -> bool:
        from ..utils import faultinject
        return faultinject.enabled()

    def fused_reasons(self) -> List[str]:
        """Every reason ``supports_fused()`` is False, as specific
        human-readable blockers — empty when the fused path is
        eligible.  The ``reasons()`` companion of ``supports_fused()``:
        consumed by the ``train_superepoch`` error (which must name the
        exact objective/sampling/config condition that failed, not just
        point back at the predicate) and recorded by chip_smoke.py when
        a run falls to the per-iteration loop."""
        cfg = self.config
        reasons: List[str] = []
        if type(self) is not GBDTModel:
            reasons.append(
                f"boosting={cfg.boosting}: DART/RF drive the iteration "
                "loop host-side (tree weights / bias folding)")
        if self.objective is None:
            reasons.append(
                "custom objective (fobj): gradients arrive from the host "
                "every iteration")
        else:
            if self.objective.need_renew_tree_output:
                reasons.append(
                    f"objective={self.objective.name} renews leaf outputs "
                    "host-side (RenewTreeOutput)")
            if self.objective.host_state_per_iter:
                reasons.append(
                    f"objective={self.objective.name} mutates host state "
                    "every iteration")
        if self.num_class != 1:
            reasons.append(
                f"num_class={self.num_class}: multiclass grows one tree "
                "per class per iteration through the host loop")
        if cfg.linear_tree:
            reasons.append("linear_tree fits per-leaf linear models "
                           "host-side")
        if self._learner_kind != "masked":
            reasons.append(
                f"tpu_learner={self._learner_kind}: only the one-program "
                "masked grower runs inside a fused scan")
        if self._dist is not None:
            reasons.append(
                f"tree_learner={self._dist}: distributed growers "
                "re-materialize tree arrays per iteration")
        if self._custom_hist_reduce:
            reasons.append("caller-supplied hist_reduce hook")
        if self._forced_spec is not None:
            reasons.append("forced_splits need host node bookkeeping")
        if cfg.superepoch < 0 or (cfg.superepoch == 0
                                  and cfg.fused_chunk <= 1):
            reasons.append(f"fused_chunk={cfg.fused_chunk}, superepoch="
                           f"{cfg.superepoch}: no epoch size (set one > 1)")
        if self._faults_active():
            reasons.append(
                "fault injection active: host-side injection sites "
                "cannot fire inside a fused device program")
        if self._integrity is not None:
            reasons.append(
                "integrity_check_freq > 0: the computation-integrity "
                "layer's shadow compares and transient re-runs are "
                "host-driven (docs/Fault-Tolerance.md layer 7)")
        return reasons

    # -- super-epoch trainer: whole-run on-device boosting -----------------

    def _se_steps(self) -> int:
        """Static per-tree traversal budget for the in-scan valid-set
        scoring (utils/shapes.traversal_steps): the scan cannot size a
        fori_loop from a grown tree's ACTUAL depth (a traced value), so
        every tree in the epoch walks the config-derived worst case."""
        from ..utils.shapes import traversal_steps
        cfg = self.config
        return traversal_steps(cfg.max_depth,
                               self._leaf_pad or max(cfg.num_leaves, 2))

    def _se_valid_dev(self, vi: int) -> Tuple[jax.Array, jax.Array]:
        """Device (label, weight) operands of valid set ``vi``, padded to
        its bucketed score length — pad rows carry weight 0 so the traced
        weighted metrics reduce them away exactly."""
        cached = self._se_valid_cache.get(vi)
        if cached is not None:
            return cached
        vds, _, vscore = self.valid_sets[vi]
        rows, nv = vscore.shape[0], vds.num_data
        lbl = np.zeros(rows, np.float32)
        lbl[:nv] = np.asarray(vds.metadata.label, np.float32).reshape(-1)
        w = np.zeros(rows, np.float32)
        if vds.metadata.weight is not None:
            w[:nv] = np.asarray(vds.metadata.weight,
                                np.float32).reshape(-1)
        else:
            w[:nv] = 1.0
        out = (jnp.asarray(lbl), jnp.asarray(w))
        self._se_valid_cache[vi] = out
        return out

    def _teval_fn(self, eval_spec):
        """The shared traced-eval program for ``eval_spec`` (model-level
        cache; metrics.build_traced_eval).  Both the super-epoch replay
        rows and Booster.eval_valid_traced report through THIS program,
        which is what makes their values bit-identical."""
        key = ("teval", tuple(eval_spec))
        fn = self._fused_cache.get(key)
        if fn is None:
            from ..metrics import build_traced_eval
            fn = build_traced_eval(tuple(eval_spec), self.config)
            self._fused_cache[key] = fn
        return fn

    def _obj_array_attrs(self):
        """Partition the live objective's attributes into (array attr
        names, array values, scalar key parts) so the super-epoch program
        can bake a data-free objective template and receive the arrays as
        ARGUMENTS (process-level program sharing).  Returns None when an
        attribute defies classification — the caller then falls back to a
        private jit that closes over the objective whole."""
        names: List[str] = []
        vals: List[jax.Array] = []
        scal: List[Tuple[str, str]] = []
        for name in sorted(vars(self.objective)):
            if name == "config" or name in self.objective.host_only_attrs:
                continue    # config: keyed via Config.to_dict already
            v = getattr(self.objective, name)
            if isinstance(v, (jax.Array, np.ndarray)):
                names.append(name)
                vals.append(jnp.asarray(v))
            elif v is None or isinstance(v, (bool, int, float, str)):
                scal.append((name, repr(v)))
            elif isinstance(v, tuple) and all(
                    isinstance(t, (bool, int, float, str)) for t in v):
                scal.append((name, repr(v)))
            else:
                return None
        return tuple(names), tuple(vals), tuple(scal)

    def _superepoch_key(self, eval_spec, es_spec, obj_parts):
        """Process-level sharing key for the super-epoch program, or None
        when this model's state cannot ride as arguments (private jit in
        ``self._fused_cache`` instead).  ``num_leaves`` is deliberately
        REPLACED by the effective super-step width when the leaf budget
        is padded: with ``padded_leaves`` the budget is a traced argument
        and the only structural residue of ``num_leaves`` is the grower's
        K = min(split_batch, num_leaves - 1) — so a 31/63 leaf sweep at
        split_batch <= 30 shares ONE compiled scan (the check_retraces.py
        ``superepoch`` scenario pins exactly that)."""
        cfg = self.config
        if obj_parts is None:
            return None
        if (self._use_efb or self.efb_maps is not None
                or self._ic_grow is not None
                or self._cegb_state is not None
                or self._mono is not None or self._inter is not None
                or self._feature_contri is not None or self._pc > 1):
            return None
        if self._goss or self._bagging_active:
            return None     # sampling bakes bound methods (model state)
        from ..sparse_data import SparseBinned
        if isinstance(self.binned_dev, SparseBinned) or any(
                not isinstance(vb, jax.Array)
                for _, vb, _ in self.valid_sets):
            return None
        cfg_items = tuple(sorted(
            (k, repr(v)) for k, v in cfg.to_dict().items()
            if k != "num_leaves" or self._leaf_pad is None))
        k_eff = max(1, min(self._split_batch, cfg.num_leaves - 1)) \
            if cfg.num_leaves > 1 else 1
        names, _, scal = obj_parts
        return (cfg_items, k_eff, self._split_batch, self._block_rows,
                self._leaf_pad, self._hist_overlap, self._learner_kind,
                self._se_steps(), float(self.learning_rate), self.max_bin,
                type(self.objective).__name__, names, scal,
                len(self.valid_sets), tuple(eval_spec), repr(es_spec))

    def _build_superepoch_body(self, eval_spec, es_spec, obj_parts,
                               member_args=False):
        """Build the UNJITTED super-epoch scan body: ONE ``lax.scan``
        over k FULL boosting iterations — gradients, grow, score update,
        valid-set traversal+scoring, traced metric eval, early-stop vote
        — with zero host syncs inside.  The per-iteration tree math is
        ``train_one_iter``'s (same RNG streams: feature masks are
        pre-drawn host-side, GOSS keys are seeded by iteration index
        in-graph; same finite-guard policies), followed by the traced
        eval tail, which is empty without valid sets; model data arrays
        ride as arguments so no dataset is baked into the executable and
        keyable configs share the compile process-wide (``_SE_CACHE``).

        ``member_args=True`` is the fleet trainer's form: the trailing
        ``mrng = (learning_rate, sampling_seed, quant_seed)`` operand
        replaces the corresponding baked constants so the SAME body can
        be ``jax.vmap``-ped over a member axis (fleet/trainer.py) with
        per-member streams.  Feeding a value as an argument instead of a
        closure constant does not change a single emitted arithmetic op,
        which is what keeps fleet members byte-identical to solo runs."""
        from ..metrics import traced_metric_fn
        from ..obs.flops import (eval_flops_bytes, note_traced,
                                 score_update_flops_bytes)

        cfg = self.config
        grow = make_grower(
            num_leaves=cfg.num_leaves, num_bins=self.max_bin,
            params=self.split_params, max_depth=cfg.max_depth,
            block_rows=self._block_rows,
            efb=self.efb_dev if self._use_efb else None,
            gain_scale=self._feature_contri,
            extra_trees=self._extra_trees, extra_seed=cfg.extra_seed,
            split_batch=self._split_batch,
            hist_overlap=self._hist_overlap,
            mono=self._mono if self._learner_kind == "masked" else None,
            mono_penalty=cfg.monotone_penalty,
            interaction_groups=self._inter,
            bynode_frac=cfg.feature_fraction_bynode,
            bynode_seed=cfg.feature_fraction_seed + 1,
            cegb=self._cegb_state,
            padded_leaves=self._leaf_pad,
            quant=self._quant,
            # the fleet runs this body under vmap over its members
            vmapped=member_args,
            jit=False)
        if obj_parts is not None:
            arr_names = obj_parts[0]
            obj_template = copy.copy(self.objective)
            for nm in arr_names:
                setattr(obj_template, nm, None)   # arrays ride as args
        else:
            arr_names = ()
            obj_template = self.objective      # private jit: close over
        lr = jnp.float32(self.learning_rate)
        use_goss = self._goss
        use_bag = self._bagging_active and not use_goss
        # bound methods hold the model alive — only bake them when the
        # sampling mode actually uses them (sampling also excludes the
        # model from _SE_CACHE sharing, so a baked method never leaks
        # into another model's program)
        goss_vals = self._goss_vals if use_goss else None
        bagging_w = self._bagging_w if use_bag else None
        rng_iter_kw = (self._extra_trees or self._bynode_masked
                       or self._quant is not None)
        use_quant_seed = member_args and self._quant is not None
        ic = self._ic_grow
        fin_freq = cfg.finite_check_freq
        fin_policy = cfg.finite_check_policy
        use_cegb = self._cegb_state is not None
        nf = self.num_features
        leaf_padded = self._leaf_pad is not None
        steps = self._se_steps()
        efb_maps = self.efb_maps
        n_rows = self.num_data
        grower_follows = self._grower_follows

        # eval plumbing: one traced metric per (valid set, metric) entry,
        # in booster.eval_valid() order.  The in-scan eval exists ONLY
        # to drive the early-stop vote (callback.early_stopping's
        # update-then-check at min_delta == 0): reported values are
        # recomputed post-scan through the shared teval program
        # (metrics.build_traced_eval) from the stacked per-iteration
        # valid scores the scan emits, because a reduction fused INTO
        # the scan body may round the last ulp differently than the
        # standalone program — bit-identity with the per-iteration
        # fused_eval path requires the same program shape
        n_entries = len(eval_spec)
        vote_eval = es_spec is not None and n_entries > 0
        metric_idx = tuple(
            (vi, traced_metric_fn(mname, cfg))
            for (vi, _sname, mname, _hib) in eval_spec) if vote_eval \
            else ()
        if es_spec is not None:
            es_rounds = int(es_spec["stopping_rounds"])
            es_elig = jnp.asarray(np.asarray(es_spec["eligible"], bool))
            es_hib = jnp.asarray(
                np.asarray([hib for (_, _, _, hib) in eval_spec], bool))

        # the scan body assembles the objective from the array arguments
        # (process-level program sharing keeps data out of the closure)
        def sepoch_body(score, vscores, es_state, fmasks, iters, eiters,
                        cuse0, ml, binned, nb, na, na_bin, obj_arrs,
                        valid_ops, mrng=None):
            if member_args:
                lr_, samp_seed, q_seed = mrng
            else:
                lr_, samp_seed, q_seed = lr, None, None
            obj = copy.copy(obj_template)
            for nm, arr in zip(arr_names, obj_arrs):
                setattr(obj, nm, arr)
            followers = _followers(grower_follows, cfg.linear_tree,
                                   [op[0] for op in valid_ops])

            def one_iter(carry, xs):
                score, vsc, esb, esi, esh, stop, dead, cuse, ml = carry
                fmask, it, eit = xs
                blocked = dead | stop
                with jax.named_scope("lgbtpu.grad"):
                    g, h = obj.get_gradients(score[:, 0])
                if fin_freq > 0 and fin_policy == "clamp":
                    # clamp is sync-free, so it applies every iteration
                    g = jnp.nan_to_num(g, nan=0.0, posinf=_FINITE_CLAMP,
                                       neginf=-_FINITE_CLAMP)
                    h = jnp.nan_to_num(h, nan=0.0, posinf=_FINITE_CLAMP,
                                       neginf=0.0)
                with jax.named_scope("lgbtpu.sample"):
                    if use_goss:
                        w = goss_vals(g, h, it, seed=samp_seed)
                    elif use_bag:
                        w = bagging_w(it, seed=samp_seed)
                    else:
                        w = jnp.ones_like(g)
                    vals = jnp.stack([g * w, h * w, w], axis=1)
                kw = {"is_cat": ic} if ic is not None else {}
                if rng_iter_kw:
                    # quant: the scan's iteration index keys the
                    # stochastic-rounding stream, so scanned and per-iter
                    # paths quantize identically
                    kw["rng_iter"] = it
                if use_quant_seed:
                    kw["quant_seed"] = q_seed
                if use_cegb:
                    kw["cegb_used"] = cuse
                if leaf_padded:
                    # the actual budget is an ARGUMENT (not a baked
                    # constant) so the HLO is identical across a
                    # num_leaves bucket
                    kw["max_leaves"] = ml
                if followers is not None:
                    arrays, vleaves = grow(binned, vals, fmask, nb, na,
                                           followers=followers, **kw)
                else:
                    arrays = grow(binned, vals, fmask, nb, na, **kw)
                if use_cegb:
                    # fold this tree's split features into the CEGB
                    # cross-tree used set for the next scan iteration
                    node_on = (jnp.arange(arrays.split_feature.shape[0])
                               < arrays.num_leaves - 1)
                    marks = jnp.zeros(nf, jnp.int32) \
                        .at[arrays.split_feature].add(
                            node_on.astype(jnp.int32))
                    cuse = cuse | (marks > 0)
                if fin_freq > 0 and fin_policy == "clamp":
                    # clamp BEFORE shrinkage, exactly where the per-iter
                    # path clamps its host leaf_values — an inf leaf must
                    # become ±bound*lr on both paths
                    lv = jnp.nan_to_num(
                        arrays.leaf_value, nan=0.0, posinf=_FINITE_CLAMP,
                        neginf=-_FINITE_CLAMP) * lr_
                else:
                    lv = arrays.leaf_value * lr_
                # finite guard: ONE fused isfinite reduction over
                # grad/hess and the new tree's leaf outputs at check
                # iterations; the per-iteration flag ships with the tree
                # records, so the epoch still costs a single host sync
                # (the policy engages host-side in _se_ingest)
                if fin_freq > 0 and fin_policy != "clamp":
                    check_now = ((it + 1) % fin_freq) == 0
                    fin = (jnp.isfinite(g).all() & jnp.isfinite(h).all()
                           & jnp.isfinite(lv).all())
                    bad = check_now & ~fin
                else:
                    bad = jnp.bool_(False)
                # per-iteration semantics stop training at the FIRST
                # no-split tree (gbdt.cpp "no more leaves..."); once dead,
                # later scan iterations must contribute nothing, even if a
                # different feature mask could have split (the host loop
                # discards their tree records)
                ok = jnp.where(blocked | bad, 0.0,
                               (arrays.num_leaves > 1)
                               .astype(jnp.float32))
                if fin_freq > 0 and fin_policy == "raise":
                    # halt at the first tripped check: later iterations
                    # contribute nothing, so the host can raise at the
                    # flagged iteration with a consistent score/model
                    dead = dead | (arrays.num_leaves <= 1) | bad
                else:
                    # skip_iter: the flagged iteration contributes a zero
                    # stump; a NaN-induced natural stump must NOT end
                    # training
                    dead = dead | ((arrays.num_leaves <= 1) & ~bad)
                note_traced("score",
                            *score_update_flops_bytes(score.shape[0]),
                            phase="score", cadence="iter")
                with jax.named_scope("lgbtpu.score"):
                    delta = jnp.where(ok > 0.0,
                                      jnp.take(lv, arrays.leaf_of_row), 0.0)
                    score = score.at[:, 0].add(delta)
                if fin_freq > 0 and fin_policy == "skip_iter":
                    # a tripped check heals the score carry too: a NaN
                    # that slipped in at an UNCHECKED iteration (freq>1)
                    # would otherwise re-poison every later gradient and
                    # the guard would skip forever
                    score = jnp.where(bad, jnp.nan_to_num(
                        score, nan=0.0, posinf=_FINITE_CLAMP,
                        neginf=-_FINITE_CLAMP), score)
                # valid-set scoring, by the per-iteration path's rule
                # (_followers): the held-out leaves come out of the
                # grower with the tree, or from the same traversal the
                # per-iteration path runs (predict_device add_tree_score
                # at weight 1.0 == plain gather-add), under ONE static
                # step budget so every tree of the epoch shares the trace
                new_vsc = []
                for vi2 in range(len(valid_ops)):
                    if followers is not None:
                        leaf = vleaves[vi2]
                    else:
                        leaf = traverse_tree_binned(
                            valid_ops[vi2][0], arrays.split_feature,
                            arrays.threshold_bin, arrays.default_left,
                            arrays.left_child, arrays.right_child, na_bin,
                            arrays.is_cat_node, arrays.cat_rank, efb_maps,
                            steps=steps)
                    with jax.named_scope("lgbtpu.score"):
                        vd = jnp.where(ok > 0.0, jnp.take(lv, leaf), 0.0)
                        new_vsc.append(vsc[vi2].at[:, 0].add(vd))
                vsc = tuple(new_vsc)
                # early-stop vote (callback.early_stopping traced form,
                # min_delta == 0): update-then-check exactly like the
                # host closure — non-eligible entries (training set /
                # first_metric_only filter) still update their best.
                # The vote's in-scan metric values may differ from the
                # reported teval values in the last ulp (fusion-order);
                # engine.train heals vote/replay disagreement either
                # way (drop_iterations / clear_es_stop), so the vote is
                # a work-bound, never the source of truth
                if vote_eval:
                    note_traced("fused_eval",
                                *eval_flops_bytes(n_rows, n_entries),
                                phase="eval", cadence="iter")
                    with jax.named_scope("lgbtpu.eval"):
                        ev = jnp.stack([
                            fn_m(vsc[vi2][:, 0], valid_ops[vi2][1],
                                 valid_ops[vi2][2])
                            for (vi2, fn_m) in metric_idx])
                    fin2 = jnp.isfinite(ev)
                    cmp2 = jnp.where(es_hib, ev > esb, ev < esb)
                    improved = fin2 & (~esh | cmp2) & ~blocked
                    esb = jnp.where(improved, ev, esb)
                    esi = jnp.where(improved, eit, esi)
                    esh = esh | improved
                    trip = (es_elig & ((eit - esi) >= es_rounds)
                            & ~blocked)
                    stop = stop | trip.any()
                # keep the scan outputs tree-sized: drop the [N] row->leaf
                # vector, ship shrunk leaf values
                out = arrays._replace(
                    leaf_of_row=jnp.zeros((), jnp.int32), leaf_value=lv)
                return ((score, vsc, esb, esi, esh, stop, dead, cuse,
                         ml), (out, bad, stop,
                               tuple(v[:, 0] for v in vsc)))

            esb, esi, esh, stop = es_state
            carry0 = (score, vscores, esb, esi, esh, stop,
                      jnp.bool_(False), cuse0, ml)
            (score, vscores, esb, esi, esh, stop, _, _, _), \
                (out, bad, stops, vstack) = jax.lax.scan(
                    one_iter, carry0, (fmasks, iters, eiters))
            return (score, vscores, (esb, esi, esh, stop), out, bad,
                    stops, vstack)

        return sepoch_body

    def _build_superepoch(self, eval_spec, es_spec, obj_parts):
        """Compile the (solo) super-epoch program: the scan body from
        ``_build_superepoch_body`` under one jit with donated carries."""
        import functools
        from ..utils.compile_cache import trace_event
        body = self._build_superepoch_body(eval_spec, es_spec, obj_parts)

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def sepoch(score, vscores, es_state, fmasks, iters, eiters,
                   cuse0, ml, binned, nb, na, na_bin, obj_arrs,
                   valid_ops):
            trace_event("superepoch")
            return body(score, vscores, es_state, fmasks, iters, eiters,
                        cuse0, ml, binned, nb, na, na_bin, obj_arrs,
                        valid_ops)

        return sepoch

    def build_fleet_superepoch(self, eval_spec, es_spec, obj_parts):
        """Compile the FLEET super-epoch program (fleet/trainer.py): the
        same scan body as ``_build_superepoch``, ``jax.vmap``-ped over a
        leading member axis of every member-varying operand — scores,
        valid scores, ES state, feature masks, iteration indices, leaf
        budgets, and the per-member ``(lr, sampling seed, quant seed)``
        stream block — while the binned matrix, NA table, objective
        arrays and valid-set operands stay shared (in_axes=None).  N
        forests grow inside ONE compiled program with ONE trace
        (``fleet_superepoch``); per-member early-stop flags mask (not
        branch) finished members, so lanes at different progress points
        coexist without retracing."""
        import functools
        from ..utils.compile_cache import trace_event
        body = self._build_superepoch_body(eval_spec, es_spec, obj_parts,
                                           member_args=True)
        vbody = jax.vmap(body, in_axes=(0, 0, 0, 0, 0, 0, None, 0,
                                        None, None, None, None, None,
                                        None, 0))

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def fleet_sepoch(score, vscores, es_state, fmasks, iters,
                         eiters, cuse0, ml, binned, nb, na, na_bin,
                         obj_arrs, valid_ops, mrng):
            trace_event("fleet_superepoch")
            return vbody(score, vscores, es_state, fmasks, iters,
                         eiters, cuse0, ml, binned, nb, na, na_bin,
                         obj_arrs, valid_ops, mrng)

        return fleet_sepoch

    def fleet_superepoch_fn(self, eval_spec, es_spec, obj_parts,
                            n_members: int):
        """The FLEET super-epoch program with process-level sharing
        (fleet/trainer.py): same ``_SE_CACHE`` discipline as the solo
        path, keyed by the solo sharing key plus the member count — a
        warmed-up process redispatches the same fleet shape without
        recompiling.  Unkeyable state (bagging/GOSS bound methods etc.)
        falls back to this model's private ``_fused_cache``."""
        key = self._superepoch_key(eval_spec, es_spec, obj_parts)
        if key is not None:
            key = ("fleet", int(n_members)) + key
            with _SE_CACHE_LOCK:
                fn = _SE_CACHE.get(key)
                if fn is not None:
                    _SE_CACHE.move_to_end(key)
            if fn is None:
                fn = self.build_fleet_superepoch(eval_spec, es_spec,
                                                 obj_parts)
                with _SE_CACHE_LOCK:
                    _SE_CACHE[key] = fn
                    while len(_SE_CACHE) > _SE_CACHE_MAX:
                        _SE_CACHE.popitem(last=False)
            return fn
        pk = ("fleet_superepoch", int(n_members), tuple(eval_spec),
              repr(es_spec))
        fn = self._fused_cache.get(pk)
        if fn is None:
            fn = self.build_fleet_superepoch(eval_spec, es_spec,
                                             obj_parts)
            self._fused_cache[pk] = fn
        return fn

    def train_superepoch(self, k: int, es_it0: int, eval_spec=(),
                         es_spec=None) -> dict:
        """Run ``k`` FULL boosting iterations — grow, score update,
        valid-set scoring, traced metric eval and the early-stop vote —
        as ONE device program with exactly ONE host fetch (stacked tree
        records + finite-guard flags + the [k, E] eval block + per-
        iteration stop flags).  ``engine.train`` replays the fetched
        block through the real host callbacks afterwards, so
        ``record_evals``/``early_stopping``/``best_iteration`` are
        byte-identical to the per-iteration path.

        ``es_it0`` is the absolute ``env.iteration`` of the epoch's
        first row (the PR 9 absolute best_iteration contract —
        resume-correct); ``eval_spec`` is a tuple of
        ``(valid_idx, set_name, metric_name, higher_better)`` entries in
        ``booster.eval_valid()`` order; ``es_spec`` (optional) is
        ``{"stopping_rounds", "first_metric_only", "eligible"}`` for the
        traced vote (scalar ``min_delta == 0`` only — engine gates).

        Returns ``{"evals": f32 [done, E], "done": int, "stump": bool,
        "stop_row": Optional[int]}``."""
        cfg = self.config
        start_iter = self.iter_
        init0, _sp = self._se_begin(k, len(eval_spec))
        obs = self._obs
        obj_parts = self._obj_array_attrs()
        key = self._superepoch_key(eval_spec, es_spec, obj_parts)
        fn = None
        if key is not None:
            with _SE_CACHE_LOCK:
                fn = _SE_CACHE.get(key)
                if fn is not None:
                    _SE_CACHE.move_to_end(key)
            if fn is None:
                fn = self._build_superepoch(eval_spec, es_spec, obj_parts)
                with _SE_CACHE_LOCK:
                    _SE_CACHE[key] = fn
                    while len(_SE_CACHE) > _SE_CACHE_MAX:
                        _SE_CACHE.popitem(last=False)
        else:
            pk = ("superepoch", tuple(eval_spec), repr(es_spec))
            fn = self._fused_cache.get(pk)
            if fn is None:
                fn = self._build_superepoch(eval_spec, es_spec, obj_parts)
                self._fused_cache[pk] = fn

        (fmasks, iters, eiters, cuse0, es_state, vscores,
         valid_ops) = self._se_operands(k, es_it0, len(eval_spec))
        obj_arrs = obj_parts[1] if obj_parts is not None else ()
        (self.score, new_vsc, es_out, stacked, bad_flags, stops_dev,
         vstack) = fn(self.score, vscores, es_state, fmasks, iters,
                      eiters, cuse0, jnp.int32(cfg.num_leaves),
                      self.binned_dev, self._nb_grow, self._na_grow,
                      self.na_bin_dev, obj_arrs, valid_ops)
        self._se_absorb(new_vsc, es_out)
        ev_dev = self._se_eval_block(vstack, eval_spec, k)
        # the one sync per super-epoch (tree records + finite-guard
        # flags + eval block + stop flags)
        host, bad_host, ev_host, stops_np = self._eget(
            (stacked, bad_flags, ev_dev, stops_dev), "fused_fetch")
        if obs is not None:
            _sp.end()
            if obs.profiler is not None:
                obs.profiler.on_iter_end(start_iter + k - 1)
        return self._se_ingest(host, stacked, bad_host, stops_np,
                               ev_host, k, start_iter, init0,
                               len(eval_spec))

    def _se_begin(self, k: int, n_entries: int):
        """Super-epoch prologue (shared with fleet/trainer.py): peer
        liveness, fusability guard, the first-iteration
        boost_from_average bias applied to train AND valid scores, and
        the obs span.  Returns ``(init0, span_or_None)``."""
        if self._elastic is not None:
            self._elastic.check_peers()
        if not self._fusable_config():
            raise ValueError(
                "train_superepoch: config not fusable: "
                + "; ".join(r for r in self.fused_reasons()
                            if not r.startswith("fused_chunk=")))
        cfg = self.config
        start_iter = self.iter_
        init0 = 0.0
        if start_iter == 0 and self.objective is not None \
                and cfg.boost_from_average and not self._init_applied:
            init0 = self._boost_from_score(0)
            self._init_scores = [init0]
            if init0 != 0.0:
                self.score = self.score + jnp.float32(init0)
                # valid scores carry the same bias (train_one_iter's
                # boost_from path does this per-set too)
                for vi in range(len(self.valid_sets)):
                    vds, vb, vs = self.valid_sets[vi]
                    self.valid_sets[vi] = (vds, vb,
                                           vs + jnp.float32(init0))
        obs = self._obs
        _sp = None
        if obs is not None:
            obs.activate()
            _sp = obs.span("train_superepoch", mirror=False, n_iters=k,
                           iteration=start_iter, n_evals=n_entries)
            if obs.profiler is not None:
                for it in range(start_iter, start_iter + k):
                    obs.profiler.on_iter_begin(it)
        return init0, _sp

    def _se_operands(self, k: int, es_it0: int, n_entries: int):
        """The epoch's device operands (shared with fleet/trainer.py).
        Draws the k stateful feature-fraction masks — call EXACTLY once
        per dispatched epoch, in member order, or the host RNG stream
        diverges from the solo run."""
        cfg = self.config
        if cfg.feature_fraction < 1.0:
            fmasks = jnp.asarray(
                np.stack([self._feature_mask() for _ in range(k)]))
        else:
            fmasks = jnp.ones((k, self.num_features), bool)
        it0 = self.iter_ + self._iter_rng_offset
        iters = jnp.arange(it0, it0 + k, dtype=jnp.int32)
        eiters = jnp.arange(es_it0, es_it0 + k, dtype=jnp.int32)
        cuse0 = jnp.asarray(self._cegb_state.used) \
            if self._cegb_state is not None \
            else jnp.zeros(1, bool)
        es_state = self._es_dev
        if es_state is None:
            es_state = (jnp.zeros(n_entries, jnp.float32),
                        jnp.zeros(n_entries, jnp.int32),
                        jnp.zeros(n_entries, bool),
                        jnp.bool_(False))
        vscores = tuple(vs for _, _, vs in self.valid_sets)
        valid_ops = tuple(
            (self.valid_sets[vi][1],) + self._se_valid_dev(vi)
            for vi in range(len(self.valid_sets)))
        return (fmasks, iters, eiters, cuse0, es_state, vscores,
                valid_ops)

    def _se_absorb(self, new_vsc, es_out) -> None:
        """Store the epoch's updated valid scores + ES vote state."""
        for vi in range(len(self.valid_sets)):
            vds, vb, _ = self.valid_sets[vi]
            self.valid_sets[vi] = (vds, vb, new_vsc[vi])
        self._es_dev = es_out

    def _se_eval_block(self, vstack, eval_spec, k: int, teval=None):
        """Reported eval values: the SAME jitted program the
        per-iteration fused_eval path runs (metrics.build_traced_eval),
        applied to each iteration's stacked valid-score row — in-scan
        reductions can fuse (and round the last ulp) differently than
        the standalone program, so re-evaluating through the shared
        program is what makes super-epoch record_evals bit-identical to
        per-iteration.  The k dispatches are async; no host sync here.
        ``teval`` (optional) supplies the program — the fleet trainer
        passes member 0's so ALL members report through ONE trace."""
        if not len(eval_spec):
            return jnp.zeros((k, 0), jnp.float32)
        if teval is None:
            teval = self._teval_fn(eval_spec)
        t_ops = tuple(self._se_valid_dev(vi)
                      for vi in range(len(self.valid_sets)))
        return jnp.stack([
            teval(tuple(vstack[vi][j]
                        for vi in range(len(vstack))), t_ops)
            for j in range(k)])

    def _se_ingest(self, host, stacked, bad_host, stops_np, ev_host,
                   k: int, start_iter: int, init0: float,
                   n_entries: int) -> dict:
        """Replay the fetched epoch block into host/device tree state:
        one ``Tree.from_arrays`` + ``_DeviceTree`` per row, finite-guard
        stub handling, CEGB feature marking, and the obs/bbox epoch
        accounting.  Shared with fleet/trainer.py, which slices each
        member's rows out of the [N, k, ...] fleet fetch and ingests
        them through this exact path."""
        cfg = self.config
        obs = self._obs
        E = n_entries
        it0 = start_iter + self._iter_rng_offset
        lr = self.learning_rate
        stopped = False
        stop_row = None
        for j in range(k):
            tj = TreeArrays(*(np.asarray(fld[j]) for fld in host))
            nl = int(tj.num_leaves)
            if bool(bad_host[j]):
                from ..utils.log import Log
                msg = ("non-finite gradient/hessian or leaf output "
                       f"detected at iteration {it0 + j + 1} "
                       f"(finite_check_freq={cfg.finite_check_freq})")
                if self._bbox is not None:
                    self._bbox.record(event="finite_check_trip",
                                      iteration=it0 + j + 1,
                                      policy=cfg.finite_check_policy,
                                      fused=True)
                    self._bbox.dump("finite_check")
                if cfg.finite_check_policy == "raise":
                    from ..basic import LightGBMError
                    raise LightGBMError(
                        msg + "; aborting (finite_check_policy=raise)")
                # skip_iter: the iteration already contributed nothing
                # in-graph; record a zero stump so iteration counts and
                # model text match the per-iteration path exactly
                Log.warning(msg + "; iteration contributes nothing "
                                  "(finite_check_policy=skip_iter)")
                self.step_counts.append(int(tj.n_steps))
                self._note_contracted(tj.rung_steps)
                ht = Tree(1)
                ht.shrinkage = lr
                ht.leaf_value = np.asarray(
                    [init0 if (start_iter == 0 and j == 0) else 0.0],
                    np.float64)
                self.models.append(ht)
                dev_arrays = TreeArrays(*(fld[j] for fld in stacked))
                self.device_trees.append(_DeviceTree(
                    dev_arrays, jnp.zeros_like(dev_arrays.leaf_value),
                    1))
                self.tree_weights.append(1.0)
                self.iter_ += 1
                if bool(stops_np[j]):
                    stop_row = j
                    break
                continue
            self.step_counts.append(int(tj.n_steps))
            self._note_contracted(tj.rung_steps)
            lvj = np.asarray(tj.leaf_value, np.float64).copy()
            if self._cegb_state is not None and nl > 1:
                # mirror the in-graph CEGB used-set update on the host so
                # the NEXT epoch starts from the right cross-tree state
                self._cegb_state.used[
                    np.asarray(tj.split_feature)[:nl - 1]] = True
            if nl <= 1:
                stopped = True
                lvj[:] = 0.0
            ht = Tree.from_arrays(tj, self.train_set.used_features,
                                  self.train_set.bin_mappers)
            ht.internal_value = ht.internal_value * lr
            ht.shrinkage = lr
            bias = init0 if (start_iter == 0 and j == 0) else 0.0
            ht.leaf_value = lvj[:max(nl, 1)] + bias   # Tree::AddBias
            self.models.append(ht)

            dev_arrays = TreeArrays(*(fld[j] for fld in stacked))
            dev_lv = dev_arrays.leaf_value if nl > 1 else \
                jnp.zeros_like(dev_arrays.leaf_value)
            steps = round_up_pow2(max(ht.max_depth(), 1))
            self.device_trees.append(
                _DeviceTree(dev_arrays, dev_lv, steps))
            self.tree_weights.append(1.0)
            self.iter_ += 1
            if stopped or bool(stops_np[j]):
                if bool(stops_np[j]):
                    stop_row = j
                break
        done = self.iter_ - start_iter
        if obs is not None:
            obs.metrics.counter("train.iterations").inc(done)
            obs.metrics.counter("train.superepochs").inc()
            self._count_valid_leaves(
                self._valid_followers() is not None, done)
            for s in self.step_counts[len(self.step_counts) - done:]:
                obs.metrics.histogram("train.steps_per_tree").observe(s)
                obs.record_flops(s)
        if self._bbox is not None:
            rec = {"event": "superepoch", "iterations": done,
                   "first_iteration": start_iter + 1,
                   "n_evals": E,
                   "steps": self.step_counts[len(self.step_counts)
                                             - done:]}
            if self._flops is not None:
                fl = hb = 0
                for s in rec["steps"]:
                    f_, b_ = self._flops.per_iteration(s)
                    fl, hb = fl + f_, hb + b_
                rec["flops"], rec["hbm_bytes"] = fl, hb
            self._bbox.record(**rec)
        self._last_iter_state = None   # rollback not supported past an epoch
        return {"evals": np.asarray(ev_host, np.float32).reshape(k, E),
                "done": done, "stump": stopped, "stop_row": stop_row}

    def drop_iterations(self, n: int) -> None:
        """Host-slice the last ``n`` recorded iterations.  Super-epoch
        replay healing only: when the host callback replay stops earlier
        than the traced vote predicted (defensive — the vote consumes
        the same fetched values the replay does), training is over and
        the surplus trees must not appear in the saved model.  Scores
        are rebuilt by subtracting each dropped tree's contribution via
        device traversal (float add-then-subtract: not bit-perfect, but
        this path ends training — nothing trains on the healed score)."""
        n = int(n)
        if n <= 0:
            return
        nt = n * self.num_class
        for dt in self.device_trees[-nt:]:
            self.score = self.score.at[:, 0].add(
                -jnp.take(dt.leaf_value,
                          _tree_leaves(self.binned_dev, dt,
                                       self.na_bin_dev, self.efb_maps)))
            for vi in range(len(self.valid_sets)):
                vds, vb, vs = self.valid_sets[vi]
                vd = _apply_tree(jnp.zeros_like(vs[:, 0]), vb, dt,
                                 self.na_bin_dev, 1.0, self.efb_maps)
                self.valid_sets[vi] = (vds, vb, vs.at[:, 0].add(-vd))
        del self.models[-nt:]
        del self.device_trees[-nt:]
        del self.tree_weights[-nt:]
        del self.step_counts[-nt:]
        self.iter_ -= n
        self._last_iter_state = None

    def clear_es_stop(self) -> None:
        """Reset the traced early-stop vote's stop latch (defensive
        counterpart of drop_iterations: the vote tripped but the host
        replay did not raise — trust the host and keep training)."""
        if self._es_dev is not None:
            esb, esi, esh, _ = self._es_dev
            self._es_dev = (esb, esi, esh, jnp.bool_(False))

    def train_one_iter(self, grad: Optional[np.ndarray] = None,
                       hess: Optional[np.ndarray] = None) -> bool:
        """One boosting iteration (gbdt.cpp:371 TrainOneIter).
        Returns True if training should stop (no splits possible)."""
        if self._elastic is not None:
            # per-iteration liveness poll (parallel/elastic.py): a peer
            # whose heartbeat went stale becomes a classified
            # ElasticFailure BEFORE this iteration queues collectives
            # that would hang on the dead shard
            self._elastic.check_peers()
        cfg = self.config
        obs = self._obs
        if obs is not None:
            obs.iter_begin(self.iter_)
        bbox = self._bbox
        if bbox is not None:
            import time as _time
            t_bb0 = _time.perf_counter()
        init_scores = [0.0] * self.num_class
        if self.iter_ == 0 and self.objective is not None \
                and cfg.boost_from_average and not self._init_applied:
            # BoostFromAverage (gbdt.cpp:346): add init to train+valid
            # scorers before gradient computation; the saved tree gets the
            # bias via AddBias AFTER UpdateScore (gbdt.cpp:416-418)
            if obs is not None:
                _sp = obs.phase("init_score", self.iter_)
            for k in range(self.num_class):
                init_scores[k] = self._boost_from_score(k)
            self._init_scores = list(init_scores)
            if any(s != 0.0 for s in init_scores) and not self._bias_in_every_tree:
                bias = jnp.asarray(init_scores, jnp.float32)
                self.score = self.score + bias
                for vi, (vds, vb, vs) in enumerate(self.valid_sets):
                    self.valid_sets[vi] = (vds, vb, vs + bias)
            if obs is not None:
                obs.end_phase(_sp, self.score)
        # gradients (GBDT::Boosting, gbdt.cpp:172)
        gscore = self._score_for_gradients()
        if self._bias_in_every_tree:
            init_scores = list(getattr(self, "_init_scores", init_scores))
        if obs is not None:
            _sp = obs.phase("grad", self.iter_)
        if grad is None:
            g_all, h_all = self.objective.get_gradients(
                gscore[:, 0] if self.num_class == 1 else gscore)
        else:
            g_all = jnp.asarray(grad, jnp.float32)
            h_all = jnp.asarray(hess, jnp.float32)
        if self.num_class == 1:
            g_all = g_all.reshape(self.num_data, 1)
            h_all = h_all.reshape(self.num_data, 1)
        else:
            g_all = g_all.reshape(self.num_data, self.num_class)
            h_all = h_all.reshape(self.num_data, self.num_class)
        if obs is not None:
            obs.end_phase(_sp, (g_all, h_all))
            # row sampling and the accumuland stack, up to the grower
            _sp = obs.phase("sample", self.iter_)

        it_global = self.iter_ + self._iter_rng_offset
        # fault injection: gradient poisoning at iteration k (the
        # 'nan_grads' site's hit index IS the iteration number)
        from ..utils import faultinject
        if faultinject.enabled() and faultinject.fires("nan_grads"):
            g_all = g_all.at[0].set(jnp.nan)
            h_all = h_all.at[0].set(jnp.nan)

        # finite guard (gbdt.cpp has none; one NaN batch silently poisons
        # a million-iteration model): every finite_check_freq iterations,
        # one fused isfinite scalar over grad/hess — fetched together
        # with this iteration's leaf-output check below, so the guard
        # costs a single amortized scalar sync.  clamp is sync-free and
        # therefore applies every iteration.
        fin_freq = cfg.finite_check_freq
        fin_policy = cfg.finite_check_policy
        fin_check = fin_freq > 0 and (it_global + 1) % fin_freq == 0
        gh_ok = None
        if fin_freq > 0 and fin_policy == "clamp":
            g_all = jnp.nan_to_num(g_all, nan=0.0, posinf=_FINITE_CLAMP,
                                   neginf=-_FINITE_CLAMP)
            h_all = jnp.nan_to_num(h_all, nan=0.0, posinf=_FINITE_CLAMP,
                                   neginf=0.0)
        elif fin_check:
            gh_ok = jnp.isfinite(g_all).all() & jnp.isfinite(h_all).all()

        bag = self._bagging_w(jnp.int32(it_global)) \
            if self._bagging_active and not self._goss else None
        fmask = jnp.asarray(self._feature_mask())

        stopped = True
        heal_score = False
        iter_trees: List[Tree] = []
        iter_state = {"leaf_of_rows": [], "leaf_values": [], "trees": [],
                      "train_deltas": [], "valid_deltas": []}
        for k in range(self.num_class):
            if obs is not None and k:
                _sp = obs.phase("sample", self.iter_)
            g, h = g_all[:, k], h_all[:, k]
            if self._goss:
                w = self._goss_vals(g, h)
            elif bag is not None:
                w = bag
            else:
                w = jnp.ones(self.num_data, jnp.float32)
            vals = jnp.stack([g * w, h * w, w], axis=1)
            gkw = {}
            if self._ic_grow is not None:
                gkw["is_cat"] = self._ic_grow
            from ..grower_partitioned import PartitionedGrower
            if self._quant is not None:
                # every learner family keys the quantizer's stochastic-
                # rounding stream by the global iteration index, so
                # resume replays the exact rounding of a straight run
                gkw["rng_iter"] = jnp.int32(it_global)
            if isinstance(self.grower, PartitionedGrower):
                if self._forced_spec is not None:
                    gkw["forced"] = self._forced_spec
                if self._cegb_state is not None:
                    gkw["cegb_state"] = self._cegb_state
            else:
                if (self._extra_trees or self._bynode_masked) \
                        and self._dist is None:
                    # per-iteration extra_trees/bynode key component (the
                    # partitioned learner's host RNG advances statefully)
                    gkw["rng_iter"] = jnp.int32(it_global)
                if self._cegb_state is not None and self._dist is None:
                    # CEGB on the masked grower: cross-tree used-feature
                    # state goes in as an argument; the in-tree updates
                    # happen in-graph and are folded back below from the
                    # fetched split records
                    gkw["cegb_used"] = jnp.asarray(self._cegb_state.used)
                if self._leaf_pad is not None:
                    # leaf-padded trace: the ACTUAL budget rides in as a
                    # traced scalar (the while_loop exit bound) so one
                    # padded trace serves the whole num_leaves bucket
                    gkw["max_leaves"] = jnp.int32(cfg.num_leaves)
            vals_g = self._prep_vals(vals)
            fmask_g = self._prep_fmask(fmask)
            followers = self._valid_followers()
            if followers is not None:
                gkw["followers"] = followers
            vleaves = [None]    # the followers' leaves in the tree kept

            # the feature-sharded grower takes its columns' metadata in
            # shards and, for the partition, whole
            na_part = (self._na_part,) if self._dist == "feature" else ()

            def _run_grow(fn, keep=None):
                out = fn(self.binned_dev, vals_g, fmask_g,
                         self._nb_grow, self._na_grow, *na_part, **gkw)
                if followers is not None:
                    # the tree alone is what the callers compare and fetch
                    out, leaves = out
                    if keep is not None:
                        keep[0] = leaves
                return out

            def _grow():
                a = _run_grow(self.grower, vleaves)
                if faultinject.enabled():
                    # SDC chaos substrate (integrity.py tests/soak): one
                    # deterministic bit of the new tree's leaf-count
                    # array flips when hist_sdc fires (leaf 0: always a
                    # live slot)
                    a = a._replace(leaf_count=faultinject.maybe_bitflip(
                        "hist_sdc", a.leaf_count, index=0))
                if self._pc > 1 and self._dist is not None:
                    # multi-process: the grower returned GLOBAL arrays
                    # (tree fields replicated, leaf_of_row row-sharded).
                    # Mixing them into this process's local score/valid
                    # math would make every later eager op a
                    # cross-process collective, so re-materialize
                    # everything process-locally: tree fields via one
                    # replicated fetch, this process's leaf_of_row rows
                    # from its own addressable shards.
                    sm = a._replace(leaf_of_row=a.num_leaves)
                    host_g = self._eget(sm, "fetch")
                    a = jax.tree.map(jnp.asarray, host_g)._replace(
                        leaf_of_row=self._localize_rows(a.leaf_of_row))
                elif self._row_pad:
                    # drop padded rows before any host/score use of the
                    # row->leaf vector
                    a = a._replace(
                        leaf_of_row=a.leaf_of_row[:self.num_data])
                return a

            if obs is not None:
                obs.end_phase(_sp, vals_g)
                _sp = obs.phase("grow", self.iter_)
            arrays = _grow()
            if obs is not None:
                obs.end_phase(_sp, arrays.num_leaves)
                if not self._grower_memory_noted:
                    self._note_grower_memory(
                        obs, (self.binned_dev, vals_g, fmask_g,
                              self._nb_grow, self._na_grow) + na_part, gkw)
                _sp = obs.phase("fetch", self.iter_)
            # ONE batched host transfer of the tree-sized fields; the [N]
            # leaf_of_row stays on device (only pulled when renew/linear
            # paths need it): one sync, not one per field
            ichk = self._integrity
            check_now = False
            small = arrays._replace(leaf_of_row=arrays.num_leaves)
            if ichk is None:
                host = self._eget(small, "fetch") \
                    ._replace(leaf_of_row=arrays.leaf_of_row)
            else:
                # integrity layer (lightgbm_tpu/integrity.py): the
                # traced invariant flag — and, on check iterations, the
                # independently-jitted shadow re-execution — rides the
                # SAME consolidated fetch, so steady state gains zero
                # extra host syncs
                from .. import integrity as integrity_mod
                check_now = ichk.should_check(it_global)
                shadow_small = None
                if check_now:
                    s = _run_grow(ichk.shadow_fn)
                    shadow_small = s._replace(leaf_of_row=s.num_leaves)
                inv_dev = integrity_mod.invariant_flags(arrays)
                host_small, inv_ok, shadow_host = self._eget(
                    (small, inv_dev, shadow_small), "fetch")
                arrays, host_small = ichk.verify_grow(
                    self, it_global, _grow, _run_grow, arrays,
                    host_small, bool(inv_ok), shadow_host)
                host = host_small._replace(leaf_of_row=arrays.leaf_of_row)
            if obs is not None:
                # device_get blocks by itself; no fence needed
                obs.end_phase(_sp)
                # the host's tree out of the fetched arrays (no device
                # work: no fence)
                _sp = obs.phase("tree_host", self.iter_)
            nl = int(host.num_leaves)
            # perf observability: grower loop steps per tree (== splits
            # for strict leaf-wise; the super-step count for split_batch)
            self.step_counts.append(int(host.n_steps))
            self._note_contracted(host.rung_steps)
            if "cegb_used" in gkw and nl > 1:
                self._cegb_state.used[
                    np.asarray(host.split_feature)[:nl - 1]] = True
            leaf_values = np.asarray(host.leaf_value, np.float64).copy()
            skip_tree = False
            if fin_freq > 0 and fin_policy == "clamp":
                leaf_values = np.nan_to_num(
                    leaf_values, nan=0.0, posinf=_FINITE_CLAMP,
                    neginf=-_FINITE_CLAMP)
            elif fin_check:
                fin_ok = bool(np.isfinite(leaf_values[:max(nl, 1)]).all())
                if fin_ok and gh_ok is not None:
                    fin_ok = bool(self._eget(gh_ok, "finite_check"))
                    gh_ok = None      # the one scalar sync per check
                if not fin_ok:
                    msg = ("non-finite gradient/hessian or leaf output "
                           f"detected at iteration {it_global + 1} "
                           f"(finite_check_freq={fin_freq})")
                    if bbox is not None:
                        # the finite guard IS a flight-recorder trigger:
                        # dump the trailing ring before acting on the
                        # policy so the post-mortem survives a raise
                        bbox.record(event="finite_check_trip",
                                    iteration=it_global + 1,
                                    policy=fin_policy)
                        bbox.dump("finite_check")
                    if fin_policy == "raise":
                        from ..basic import LightGBMError
                        raise LightGBMError(
                            msg + "; aborting (finite_check_policy=raise)")
                    from ..utils.log import Log
                    Log.warning(msg + "; iteration contributes nothing "
                                      "(finite_check_policy=skip_iter)")
                    skip_tree = True
            if skip_tree:
                # the iteration contributes a zero stump; training
                # continues (a NaN-induced stump must not end the run)
                nl = 1
                host = host._replace(num_leaves=np.int32(1))
                leaf_values[:] = 0.0
                stopped = False
                heal_score = True
            elif nl <= 1:
                leaf_values[:] = 0.0  # stump contributes nothing (gbdt.cpp warn)
            else:
                stopped = False
                if self.objective is not None and \
                        self.objective.need_renew_tree_output:
                    # RenewTreeOutput (serial_tree_learner.cpp:717)
                    score_np = np.asarray(self.score[:, k])
                    leaf_values[:nl] = self.objective.renew_leaf_values(
                        score_np, np.asarray(arrays.leaf_of_row), nl,
                        leaf_values[:nl].copy())

            shrinkage = 1.0 if cfg.boosting == "rf" else self.learning_rate
            if self._shrinks_in_float32():
                # shrink with f32 semantics (an exact f64 product of f32
                # operands rounded back to f32 equals the hardware f32
                # multiply) so the scanned path, which shrinks on
                # device, yields bit-identical leaf values and scores
                leaf_values = (leaf_values
                               * np.float64(np.float32(shrinkage))
                               ).astype(np.float32).astype(np.float64)
            else:
                # DART/RF/multiclass/renew configs can never fuse; keep
                # the reference's full f64 leaf outputs
                leaf_values *= shrinkage
            # device trees carry UNBIASED values when the bias was already
            # added to the scorers (gbdt); RF folds the bias into every tree
            # (rf.hpp:137) so its device values include it too
            bias = init_scores[k] if self._bias_in_every_tree else 0.0
            dev_values = leaf_values + bias
            host_values = leaf_values + init_scores[k]  # Tree::AddBias

            # host tree (from the already-fetched host copy — from_arrays
            # never reads leaf_of_row)
            ht = Tree.from_arrays(host, self.train_set.used_features,
                                  self.train_set.bin_mappers)
            if skip_tree:
                # the stump's leaf stats came from a NaN-poisoned pass —
                # zero them so the serialized tree is clean
                ht.leaf_weight[:] = 0.0
                ht.leaf_count[:] = 0
            ht.internal_value = ht.internal_value * shrinkage
            ht.shrinkage = shrinkage
            iter_trees.append(ht)

            if obs is not None:
                obs.end_phase(_sp)
                _sp = obs.phase("score", self.iter_)
            linear = cfg.linear_tree and nl > 1
            if linear:
                # fit per-leaf linear models on bias-free leaf values, then
                # fold the init bias in afterwards (score already has it)
                ht.leaf_value = leaf_values[:max(nl, 1)].copy()
                self._fit_linear_leaves(arrays, ht, g, h, w, shrinkage, 0.0)
                lor_np = np.asarray(arrays.leaf_of_row)
                delta = jnp.asarray(self._linear_outputs(
                    ht, lor_np, self.train_set.raw_data), jnp.float32)
                self.score = self.score.at[:, k].add(delta)
                if init_scores[k] != 0.0:
                    ht.leaf_value += init_scores[k]
                    ht.leaf_const += init_scores[k]
                lv_dev = jnp.asarray(dev_values, jnp.float32)
            else:
                ht.leaf_value = host_values[:max(nl, 1)].copy()
                # score update via row->leaf gather (no traversal needed)
                lv_dev = jnp.asarray(dev_values, jnp.float32)
                delta = leaf_values_of_rows(lv_dev, arrays.leaf_of_row)
                if faultinject.enabled():
                    delta = faultinject.maybe_bitflip("score_sdc", delta)
                if check_now:
                    # covers the on-device row partition + gather that
                    # the tree-sized fetch can't see; one extra scalar
                    # sync on CHECK iterations only
                    delta = ichk.verify_score(
                        self, lv_dev, arrays.leaf_of_row, delta,
                        it_global)
                self.score = self.score.at[:, k].add(delta)
            if obs is not None:
                obs.end_phase(_sp, self.score)
                # score-update site note (obs/flops.py) — host-side
                # arithmetic only, gated so the telemetry-off path
                # stays exactly one is-None branch
                from ..obs.flops import (note_traced,
                                         score_update_flops_bytes)
                note_traced("score",
                            *score_update_flops_bytes(self.num_data),
                            phase="score", cadence="iter")
                # the device copy of the tree and its walk over every
                # valid set
                _sp = obs.phase("valid_score", self.iter_)
            iter_state["train_deltas"].append(delta)

            steps = round_up_pow2(max(ht.max_depth(), 1))
            dt = _DeviceTree(arrays, dev_values, steps)
            self.device_trees.append(dt)
            self.tree_weights.append(1.0)
            iter_state["leaf_of_rows"].append(arrays.leaf_of_row)
            iter_state["leaf_values"].append(lv_dev)
            iter_state["trees"].append(dt)

            # validation score updates (per-set deltas kept so
            # rollback_one_iter removes exactly what was added, including
            # linear-leaf outputs)
            vdeltas = []
            for vi, (vds, vbinned, vscore) in enumerate(self.valid_sets):
                if linear:
                    vleaves = np.asarray(_tree_leaves(
                        vbinned, dt, self.na_bin_dev,
                        self.efb_maps))[:vds.num_data]
                    vdelta = self._linear_outputs(ht, vleaves, vds.raw_data) \
                        - (init_scores[k] if init_scores[k] != 0.0 else 0.0)
                    vdelta = np.asarray(vdelta, np.float32)
                    if len(vscore) > vds.num_data:   # row-bucketed pad
                        vdelta = np.pad(
                            vdelta, (0, len(vscore) - vds.num_data))
                    vd = jnp.asarray(vdelta, jnp.float32)
                elif followers is not None:
                    # the grower carried the held-out rows through the
                    # tree's splits: the delta is the train score's own
                    vd = leaf_values_of_rows(lv_dev, vleaves[0][vi])
                else:
                    vd = _apply_tree(jnp.zeros_like(vscore[:, k]), vbinned,
                                     dt, self.na_bin_dev, 1.0, self.efb_maps)
                vdeltas.append(vd)
                self.valid_sets[vi] = (vds, vbinned,
                                       vscore.at[:, k].add(vd))
            iter_state["valid_deltas"].append(vdeltas)
            self._count_valid_leaves(followers is not None)
            if obs is not None:
                obs.end_phase(_sp, [vs for _, _, vs in self.valid_sets]
                              or None)

        if heal_score:
            # a tripped skip_iter check heals the score carry too: a NaN
            # that slipped in at an UNCHECKED iteration (freq>1) would
            # otherwise re-poison every later gradient and the guard
            # would skip forever (same sanitization point as the fused
            # path — the two stay byte-identical)
            self.score = jnp.nan_to_num(self.score, nan=0.0,
                                        posinf=_FINITE_CLAMP,
                                        neginf=-_FINITE_CLAMP)
        self.models.extend(iter_trees)
        self._last_iter_state = iter_state
        self.iter_ += 1
        if obs is not None:
            # all of this iteration's trees (num_class of them) count
            # toward its step/comm accounting
            obs.iter_end(self.iter_ - 1,
                         sum(self.step_counts[-self.num_class:]))
        if bbox is not None:
            # one host-side record per iteration (no device syncs: all
            # fields are values the driver already holds)
            import time as _time
            steps = sum(self.step_counts[-self.num_class:])
            rec = {"iteration": self.iter_,
                   "dur_s": round(_time.perf_counter() - t_bb0, 6),
                   "steps": steps, "stopped": stopped,
                   "skipped": heal_score}
            if self._flops is not None:
                fl, hb = self._flops.per_iteration(steps)
                rec["flops"], rec["hbm_bytes"] = fl, hb
            comm = getattr(self.grower, "comm", None)
            if comm is not None:
                rec["comm_wire_bytes"] = comm.bytes_per_iteration(steps)
            bbox.record(**rec)
        return stopped

    def rollback_one_iter(self) -> None:
        """GBDT::RollbackOneIter (gbdt.cpp:451)."""
        if self.iter_ == 0 or self._last_iter_state is None:
            if self.iter_ > 0:
                from ..utils.log import Log
                Log.warning(
                    "rollback_one_iter: no per-iteration state to roll "
                    "back (last iterations ran inside a scan; set "
                    "superepoch=-1 if rollback is needed)")
            return
        st = self._last_iter_state
        for k in range(self.num_class):
            self.score = self.score.at[:, k].add(-st["train_deltas"][k])
            for vi, (vds, vbinned, vscore) in enumerate(self.valid_sets):
                if vi < len(st["valid_deltas"][k]):
                    vscore = vscore.at[:, k].add(-st["valid_deltas"][k][vi])
                    self.valid_sets[vi] = (vds, vbinned, vscore)
        del self.models[-self.num_class:]
        del self.device_trees[-self.num_class:]
        del self.tree_weights[-self.num_class:]
        del self.step_counts[-self.num_class:]
        self.iter_ -= 1
        self._last_iter_state = None

    # -- scores ------------------------------------------------------------
    @property
    def num_iterations_trained(self) -> int:
        return self.iter_

    def train_score(self) -> np.ndarray:
        s = np.asarray(self.score)
        if self.config.boosting == "rf" and self.iter_ > 0:
            s = s / self.iter_
        return s

    def valid_score(self, i: int) -> np.ndarray:
        vds = self.valid_sets[i][0]
        # slice off the row-bucket padding (add_valid_set) before any
        # metric/consumer sees the scores
        s = np.asarray(self.valid_sets[i][2])[:vds.num_data]
        if self.config.boosting == "rf" and self.iter_ > 0:
            s = s / self.iter_
        return s


def create_boosting(config: Config, train_set: Dataset,
                    objective, hist_reduce=None, obs=None) -> GBDTModel:
    """Boosting factory (boosting.cpp:35-68 CreateBoosting analog).
    ``obs``: the telemetry session the caller opened for this booster
    (None: the model builds its own when ``telemetry`` is on)."""
    if config.boosting in ("gbdt", "gbrt"):
        return GBDTModel(config, train_set, objective, hist_reduce, obs)
    if config.boosting == "dart":
        from .dart import DARTModel
        return DARTModel(config, train_set, objective, hist_reduce, obs)
    if config.boosting in ("rf", "random_forest"):
        from .rf import RFModel
        return RFModel(config, train_set, objective, hist_reduce, obs)
    raise ValueError(f"Unknown boosting type: {config.boosting}")
