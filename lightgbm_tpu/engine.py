"""Training entry points: ``train`` and ``cv``.

Analog of the reference python-package engine
(/root/reference/python-package/lightgbm/engine.py:25 ``train``, :375 ``cv``):
parameter normalization, valid-set wiring, per-iteration callbacks, early
stopping via EarlyStopException (engine.py:252), and CVBooster aggregation.
"""

from __future__ import annotations

import collections
import copy
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import numpy as np

from . import callback as callback_mod
from .booster import Booster
from .callback import CallbackEnv, EarlyStopException
from .config import Config
from .dataset import Dataset
from .fold_rows import FoldRows


def train(params: Dict[str, Any], train_set: Dataset,
          num_boost_round: int = 100,
          valid_sets: Optional[List[Dataset]] = None,
          valid_names: Optional[List[str]] = None,
          fobj: Optional[Callable] = None,
          feval: Optional[Callable] = None,
          init_model: Optional[Union[str, Booster]] = None,
          feature_name="auto", categorical_feature="auto",
          keep_training_booster: bool = False,
          callbacks: Optional[List[Callable]] = None) -> Booster:
    """Train a gradient-boosted model (engine.py:25 analog).

    ``fobj`` sits in the reference's positional slot — between
    ``valid_names`` and ``feval`` (v3.3.2 engine.py:25), matching ``cv``
    — so reference-style positional calls bind the custom objective and
    custom metric to the right parameters.

    ``resume=true`` in ``params`` auto-resumes from the newest VALID
    snapshot of ``output_model`` (manifest params-signature + data
    fingerprint must match, snapshot.py) through this function's
    init_model path; train-straight and crash-then-resume produce
    byte-identical model text (docs/Fault-Tolerance.md).

    Under ``integrity_policy=rewind`` (lightgbm_tpu/integrity.py) a
    sticky silent-data-corruption failure rewinds here: training
    re-enters with ``resume=true``, which lands on the newest
    integrity-VERIFIED snapshot (``find_latest_snapshot`` prefers the
    stamp) and replays byte-identically — up to
    ``integrity.MAX_REWINDS`` times before the failure propagates."""
    from .integrity import MAX_REWINDS, IntegrityFailure
    rewinds = 0
    while True:
        try:
            return _train_impl(params, train_set, num_boost_round,
                               valid_sets, valid_names, fobj, feval,
                               init_model, feature_name,
                               categorical_feature,
                               keep_training_booster, callbacks)
        except IntegrityFailure as sdc:
            from .config import canonical_params
            cp = canonical_params(dict(params or {}))
            policy = str(cp.get("integrity_policy", "raise"))
            if policy != "rewind" or init_model is not None \
                    or rewinds >= MAX_REWINDS:
                # raise/quarantine surface the classified failure (the
                # elastic ladder catches kind "sdc" and re-enters with
                # a quarantined mesh); an explicit init_model run has
                # no self-owned snapshot history to rewind into
                raise
            rewinds += 1
            from .integrity import _metrics as _int_metrics
            _int_metrics().counter("integrity.rewinds").inc()
            from .utils.log import Log
            Log.warning(
                f"integrity: sticky SDC at iteration {sdc.iteration}; "
                "rewinding to the newest integrity-verified snapshot "
                f"(attempt {rewinds}/{MAX_REWINDS})")
            params = dict(params or {})
            params["resume"] = True


def _train_impl(params: Dict[str, Any], train_set: Dataset,
                num_boost_round: int,
                valid_sets, valid_names, fobj, feval, init_model,
                feature_name, categorical_feature,
                keep_training_booster, callbacks) -> Booster:
    """One training attempt (the body of :func:`train`; the wrapper
    owns only the integrity-rewind re-entry loop)."""
    params = dict(params or {})
    # resume is a run-control switch, not a model hyperparameter: strip
    # it (and its aliases) from the params that reach the Booster so the
    # saved parameters section is identical between a straight run and a
    # crash+resume run
    from .config import _ALIASES, _coerce
    resume_req = False
    for k in list(params):
        if _ALIASES.get(k, k) == "resume":
            resume_req = bool(_coerce("resume", bool, params.pop(k)))
    cfg = Config(params)
    cfg.resume = resume_req
    # persistent-compile-cache bring-up before any jax work (binning /
    # init-score prediction may already trace): warm-starts every compile
    # of this process from the on-disk cache (docs/Compile-Cache.md)
    from .utils.compile_cache import maybe_enable_from_config
    maybe_enable_from_config(cfg)
    from .config import canonical_params
    if "num_iterations" in canonical_params(params):
        # any num_iterations alias in params overrides the keyword
        # unconditionally (reference train pops the alias and wins)
        num_boost_round = cfg.num_iterations
    # ...and the effective round count is written back so the saved
    # model's parameters section records it (reference train sets
    # params["num_iterations"] = num_boost_round)
    params["num_iterations"] = num_boost_round
    if valid_sets is not None and not isinstance(valid_sets, (list, tuple)):
        valid_sets = [valid_sets]       # reference accepts a bare Dataset
    if isinstance(valid_names, str):
        valid_names = [valid_names]
    if feature_name != "auto" and not train_set._constructed:
        train_set.set_feature_name(feature_name)
    if categorical_feature != "auto" and not train_set._constructed:
        train_set.set_categorical_feature(categorical_feature)

    # continued training: init_model predictions become the init score
    # (application.cpp:88-94 input_model pattern)
    prev_booster = None
    resume_start = 0
    snap_sig = None
    if cfg.snapshot_freq > 0 or resume_req:
        from .snapshot import params_signature
        snap_sig = params_signature(params)
    if init_model is not None:
        prev_booster = (Booster(model_file=init_model)
                        if isinstance(init_model, str) else init_model)
        raw = prev_booster.predict(_dataset_raw(train_set), raw_score=True)
        train_set.set_init_score(np.asarray(raw, np.float64))
    elif resume_req:
        from .snapshot import find_latest_snapshot
        from .utils.log import Log
        found = find_latest_snapshot(cfg.output_model, snap_sig, train_set)
        if found is not None:
            resume_start, snap_path, snap_score = found
            try:
                prev_booster = Booster(model_file=snap_path)
            except FileNotFoundError:
                # the snapshot the finder located was pruned before the
                # open (a concurrent writer's prune_snapshots —
                # find->open TOCTOU): re-scan ONCE instead of failing
                # the bring-up; an older valid snapshot still resumes
                Log.warning(f"snapshot {snap_path} vanished between "
                            "lookup and load; re-scanning once")
                found = find_latest_snapshot(cfg.output_model, snap_sig,
                                             train_set)
                if found is not None:
                    resume_start, snap_path, snap_score = found
                    prev_booster = Booster(model_file=snap_path)
                else:
                    resume_start = 0
        if found is None:
            Log.info("resume=true but no valid snapshot found for "
                     f"{cfg.output_model!r}; training from scratch")
        else:
            # the saved f32 training score IS the device state at the
            # snapshot — feeding it back through the init_model path
            # continues training bit-exactly where the crash hit (a
            # re-prediction of the snapshot model would differ in the
            # last ulp and change the trees grown after the resume)
            row_range = getattr(train_set, "elastic_row_range", None)
            if row_range is not None:
                # elastic multi-process resume: the snapshot carries
                # the GLOBAL score (GBDTModel.snapshot_state); this
                # process feeds back only its own shard's rows
                snap_score = snap_score[row_range[0]:row_range[1]]
            train_set.set_init_score(np.asarray(snap_score, np.float64))
            Log.info(f"auto-resume: continuing from {snap_path} "
                     f"(iteration {resume_start})")

    booster = Booster(params=params, train_set=train_set)
    if resume_start and booster._model is not None:
        # align iteration-keyed RNG streams (bagging epochs, GOSS keys,
        # feature-fraction draws) with the straight run
        booster._model.set_resume_state(resume_start)
    # early stopping reports best_iteration ABSOLUTE over the final
    # merged forest: with an explicit init_model the loop index starts
    # at 0 while the forest still carries the previous model's trees
    # (predict/save slicing at a run-relative index would silently drop
    # the continuation's best trees); a RESUMED run's loop index is
    # already absolute (it starts at resume_start == the snapshot's
    # iterations), so the two offsets cancel there
    best_iter_offset = 0
    if prev_booster is not None:
        k = max(1, booster._num_tree_per_iteration)
        best_iter_offset = len(prev_booster.trees) // k - resume_start
    train_eval_name = None
    if valid_sets:
        names = valid_names or [
            "training" if vs is train_set else f"valid_{i}"
            for i, vs in enumerate(valid_sets)]
        for vs, name in zip(valid_sets, names):
            if vs is train_set:
                # reference semantics: the training set in valid_sets
                # means "report training metrics under this name"
                # (engine.py train: name_valid_sets / 'training')
                train_eval_name = name
                booster._train_data_name = name
                continue
            booster.add_valid(vs, name)

    cbs = list(callbacks or [])
    if cfg.early_stopping_round and cfg.early_stopping_round > 0:
        cbs.append(callback_mod.early_stopping(
            cfg.early_stopping_round, cfg.first_metric_only, cfg.verbosity > 0))
    cbs_before = [c for c in cbs if getattr(c, "before_iteration", False)]
    cbs_after = [c for c in cbs if not getattr(c, "before_iteration", False)]
    cbs_before.sort(key=lambda c: getattr(c, "order", 0))
    cbs_after.sort(key=lambda c: getattr(c, "order", 0))

    import time as _time
    t_start = _time.time()

    start_round = resume_start
    scan_stopped = False

    # super-epochs: the whole-run on-device path — k FULL iterations
    # (growth + score + valid scoring + traced eval + early-stop vote;
    # without valid sets, growth + score alone) per device program, ONE
    # host sync each instead of ~5 per iteration, then the fetched eval
    # block replayed through the REAL callbacks so record_evals /
    # early_stopping / best_iteration are byte-identical per-iteration.
    # What the plan declines, and any remainder under 2 rounds, falls
    # through to the per-iteration loop.
    se_plan = _superepoch_plan(
        cfg, booster, fobj, feval, cbs_before, cbs_after,
        train_eval_name)
    if se_plan is not None:
        base_k, eval_spec, es_spec = se_plan
        from .utils.log import Log
        while not scan_stopped:
            k_eff = min(base_k, num_boost_round - start_round)
            if cfg.snapshot_freq > 0:
                # clip to the snapshot boundary so periodic snapshots
                # land at EXACTLY the per-iteration cadence
                k_eff = min(k_eff, cfg.snapshot_freq
                            - start_round % cfg.snapshot_freq)
            if k_eff < 2:
                break
            out = booster.update_superepoch(k_eff, start_round,
                                            eval_spec, es_spec)
            done = out["done"]
            if cfg.snapshot_freq > 0 and done == k_eff \
                    and (start_round + done) % cfg.snapshot_freq == 0:
                # per-iteration order is update -> snapshot -> evals ->
                # callbacks, so the boundary snapshot is written BEFORE
                # the replay may raise EarlyStopException
                from .snapshot import write_snapshot
                try:
                    write_snapshot(booster, prev_booster, cfg,
                                   start_round + done, snap_sig,
                                   train_set)
                except Exception as e:
                    Log.warning(f"snapshot at iteration "
                                f"{start_round + done} failed ({e}); "
                                "training continues")
            es_raised = False
            for j in range(done):
                ev_row = [(nm, mn, float(out["evals"][j][e]), hib)
                          for e, (_vi, nm, mn, hib)
                          in enumerate(eval_spec)]
                env = CallbackEnv(model=booster, params=params,
                                  iteration=start_round + j,
                                  begin_iteration=0,
                                  end_iteration=num_boost_round,
                                  evaluation_result_list=ev_row)
                try:
                    for cb in cbs_after:
                        cb(env)
                except EarlyStopException as e:
                    booster.best_iteration = (best_iter_offset
                                              + e.best_iteration + 1)
                    for (name, metric, value, _) in e.best_score:
                        booster.best_score.setdefault(
                            name, {})[metric] = value
                    es_raised = True
                    extra = done - (j + 1)
                    if extra > 0:
                        # defensive: the traced vote and this replay
                        # consume the SAME fetched f32 values, so they
                        # agree on the stop row — heal by slicing the
                        # surplus trees if they ever don't
                        Log.warning(
                            "super-epoch vote overshot the host early "
                            f"stop by {extra} iteration(s); dropping "
                            "surplus trees")
                        booster._model.drop_iterations(extra)
                        booster._sync_trees()
                    break
            if es_raised or out["stump"]:
                scan_stopped = True
            elif out["stop_row"] is not None:
                # vote tripped but the replay did not raise (defensive
                # mirror of the overshoot case): trust the host, clear
                # the latch, keep training
                Log.warning("super-epoch early-stop vote tripped but "
                            "the host callbacks did not; resuming")
                booster._model.clear_es_stop()
            # current_iteration counts only THIS booster's iterations;
            # a resumed run's global round index carries the offset
            start_round = resume_start + booster.current_iteration
        if not scan_stopped and start_round < num_boost_round \
                and eval_spec:
            # remainder rounds run per-iteration but keep the TRACED
            # metric values, so the whole run's record_evals stays
            # bit-identical to a pure super-epoch run
            booster._traced_eval = True
    elif str(cfg.fused_eval).lower() == "true" and feval is None \
            and booster._valid_names \
            and getattr(booster, "_model", None) is not None:
        # fused_eval=true: per-iteration runs evaluate via the traced
        # metric kernels too (ONE fetch per iteration for all metrics)
        # — the reference twin the super-epoch byte-identity tests
        # compare against
        import jax
        from .metrics import traced_metric_fn
        if all(traced_metric_fn(mt.name, cfg) is not None
               for ms in booster._valid_metrics for mt in ms) \
                and all(isinstance(vb, jax.Array) for _, vb, _
                        in booster._model.valid_sets):
            booster._traced_eval = True

    for i in range(start_round, num_boost_round if not scan_stopped else 0):
        env = CallbackEnv(model=booster, params=params, iteration=i,
                          begin_iteration=0, end_iteration=num_boost_round,
                          evaluation_result_list=None)
        for cb in cbs_before:
            cb(env)
        try:
            stopped = booster.update(fobj=fobj)
        except Exception:
            # flight-recorder trigger (obs/blackbox.py): dump the last
            # K iteration records before the exception propagates —
            # cheap no-op when no recorder is live
            from .obs import blackbox
            blackbox.dump_all("train_exception")
            raise
        if cfg.verbosity > 1:
            from .utils.log import Log
            Log.info(f"{_time.time() - t_start:.6f} seconds elapsed, "
                     f"finished iteration {i + 1}")
        if cfg.snapshot_freq > 0 and (i + 1) % cfg.snapshot_freq == 0:
            # integrity boundary check FIRST, and OUTSIDE the write's
            # skip-and-warn: the manifest's integrity stamp must mean
            # 'verified AT this snapshot', and a sticky boundary
            # mismatch must fail the run (IntegrityFailure), never be
            # swallowed as a failed write
            ib = getattr(getattr(booster, "_model", None),
                         "integrity_boundary_check", None)
            if ib is not None:
                ib()
            # periodic crash-safe snapshot: model + f32 score state +
            # manifest, each written atomically; prunes to snapshot_keep
            # (gbdt.cpp:279-284 snapshot_freq + snapshot.py)
            from .snapshot import write_snapshot
            try:
                write_snapshot(booster, prev_booster, cfg, i + 1,
                               snap_sig, train_set)
            except Exception as e:
                # a full disk (or an injected write failure) must not
                # kill a long training run — skip the snapshot, loudly
                from .utils.log import Log
                Log.warning(f"snapshot at iteration {i + 1} failed "
                            f"({e}); training continues")
        evals = []
        if booster._valid_names or cfg.is_provide_training_metric \
                or train_eval_name is not None:
            if cfg.is_provide_training_metric or train_eval_name is not None:
                evals.extend(booster.eval_train(feval))
            if getattr(booster, "_traced_eval", False) and feval is None:
                evals.extend(booster.eval_valid_traced())
            else:
                evals.extend(booster.eval_valid(feval))
        if evals:
            # flight recorder: fold the train/valid metrics (computed
            # after the iteration record landed) into that record
            bb = getattr(getattr(booster, "_model", None), "_bbox", None)
            if bb is not None:
                bb.annotate_last(evals=[[nm, met, float(v)]
                                        for (nm, met, v, _) in evals])
        env = CallbackEnv(model=booster, params=params, iteration=i,
                          begin_iteration=0, end_iteration=num_boost_round,
                          evaluation_result_list=evals)
        try:
            for cb in cbs_after:
                cb(env)
        except EarlyStopException as e:
            booster.best_iteration = best_iter_offset + e.best_iteration + 1
            for (name, metric, value, _) in e.best_score:
                booster.best_score.setdefault(name, {})[metric] = value
            # roll back to best iteration for prediction default
            break
        if stopped:
            break

    if prev_booster is not None:
        # merge: previous trees come first (continued training model)
        booster.trees = prev_booster.trees + booster.trees
        booster.tree_weights = (prev_booster.tree_weights
                                + booster.tree_weights)
    return booster


def _superepoch_plan(cfg, booster, fobj, feval, cbs_before, cbs_after,
                     train_eval_name):
    """Decide whether the super-epoch trainer (GBDTModel.
    train_superepoch) can drive this run, and with what epoch size.
    Returns ``(base_k, eval_spec, es_spec)`` or None for the
    per-iteration path.  Requirements (docs/Fused-Training.md): the
    fused-path model config, no custom fobj/feval, no training-set
    eval, only replay-safe callbacks, dense device valid sets whose
    metrics all have traced kernels, and at most one early-stopping
    callback in its scalar ``min_delta == 0`` form."""
    if cfg.superepoch == -1:
        return None
    if not (cfg.superepoch > 0 or cfg.fused_chunk > 1):
        return None
    if fobj is not None or feval is not None:
        return None
    if cfg.is_provide_training_metric or train_eval_name is not None:
        return None
    if cfg.verbosity > 1:
        return None       # per-iteration elapsed-time logging
    if cbs_before:
        return None
    if any(not getattr(cb, "_replayable", False) for cb in cbs_after):
        return None
    model = getattr(booster, "_model", None)
    if model is None or not hasattr(model, "train_superepoch") \
            or not model.supports_fused():
        return None
    import jax
    if str(cfg.fused_eval).lower() == "false" and model.valid_sets:
        return None
    if any(not isinstance(vb, jax.Array)
           for _, vb, _ in model.valid_sets):
        return None       # sparse-binned valid rows: no in-scan walk
    from .metrics import traced_metric_fn
    eval_spec = []
    for vi, name in enumerate(booster._valid_names):
        for mt in booster._valid_metrics[vi]:
            if traced_metric_fn(mt.name, cfg) is None:
                return None
            eval_spec.append((vi, name, mt.name,
                              bool(mt.is_higher_better)))
    eval_spec = tuple(eval_spec)
    es_cbs = [cb for cb in cbs_after
              if getattr(cb, "_es_spec", None) is not None]
    if len(es_cbs) > 1:
        return None
    es_spec = None
    if es_cbs:
        spec = es_cbs[0]._es_spec
        md = spec["min_delta"]
        if isinstance(md, (list, tuple)) or float(md) != 0.0:
            return None
        # which entries the host closure's trip-check actually reaches:
        # 'training'-named sets and first_metric_only mismatches update
        # their best but never raise (callback.early_stopping)
        first_metric = eval_spec[0][2].split("@")[0] if eval_spec else ""
        eligible = tuple(
            (nm != "training")
            and (not spec["first_metric_only"]
                 or mn.split("@")[0] == first_metric)
            for (_vi, nm, mn, _h) in eval_spec)
        es_spec = {"stopping_rounds": int(spec["stopping_rounds"]),
                   "first_metric_only": bool(spec["first_metric_only"]),
                   "eligible": eligible}
    # epoch size: explicit superepoch wins; auto sizes to the fused
    # chunk, bounded by the early-stop horizon so a stop wastes at most
    # ~one epoch of post-stop (zeroed) in-scan iterations
    if cfg.superepoch > 0:
        base_k = cfg.superepoch
    elif es_spec is not None:
        base_k = max(2, min(cfg.fused_chunk,
                            es_spec["stopping_rounds"]))
    else:
        base_k = cfg.fused_chunk
    return max(int(base_k), 2), eval_spec, es_spec


def _dataset_raw(ds: Dataset):
    if ds.raw_data is not None:
        return ds.raw_data
    if ds._raw_input is not None:
        return ds._raw_input
    raise ValueError("init_model needs the training data raw values "
                     "(construct the Dataset with free_raw_data=False)")


class CVBooster:
    """Container of per-fold boosters (engine.py:264 analog)."""

    def __init__(self):
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def append(self, b: Booster) -> None:
        self.boosters.append(b)

    def __getattr__(self, name):
        def _handler(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]
        return _handler


def _make_folds(ds: Dataset, nfold: int, stratified: bool, shuffle: bool,
                seed: int, cfg: Config):
    ds.construct(cfg)
    n = ds.num_data
    rng = np.random.RandomState(seed)
    if ds.metadata.query_boundaries is not None:
        # group-aware folds: the reference delegates to sklearn's
        # GroupKFold for ranking cv (engine.py _make_n_folds uses
        # _LGBMGroupKFold), so a user passing folds=GroupKFold(n) gets
        # IDENTICAL splits to nfold=n — keep that equivalence
        sizes = np.diff(ds.metadata.query_boundaries)
        groups = np.repeat(np.arange(len(sizes)), sizes)
        from sklearn.model_selection import GroupKFold
        yield from GroupKFold(n_splits=nfold).split(
            np.empty((n, 1)), groups=groups)
        return
    if stratified and cfg.objective in ("binary", "multiclass", "multiclassova"):
        label = np.asarray(ds.metadata.label).astype(np.int64)
        idx_by_class = [np.nonzero(label == c)[0] for c in np.unique(label)]
        folds = [[] for _ in range(nfold)]
        for idx in idx_by_class:
            if shuffle:
                idx = idx[rng.permutation(len(idx))]
            for fi, part in enumerate(np.array_split(idx, nfold)):
                folds[fi].append(part)
        for fi in range(nfold):
            test = np.concatenate(folds[fi])
            mask = np.zeros(n, bool)
            mask[test] = True
            yield np.nonzero(~mask)[0], np.nonzero(mask)[0]
        return
    order = rng.permutation(n) if shuffle else np.arange(n)
    for part in np.array_split(order, nfold):
        mask = np.zeros(n, bool)
        mask[part] = True
        yield np.nonzero(~mask)[0], np.nonzero(mask)[0]


def cv(params: Dict[str, Any], train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True, shuffle: bool = True,
       metrics=None, fobj=None, feval=None, init_model=None,
       feature_name="auto", categorical_feature="auto",
       fpreproc=None, seed: int = 0, callbacks=None,
       eval_train_metric: bool = False,
       return_cvbooster: bool = False) -> Dict[str, List[float]]:
    """K-fold cross-validation (engine.py:375 analog).

    fpreproc: ``f(fold_train, fold_valid, params) -> (train, valid,
    params)`` applied per fold before training (the reference's
    preprocessing hook).  eval_train_metric adds ``train <metric>-mean``
    series alongside the ``valid`` ones.
    """
    params = dict(params or {})
    if metrics is not None:
        params["metric"] = metrics
    from .config import canonical_params
    if "num_iterations" in canonical_params(params):
        # params win unconditionally, like train() (reference pops the
        # alias in both entry points)
        num_boost_round = Config(params).num_iterations
    if feature_name != "auto" and not train_set._constructed:
        train_set.set_feature_name(feature_name)
    if categorical_feature != "auto" and not train_set._constructed:
        train_set.set_categorical_feature(categorical_feature)
    cfg = Config(params)
    if not train_set._constructed and train_set.params:
        # dataset's own params are the binning base, cv params override
        # (reference _update_params semantics — see Booster.__init__)
        from .config import canonical_params
        cfg = Config({**canonical_params(train_set.params),
                      **canonical_params(params)})
    train_set.construct(cfg)

    if folds is None:
        folds = list(_make_folds(train_set, nfold, stratified, shuffle, seed, cfg))
    elif hasattr(folds, "split"):
        # scikit-learn splitter object (reference cv accepts these):
        # split over row indices, group-aware when the splitter wants it
        lbl = train_set.get_label()
        g = train_set.get_group()
        groups = np.repeat(np.arange(len(g)), g) if g is not None else None
        folds = list(folds.split(np.empty((train_set.num_data, 1)),
                                 y=lbl, groups=groups))

    cvbooster = CVBooster()
    results = collections.defaultdict(list)
    from .obs import maybe_session
    # the call's binned matrix, sent to the device once by the first fold
    # that takes its rows from there, and dropped once the folds hold
    # their own (fold_rows.py)
    fold_rows = FoldRows(train_set)
    try:
        for fold, (tr_idx, te_idx) in enumerate(folds):
            # the fold's telemetry session comes before its Booster: the
            # set-up of a fold (the label gathers, a Booster, its
            # matrices) is the first thing it times, and the Booster takes
            # it over
            obs = maybe_session(cfg)
            if obs is not None:
                _sp = obs.span("cv.fold_setup", fold=fold)
            # subset() reconstructs per-fold query groups from the
            # parent's boundaries itself
            tr = train_set.subset(tr_idx, _obs=obs)
            te = train_set.subset(te_idx, _obs=obs)
            fold_params = params
            if fpreproc is not None:
                tr, te, fold_params = fpreproc(tr, te, dict(params))
            tr._fold_rows = te._fold_rows = fold_rows
            try:
                bst = Booster(params=dict(fold_params), train_set=tr,
                              _obs=obs)
                bst._train_data_name = "train"
                bst.add_valid(te, "valid")
            finally:
                tr._fold_rows = te._fold_rows = None
            cvbooster.append(bst)
            if obs is not None:
                obs.end_setup(_sp)
    finally:
        fold_rows.close()

    # lockstep boosting (the reference's CVBooster: every fold advances
    # one iteration, then the AGGREGATED metrics go to the callbacks as
    # ('cv_agg', '<set> <metric>', mean, higher_better, stdv) 5-tuples —
    # which is what gives cv early stopping and cv record_evaluation
    # their reference semantics)
    cbs = list(callbacks or [])
    cfg2 = Config(params)
    if cfg2.early_stopping_round and cfg2.early_stopping_round > 0:
        cbs.append(callback_mod.early_stopping(
            cfg2.early_stopping_round, cfg2.first_metric_only,
            cfg2.verbosity > 0))
    cbs_before = [c for c in cbs if getattr(c, "before_iteration", False)]
    cbs_after = [c for c in cbs if not getattr(c, "before_iteration", False)]
    cbs_before.sort(key=lambda c: getattr(c, "order", 0))
    cbs_after.sort(key=lambda c: getattr(c, "order", 0))
    best_iter = -1      # stays -1 unless early stopping fires (reference)
    for i in range(num_boost_round):
        env = CallbackEnv(model=cvbooster, params=params, iteration=i,
                          begin_iteration=0, end_iteration=num_boost_round,
                          evaluation_result_list=None)
        for cb in cbs_before:
            cb(env)
        per_key: Dict[str, list] = collections.OrderedDict()
        hib_of: Dict[str, bool] = {}
        for bst in cvbooster.boosters:
            bst.update(fobj=fobj)
            one = list(bst.eval_train(feval)) if eval_train_metric else []
            one.extend(bst.eval_valid(feval))
            for (nm, met, val, hib) in one:
                key = f"{nm} {met}"
                per_key.setdefault(key, []).append(val)
                hib_of[key] = hib
        agg = [("cv_agg", k, float(np.mean(v)), hib_of[k], float(np.std(v)))
               for k, v in per_key.items()]
        for (_, k, mean, _h, std) in agg:
            results[f"{k}-mean"].append(mean)
            results[f"{k}-stdv"].append(std)
        env = CallbackEnv(model=cvbooster, params=params, iteration=i,
                          begin_iteration=0, end_iteration=num_boost_round,
                          evaluation_result_list=agg)
        try:
            for cb in cbs_after:
                cb(env)
        except EarlyStopException as e:
            best_iter = e.best_iteration + 1
            for b in cvbooster.boosters:
                b.best_iteration = best_iter
            # the reference trims the history to the best iteration
            for k in results:
                results[k] = results[k][:best_iter]
            break
    out = dict(results)
    if return_cvbooster:
        cvbooster.best_iteration = best_iter
        out["cvbooster"] = cvbooster
    return out


