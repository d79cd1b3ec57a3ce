"""Booster: the user-facing model handle.

Analog of the reference python-package ``Booster`` (basic.py:2548) fused
with the C-API Booster wrapper (c_api.cpp:106) — in this TPU-native rebuild
there is no C shim between them, the Booster drives the device boosting
model directly.  Model (de)serialization follows the reference text format
(``GBDT::SaveModelToString`` / ``LoadModelFromString``,
/root/reference/src/boosting/gbdt_model_text.cpp:311, 421) so models
round-trip and remain ecosystem-readable.
"""

from __future__ import annotations

import functools
import io
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .config import Config
from .dataset import Dataset
from .metrics import (AUC_DEVICE_MAX_ROWS, AUCMetric, Metric, auc_classes,
                      auc_from_parts, auc_rank_parts, create_metric)
from .models import create_boosting
from .objectives import create_objective
from .tree_model import Tree


def _objective_to_string(cfg: Config) -> str:
    o = cfg.objective
    if o == "binary":
        return f"binary sigmoid:{cfg.sigmoid:g}"
    if o in ("multiclass", "multiclassova"):
        return f"{o} num_class:{cfg.num_class}"
    if o == "lambdarank":
        return "lambdarank"
    if o == "quantile":
        return f"quantile alpha:{cfg.alpha:g}"
    if o == "huber":
        return f"huber alpha:{cfg.alpha:g}"
    if o == "fair":
        return f"fair fair_c:{cfg.fair_c:g}"
    if o == "tweedie":
        return f"tweedie tweedie_variance_power:{cfg.tweedie_variance_power:g}"
    return o


def _objective_from_string(s: str) -> Dict[str, Any]:
    toks = s.split()
    out: Dict[str, Any] = {"objective": toks[0]} if toks else {}
    for t in toks[1:]:
        if ":" in t:
            k, v = t.split(":", 1)
            out[k] = v
    return out


def _finalize_score(score: np.ndarray, k: int, objective, average_output,
                    t0: int, t1: int, raw_score: bool) -> np.ndarray:
    """The ONE score-finalization tail shared by every predict path
    (host walk, bucketed engine, serve engine): RF averaging over the
    predicted range, then the objective's output conversion.  Byte-
    identical results across paths depend on this being a single
    definition — do not inline copies."""
    if average_output and t1 > t0:
        score /= (t1 - t0) // k
    if not raw_score and objective is not None:
        import jax.numpy as jnp
        conv = objective.convert_output(
            jnp.asarray(score if k > 1 else score[:, 0]))
        return np.asarray(conv)
    return score if k > 1 else score[:, 0]


class _IntAndCall(int):
    """int that also answers the reference's METHOD spelling — basic.py
    exposes ``bst.current_iteration()`` as a method while this framework
    grew it as an attribute; a callable int serves both."""

    def __call__(self) -> int:
        return int(self)


class Booster:
    """Training/prediction handle (basic.py:2548 / boosting.h:27 analog)."""

    def __init__(self, params: Optional[Dict] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None,
                 hist_reduce=None, _obs=None):
        self.best_iteration = -1
        self.best_score: Dict = {}
        self._valid_names: List[str] = []
        self._train_metrics: List[Metric] = []
        self._valid_metrics: List[List[Metric]] = []
        self.trees: List[Tree] = []
        self.tree_weights: List[float] = []
        self.feature_names: List[str] = []
        self.pandas_categorical = None
        self._model = None
        self.train_set = None
        self._num_class = 1
        self._num_tree_per_iteration = 1
        self._average_output = False
        self._max_feature_idx = 0
        # bucketed predictor engine (serve/engine.py), built lazily by
        # predict(); False = engine refused this model (don't retry),
        # None = not built yet.  Dropped on every model mutation.
        self._engine_cache = None

        if model_file is not None:
            # utf-8 to match the write side (atomic_write / snapshot
            # checksums hash utf-8 bytes); the locale default would
            # desynchronize read and write on non-utf-8 hosts
            with open(model_file, encoding="utf-8") as f:
                self._load_model_string(f.read())
            return
        if model_str is not None:
            self._load_model_string(model_str)
            return
        if train_set is None:
            raise ValueError("Booster needs train_set, model_file or model_str")

        self.config = Config(params or {})
        # telemetry session first, so that it sees this constructor's
        # own work; ``_obs`` is the one ``lgb.cv`` opened for the fold
        from .obs import maybe_session
        obs = _obs if _obs is not None and self.config.telemetry \
            else maybe_session(self.config)
        if obs is not None:
            _sp = obs.span("booster.init")
        # persistent-compile-cache bring-up + compile counters: every
        # training Booster warm-starts its jit compiles from (and
        # contributes to) the on-disk cache unless compile_cache=false
        from .utils.compile_cache import maybe_enable_from_config
        maybe_enable_from_config(self.config)
        # reference _update_params semantics (basic.py: train-time params
        # are update()d ONTO the dataset's own params): a not-yet-
        # constructed dataset bins with its OWN params as the base and
        # the booster's params overriding — a Dataset(params={'max_bin':
        # 63}) keeps its 63 bins when the booster params don't mention
        # binning.  The C API relies on this: LGBM_DatasetCreateFromMat
        # carries the binning params, LGBM_BoosterCreate the training
        # params (c_api.cpp bins at dataset-create time).
        construct_cfg = self.config
        if not train_set._constructed and train_set.params:
            from .config import canonical_params
            construct_cfg = Config({**canonical_params(train_set.params),
                                    **canonical_params(params or {})})
        self.train_set = train_set.construct(construct_cfg)
        self.objective = create_objective(self.config)
        self._model = create_boosting(self.config, self.train_set,
                                      self.objective, hist_reduce, obs)
        self._num_class = self.config.num_class
        self._num_tree_per_iteration = self.config.num_model_per_iteration
        self._average_output = getattr(self._model, "average_output", False)
        self.feature_names = list(self.train_set.feature_names)
        self._max_feature_idx = self.train_set.num_total_features - 1

        self._train_metrics = self._make_metrics(self.train_set.metadata,
                                                 self.train_set.num_data)
        if obs is not None:
            obs.end_setup(_sp)

    # ------------------------------------------------------------------
    def add_valid(self, data: Dataset, name: str) -> "Booster":
        if self._model is None:
            raise ValueError("cannot add validation data to a loaded model")
        data.reference = self.train_set
        data.construct(self.config)
        metrics = self._make_metrics(data.metadata, data.num_data)
        ranked = [m for m in metrics if self._ranks_on_device(m)]
        classes = self._model.add_valid_set(
            data, auc_classes(ranked[0].label) if ranked else None)
        for m in ranked:
            m.classes = classes
        self._valid_names.append(name)
        self._valid_metrics.append(metrics)
        return self

    def _ranks_on_device(self, metric: Metric) -> bool:
        """Whether a held-out set's ``metric`` is an AUC that the device
        ranks (``metrics.auc_rank_parts``): unweighted, one tree a round,
        not ``rf`` (whose host score ``valid_score`` divides by the
        rounds), and no more rows than ``_auc`` counts exactly.  The rest
        keep the host's ``_auc``, to the same value."""
        return (isinstance(metric, AUCMetric) and metric.weight is None
                and self._num_tree_per_iteration == 1
                and self._model.config.boosting != "rf"
                and metric.num_data <= AUC_DEVICE_MAX_ROWS)

    def _make_metrics(self, metadata, num_data) -> List:
        """Configured metric objects bound to one dataset's metadata."""
        ms = []
        for mname in self.config.default_metric():
            m = create_metric(mname, self.config)
            if m is not None:
                m.init(metadata, num_data)
                ms.append(m)
        return ms

    def update(self, train_set=None, fobj=None) -> bool:
        """One boosting iteration; returns True if no further splits
        (LGBM_BoosterUpdateOneIter analog, c_api.cpp:1686)."""
        if fobj is not None:
            preds = self._model.train_score()
            if self._num_tree_per_iteration == 1:
                preds = preds[:, 0]
            grad, hess = fobj(preds, self.train_set)
            grad, hess = np.asarray(grad), np.asarray(hess)
            n = self.train_set.num_data
            k = self._num_tree_per_iteration
            if grad.size != hess.size:
                raise ValueError(
                    f"Lengths of gradient ({grad.size}) and Hessian "
                    f"({hess.size}) don't match")
            if grad.size != n * k:
                # reference-exact message shape (basic.py __boost)
                raise ValueError(
                    f"Lengths of gradient ({grad.size}) and Hessian "
                    f"({hess.size}) don't match training data length "
                    f"({n}) * number of models per one iteration ({k})")
            if k > 1 and grad.ndim == 1:
                # flat multiclass gradients arrive CLASS-major (the
                # reference C convention, basic.py __boost F-ravel);
                # internal layout is [n, k]
                grad = grad.reshape(k, n).T
                hess = hess.reshape(k, n).T
            stopped = self._model.train_one_iter(grad, hess)
        else:
            stopped = self._model.train_one_iter()
        self._sync_trees()
        return stopped

    def update_superepoch(self, k: int, es_it0: int, eval_spec=(),
                          es_spec=None) -> dict:
        """Run ``k`` FULL iterations — growth, score updates, valid-set
        scoring, traced metric eval, early-stop vote — fused in one
        device program with ONE host fetch (GBDTModel.train_superepoch).
        Returns the fetched replay block for engine.train's host-side
        callback replay."""
        out = self._model.train_superepoch(k, es_it0, eval_spec, es_spec)
        self._sync_trees()
        return out

    def fused_reasons(self) -> List[str]:
        """Why a run takes the per-iteration loop and not the scan —
        specific blockers, empty when the model config can be scanned
        (GBDTModel.fused_reasons)."""
        if self._model is None or not hasattr(self._model,
                                              "fused_reasons"):
            return ["no active training model"]
        return self._model.fused_reasons()

    def eval_valid_traced(self) -> List[Tuple]:
        """Every valid-set metric evaluated by the TRACED metric kernels
        in one jitted program + ONE host fetch — the SAME program
        (metrics.build_traced_eval) the super-epoch replay reports
        through, so a ``fused_eval=true`` per-iteration run produces
        bit-identical eval values to a super-epoch run (the
        byte-identity contract the tests pin); the host f64 ``eval_*``
        path stays available via ``fused_eval=false``."""
        m = self._model
        obs = m._obs
        if obs is not None:
            _sp = obs.span("eval", mirror=False, rows=sum(
                vs[0].num_data for vs in m.valid_sets))
        spec = tuple(
            (vi, name, mt.name, mt.is_higher_better)
            for vi, name in enumerate(self._valid_names)
            for mt in self._valid_metrics[vi])
        fn = m._teval_fn(spec)
        svecs = tuple(vs[:, 0] for _, _, vs in m.valid_sets)
        ops = tuple(m._se_valid_dev(vi)
                    for vi in range(len(m.valid_sets)))
        dev = fn(svecs, ops)
        if obs is not None:
            # the metric runs on the device (scope ``lgbtpu.eval``): the
            # host's part is the fetch that waits for it
            _fp = obs.span("eval.fetch")
        vals = m._eget(dev, "traced_eval")
        if obs is not None:
            obs.end_eval_part(_fp)
            obs.end_eval(_sp)
        return [(name, mn, float(vals[e]), hib)
                for e, (vi, name, mn, hib) in enumerate(spec)]

    def rollback_one_iter(self) -> "Booster":
        self._model.rollback_one_iter()
        self._sync_trees()
        return self

    # -- telemetry (obs/ subsystem; docs/Observability.md) ----------------
    def telemetry_snapshot(self) -> dict:
        """Current metrics snapshot (deterministic dict).  With
        ``telemetry=false`` (the default) the obs metrics are absent but
        the process-wide compile accounting is still included —
        ``compile.count`` / ``compile.seconds`` (backend compiles),
        ``compile.cache_hits`` / ``compile.cache_misses`` (persistent
        cache), ``compile.traces`` (library jit traces) — so warm-start
        is observable, not assumed (docs/Compile-Cache.md).

        With telemetry on, the ``perf.*`` roofline keys join the
        static flop ledger with the fenced phase spans: per-phase
        flops / hbm_bytes (deterministic, dp == serial), achieved
        FLOP/s and bytes/s, MFU against the device peak table, and a
        compute-vs-memory ``bound`` verdict (obs/attrib.py,
        docs/Observability.md "Roofline & flight recorder").

        Returns a DEEP COPY: callers may mutate the result freely
        without corrupting the live registry/ledger state the next
        snapshot is built from.  Multi-process: per-shard obs
        registries are gathered and merged, so every process sees
        host 0's aggregated view."""
        import copy
        m = self._model
        obs = None if m is None else getattr(m, "_obs", None)
        snap = {} if obs is None else dict(obs.snapshot())
        from .utils.compile_cache import compile_snapshot
        snap.update(compile_snapshot())
        if obs is not None:
            # no-op (returns {}) unless flops.* counters exist — on a
            # multi-process pod the gathered snapshot carries host 0's
            # ledger counters, so every process derives the same keys
            from .obs.attrib import perf_summary
            snap.update(perf_summary(snap, peaks=obs.peaks))
        return copy.deepcopy(snap)

    def telemetry_finish(self) -> dict:
        """Stop any active profiler window, flush the JSONL trace sink,
        and return the final aggregated metrics snapshot."""
        m = self._model
        if m is None or getattr(m, "_obs", None) is None:
            return {}
        return m._obs.finish()

    def _sync_trees(self) -> None:
        self.trees = self._model.models
        self.tree_weights = self._model.tree_weights
        self._drop_predict_cache()

    def _drop_predict_cache(self) -> None:
        """Invalidate the cached predictor engine after any model
        mutation (training step, rollback, merge, shuffle, refit)."""
        self._engine_cache = None

    # auto mode's build threshold: rows x trees below this predicts
    # faster through the host walk than through a fresh XLA trace
    _ENGINE_AUTO_WORK = 1 << 16

    def predict_engine(self, n_rows: Optional[int] = None):
        """The bucketed SoA predictor engine for the CURRENT model
        (serve/engine.py), or None when ``predict_bucketed`` rules it
        out or the model shape is unsupported.  ``predict_bucketed``:
        ``auto`` (default) builds the engine once rows x trees is large
        enough to repay the trace — an engine already built (a larger
        earlier call, or serving installing its own) serves ALL sizes;
        ``true`` always builds; ``false`` never.  Cached until the
        model mutates."""
        mode = str(getattr(self.config, "predict_bucketed",
                           "auto")).lower()
        if mode in ("false", "0", "no", "off", "-"):
            return None
        eng = getattr(self, "_engine_cache", None)
        if eng is False:
            return None
        if eng is not None and len(eng.trees) != len(self.trees):
            eng = None                    # stale (defensive; _sync_trees
            #                               normally drops it)
        if eng is None:
            if mode == "auto" and (n_rows is None or n_rows *
                                   max(len(self.trees), 1)
                                   < self._ENGINE_AUTO_WORK):
                return None
            from .serve.engine import EngineUnsupported, PredictorEngine
            try:
                eng = PredictorEngine.from_booster(self)
            except EngineUnsupported as e:
                from .utils.log import Log
                Log.debug(f"bucketed predict disabled for this model: "
                          f"{e}")
                self._engine_cache = False
                return None
            self._engine_cache = eng
        return eng

    @property
    def current_iteration(self) -> "_IntAndCall":
        if self._model is not None:
            return _IntAndCall(self._model.num_iterations_trained)
        return _IntAndCall(len(self.trees) // self._num_tree_per_iteration)

    def num_trees(self) -> int:
        return len(self.trees)

    # -- pickling / copying (basic.py __getstate__: a Booster serializes
    #    as its model string — the live training state holds jitted
    #    device programs that cannot and should not be pickled) --------
    def __getstate__(self):
        return {"model_str": self.model_to_string(),
                "best_iteration": int(self.best_iteration),
                "best_score": dict(self.best_score)}

    def __setstate__(self, state):
        self.__init__(model_str=state["model_str"])
        self.best_iteration = state["best_iteration"]
        self.best_score = state["best_score"]

    def __copy__(self):
        return self.__deepcopy__(None)

    def __deepcopy__(self, _memo):
        new = Booster(model_str=self.model_to_string())
        new.best_iteration = self.best_iteration
        new.best_score = dict(self.best_score)
        return new

    def num_model_per_iteration(self) -> int:
        return self._num_tree_per_iteration

    def num_feature(self) -> int:
        """Number of features the model was trained on (basic.py
        Booster.num_feature / LGBM_BoosterGetNumFeature)."""
        return self._max_feature_idx + 1

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        """Re-set training parameters for FUTURE iterations
        (LGBM_BoosterResetParameter, src/c_api.cpp ResetConfig; Python
        basic.py reset_parameter).  Structural parameters that would
        require re-binning or a new grower (num_leaves, max_bin,
        tree_learner, ...) are rejected like the reference's
        ResetConfig does for dataset-coupled params."""
        if self._model is None:
            raise ValueError("reset_parameter needs an active training "
                             "Booster (not a loaded model)")
        # bagging_* is excluded: Config zeroes bagging_freq at construction
        # when all fractions are 1.0, so enabling bagging mid-training
        # would silently no-op — reject it instead of pretending
        allowed_now = {"learning_rate", "verbosity", "verbose",
                       "metric_freq", "feature_fraction",
                       "feature_fraction_seed", "first_metric_only",
                       # CEGB penalties are per-call grower inputs, so
                       # resetting them only needs the state rebuilt
                       # below (ResetConfig swaps the config the tree
                       # learner reads, c_api.cpp ResetConfig)
                       "cegb_tradeoff", "cegb_penalty_split",
                       "cegb_penalty_feature_coupled",
                       "cegb_penalty_feature_lazy"}
        from .config import _ALIASES, _coerce, _PARAMS
        cegb_touched = False
        for k, v in params.items():
            canon = _ALIASES.get(k, k)
            if canon not in allowed_now:
                raise ValueError(
                    f"cannot reset parameter {k!r} on a live Booster "
                    "(requires dataset/grower reconstruction)")
            setattr(self._model.config, canon,
                    _coerce(canon, _PARAMS[canon][0], v))
            # the saved model's parameters section serializes raw_params
            self._model.config.raw_params[canon] = v
            self.config.raw_params[canon] = v
            cegb_touched = cegb_touched or canon.startswith("cegb_")
        if cegb_touched:
            if self._model._dist is not None:
                raise ValueError(
                    "CEGB is not supported with distributed learners")
            self._model._cegb_state = self._model._make_cegb(
                self._model.config, self._model.train_set)
        if "learning_rate" in params or "eta" in params \
                or "shrinkage_rate" in params:
            self._model.learning_rate = float(
                self._model.config.learning_rate)
        # a private scan program bakes the learning rate (and sampling
        # config) into its jitted closure — drop it so the next epoch
        # re-traces with the new values
        self._model._fused_cache.clear()
        return self

    # ------------------------------------------------------------------
    def eval_train(self, feval=None) -> List[Tuple]:
        m = self._model
        return self._eval_sets([(
            getattr(self, "_train_data_name", "training"), m.score,
            m.train_score, self._train_metrics, self.train_set)], feval)

    def eval_valid(self, feval=None) -> List[Tuple]:
        m = self._model
        return self._eval_sets([
            (name, m.valid_sets[i][2], functools.partial(m.valid_score, i),
             self._valid_metrics[i], m.valid_sets[i][0])
            for i, name in enumerate(self._valid_names)], feval)

    def _eval_sets(self, sets, feval) -> List[Tuple]:
        """``_eval_set`` of each ``(name, dev, fetch, metrics, dataset)``
        in ``sets``: ``fetch()`` brings the device score ``dev`` to the
        host.  An AUC whose ``classes`` the device holds is ranked there
        (``metrics.auc_rank_parts``) and only its exact parts come back;
        the score crosses only where a host metric or ``feval`` reads it.
        With telemetry on, one ``eval`` span, and in it, a set after
        another, the two spans that partition it: ``eval.fetch`` (the wait
        for the device's parts and the score's transfer, which block by
        themselves; their bytes, the row bucket's padding too, go to
        ``xfer.d2h_bytes{site=eval}``) and ``eval.metric`` (the division
        of the parts, the host metrics and ``feval``, which queue no
        device work); each AUC counts once in
        ``eval.auc_route{route=device|host}``.  No fence."""
        obs = self._model._obs
        if obs is not None:
            sp = obs.span("eval", mirror=False,
                          rows=sum(ds.num_data for *_, ds in sets))
        out = []
        for name, dev, fetch, metrics, ds in sets:
            ranked = [m for m in metrics
                      if getattr(m, "classes", None) is not None]
            launched = auc_rank_parts(dev, ranked[0].classes) \
                if ranked else None
            on_host = feval is not None or len(ranked) < len(metrics)
            if obs is not None:
                part = obs.span("eval.fetch")
            parts = self._model._eget(launched, "eval") if ranked else None
            score = fetch() if on_host else None
            if obs is not None:
                obs.end_eval_part(part)
                if on_host:
                    obs.count_d2h("eval", dev)
                for m in metrics:
                    if isinstance(m, AUCMetric):
                        obs.metrics.counter(
                            "eval.auc_route",
                            route="device" if m in ranked else "host").inc()
                part = obs.span("eval.metric")
            out.extend(self._eval_set(name, score, metrics, ds, feval,
                                      ranked, parts))
            if obs is not None:
                obs.end_eval_part(part)
        if obs is not None:
            obs.end_eval(sp)
        return out

    def _eval_set(self, name, score, metrics, dataset, feval,
                  ranked=(), parts=None) -> List[Tuple]:
        """The values of ``metrics`` and ``feval`` over the host
        ``score``; the AUCs in ``ranked`` take theirs from ``parts``,
        ``auc_rank_parts``' fetched output."""
        s = score[:, 0] if score is not None \
            and self._num_tree_per_iteration == 1 else score
        results = []
        for m in metrics:
            got = [(m.name, auc_from_parts(*parts), True)] \
                if m in ranked else m.eval(s)
            for mname, val, hib in got:
                results.append((name, mname, val, hib))
        if feval is not None:
            for fe in (feval if isinstance(feval, (list, tuple)) else [feval]):
                r = fe(s, dataset)
                rs = r if isinstance(r, list) else [r]
                for (mname, val, hib) in rs:
                    results.append((name, mname, val, hib))
        return results

    # ------------------------------------------------------------------
    def predict(self, data, start_iteration: int = 0,
                num_iteration: Optional[int] = None, raw_score: bool = False,
                pred_leaf: bool = False, pred_contrib: bool = False,
                pred_early_stop: bool = False, pred_early_stop_freq: int = 10,
                pred_early_stop_margin: float = 10.0, **kw) -> np.ndarray:
        """Prediction on raw features (gbdt_prediction.cpp:97 inner loop,
        Predictor analog).  ``pred_early_stop``: margin-based early exit
        across trees (prediction_early_stop.cpp:91)."""
        from .dataset import _is_scipy_sparse, _to_numpy_2d
        if isinstance(data, (str, os.PathLike)):
            # predict-from-file (the reference Predictor's text-input
            # path, c_api.cpp LGBM_BoosterPredictForFile): CSV/TSV/
            # LibSVM sniffed by the loader
            from .data_io import load_text
            data, _ = load_text(str(data))
        # reference contract (c_api predict + basic.py): the feature-count
        # mismatch only raises when predict_disable_shape_check is false
        # (config, or a predict-time override), and the error tells the
        # user about the param
        disable_shape_check = bool(kw.get(
            "predict_disable_shape_check",
            self.config.predict_disable_shape_check))
        if hasattr(data, "shape") and len(getattr(data, "shape", ())) == 2 \
                and data.shape[1] != self._max_feature_idx + 1 \
                and not disable_shape_check:
            # checked BEFORE the chunked-sparse recursion and without a
            # truthiness guard (a 1-feature model has _max_feature_idx
            # == 0 — falsy, but the check must still fire)
            from .basic import LightGBMError
            raise LightGBMError(
                f"The number of features in data ({data.shape[1]}) is "
                f"not the same as it was in training data "
                f"({self._max_feature_idx + 1}).\n"
                "You can set ``predict_disable_shape_check=true`` to "
                "discard this error, but please be aware what you are "
                "doing.")
        if _is_scipy_sparse(data) and data.shape[0] > 65536:
            # CSR prediction (LGBM_BoosterPredictForCSR analog): densify in
            # row chunks so peak memory stays bounded.
            csr = data.tocsr()
            chunks = [self.predict(csr[i:i + 65536],
                                   start_iteration=start_iteration,
                                   num_iteration=num_iteration,
                                   raw_score=raw_score, pred_leaf=pred_leaf,
                                   pred_contrib=pred_contrib,
                                   pred_early_stop=pred_early_stop,
                                   pred_early_stop_freq=pred_early_stop_freq,
                                   pred_early_stop_margin=pred_early_stop_margin,
                                   **kw)
                      for i in range(0, data.shape[0], 65536)]
            return np.concatenate(chunks, axis=0)
        x, _, _ = _to_numpy_2d(data)
        if x.shape[1] != self._max_feature_idx + 1:
            if not disable_shape_check:
                from .basic import LightGBMError
                raise LightGBMError(
                    f"The number of features in data ({x.shape[1]}) is not "
                    f"the same as it was in training data "
                    f"({self._max_feature_idx + 1}).\n"
                    "You can set ``predict_disable_shape_check=true`` to "
                    "discard this error, but please be aware what you are "
                    "doing.")
            # shape check disabled: the reference Predictor copies each
            # row into a ZERO-initialized num_feature buffer, so a
            # missing tail of features compares as 0.0 (a regular value
            # under the default zero_as_missing=false) — zero-fill, not
            # NaN; extra columns are ignored (trees only read trained
            # feature ids)
            nf_model = self._max_feature_idx + 1
            if x.shape[1] < nf_model:
                x = np.concatenate(
                    [x, np.zeros((len(x), nf_model - x.shape[1]),
                                 dtype=x.dtype)], axis=1)
            else:
                x = x[:, :nf_model]
        n = len(x)
        k = self._num_tree_per_iteration
        start_iteration = max(0, start_iteration)
        if num_iteration is None:
            # only an OMITTED num_iteration defaults to the best
            # iteration, and only from the start; an explicit <= 0 means
            # all trees (basic.py predict contract: None -> best, the C
            # side treats non-positive as unbounded)
            num_iteration = (self.best_iteration
                             if self.best_iteration > 0
                             and start_iteration <= 0 else
                             len(self.trees) // k)
        elif num_iteration <= 0:
            num_iteration = len(self.trees) // k
        t0, t1 = start_iteration * k, min((start_iteration + num_iteration) * k,
                                          len(self.trees))
        if n == 0 and not pred_contrib:
            # zero-row input: the empty result of the correct shape and
            # dtype, with NO device work (tracing a zero-row program per
            # batch shape is pure waste) — consistent with the
            # predict_disable_shape_check contract: the feature-count
            # check above already ran
            if pred_leaf:
                return np.zeros((0, t1 - t0), np.int32)
            if not raw_score and self.objective is not None:
                # converted output rides through f32 (convert_output)
                return np.zeros((0, k) if k > 1 else (0,), np.float32)
            return np.zeros((0, k) if k > 1 else (0,), np.float64)
        # bucketed engine path (serve/engine.py): device traversal under
        # a power-of-two-bucket compile cache; leaf routing and score
        # accumulation are byte-identical to the host walk below
        eng = self.predict_engine(n) if not pred_contrib \
            and not pred_early_stop else None
        if eng is not None:
            leaves = eng.leaf_ids(x)
            if pred_leaf:
                return np.ascontiguousarray(leaves[:, t0:t1])
            score = eng.raw_scores(x, t0, t1, leaves=leaves)
            return _finalize_score(score, k, self.objective,
                                   self._average_output, t0, t1,
                                   raw_score)
        if pred_leaf:
            out = np.zeros((n, t1 - t0), np.int32)
            for i, ti in enumerate(range(t0, t1)):
                out[:, i] = self.trees[ti].predict_leaf(x)
            return out
        if pred_contrib:
            from .shap import predict_contrib
            return predict_contrib(self, x, t0, t1)

        score = np.zeros((n, k))
        active = np.ones(n, bool) if pred_early_stop else None
        for it, ti in enumerate(range(t0, t1)):
            if active is not None and not active.any():
                break
            rows = active if active is not None else slice(None)
            score[rows, ti % k] += (self.tree_weights[ti]
                                    * self.trees[ti].predict(
                                        x[rows] if active is not None else x))
            if active is not None and ti % k == k - 1 \
                    and (it // k + 1) % pred_early_stop_freq == 0:
                if k == 1:
                    margin = np.abs(score[:, 0])
                else:
                    part = np.partition(score, -2, axis=1)
                    margin = part[:, -1] - part[:, -2]
                active &= margin < pred_early_stop_margin
        return _finalize_score(score, k, self.objective,
                               self._average_output, t0, t1, raw_score)

    # ------------------------------------------------------------------
    def to_c_code(self, num_iteration: Optional[int] = None) -> str:
        """Standalone C source for this model (GBDT::ModelToIfElse,
        gbdt_model_text.cpp:124 analog; CLI ``task=convert_model``)."""
        from .codegen import model_to_c
        return model_to_c(self, num_iteration=num_iteration)

    # ------------------------------------------------------------------
    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        """FeatureImportance (gbdt.cpp / boosting.h:270)."""
        nf = self._max_feature_idx + 1
        imp = np.zeros(nf)
        trees = self.trees if iteration is None else \
            self.trees[:iteration * self._num_tree_per_iteration]
        for t in trees:
            for i in range(t.num_nodes()):
                if importance_type == "split":
                    imp[t.split_feature[i]] += 1
                else:
                    imp[t.split_feature[i]] += t.split_gain[i]
        return imp

    # ------------------------------------------------------------------
    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0) -> str:
        """SaveModelToString (gbdt_model_text.cpp:311)."""
        cfg = getattr(self, "config", None)
        buf = io.StringIO()
        buf.write("tree\n")
        buf.write("version=v3\n")
        buf.write(f"num_class={self._num_class}\n")
        buf.write(f"num_tree_per_iteration={self._num_tree_per_iteration}\n")
        buf.write("label_index=0\n")
        buf.write(f"max_feature_idx={self._max_feature_idx}\n")
        obj_str = _objective_to_string(cfg) if cfg else getattr(
            self, "_objective_str", "regression")
        buf.write(f"objective={obj_str}\n")
        if self._average_output:
            buf.write("average_output\n")
        names = self.feature_names or [f"Column_{i}"
                                       for i in range(self._max_feature_idx + 1)]
        buf.write("feature_names=" + " ".join(names) + "\n")
        buf.write("feature_infos=" + " ".join(self._feature_infos()) + "\n")

        k = self._num_tree_per_iteration
        t0 = start_iteration * k
        t1 = len(self.trees) if num_iteration is None else \
            min(t0 + num_iteration * k, len(self.trees))
        blocks = []
        for i, ti in enumerate(range(t0, t1)):
            t = self.trees[ti]
            w = self.tree_weights[ti] if ti < len(self.tree_weights) else 1.0
            if w != 1.0:
                import copy
                t = copy.deepcopy(t)
                t.leaf_value *= w
                t.internal_value *= w
            blocks.append(t.to_string(i) + "\n")
        sizes = [len(b.encode()) for b in blocks]
        buf.write("tree_sizes=" + " ".join(str(s) for s in sizes) + "\n\n")
        for b in blocks:
            buf.write(b)
        buf.write("end of trees\n\n")
        buf.write("feature_importances:\n")
        # gains summed over the trees WRITTEN above ([t0:t1], like the
        # reference's FeatureImportance over the saved range) and rounded
        # through the same %g the tree blocks print: the importance
        # section stays consistent with THIS file's trees, so
        # save -> load -> save is byte-stable (subset saves included) and
        # a crash+resume run (whose leading trees were parsed from a
        # snapshot) sums exactly the gains a straight run's text records
        imp = np.zeros(self._max_feature_idx + 1)
        for t in self.trees[t0:t1]:
            for i in range(t.num_nodes()):
                imp[t.split_feature[i]] += float(f"{t.split_gain[i]:g}")
        order = np.argsort(-imp)
        for fi in order:
            if imp[fi] > 0:
                buf.write(f"{names[fi]}={imp[fi]:g}\n")
        buf.write("\nparameters:\n")
        if cfg is not None:
            for key, val in sorted(cfg.raw_params.items()):
                buf.write(f"[{key}: {val}]\n")
        buf.write("end of parameters\n\n")
        buf.write("pandas_categorical:null\n")
        return buf.getvalue()

    def _feature_infos(self) -> List[str]:
        infos = []
        ds = self.train_set
        if ds is None or ds.bin_mappers is None:
            return ["none"] * (self._max_feature_idx + 1)
        for f in range(ds.num_total_features):
            m = ds.bin_mappers[f]
            if m.is_trivial:
                infos.append("none")
            elif m.bin_type.name == "CATEGORICAL":
                infos.append(":".join(str(int(c)) for c in m.categories))
            else:
                ub = m.bin_upper_bound
                finite = ub[np.isfinite(ub)]
                lo = float(finite[0]) if len(finite) else 0.0
                hi = float(finite[-1]) if len(finite) else 0.0
                infos.append(f"[{lo:g}:{hi:g}]")
        return infos

    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> "Booster":
        """Write the model text atomically (temp file + ``os.replace``,
        utils/resilience.py): a crash mid-save can never leave a
        truncated model — the reference writes in place (gbdt_model_text
        SaveModelToFile), which is exactly how the round-5 outage could
        have corrupted its only snapshot."""
        from .utils.resilience import atomic_write
        atomic_write(filename,
                     self.model_to_string(num_iteration, start_iteration))
        return self

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   object_hook=None) -> Dict[str, Any]:
        """JSON model dump (GBDT::DumpModel, gbdt_model_text.cpp:21).
        ``object_hook`` is applied to every JSON object exactly like the
        reference (basic.py dump_model json.loads object_hook)."""
        k = self._num_tree_per_iteration
        t0 = start_iteration * k
        t1 = len(self.trees) if num_iteration is None else \
            min(t0 + num_iteration * k, len(self.trees))
        names = self.feature_names or [f"Column_{i}"
                                       for i in range(self._max_feature_idx + 1)]

        def node_json(t: Tree, node: int) -> Dict[str, Any]:
            if node < 0:
                leaf = ~node
                return {
                    "leaf_index": int(leaf),
                    "leaf_value": float(t.leaf_value[leaf]),
                    "leaf_weight": float(t.leaf_weight[leaf]),
                    "leaf_count": int(t.leaf_count[leaf]),
                }
            is_cat = bool(t.decision_type[node] & 1)
            return {
                "split_index": int(node),
                "split_feature": int(t.split_feature[node]),
                "split_gain": float(t.split_gain[node]),
                "threshold": float(t.threshold[node]),
                "decision_type": "==" if is_cat else "<=",
                "default_left": bool(t.decision_type[node] & 2),
                "missing_type": ["None", "Zero", "NaN"][
                    (t.decision_type[node] >> 2) & 3],
                "internal_value": float(t.internal_value[node]),
                "internal_weight": float(t.internal_weight[node]),
                "internal_count": int(t.internal_count[node]),
                "left_child": node_json(t, t.left_child[node]),
                "right_child": node_json(t, t.right_child[node]),
            }

        trees = []
        for i, ti in enumerate(range(t0, t1)):
            t = self.trees[ti]
            trees.append({
                "tree_index": i,
                "num_leaves": int(t.num_leaves),
                "num_cat": int(t.num_cat),
                "shrinkage": float(t.shrinkage),
                "tree_structure": node_json(t, 0 if t.num_leaves > 1 else -1),
            })
        out = {
            "name": "tree",
            "version": "v3",
            "num_class": self._num_class,
            "num_tree_per_iteration": self._num_tree_per_iteration,
            "label_index": 0,
            "max_feature_idx": self._max_feature_idx,
            "objective": getattr(self, "_objective_str", None) or
                (_objective_to_string(self.config) if hasattr(self, "config")
                 else "regression"),
            "average_output": self._average_output,
            "feature_names": names,
            "feature_importances": {
                names[f]: float(v)
                for f, v in enumerate(self.feature_importance("gain")) if v > 0},
            "tree_info": trees,
        }
        if object_hook is not None:
            import json as _json
            out = _json.loads(_json.dumps(out), object_hook=object_hook)
        return out

    # -- python-package convenience surface (basic.py parity) ----------
    def attr(self, key: str):
        """In-memory model attribute (basic.py Booster.attr)."""
        return getattr(self, "_attrs", {}).get(key)

    def set_attr(self, **kwargs) -> "Booster":
        """Set/unset (value None) model attributes (basic.py set_attr)."""
        attrs = getattr(self, "_attrs", None)
        if attrs is None:
            attrs = self._attrs = {}
        for k, v in kwargs.items():
            if v is None:
                attrs.pop(k, None)
            else:
                attrs[k] = str(v)
        return self

    def feature_name(self) -> List[str]:
        return list(self.feature_names)

    def shuffle_models(self, start_iteration: int = 0,
                       end_iteration: int = -1) -> "Booster":
        """LGBM_BoosterShuffleModels analog (basic.py shuffle_models)."""
        self._shuffle_models(start_iteration, end_iteration)
        return self

    def _bounds(self):
        """(lower, upper) summed per tree.  The reference folds shrinkage
        into leaf values so GetLowerBoundValue sums raw leaf extrema; this
        framework applies tree_weights at predict time (DART/RF), so the
        extrema must be scaled by the same weights here."""
        weights = list(self.tree_weights) if self.tree_weights else []
        lo = hi = 0.0
        for ti, t in enumerate(self.trees):
            w = float(weights[ti]) if ti < len(weights) else 1.0
            mn = float(np.min(t.leaf_value[:max(t.num_leaves, 1)])) * w
            mx = float(np.max(t.leaf_value[:max(t.num_leaves, 1)])) * w
            lo += min(mn, mx)
            hi += max(mn, mx)
        return lo, hi

    def lower_bound(self) -> float:
        """Weighted sum of per-tree minimum leaf values
        (GetLowerBoundValue)."""
        return self._bounds()[0]

    def upper_bound(self) -> float:
        """Weighted sum of per-tree maximum leaf values
        (GetUpperBoundValue)."""
        return self._bounds()[1]

    def get_leaf_output(self, tree_id: int, leaf_id: int) -> float:
        return float(self.trees[tree_id].leaf_value[leaf_id])

    def set_train_data_name(self, name: str) -> "Booster":
        self._train_data_name = name
        return self

    def get_split_value_histogram(self, feature, bins=None):
        """Histogram of a feature's split thresholds across the model
        (basic.py get_split_value_histogram)."""
        if isinstance(feature, str):
            feature = self.feature_names.index(feature)
        vals = [float(t.threshold[n]) for t in self.trees
                for n in range(t.num_nodes())
                if int(t.split_feature[n]) == int(feature)]
        vals = np.asarray(vals, np.float64)
        if bins is None:
            bins = max(min(len(vals), 32), 1)
        return np.histogram(vals, bins=bins)

    def trees_to_dataframe(self):
        """One row per node/leaf across the model
        (basic.py trees_to_dataframe); requires pandas."""
        import pandas as pd
        rows = []
        for ti, t in enumerate(self.trees):
            parents = {}
            for n in range(t.num_nodes()):
                for c in (t.left_child[n], t.right_child[n]):
                    parents[int(c)] = f"{ti}-S{n}"
            # 1-based depth by walk from the root (basic.py column)
            depth = {0: 1} if t.num_nodes() else {}
            stack = [0] if t.num_nodes() else [~0]
            if not t.num_nodes():
                depth[~0] = 1
            while stack:
                n = stack.pop()
                if n < 0:
                    continue
                for c in (int(t.left_child[n]), int(t.right_child[n])):
                    depth[c] = depth[n] + 1
                    if c >= 0:
                        stack.append(c)
            for n in range(t.num_nodes()):
                rows.append({
                    "tree_index": ti,
                    "node_depth": depth.get(n),
                    "node_index": f"{ti}-S{n}",
                    "left_child": f"{ti}-S{t.left_child[n]}"
                    if t.left_child[n] >= 0 else f"{ti}-L{~t.left_child[n]}",
                    "right_child": f"{ti}-S{t.right_child[n]}"
                    if t.right_child[n] >= 0 else f"{ti}-L{~t.right_child[n]}",
                    "parent_index": parents.get(n),
                    "split_feature": (self.feature_names[
                        int(t.split_feature[n])]
                        if self.feature_names else int(t.split_feature[n])),
                    "split_gain": float(t.split_gain[n]),
                    "threshold": float(t.threshold[n]),
                    "decision_type": "==" if (t.decision_type[n] & 1)
                    else "<=",
                    "missing_direction": "left"
                    if (t.decision_type[n] & 2) else "right",
                    "missing_type": ["None", "Zero", "NaN"][
                        (int(t.decision_type[n]) >> 2) & 3],
                    "value": float(t.internal_value[n]),
                    "weight": float(t.internal_weight[n]),
                    "count": int(t.internal_count[n]),
                })
            for leaf in range(t.num_leaves):
                rows.append({
                    "tree_index": ti,
                    "node_depth": depth.get(~leaf, 1),
                    "node_index": f"{ti}-L{leaf}",
                    "left_child": None, "right_child": None,
                    "parent_index": parents.get(~leaf),
                    "split_feature": None, "split_gain": None,
                    "threshold": None, "decision_type": None,
                    "missing_direction": None, "missing_type": None,
                    "value": float(t.leaf_value[leaf]),
                    # a stump records no weight/count (the reference's
                    # single-leaf tree_structure carries only the value)
                    "weight": float(t.leaf_weight[leaf])
                    if t.num_nodes() else None,
                    "count": int(t.leaf_count[leaf])
                    if t.num_nodes() else None,
                })
        return pd.DataFrame(rows)

    def eval(self, data: Dataset, name: str, feval=None) -> List[Tuple]:
        """Evaluate on an arbitrary dataset (basic.py Booster.eval)."""
        # grab the raw values BEFORE construct() (which may free them
        # under free_raw_data=True); predict() accepts dense or sparse
        raw = data.get_data()
        data.construct(self.config)
        score = np.asarray(self.predict(raw, raw_score=True))
        score = score.reshape(data.num_data, -1)
        metrics = self._make_metrics(data.metadata, data.num_data)
        return self._eval_set(name, score, metrics, data, feval)

    def refit(self, data, label, decay_rate: float = 0.9, **kw) -> "Booster":
        """Refit existing tree structures on new data
        (Booster.refit, basic.py / GBDT::RefitTree gbdt.cpp:287)."""
        import copy as _copy
        from .cli import refit as _refit
        from .dataset import _to_numpy_2d
        x, _, _ = _to_numpy_2d(data)
        new_booster = Booster(model_str=self.model_to_string())
        cfg = new_booster.config
        cfg.refit_decay_rate = decay_rate
        return _refit(new_booster, x, np.asarray(label, np.float32), cfg)

    def refit_with_leaves(self, leaf_preds: np.ndarray) -> "Booster":
        """GBDT::RefitTree with GIVEN per-tree leaf assignments
        (LGBM_BoosterRefit, c_api.h:578; gbdt.cpp:287-323): re-fit every
        tree's leaf values from the training labels' gradients at the
        evolving score, blending with refit_decay_rate.  ``leaf_preds``
        is [num_data, num_trees] (the pred_leaf layout)."""
        if self.train_set is None:
            raise ValueError("refit_with_leaves needs a booster with "
                             "training data (LGBM_BoosterCreate)")
        from .cli import refit_leaf_values
        leaf_preds = np.asarray(leaf_preds, np.int32)
        y = np.asarray(self.train_set.metadata.label, np.float32)
        refit_leaf_values(self, leaf_preds, y, self.config)
        # sync the model's cached state with the new leaf values (the
        # reference RefitTree runs train_score_updater_->AddScore per
        # tree, gbdt.cpp:320): device copies + the training score, so a
        # following UpdateOneIter/GetPredict sees the refit model
        m = getattr(self, "_model", None)
        if m is not None:
            import jax.numpy as jnp
            k = self._num_tree_per_iteration
            score = np.zeros((leaf_preds.shape[0], k), np.float64)
            for ti, t in enumerate(self.trees):
                if ti < len(m.device_trees):
                    dt = m.device_trees[ti]
                    lv = np.zeros(np.asarray(dt.leaf_value).shape[0],
                                  np.float32)
                    lv[:t.num_leaves] = t.leaf_value[:t.num_leaves]
                    dt.leaf_value = jnp.asarray(lv)
                w = m.tree_weights[ti] if ti < len(m.tree_weights) else 1.0
                score[:, ti % k] += w * t.leaf_value[leaf_preds[:, ti]]
            m.score = jnp.asarray(score, jnp.float32)
        self._drop_predict_cache()   # leaf values changed in place
        return self

    def _merge_from(self, other: "Booster") -> None:
        """LGBM_BoosterMerge (c_api.h:522): insert other's trees at the
        FRONT of this booster, self's after — GBDT::MergeFrom
        (gbdt.h:63-80) pushes the other booster's models first, so
        order-sensitive consumers (pred_leaf columns, iteration slicing,
        tree indices, saved tree order) must see other-first here too."""
        if other._num_tree_per_iteration != self._num_tree_per_iteration:
            raise ValueError("cannot merge boosters with different "
                             "num_tree_per_iteration")
        import copy as _copy
        new_trees = [_copy.deepcopy(t) for t in other.trees]
        new_weights = (list(other.tree_weights) if other.tree_weights
                       else [1.0] * len(new_trees))
        if self._model is not None:
            m = self._model
            m.models[:0] = new_trees
            m.tree_weights[:0] = new_weights
            # device_trees must stay aligned to the TAIL of models
            # (models/gbdt.py add_valid_set: the first
            # len(models)-len(device_trees) trees replay host-side).
            # Inserting at the front keeps self's device tail intact;
            # other's device copies can only be prepended when BOTH
            # sides have full device coverage (otherwise a gap would
            # break the tail invariant).
            other_dev = (other._model.device_trees
                         if getattr(other, "_model", None) is not None
                         else [])
            if (len(other_dev) == len(new_trees)
                    and len(m.device_trees)
                    == len(m.models) - len(new_trees)):
                m.device_trees[:0] = other_dev
            m.iter_ += len(new_trees) // self._num_tree_per_iteration
            self._sync_trees()
        else:
            self.trees[:0] = new_trees
            self.tree_weights[:0] = new_weights
            self._drop_predict_cache()

    def _shuffle_models(self, start_iter: int, end_iter: int) -> None:
        """LGBM_BoosterShuffleModels (c_api.h:512; GBDT::ShuffleModels):
        permute whole iterations in [start_iter, end_iter) (<=0 end =
        all) with the reference's fixed Random(17) swap sequence."""
        k = self._num_tree_per_iteration
        trees = self.trees
        n_iter = len(trees) // k
        end_iter = n_iter if end_iter <= 0 else min(end_iter, n_iter)
        start_iter = max(0, start_iter)
        if end_iter - start_iter < 2:
            return
        # reference-exact permutation: GBDT::ShuffleModels (gbdt.h:82-105)
        # runs a partial Fisher-Yates with its own LCG seeded at 17
        # (Random::NextShort, utils/random.h: x = 214013*x + 2531011,
        # take bits 16..30) — reproduce the identical swap sequence so
        # LGBM_BoosterShuffleModels matches the reference ABI bit-for-bit
        lcg = 17

        def _next_short(lo: int, hi: int) -> int:
            nonlocal lcg
            lcg = (214013 * lcg + 2531011) & 0xFFFFFFFF
            return ((lcg >> 16) & 0x7FFF) % (hi - lo) + lo

        indices = list(range(n_iter))
        for i in range(start_iter, end_iter - 1):
            j = _next_short(i + 1, end_iter)
            indices[i], indices[j] = indices[j], indices[i]
        perm = [indices[i] for i in range(start_iter, end_iter)]

        def _permute(seq):
            """Apply the same iteration-block permutation to any list
            position-paired with the trees (weights, device trees)."""
            if len(seq) != len(trees):
                return seq             # not paired 1:1 — leave untouched
            blocks = [seq[i * k:(i + 1) * k] for i in range(n_iter)]
            shuffled = (blocks[:start_iter]
                        + [blocks[i] for i in perm]
                        + blocks[end_iter:])
            return [t for b in shuffled for t in b]

        new_trees = _permute(trees)
        if self._model is not None:
            m = self._model
            m.tree_weights[:] = _permute(list(m.tree_weights))
            if len(m.device_trees) == len(trees):
                m.device_trees[:] = _permute(list(m.device_trees))
            elif m.device_trees:
                # partial device coverage cannot stay tail-aligned under
                # a permutation of all models — drop the device copies
                # and let consumers (add_valid_set) replay host-side
                m.device_trees.clear()
            m.models[:] = new_trees
            self._sync_trees()
        else:
            self.tree_weights[:] = _permute(list(self.tree_weights))
            self.trees[:] = new_trees
            self._drop_predict_cache()

    def reset_training_data(self, train_set) -> "Booster":
        """LGBM_BoosterResetTrainingData (c_api.h:540): keep the model,
        continue training on a different dataset.  The training score is
        rebuilt by predicting the new data with the current model."""
        from .models import create_boosting
        from .objectives import create_objective
        import jax.numpy as jnp
        old_models = self._model.models if self._model is not None \
            else list(self.trees)
        old_weights = self._model.tree_weights if self._model is not None \
            else list(self.tree_weights)
        old_iter = (self._model.iter_ if self._model is not None
                    else len(old_models) // self._num_tree_per_iteration)
        cfg = self.config
        if not train_set._constructed and train_set.params:
            # dataset params are the binning base (see __init__); the
            # booster's training params override
            from .config import canonical_params
            cfg = Config({**canonical_params(train_set.params),
                          **canonical_params(self.config.raw_params)})
        new_train = train_set.construct(cfg)
        if old_models and new_train.raw_data is None:
            # without raw values the existing ensemble cannot be scored
            # on the new data — continuing would silently train as if
            # the model predicted zero everywhere (same guard as
            # add_valid_set for the free_raw_data=True case); checked
            # BEFORE any state is replaced so a caught error leaves the
            # booster usable
            raise ValueError(
                "reset_training_data on a non-empty booster needs the new "
                "dataset's raw values to rebuild the training score; "
                "construct it with free_raw_data=False")
        self.train_set = new_train
        self._model = create_boosting(self.config, self.train_set,
                                      create_objective(self.config))
        m = self._model
        m.models = list(old_models)
        m.tree_weights = list(old_weights)
        m.iter_ = old_iter
        if old_models and self.train_set.raw_data is not None:
            raw = np.asarray(self.train_set.raw_data, np.float64)
            score = np.zeros((len(raw), self._num_tree_per_iteration),
                             np.float64)
            for ti, t in enumerate(old_models):
                kk = ti % self._num_tree_per_iteration
                w = old_weights[ti] if ti < len(old_weights) else 1.0
                score[:, kk] += w * t.predict(raw)
            m.score = jnp.asarray(score, jnp.float32)
        self._sync_trees()
        return self

    # ------------------------------------------------------------------
    def _load_model_string(self, s: str) -> None:
        """LoadModelFromString (gbdt_model_text.cpp:421)."""
        if "num_class=" not in s:
            raise ValueError("input is not a lightgbm_tpu model "
                             "(missing header)")
        header, _, rest = s.partition("\nTree=")
        kv: Dict[str, str] = {}
        for line in header.splitlines():
            if "=" in line:
                k, v = line.split("=", 1)
                kv[k.strip()] = v.strip()
            elif line.strip() == "average_output":
                self._average_output = True
        self._num_class = int(kv.get("num_class", "1"))
        self._num_tree_per_iteration = int(kv.get("num_tree_per_iteration", "1"))
        self._max_feature_idx = int(kv.get("max_feature_idx", "0"))
        self._objective_str = kv.get("objective", "regression")
        self.feature_names = kv.get("feature_names", "").split(" ") \
            if kv.get("feature_names") else []
        obj_kv = _objective_from_string(self._objective_str)
        params = {"objective": obj_kv.pop("objective", "regression")}
        params.update(obj_kv)
        self.config = Config(params)
        # loaded boosters predict through jitted paths too (bucketed
        # engine / serve): same cache bring-up as the training path
        from .utils.compile_cache import maybe_enable_from_config
        maybe_enable_from_config(self.config)
        self.objective = create_objective(self.config)

        body = "Tree=" + rest
        tree_blocks = body.split("\nend of trees")[0]
        self.trees = []
        for block in tree_blocks.split("Tree="):
            block = block.strip()
            if not block:
                continue
            self.trees.append(Tree.from_string("Tree=" + block))
        self.tree_weights = [1.0] * len(self.trees)
        self.best_iteration = -1

    @classmethod
    def model_from_string(cls, model_str: str) -> "Booster":
        return cls(model_str=model_str)
