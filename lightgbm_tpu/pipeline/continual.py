"""Continual boosting pipeline: train -> publish -> serve as ONE loop.

ROADMAP item 6 closed: byte-identical resume (snapshot.py), ``init_model``
continuation (engine.py), SHA-verified artifacts + engine self-check and
hot-swap serving (serve/) all exist — this module connects them into a
production continual-training system with freshness guarantees:

- :class:`ContinualTrainer` runs GENERATIONS.  Each generation (a)
  appends a new data chunk, (b) boosts ``continual_rounds`` more
  iterations from the newest COMPLETE snapshot through the existing
  ``engine.train`` init_model path (``continual_decay`` optionally
  shrinks the carried-over trees' contributions), (c) publishes a
  SHA-pinned snapshot artifact atomically (manifest written last — the
  completeness marker crash-safe training already relies on), and (d)
  promotes it into the serving :class:`~..serve.registry.ModelRegistry`
  only after the TWO-STAGE gate below.
- The gate (:func:`gated_promote`): stage 1 is the SHA-verified shadow
  load — manifest checksum enforced end to end plus the engine's
  byte-parity ``self_check``, whose FAILURE here is a gate refusal (plain
  serving merely demotes to the host walk; a continual promotion never
  ships an unproven engine).  Stage 2 is the SHADOW-TRAFFIC PARITY
  PROBE: the last K live serve batches replay through the candidate in a
  background thread; it must score within an objective-aware tolerance
  of the incumbent (``shadow_probe_tolerance`` — probabilities compare
  absolutely, unbounded outputs relative to the incumbent's scale) and
  must not regress the eval metric on the newest chunk by more than
  ``shadow_probe_metric_tolerance``.  Only then does the registry
  pointer swap — the PV-Tree discipline (arXiv:1611.01276) applied to
  model promotion: an explicit vote, never optimism.
- On ANY gate failure, probe timeout (``continual_timeout_s``) or
  in-process crash the generation ROLLS BACK automatically: the
  incumbent keeps serving (the registry was never activated), the
  candidate artifact is QUARANTINED (moved under
  ``continual_quarantine_dir`` with a blackbox reason dump, manifest
  first so a crash mid-quarantine can never leave it looking complete)
  and ``continual.rollbacks`` counts it.  A process death mid-generation
  is handled by the publish discipline instead: restart boosts from the
  newest complete snapshot and converges byte-identically with the
  uninterrupted run (tests/test_zcontinual.py kill matrix).

Every stage runs under ``utils/resilience.RetryPolicy`` with its own
fault-injection site (``continual_append`` / ``continual_boost`` /
``continual_publish`` / ``continual_promote`` / ``shadow_probe``) and
emits ``continual.*`` metrics (freshness lag seconds, generations
published / rolled back, gate latency) plus spans.  Drivable via
``cli task=continual`` and the serve server's ``POST /promote`` +
``GET /freshness`` surface; chaos-proven by
``tools/soak_serve.py --continual``.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..config import Config
from ..utils import faultinject
from ..utils.log import Log
from ..utils.resilience import (RetryPolicy, atomic_write,
                                is_retryable_device_error, retry_call)

# probability-valued objective outputs: the parity probe compares these
# absolutely (the scores live in [0, 1]); everything else compares
# relative to the incumbent's scale
_PROBABILITY_OBJECTIVES = {"binary", "multiclass", "multiclassova",
                           "cross_entropy", "cross_entropy_lambda"}


class GateFailure(RuntimeError):
    """A promotion gate refused the candidate (verification, engine
    self-check, shadow parity, metric regression, or probe timeout).
    The incumbent keeps serving; the caller quarantines the candidate.
    Never retried — a refusal is a verdict, not a transient."""

    def __init__(self, stage: str, reason: str,
                 version: Optional[str] = None):
        self.stage = stage
        self.reason = reason
        # the refused candidate's registry version id (when it got as
        # far as a shadow load) — soak/ops tooling asserts it never
        # served a request
        self.version = version
        super().__init__(f"continual gate failed at {stage}: {reason}")


# ---------------------------------------------------------------------------
# gate primitives
# ---------------------------------------------------------------------------

def score_gate_reason(objective: str, cand: np.ndarray, inc: np.ndarray,
                      tol: float) -> Optional[str]:
    """Objective-aware shadow-parity check of one replayed batch:
    None when the candidate's scores are acceptably close to the
    incumbent's, else a reason string.  This bounds score MOVEMENT, not
    byte parity — a continual candidate legitimately differs from the
    incumbent by its fresh trees; a corrupt or insane one differs by
    orders of magnitude."""
    cand = np.asarray(cand, np.float64)
    inc = np.asarray(inc, np.float64)
    if cand.shape != inc.shape:
        return (f"output shape {cand.shape} != incumbent's {inc.shape}")
    if not np.all(np.isfinite(cand)):
        return "candidate produced non-finite scores"
    if cand.size == 0:
        return None
    # a degraded INCUMBENT (non-finite scores) must not blind the gate:
    # NaN poisons max() and every NaN comparison is False, which would
    # pass ANY candidate exactly when serving is already sick.  Compare
    # on the incumbent's finite entries only
    finite = np.isfinite(inc)
    if not np.any(finite):
        return None     # nothing sane to compare against
    worst = float(np.max(np.abs(cand[finite] - inc[finite])))
    if objective in _PROBABILITY_OBJECTIVES:
        if worst > tol:
            return (f"probability drift {worst:.6g} > "
                    f"shadow_probe_tolerance {tol:g}")
        return None
    # unbounded outputs (regression/ranking/raw): relative to the
    # incumbent's scale, floored at 1 so near-zero scores don't demand
    # absolute agreement tighter than the tolerance itself
    scale = max(1.0, float(np.max(np.abs(inc[finite]))))
    if worst / scale > tol:
        return (f"relative score drift {worst / scale:.6g} > "
                f"shadow_probe_tolerance {tol:g} "
                f"(|delta| {worst:.6g} at scale {scale:.6g})")
    return None


def lineage_gate_reason(candidate, incumbent, rows: np.ndarray,
                        decay: float, rtol: float) -> Optional[str]:
    """The SHARP parity invariant of a continual candidate: its leading
    trees ARE the incumbent's (scaled by ``continual_decay``), so its
    raw-score prefix prediction must reproduce the incumbent's raw
    scores to float rounding — independent of how far training has
    converged, which the drift check cannot be.  A corrupt, truncated
    or wrong-lineage candidate fails HERE even when its outputs look
    plausible.  None = parity holds; only meaningful when the candidate
    was boosted from the serving incumbent (the trainer's case — an
    operator promoting an unrelated retrain skips it)."""
    k = max(1, incumbent._num_tree_per_iteration)
    n_prev = len(incumbent.trees) // k
    if len(candidate.trees) < len(incumbent.trees):
        return (f"candidate carries {len(candidate.trees)} trees, fewer "
                f"than the incumbent's {len(incumbent.trees)} — not a "
                "continuation")
    if n_prev == 0 or not len(rows):
        return None
    prefix = np.asarray(candidate.predict(rows, num_iteration=n_prev,
                                          raw_score=True), np.float64)
    base = np.asarray(incumbent.predict(rows, raw_score=True),
                      np.float64) * decay
    if prefix.shape != base.shape:
        return (f"prefix output shape {prefix.shape} != incumbent's "
                f"{base.shape}")
    if not np.all(np.isfinite(prefix)):
        return "candidate prefix produced non-finite scores"
    # non-finite incumbent entries are the incumbent's degradation, not
    # lineage evidence either way — compare on the finite ones (NaN
    # comparisons are always False and would silently PASS corruption)
    finite = np.isfinite(base)
    if not np.any(finite):
        return None
    scale = np.maximum(1.0, np.abs(base[finite]))
    worst = float(np.max(np.abs(prefix[finite] - base[finite]) / scale))
    if worst > rtol:
        return (f"lineage parity violated: candidate's first {n_prev} "
                f"iterations diverge from the incumbent by "
                f"{worst:.3g} relative (allowed {rtol:g}, decay "
                f"{decay:g}) — the candidate is not the incumbent "
                "plus new trees")
    return None


def gate_metric_value(objective: str, pred: np.ndarray,
                      y: np.ndarray) -> Tuple[str, float, bool]:
    """Self-contained ``(name, value, higher_better)`` eval of
    predictions on the gate set — the metric-regression leg of the
    probe.  Deliberately tiny: logloss for the classification families,
    L2 for everything else (a loaded candidate has no Dataset to drive
    the full metric registry with)."""
    pred = np.asarray(pred, np.float64)
    y = np.asarray(y, np.float64).reshape(-1)
    eps = 1e-15
    if objective == "binary":
        p = np.clip(pred.reshape(-1), eps, 1.0 - eps)
        return ("binary_logloss",
                float(-np.mean(y * np.log(p)
                               + (1.0 - y) * np.log(1.0 - p))), False)
    if objective in ("multiclass", "multiclassova"):
        p = np.clip(pred.reshape(len(y), -1), eps, 1.0)
        idx = y.astype(np.int64)
        return ("multi_logloss",
                float(-np.mean(np.log(p[np.arange(len(y)), idx]))), False)
    return ("l2", float(np.mean((pred.reshape(len(y), -1)[:, 0] - y)
                                ** 2)), False)


def shadow_parity_probe(candidate, incumbent, batches: List[np.ndarray],
                        cfg: Config,
                        eval_set: Optional[Tuple[np.ndarray, np.ndarray]]
                        = None,
                        timeout_s: Optional[float] = None,
                        lineage_decay: Optional[float] = None) -> Dict:
    """Replay ``batches`` (the last K live serve batches, or chunk
    slices when there is no traffic yet) through the candidate AND the
    incumbent in a BACKGROUND thread; the serving hot path never waits
    on it.  Returns a report dict — ``ok`` True only when every batch
    scored within the objective-aware tolerance and the eval metric did
    not regress past ``shadow_probe_metric_tolerance``.  A probe that
    exceeds ``timeout_s`` (``continual_timeout_s``) is a FAILURE, not a
    wait — a hung candidate must roll back, not stall freshness."""
    result: Dict[str, Any] = {}

    def _run() -> None:
        try:
            faultinject.check("shadow_probe")
            checked = 0
            for rows in batches:
                c = candidate.predict(rows)
                i = incumbent.predict(rows)
                reason = score_gate_reason(cfg.objective, c, i,
                                           cfg.shadow_probe_tolerance)
                if reason is not None:
                    result["reason"] = f"batch {checked}: {reason}"
                    return
                checked += 1
            if lineage_decay is not None and batches:
                # batch-independent invariant: ONE raw-prefix replay
                # (the first batch) proves it — running it per batch
                # would triple the probe's forest-traversal cost for
                # no added coverage
                reason = lineage_gate_reason(
                    candidate, incumbent, batches[0], lineage_decay,
                    cfg.shadow_probe_lineage_tolerance)
                if reason is not None:
                    result["reason"] = reason
                    return
            result["batches"] = checked
            if eval_set is not None and len(eval_set[0]):
                x, y = eval_set
                name, cv, hib = gate_metric_value(
                    cfg.objective, candidate.predict(x), y)
                _n, iv, _h = gate_metric_value(
                    cfg.objective, incumbent.predict(x), y)
                worse = (iv - cv) if hib else (cv - iv)
                result["metric"] = {"name": name,
                                    "candidate": round(cv, 8),
                                    "incumbent": round(iv, 8)}
                if worse > cfg.shadow_probe_metric_tolerance:
                    result["reason"] = (
                        f"eval metric {name} regressed: candidate "
                        f"{cv:.6g} vs incumbent {iv:.6g} (allowed "
                        f"{cfg.shadow_probe_metric_tolerance:g})")
                    return
            result["ok"] = True
        except BaseException as e:      # noqa: BLE001 — the probe thread
            # must report, never kill the pipeline
            result["error"] = f"{type(e).__name__}: {e}"

    t = threading.Thread(target=_run, daemon=True,
                         name="lgbtpu-shadow-probe")
    t0 = time.perf_counter()
    t.start()
    t.join(timeout_s if timeout_s and timeout_s > 0 else None)
    if t.is_alive():
        return {"ok": False,
                "reason": f"shadow probe exceeded continual_timeout_s "
                          f"({timeout_s:g}s)"}
    out = {"ok": bool(result.get("ok")),
           "probe_s": round(time.perf_counter() - t0, 6)}
    for k in ("batches", "metric"):
        if k in result:
            out[k] = result[k]
    if not out["ok"]:
        out["reason"] = result.get("error") \
            or result.get("reason", "probe aborted")
    return out


def gated_promote(registry, *, snapshot: Optional[str] = None,
                  model_file: Optional[str] = None,
                  expected_sha256: Optional[str] = None,
                  cfg: Optional[Config] = None,
                  batches: Optional[List[np.ndarray]] = None,
                  eval_set: Optional[Tuple[np.ndarray, np.ndarray]] = None,
                  metrics=None, version: Optional[str] = None,
                  lineage_decay: Optional[float] = None,
                  activate: bool = True) -> Tuple[str, Dict]:
    """Two-stage gated promotion into a ``ModelRegistry`` — the ONLY
    sanctioned way a continual candidate starts serving.

    Stage 1: SHA-verified SHADOW load (``activate=False`` — the
    candidate is resident but takes no traffic).  The registry enforces
    the checksum pin and runs the engine ``self_check``; a self-check
    that FAILED is a gate refusal here (``ServedModel
    .self_check_failed``), not the host-walk demotion plain serving
    settles for.  Stage 2: the shadow-traffic parity probe against the
    incumbent.  Both pass -> ``registry.activate`` flips the pointer
    (in-flight requests finish on the incumbent, the hot-swap
    contract).  Anything fails -> the candidate is unloaded (it never
    served a request) and :class:`GateFailure` raises for the caller to
    quarantine.  Returns ``(version, gate_report)``.

    ``activate=False`` runs the FULL gate but leaves the passed
    candidate resident without flipping the registry's current pointer
    — the per-segment promote (fleet serving): the caller routes a
    segment at the returned version instead of making it the
    default."""
    cfg = cfg if cfg is not None else Config({})
    faultinject.check("continual_promote")
    from ..serve.registry import NoModelError
    t0 = time.perf_counter()
    had_incumbent = True
    try:
        registry.current()
    except NoModelError:
        had_incumbent = False
    if snapshot is not None:
        version = registry.load_snapshot(snapshot, version=version,
                                         activate=False,
                                         expected_sha256=expected_sha256)
    else:
        version = registry.load(model_file=model_file, version=version,
                                activate=False,
                                expected_sha256=expected_sha256)
    report: Dict[str, Any] = {"version": version}
    try:
        cand = registry.get(version)
        if cand.self_check_failed:
            raise GateFailure(
                "self_check",
                "engine byte-parity self-check failed (plain serving "
                "would demote to the host walk; a continual promotion "
                "refuses the candidate)")
        inc = None
        if had_incumbent:
            inc = registry.current()
        if inc is not None and inc.version != version:
            probe = shadow_parity_probe(
                cand.booster, inc.booster, batches or [], cfg,
                eval_set=eval_set, timeout_s=cfg.continual_timeout_s,
                lineage_decay=lineage_decay)
            report["probe"] = probe
            if not probe["ok"]:
                raise GateFailure("shadow_probe", probe["reason"])
        if activate:
            registry.activate(version)
        report["gate_s"] = round(time.perf_counter() - t0, 6)
        if metrics is not None:
            metrics.histogram("continual.gate_seconds").observe(
                report["gate_s"])
        return version, report
    except BaseException as e:
        # the candidate never served (a shadow load takes no traffic,
        # even into an empty registry): expel it.  force is belt and
        # braces for the no-incumbent case
        try:
            registry.unload(version, force=not had_incumbent)
        except Exception:       # noqa: BLE001 — rollback is best-effort
            pass
        if isinstance(e, GateFailure):
            e.version = version
        raise


# ---------------------------------------------------------------------------
# the trainer loop
# ---------------------------------------------------------------------------

class ContinualTrainer:
    """Freshness-guaranteed continual boosting loop (module docstring).

    Construct with the training params and (optionally) the base data;
    each :meth:`run_generation` call appends a chunk and runs
    append -> boost -> publish -> promote, returning a report dict with
    ``status`` ``"published"`` or ``"rolled_back"``.  Attach a live
    ``serve.Server`` to promote into its registry (sharing its metrics
    registry and shadow-traffic ring) or run standalone — the gates run
    either way, against an in-memory incumbent.

    Thread topology: the generation loop runs on ONE trainer thread
    (stages never overlap), but a live server's HTTP threads read the
    freshness surface (``generation`` / :meth:`freshness_lag_s` /
    ``last_publish`` via ``GET /freshness``) while a generation is in
    flight — that cross-thread state is lock-guarded; the bulk data
    (``_x``/``_chunk_x`` …) is trainer-thread-only and stays lock-free.

    Lock contract (tools/analyze/check_races.py):
        _lock guards: generation, _chunk_t, _last_promote_t
        _lock guards: last_publish
        registry type: lightgbm_tpu/serve/registry.py:ModelRegistry
        server type: lightgbm_tpu/serve/server.py:Server
    """

    def __init__(self, params, x=None, y=None, *, server=None,
                 registry=None):
        self.config = params if isinstance(params, Config) \
            else Config(params or {})
        self.params: Dict[str, Any] = dict(
            self.config.raw_params if isinstance(params, Config)
            else (params or {}))
        if not self.config.output_model:
            raise ValueError("continual training needs output_model "
                             "(the published-snapshot base path)")
        if 0 < self.config.snapshot_keep < 2:
            # publish prunes to snapshot_keep; with keep=1 a gate
            # failure would quarantine the ONLY snapshot and strand the
            # next generation with nothing to boost from
            Log.warning("continual: snapshot_keep=1 cannot hold the "
                        "incumbent through a rollback; using 2")
            self.config.snapshot_keep = 2
        self.server = server
        self.registry = registry if registry is not None \
            else (server.registry if server is not None else None)
        if server is not None:
            self.metrics = server.metrics
            self.tracer = server.tracer
            server.continual = self
        else:
            from ..obs import MetricsRegistry
            self.metrics = MetricsRegistry()
            self.tracer = None
        # pre-register the counter family: a dashboard (or test) reading
        # the snapshot sees explicit zeros, not missing keys
        for c in ("continual.generations", "continual.published",
                  "continual.rollbacks", "continual.quarantined"):
            self.metrics.counter(c)
        self._retry = RetryPolicy(
            max_attempts=max(1, self.config.continual_retries + 1),
            base_delay_s=0.05, max_delay_s=1.0)
        # guards the freshness surface served to HTTP threads (class
        # docstring lock contract)
        self._lock = threading.Lock()
        self.generation = 0             # completed (promoted) generations
        self.last_publish: Dict[str, Any] = {}
        self._incumbent = None          # standalone-mode gate anchor
        self._incumbent_sha: Optional[str] = None
        self._boost_base_sha: Optional[str] = None
        self._x: Optional[np.ndarray] = None
        self._y: Optional[np.ndarray] = None
        self._chunk_x: Optional[np.ndarray] = None
        self._chunk_y: Optional[np.ndarray] = None
        self._chunk_t: Optional[float] = None
        self._last_promote_t: Optional[float] = None
        if x is not None:
            self._x = np.asarray(x, np.float64)
            self._y = np.asarray(y)
            self._chunk_x, self._chunk_y = self._x, self._y

    # -- stage plumbing ----------------------------------------------------
    def _stage(self, name: str, fn):
        """Run one pipeline stage under the retry policy + a span.
        Gate refusals are never retried (a verdict, not a transient);
        injected faults match the resilience classifier's patterns so a
        ``site:1`` spec exercises the REAL retry path."""
        span = (self.tracer.span(f"continual.{name}")
                if self.tracer is not None else None)
        try:
            return retry_call(
                fn, policy=self._retry,
                classify=lambda e: not isinstance(e, GateFailure)
                and is_retryable_device_error(e),
                label=f"continual.{name}")
        finally:
            if span is not None:
                span.end()

    @property
    def quarantine_dir(self) -> str:
        return self.config.continual_quarantine_dir \
            or self.config.output_model + ".quarantine"

    def freshness_lag_s(self, now: Optional[float] = None) -> Optional[float]:
        """Seconds between the newest chunk's arrival and its model
        serving — the headline freshness number while a generation is
        in flight, frozen at the promoted lag after it lands."""
        with self._lock:         # HTTP threads vs the trainer loop
            chunk_t = self._chunk_t
            promote_t = self._last_promote_t
        return self._lag(chunk_t, promote_t, now)

    @staticmethod
    def _lag(chunk_t, promote_t, now=None) -> Optional[float]:
        if chunk_t is None:
            return None
        now = time.time() if now is None else now
        if promote_t is not None and promote_t >= chunk_t:
            return round(promote_t - chunk_t, 6)
        return round(now - chunk_t, 6)

    def freshness_snapshot(self, now: Optional[float] = None) -> Dict:
        """One-lock snapshot of the freshness surface — the form
        ``GET /freshness`` consumes.  Composing the same fields from
        separate ``generation`` / :meth:`freshness_lag_s` /
        ``last_publish`` reads would let a promote land between them
        and serve a torn pair (generation N next to gen-N+1's publish
        record)."""
        with self._lock:
            return {"generation": self.generation,
                    "freshness_lag_s": self._lag(
                        self._chunk_t, self._last_promote_t, now),
                    "last_publish": dict(self.last_publish) or None}

    # -- stages ------------------------------------------------------------
    def append_chunk(self, x, y) -> None:
        """(a) ingest one new data chunk."""
        x = np.asarray(x, np.float64)
        y = np.asarray(y)

        def _do():
            faultinject.check("continual_append")
            if self._x is None:
                self._x, self._y = x, y
            else:
                self._x = np.concatenate([self._x, x], axis=0)
                self._y = np.concatenate([self._y, y], axis=0)
            self._chunk_x, self._chunk_y = x, y
            with self._lock:     # /freshness reads the arrival stamp
                self._chunk_t = time.time()

        self._stage("append", _do)

    def boost(self):
        """(b) boost ``continual_rounds`` more iterations from the
        newest complete snapshot through the init_model path; returns
        ``(booster, dataset)`` with the snapshot's trees merged in."""
        if self._x is None:
            raise ValueError("no data: append a chunk (or construct "
                             "with base x/y) before boosting")

        def _do():
            faultinject.check("continual_boost")
            from ..booster import Booster
            from ..dataset import Dataset
            from ..engine import train as train_fn
            from ..snapshot import find_latest_complete_snapshot
            prev = None
            self._boost_base_sha = None
            found = find_latest_complete_snapshot(
                self.config.output_model,
                verify=self.config.serve_verify_artifacts)
            if found is not None:
                prev = Booster(model_file=found[1])
                try:
                    # the base artifact's checksum: the promote gate
                    # applies the lineage-parity check only when the
                    # serving incumbent IS this snapshot (an operator
                    # may have hot-swapped an unrelated model in — a
                    # continuation of THIS base is then legitimately
                    # not a continuation of the incumbent)
                    with open(found[1] + ".manifest.json",
                              encoding="utf-8") as f:
                        self._boost_base_sha = json.load(f).get(
                            "model_sha256")
                except (OSError, ValueError):
                    pass
                decay = self.config.continual_decay
                if decay < 1.0:
                    if any(t.is_linear for t in prev.trees):
                        raise ValueError(
                            "continual_decay is not supported for "
                            "linear-tree models: only the constant "
                            "leaf values would decay, leaving the "
                            "leaf linear models at full weight")
                    for t in prev.trees:
                        t.shrink(decay)
                    prev._drop_predict_cache()
            ds = Dataset(self._x, label=self._y,
                         params=dict(self.params),
                         free_raw_data=False)
            p = dict(self.params)
            # run-control knobs stripped: the GENERATION is the unit of
            # redo (publish is the only snapshot writer; a restart
            # re-runs the whole generation deterministically), and the
            # inner round count is continual_rounds, never the params'
            from ..config import _ALIASES
            for k in list(p):
                if _ALIASES.get(k, k) in ("resume", "snapshot_freq",
                                          "num_iterations", "task",
                                          "continual_data"):
                    p.pop(k)
            return train_fn(p, ds,
                            num_boost_round=self.config.continual_rounds,
                            init_model=prev), ds

        return self._stage("boost", _do)

    def publish(self, booster, ds) -> Tuple[str, str, int]:
        """(c) write the candidate as a SHA-pinned snapshot artifact
        (atomic, manifest last) and prune to ``snapshot_keep``; returns
        ``(path, model_sha256, iteration)``."""

        def _do():
            faultinject.check("continual_publish")
            from ..snapshot import params_signature, write_snapshot
            # the FULL forest's iteration count (prev snapshot's trees
            # merged in), not current_iteration — that counts only this
            # generation's boosting
            it = len(booster.trees) // max(
                1, booster._num_tree_per_iteration)
            write_snapshot(booster, None, self.config, it,
                           params_signature(self.params), ds)
            path = f"{self.config.output_model}.snapshot_iter_{it}"
            with open(path + ".manifest.json", encoding="utf-8") as f:
                sha = json.load(f)["model_sha256"]
            return path, sha, it

        return self._stage("publish", _do)

    def promote(self, path: str, sha: str) -> Tuple[str, Dict]:
        """(d) two-stage gated promotion of the published artifact —
        into the attached registry, or against the in-memory incumbent
        when running standalone."""

        def _do():
            if self.registry is not None:
                prev = None
                try:
                    prev = self.registry.current().version
                except Exception:   # noqa: BLE001 — no incumbent yet
                    pass
                out = gated_promote(
                    self.registry, snapshot=self.config.output_model,
                    expected_sha256=sha, cfg=self.config,
                    batches=self._probe_batches(),
                    eval_set=self._eval_set(), metrics=self.metrics,
                    lineage_decay=self._lineage_decay(
                        self._registry_incumbent_sha()))
                # residency hygiene: with no serve_max_resident cap a
                # generation-every-few-minutes pipeline would keep
                # every superseded incumbent (booster + device tables)
                # resident forever — drop the displaced one after a
                # successful swap; in-flight batches finish on their
                # own references.  Under a cap, eviction owns this
                if prev is not None and prev != out[0] \
                        and self.registry.max_resident == 0:
                    try:
                        self.registry.unload(prev)
                    except Exception:   # noqa: BLE001 — best-effort
                        pass
                return out
            return self._promote_standalone(path, sha)

        return self._stage("promote", _do)

    def _promote_standalone(self, path: str, sha: str) -> Tuple[str, Dict]:
        """The registry-less gate: same two stages, in-memory incumbent."""
        faultinject.check("continual_promote")
        t0 = time.perf_counter()
        with self._lock:
            gen_next = self.generation + 1
        from ..booster import Booster
        from ..snapshot import file_sha256
        got = file_sha256(path)
        if got != sha:
            raise GateFailure("verify",
                              f"artifact checksum mismatch (file "
                              f"{got[:12]}…, pinned {sha[:12]}…)")
        cand = Booster(model_file=path)
        report: Dict[str, Any] = {}
        if self.config.serve_verify_artifacts:
            from ..serve.engine import EngineUnsupported, PredictorEngine
            try:
                eng = PredictorEngine.from_booster(cand, max_batch=256)
                if not eng.self_check():
                    raise GateFailure(
                        "self_check",
                        "engine byte-parity self-check failed")
            except EngineUnsupported:
                # an engine-unsupported model serves via the host walk
                # everywhere — nothing to prove here
                pass
        if self._incumbent is not None:
            probe = shadow_parity_probe(
                cand, self._incumbent, self._probe_batches(),
                self.config, eval_set=self._eval_set(),
                timeout_s=self.config.continual_timeout_s,
                lineage_decay=self._lineage_decay(self._incumbent_sha))
            report["probe"] = probe
            if not probe["ok"]:
                raise GateFailure("shadow_probe", probe["reason"])
        self._incumbent = cand
        self._incumbent_sha = sha
        version = f"gen{gen_next}"
        report["version"] = version
        report["gate_s"] = round(time.perf_counter() - t0, 6)
        self.metrics.histogram("continual.gate_seconds").observe(
            report["gate_s"])
        return version, report

    def _registry_incumbent_sha(self) -> Optional[str]:
        try:
            return self.registry.current().sha256
        except Exception:       # noqa: BLE001 — no incumbent yet
            return None

    def _lineage_decay(self, incumbent_sha: Optional[str]
                       ) -> Optional[float]:
        """The lineage-parity check applies ONLY when the serving
        incumbent is provably the snapshot this candidate boosted from
        (checksums match).  After an operator hot-swaps an unrelated
        model (POST /reload of a hotfix), a legitimate continuation of
        the SNAPSHOT lineage is not a continuation of the INCUMBENT —
        gating on lineage then would quarantine every generation
        forever.  The drift and metric gates still apply."""
        if self._boost_base_sha is not None \
                and incumbent_sha == self._boost_base_sha:
            return self.config.continual_decay
        return None

    # -- probe inputs ------------------------------------------------------
    def _probe_batches(self) -> List[np.ndarray]:
        """The last K live serve batches when a server is attached and
        has traffic; otherwise slices of the newest chunk (the gate
        must always have SOMETHING representative to replay)."""
        k = self.config.shadow_probe_batches
        if k <= 0:
            return []       # replay probe disabled (metric gate remains)
        if self.server is not None:
            ring = self.server.shadow_batches()
            if ring:
                return ring
        if self._chunk_x is None or not len(self._chunk_x):
            return []
        rows = self._chunk_x[-min(len(self._chunk_x), 256 * k):]
        return [b for b in np.array_split(rows, min(k, len(rows)))
                if len(b)]

    def _eval_set(self):
        if self._chunk_x is None or self._chunk_y is None \
                or not len(self._chunk_x):
            return None
        return self._chunk_x, self._chunk_y

    # -- rollback / quarantine --------------------------------------------
    def _quarantine(self, path: str, sha: str, stage: str,
                    reason: str) -> None:
        """Move a refused candidate's files out of the snapshot lineage
        (manifest FIRST: a crash mid-quarantine must never leave the
        candidate looking complete) and drop a blackbox dump beside
        them — next generation boosts from the incumbent again."""
        import shutil
        qdir = self.quarantine_dir
        os.makedirs(qdir, exist_ok=True)
        base = os.path.basename(path)
        moved = []
        for suffix in (".manifest.json", ".state.npz", ""):
            src = path + suffix
            if not os.path.exists(src):
                continue
            dst = os.path.join(qdir, base + suffix)
            try:
                os.replace(src, dst)
            except OSError:
                # cross-filesystem quarantine dir: copy, then unlink.
                # What matters is that the SOURCE goes away — above
                # all the manifest, the completeness marker: were it
                # left behind, the next generation would boost from
                # the refused candidate
                try:
                    shutil.copy2(src, dst)
                except OSError:
                    pass
                try:
                    os.unlink(src)
                except OSError as e:
                    Log.warning(f"continual: could not remove "
                                f"quarantined {src} ({e})")
                    continue
            moved.append(base + suffix)
        with self._lock:
            gen_next = self.generation + 1
        dump = {"reason": reason, "stage": stage, "model_sha256": sha,
                "generation": gen_next,
                "quarantined_at": time.time(), "files": moved}
        try:
            atomic_write(os.path.join(qdir, base + ".blackbox.json"),
                         json.dumps(dump, indent=1, sort_keys=True))
        except Exception as e:      # noqa: BLE001 — the dump is evidence,
            # not a gate: a full disk must not mask the rollback itself
            Log.warning(f"continual: quarantine blackbox dump failed "
                        f"({e})")
        from ..obs import blackbox
        blackbox.dump_all(f"continual_{stage}")
        self.metrics.counter("continual.quarantined").inc()
        Log.warning(f"continual: candidate {base} quarantined to "
                    f"{qdir} ({stage}: {reason})")

    # -- the generation ----------------------------------------------------
    def run_generation(self, x=None, y=None) -> Dict:
        """One full generation; returns the report dict.  In-process
        failures (gate refusals, exhausted retries, probe timeouts) roll
        back automatically — the incumbent keeps serving and the report
        says ``rolled_back``; process-death exceptions (InjectedKill /
        KeyboardInterrupt / SystemExit) propagate, the on-disk publish
        discipline makes the RESTART converge instead."""
        t_start = time.time()
        with self._lock:
            gen_next = self.generation + 1
        report: Dict[str, Any] = {"generation": gen_next,
                                  "status": "published"}
        published: Optional[Tuple[str, str]] = None
        stage = "append"
        try:
            if x is not None:
                self.append_chunk(x, y)
            stage = "boost"
            booster, ds = self.boost()
            stage = "publish"
            path, sha, it = self.publish(booster, ds)
            published = (path, sha)
            stage = "promote"
            version, gate = self.promote(path, sha)
            with self._lock:
                # one atomic publish of the freshness surface: an HTTP
                # reader never sees the new generation number with the
                # old promote stamp (a transiently negative/huge lag)
                self.generation += 1
                gen_done = self.generation
                promote_t = self._last_promote_t = time.time()
                lag = promote_t - (self._chunk_t or t_start)
                self.last_publish = {"version": version, "path": path,
                                     "sha256": sha, "iteration": it,
                                     "at": promote_t}
            self.metrics.counter("continual.published").inc()
            self.metrics.gauge("continual.freshness_lag_s").set(lag)
            report.update(version=version, sha256=sha, iteration=it,
                          gate=gate, freshness_lag_s=round(lag, 6))
            Log.info(f"continual: generation {gen_done} "
                     f"published as {version} (iter {it}, freshness "
                     f"lag {lag:.3f}s)")
        except Exception as e:          # noqa: BLE001 — ANY in-process
            # failure is a rollback; BaseException (kill/exit) means the
            # process is dying and restart-convergence takes over
            reason = f"{type(e).__name__}: {e}"
            stage_name = e.stage if isinstance(e, GateFailure) else stage
            self.metrics.counter("continual.rollbacks").inc()
            if published is not None:
                self._quarantine(published[0], published[1], stage_name,
                                 reason)
            report.update(status="rolled_back", stage=stage_name,
                          reason=reason)
            if getattr(e, "version", None):
                report["version_refused"] = e.version
            Log.warning(f"continual: generation "
                        f"{report['generation']} ROLLED BACK at "
                        f"{stage_name} ({reason}); incumbent keeps "
                        "serving")
        finally:
            self.metrics.counter("continual.generations").inc()
            self.metrics.histogram("continual.generation_seconds") \
                .observe(time.time() - t_start)
        return report

    def run(self, chunks) -> List[Dict]:
        """Run one generation per ``(x, y)`` chunk; returns the reports."""
        return [self.run_generation(cx, cy) for cx, cy in chunks]
