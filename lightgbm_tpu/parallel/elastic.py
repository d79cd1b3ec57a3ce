"""Elastic pod-scale training: liveness, hung-collective deadlines and
shrink-to-survive recovery (ROADMAP item 2, robustness half).

The distributed learners (``parallel/``) had no mid-run failure story:
a preempted host or a hung device claim hangs every ``psum`` /
``psum_scatter`` forever, and the only resilience was bring-up retries
plus ``dist_fallback_serial`` BEFORE training starts.  At the scale the
distributed-GBDT literature assumes (arXiv:1804.06755 billions of
examples, PV-Tree 1611.01276) worker loss is routine; and because the
owner-shard reduce makes global histograms shard-count invariant
(PR 1; ``dp == serial`` bitwise on the int32 quantized path), GBDT can
uniquely **shrink the mesh and keep boosting deterministically**
instead of aborting.  Three layers:

**Liveness.**  :class:`Heartbeat` (a per-process thread stamping
``hb_<process>.json`` in a shared directory every
``elastic_heartbeat_interval_s``) + :class:`HeartbeatMonitor` (stale
mtime past ``elastic_heartbeat_timeout_s`` = the peer is gone), polled
once per boosting iteration from ``models/gbdt.py`` via
:func:`check_peers`.  A lost peer becomes a classified
:class:`ElasticFailure` — never a silent hang.

**Collective deadline.**  :func:`guarded_get` routes the training
loop's one per-iteration host fetch (the point where every queued
collective actually blocks — async dispatch means a hung ``psum``
materializes at the ``device_get``) through
``resilience.Watchdog(on_timeout="raise")``: past
``elastic_collective_timeout_s`` the hung fetch is stack-dumped,
abandoned, and surfaced as ``ElasticFailure("collective_timeout")``.
The device claim gets the same treatment in
``GBDTModel._resolve_mesh`` (``claim_wedge``).

**Recovery ladder.**  :func:`elastic_train` wraps ``engine.train``
with snapshots + auto-resume and degrades rung by rung on classified
failures: full mesh -> shrunk mesh (devices halved, rows re-sharded,
``OwnerShardPlan`` re-derived by the dp grower for the new shard
count) -> serial — each failure episode bounded by
``elastic_recover_timeout_s`` with jittered-backoff retries, resuming
from the newest COMPLETE snapshot so at most one snapshot gap of
iterations is retrained.  Under multi-process training an in-process
shrink cannot rebuild ``jax.distributed`` around a dead peer, so the
ladder raises :class:`ElasticShrinkRequired` (after persisting the
failure record): the pod launcher — or the kill -9 subprocess test —
relaunches the survivors, and ``resume=true`` continues from the
snapshot's GLOBAL state (``GBDTModel.snapshot_state``).

Determinism contract: the shrink axis is ``tree_learner=data`` (or
serial); global histograms are shard-count invariant, so every rung
trains the SAME trees — bitwise on the int32 quantized-histogram path,
within float-reduction epsilons on the f32 path
(tests/test_zelastic.py).  ``voting``/``feature`` learners degrade
straight to serial (voting's per-shard top-k votes are
topology-dependent).  With ``elastic_enable=false`` (default) nothing
here is ever imported on the hot path and all training behavior is
byte-identical to before.

Observability: ``elastic.*`` metrics in a process-level registry
(:func:`metrics_snapshot`) — failures by kind, shrinks, recoveries,
recovery seconds, a mesh-size gauge — plus one JSONL event per
failure/recovery next to the model
(``<output_model>.elastic.jsonl``), recovery spans on the session
tracer when ``telemetry=true``, and a flight-recorder dump
(``obs/blackbox.dump_all``) at every classified failure.
"""

from __future__ import annotations

import json
import os
import random
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..obs.metrics import MetricsRegistry

FAILURE_KINDS = ("collective_timeout", "host_loss", "claim_wedge",
                 "bringup", "ingest", "sdc")

# process-level elastic metrics: always-on and host-side only (a few
# counter bumps per failure — nothing per-iteration), so they need no
# telemetry gate; tools/soak_train.py and the serve /metrics-style
# consumers read them via metrics_snapshot()
_REGISTRY = MetricsRegistry()
_REGISTRY_LOCK = threading.Lock()


def metrics_snapshot() -> dict:
    """Deterministic dict snapshot of the ``elastic.*`` metrics."""
    return _REGISTRY.snapshot()


def reset_metrics() -> None:
    """Test hook: drop all ``elastic.*`` metric state."""
    global _REGISTRY
    with _REGISTRY_LOCK:
        _REGISTRY = MetricsRegistry()


def _metrics() -> MetricsRegistry:
    with _REGISTRY_LOCK:
        return _REGISTRY


class ElasticFailure(RuntimeError):
    """A classified mid-run distributed-training failure.

    ``kind`` is one of :data:`FAILURE_KINDS`.  The message carries the
    resilience classifier's retryable patterns (``unavailable``,
    ``deadline``, ``heartbeat``) so anything that re-enters
    ``retry_call`` treats it as transient."""

    def __init__(self, kind: str, detail: str = ""):
        assert kind in FAILURE_KINDS, kind
        self.kind = kind
        self.detail = detail
        super().__init__(
            f"elastic failure [{kind}]: "
            f"{detail or 'classified distributed-training failure'} "
            "(UNAVAILABLE: deadline/heartbeat)")


class ElasticShrinkRequired(RuntimeError):
    """Raised by :func:`elastic_train` under MULTI-PROCESS training when
    a peer is lost or a collective wedges: an in-process shrink cannot
    rebuild ``jax.distributed`` around a dead client, so the launcher
    must relaunch the survivors (``resume=true`` continues from the
    snapshot's global state).  Carries the classified kind, the
    survivor process indices the heartbeat directory still vouches
    for, and the wall seconds from the episode's first classified
    failure to the confirmed shrink request (which includes the one
    heartbeat-staleness window spent telling the dead from the
    living)."""

    def __init__(self, kind: str, survivors: List[int],
                 detect_s: float, detail: str = ""):
        self.kind = kind
        self.survivors = list(survivors)
        self.detect_s = float(detect_s)
        super().__init__(
            f"elastic shrink required [{kind}]: survivors="
            f"{self.survivors} detect_s={detect_s:.3f} {detail}")


def failure_kind(exc: BaseException) -> Optional[str]:
    """Classify an exception into a :data:`FAILURE_KINDS` entry, or
    None for errors the recovery ladder must NOT swallow (programming
    errors, data errors)."""
    from ..utils.resilience import (WatchdogTimeout,
                                    is_retryable_device_error)
    if isinstance(exc, ElasticFailure):
        return exc.kind
    if isinstance(exc, WatchdogTimeout):
        return "collective_timeout"
    if is_retryable_device_error(exc):
        return "bringup"
    return None


# ---------------------------------------------------------------------------
# Liveness: heartbeat writer + staleness monitor
# ---------------------------------------------------------------------------

def _hb_path(directory: str, process_index: int) -> str:
    return os.path.join(directory, f"hb_{process_index}.json")


class Heartbeat:
    """Per-process heartbeat writer thread.

    Stamps ``hb_<process_index>.json`` (temp + ``os.replace``, so a
    reader never sees a torn file) every ``interval_s`` into a shared
    directory; peers judge liveness by the file's mtime
    (:class:`HeartbeatMonitor`).  ``start``/``stop`` are idempotent.

    Lock contract (tools/analyze/check_races.py):
        _lock guards: _thread, beats
    """

    def __init__(self, directory: str, process_index: int,
                 interval_s: float = 1.0):
        self.directory = str(directory)
        self.process_index = int(process_index)
        self.interval_s = max(0.05, float(interval_s))
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self.beats = 0

    def _write(self) -> None:
        os.makedirs(self.directory, exist_ok=True)
        path = _hb_path(self.directory, self.process_index)
        tmp = f"{path}.{os.getpid()}.tmp"
        with self._lock:
            n = self.beats = self.beats + 1
        payload = json.dumps({"process_index": self.process_index,
                              "pid": os.getpid(), "seq": n,
                              "t": time.time()})
        # plain replace, NOT resilience.atomic_write: heartbeats must
        # keep flowing while fault-injection windows (snapshot_write)
        # are armed, and losing one beat to a crash is harmless
        with open(tmp, "w", encoding="utf-8") as f:
            f.write(payload)
        os.replace(tmp, path)

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self._write()
            except OSError:
                # a transiently unwritable shared dir must not kill the
                # writer — staleness is the monitor's job to call
                pass

    def start(self) -> "Heartbeat":
        with self._lock:
            if self._thread is not None:
                return self
            t = threading.Thread(target=self._run, daemon=True,
                                 name=f"elastic-hb-{self.process_index}")
            self._thread = t
        self._write()                   # first beat lands synchronously
        t.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        with self._lock:
            t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=2.0)


class HeartbeatMonitor:
    """Judge peer liveness from the heartbeat directory.

    A peer is REGISTERED the first time its ``hb_*.json`` looks alive
    and LOST once the monitor observes no mtime PROGRESS from it for
    ``timeout_s`` of its own monotonic clock.  Staleness is judged by
    observed change, not by ``now - mtime``: pod hosts (or an NFS
    server stamping the mtimes) can disagree with this host's
    wall clock by more than the deadline, and an absolute comparison
    would declare every healthy peer dead — or mask a real death —
    under that skew.  Absolute freshness is only a REGISTRATION fast
    path; an absolutely-stale file whose mtime is seen to advance
    registers too (a live peer behind skew), while one that never
    advances is a relic of a previous incarnation and names no peer.
    ``check()`` is called once per boosting iteration (models/gbdt.py)
    and rate-limits its own directory scan to half the heartbeat
    interval, so the per-iteration cost is usually one
    monotonic-clock read.

    Lock contract (tools/analyze/check_races.py):
        _lock guards: _peers, _cand, _last_scan
    """

    def __init__(self, directory: str, self_index: int,
                 timeout_s: float = 10.0, interval_s: float = 1.0):
        self.directory = str(directory)
        self.self_index = int(self_index)
        self.timeout_s = max(0.1, float(timeout_s))
        self.scan_every_s = max(0.02, float(interval_s) / 2.0)
        self._lock = threading.Lock()
        # index -> (last seen mtime, monotonic time of last PROGRESS)
        self._peers: Dict[int, Tuple[float, float]] = {}
        # unregistered relic candidates: index -> last seen mtime
        self._cand: Dict[int, float] = {}
        self._last_scan = 0.0

    def _scan(self) -> Tuple[List[int], List[int]]:
        """(fresh, lost) peer indices as of now."""
        now = time.time()
        mono = time.monotonic()
        seen: Dict[int, float] = {}
        try:
            names = os.listdir(self.directory)
        except OSError:
            names = []
        for name in names:
            if not (name.startswith("hb_") and name.endswith(".json")):
                continue
            try:
                idx = int(name[3:-5])
                mtime = os.stat(os.path.join(self.directory, name)).st_mtime
            except (ValueError, OSError):
                continue
            if idx != self.self_index:
                seen[idx] = mtime
        fresh, lost = [], []
        with self._lock:
            for idx, mtime in seen.items():
                if idx in self._peers:
                    if mtime != self._peers[idx][0]:
                        self._peers[idx] = (mtime, mono)   # progress
                elif now - mtime <= self.timeout_s:
                    # absolutely fresh: the no-skew registration path
                    self._peers[idx] = (mtime, mono)
                elif self._cand.get(idx, mtime) != mtime:
                    # ADVANCING despite an absolutely-stale mtime: a
                    # live peer behind cross-host clock skew
                    self._peers[idx] = (mtime, mono)
                else:
                    # a relic of a PREVIOUS incarnation (e.g. the peer
                    # this relaunch exists to replace): never fresh,
                    # never advancing — names no peer of ours
                    self._cand[idx] = mtime
            for idx, (_sig, t_prog) in sorted(self._peers.items()):
                if mono - t_prog > self.timeout_s:
                    lost.append(idx)
                else:
                    fresh.append(idx)
        return fresh, lost

    def peers(self) -> List[int]:
        with self._lock:
            return sorted(self._peers)

    def survivors(self) -> List[int]:
        fresh, _lost = self._scan()
        return sorted(fresh + [self.self_index])

    def check(self) -> None:
        """Raise ``ElasticFailure("host_loss")`` when any registered
        peer's heartbeat is stale past the deadline."""
        now = time.monotonic()
        with self._lock:
            if now - self._last_scan < self.scan_every_s:
                return
            self._last_scan = now
        _fresh, lost = self._scan()
        if lost:
            raise ElasticFailure(
                "host_loss",
                f"peer heartbeat(s) stale past {self.timeout_s:g}s: "
                f"process(es) {lost}")


# ---------------------------------------------------------------------------
# Process-wide elastic context (installed by elastic_train for gbdt.py)
# ---------------------------------------------------------------------------

class ElasticContext:
    """The ladder's per-run liveness bundle: heartbeat writer + monitor
    + the failure-event sink.  Installed process-wide for the duration
    of :func:`elastic_train` so the training loop's per-iteration
    :func:`check_peers` can reach the monitor without new plumbing
    through every learner.

    All attributes are frozen at construction; mutable state lives in
    the heartbeat/monitor objects behind their own locks
    (their classes declare the machine-checked contracts).
    """

    def __init__(self, heartbeat: Optional[Heartbeat],
                 monitor: Optional[HeartbeatMonitor],
                 events_path: str = ""):
        self.heartbeat = heartbeat
        self.monitor = monitor
        self.events_path = events_path

    def close(self) -> None:
        if self.heartbeat is not None:
            self.heartbeat.stop()


_ctx_lock = threading.Lock()
_ctx: Optional[ElasticContext] = None


def install(ctx: ElasticContext) -> None:
    global _ctx
    with _ctx_lock:
        _ctx = ctx


def uninstall(ctx: Optional[ElasticContext] = None) -> None:
    global _ctx
    with _ctx_lock:
        if ctx is None or _ctx is ctx:
            _ctx = None


def current() -> Optional[ElasticContext]:
    with _ctx_lock:
        return _ctx


# ---------------------------------------------------------------------------
# Suspect-device quarantine (lightgbm_tpu/integrity.py sticky SDC)
# ---------------------------------------------------------------------------
# Device ids attributed to a sticky silent-data-corruption failure.
# GBDTModel._resolve_mesh excludes them from the next claimed mesh and
# the ladder's "sdc" rung shrinks by exactly the suspect count (full
# mesh -> mesh-minus-suspects -> ... -> serial) instead of halving.
# Guarded by _suspect_lock; reads return an immutable copy.
_suspect_lock = threading.Lock()
_suspects: set = set()


def mark_suspect(device_ids) -> None:
    """Record devices attributed to a sticky SDC failure (quarantine)."""
    with _suspect_lock:
        for d in device_ids:
            _suspects.add(int(d))
        n = len(_suspects)
    _metrics().gauge("elastic.suspect_devices").set(n)


def suspected_devices() -> frozenset:
    """Immutable snapshot of the quarantined device ids."""
    with _suspect_lock:
        return frozenset(_suspects)


def clear_suspects() -> None:
    """Drop all quarantine state (fresh elastic_train run / tests)."""
    with _suspect_lock:
        _suspects.clear()
    _metrics().gauge("elastic.suspect_devices").set(0)


def sdc_shrunk(n: int) -> int:
    """Next data-parallel rung after a sticky-SDC failure: drop exactly
    the quarantined suspects (full mesh -> mesh-minus-suspects — the
    healthy chips keep their shards; ``GBDTModel._resolve_mesh`` picks
    WHICH ids go) and fall back to the ladder's usual halving when
    attribution produced no suspects (``integrity_policy`` raise/rewind,
    or a host-array divergence with no placement)."""
    sus = len(suspected_devices())
    if sus:
        return max(1, int(n) - sus)
    return max(1, int(n) // 2)


def _record_event(event: str, **fields) -> None:
    """One JSONL failure/recovery event + the elastic.* metric bump.
    Best-effort: observability must never turn a recoverable failure
    into an unrecoverable one."""
    reg = _metrics()
    if event in FAILURE_KINDS:
        reg.counter("elastic.failures", kind=event).inc()
    ctx = current()
    path = fields.pop("events_path", "") or \
        (ctx.events_path if ctx is not None else "")
    if not path:
        return
    rec = {"event": event, "t": round(time.time(), 3), **fields}
    try:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "a", encoding="utf-8") as f:
            f.write(json.dumps(rec, sort_keys=True) + "\n")
    except OSError:
        pass


def check_peers() -> None:
    """Per-iteration liveness poll (models/gbdt.py calls this when
    ``elastic_enable``): the ``host_loss`` fault-injection site, then
    the installed monitor's staleness check.  No context installed =
    just the (usually disarmed) injection branch."""
    from ..utils import faultinject
    if faultinject.enabled() and faultinject.fires("host_loss"):
        fail = ElasticFailure("host_loss", "injected host loss")
        _on_failure(fail, site="faultinject")
        raise fail
    ctx = current()
    if ctx is not None and ctx.monitor is not None:
        try:
            ctx.monitor.check()
        except ElasticFailure as e:
            _on_failure(e, site="heartbeat")
            raise


def _on_failure(exc: ElasticFailure, site: str = "") -> None:
    """Classified-failure bookkeeping: metrics + JSONL + flight
    recorder.  Idempotence is the caller's job (each failure passes
    through here exactly once, where it is first classified)."""
    _record_event(exc.kind, site=site, detail=exc.detail)
    from ..obs import blackbox
    blackbox.dump_all(f"elastic_{exc.kind}")


def guarded_call(fn: Callable, timeout_s: float, site: str):
    """Run a blocking collective-backed call under the elastic
    deadline: past ``timeout_s`` the hung call is stack-dumped,
    abandoned in its daemon worker, and re-raised in the caller as
    ``ElasticFailure("collective_timeout")``.  ``timeout_s <= 0`` runs
    plain.  Shared by :func:`guarded_get` (the per-iteration fetch) and
    the snapshot writer's multi-process allgather
    (``GBDTModel.snapshot_state``) — which would otherwise be an
    UNBOUNDED collective at every snapshot boundary, reopening exactly
    the hang class this module exists to close."""
    from ..utils.resilience import Watchdog, WatchdogTimeout
    if timeout_s <= 0:
        return fn()
    try:
        return Watchdog(timeout_s, label=f"collective:{site}",
                        on_timeout="raise").run(fn)
    except WatchdogTimeout as e:
        fail = ElasticFailure("collective_timeout", f"{site}: {e}")
        _on_failure(fail, site=site)
        raise fail from e


def guarded_get(x, timeout_s: float, site: str = "fetch"):
    """``jax.device_get(x)`` under the elastic collective deadline.

    The training loop's host fetch is where every queued collective
    actually blocks (async dispatch), so bounding it bounds the
    collectives.  Hosts the ``collective_hang`` fault-injection site.
    ``timeout_s <= 0`` is a plain fetch."""
    import jax

    from ..utils import faultinject

    def _fetch():
        faultinject.check("collective_hang")
        return jax.device_get(x)

    if timeout_s <= 0:
        return _fetch()
    return guarded_call(_fetch, timeout_s, site)


# ---------------------------------------------------------------------------
# Recovery ladder
# ---------------------------------------------------------------------------

def _truthy(v) -> bool:
    return str(v).strip().lower() not in ("", "0", "false", "none", "no")


def _requested_devices(cfg) -> Optional[int]:
    """The rung-0 mesh width implied by the config, or None for
    'all visible devices' (resolved lazily after the first claim)."""
    if cfg.mesh_shape:
        return int(np.prod(cfg.mesh_shape))
    if cfg.num_machines > 1:
        return int(cfg.num_machines)
    return None


def elastic_train(params: dict, x, y=None, *, weight=None,
                  num_boost_round: int = 100, bin_mappers=None,
                  callbacks: Optional[list] = None,
                  valid: Optional[tuple] = None):
    """Train with the shrink-to-survive recovery ladder.

    ``x``/``y`` are the FULL (global) arrays — the ladder re-shards
    them for whatever topology each rung uses, which is what makes a
    shrunk mesh able to carry the dead shard's rows.  Callers that
    must not materialize the full data per host should pass
    ``bin_mappers`` fitted once (e.g. the distributed quantile sketch,
    ``parallel/dist_data.py``) so binning stays topology-independent;
    by default the mappers are fitted on the full data exactly like a
    serial run, which is what makes the final model byte-comparable to
    one.

    Returns the trained Booster with an ``elastic_report`` attribute:
    ``{"attempts", "shrinks", "recoveries", "failures": [...],
    "rungs": [...]}``.  Raises :class:`ElasticShrinkRequired` under
    multi-process training when the pod must be relaunched smaller,
    and re-raises unclassified (non-transient) errors unchanged.
    """
    import jax

    from .. import engine
    from ..config import Config, canonical_params
    from ..dataset import Dataset

    base = dict(canonical_params(dict(params or {})))
    base["elastic_enable"] = True
    base.setdefault("resume", True)
    cfg0 = Config(dict(base))
    if cfg0.snapshot_freq <= 0:
        # recovery loses at most one snapshot gap of iterations —
        # without a user cadence, default to ~10 gaps per run
        base["snapshot_freq"] = max(1, int(num_boost_round) // 10 or 1)
        cfg0 = Config(dict(base))
    retries = max(0, int(cfg0.elastic_retries))
    recover_budget = float(cfg0.elastic_recover_timeout_s)

    pc = jax.process_count()
    reg = _metrics()
    tracer = None
    if cfg0.telemetry:
        from ..obs.trace import Tracer
        tracer = Tracer(sink_path=(cfg0.telemetry_trace_file + ".elastic")
                        if cfg0.telemetry_trace_file else None)

    heartbeat = monitor = None
    if cfg0.elastic_heartbeat_dir:
        heartbeat = Heartbeat(cfg0.elastic_heartbeat_dir,
                              jax.process_index(),
                              cfg0.elastic_heartbeat_interval_s).start()
        monitor = HeartbeatMonitor(cfg0.elastic_heartbeat_dir,
                                   jax.process_index(),
                                   cfg0.elastic_heartbeat_timeout_s,
                                   cfg0.elastic_heartbeat_interval_s)
    ctx = ElasticContext(heartbeat, monitor,
                         events_path=cfg0.output_model + ".elastic.jsonl")
    install(ctx)
    # quarantine state is per-run: a fresh ladder starts trusting every
    # device again (suspects re-earn their place or re-fail the check)
    clear_suspects()

    report = {"attempts": 0, "shrinks": 0, "recoveries": 0,
              "failures": [], "rungs": []}

    def _topo_params(topo: Optional[int]) -> dict:
        pp = dict(base)
        if topo is None:
            return pp
        if topo <= 1:
            pp["tree_learner"] = "serial"
            pp["num_machines"] = 1
            pp.pop("mesh_shape", None)
        else:
            pp["tree_learner"] = "data" \
                if cfg0.tree_learner in ("data", "serial") \
                else cfg0.tree_learner
            pp["mesh_shape"] = [int(topo)]
            pp.pop("num_machines", None)
        return pp

    mcache = {"mappers": bin_mappers}

    def _dataset(pp: dict):
        if pc > 1:
            from . import launch
            from ..dataset import fingerprint_arrays
            shard = launch.row_shard(x, y, weight=weight)
            if mcache["mappers"] is None:
                # full-data binning on every host: identical mappers
                # everywhere AND identical to a serial run over the
                # concatenated rows — the byte-parity anchor across
                # topologies (docstring tradeoff note).  Fitted ONCE
                # per elastic_train: the mappers are a pure function of
                # (x, params), so ladder retries must not re-pay the
                # global binning inside the recovery budget
                full = Dataset(x, label=y, params=dict(pp))
                full.construct(Config(dict(pp)))
                mcache["mappers"] = full.bin_mappers
            ds = Dataset(shard.x, label=shard.y, weight=shard.weight,
                         params=dict(pp), bin_mappers=mcache["mappers"])
            # elastic multi-process snapshots carry GLOBAL state
            # (GBDTModel.snapshot_state): hand the resume path the
            # global fingerprint (to match the manifest against this
            # process's SHARD dataset) and this shard's global row
            # range (to slice the global score back to local rows) —
            # without these, a survivors>1 relaunch would silently
            # restart from iteration 0 on a fingerprint mismatch
            ds.elastic_global_fingerprint = fingerprint_arrays(y, weight)
            ds.elastic_row_range = (shard.row_start, shard.row_stop)
            return ds
        return Dataset(x, label=y, weight=weight, params=dict(pp),
                       bin_mappers=mcache["mappers"])

    def _shrunk(topo: Optional[int], kind: Optional[str] = None) -> int:
        if cfg0.tree_learner != "data":
            # voting's per-shard top-k votes are topology-dependent and
            # a serial-learner run has no mesh to shrink — the only
            # rung below the requested one is serial for both
            return 1
        n = topo
        if n is None:
            try:
                n = len(jax.local_devices()) if pc > 1 else \
                    len(jax.devices())
            except Exception:   # noqa: BLE001 — a hung claim: go serial
                return 1
            req = _requested_devices(cfg0)
            if req is not None:
                n = min(n, req)
        if kind == "sdc":
            return sdc_shrunk(n)
        return max(1, int(n) // 2)

    topo: Optional[int] = None       # None = as requested (rung 0)
    episode_t0: Optional[float] = None
    rung_attempts = 0

    try:
        while True:
            report["attempts"] += 1
            report["rungs"].append(1 if topo == 1 else
                                   (topo or "requested"))
            reg.gauge("elastic.mesh_devices").set(float(topo or 0))
            reg.counter("elastic.attempts").inc()
            pp = _topo_params(topo)
            span = tracer.span("elastic_attempt", topo=str(topo)) \
                if tracer is not None else None
            try:
                ds = _dataset(pp)
                bst = engine.train(pp, ds,
                                   num_boost_round=int(num_boost_round),
                                   callbacks=list(callbacks or []) or None,
                                   valid_sets=None if valid is None else
                                   [Dataset(valid[0], label=valid[1],
                                            params=dict(pp),
                                            reference=ds)])
            except BaseException as e:   # noqa: BLE001 — classified below
                if span is not None:
                    span.args["outcome"] = type(e).__name__
                    span.end()
                kind = failure_kind(e)
                if kind is None:
                    raise
                if not isinstance(e, ElasticFailure):
                    # first classification of a raw transient error
                    _on_failure(ElasticFailure(kind, str(e)[:200]),
                                site="ladder")
                now = time.monotonic()
                if episode_t0 is None:
                    episode_t0 = now
                report["failures"].append(
                    {"kind": kind, "topo": topo or "requested"})
                _record_event("ladder_failure", kind=kind,
                              topo=str(topo or "requested"),
                              detail=str(e)[:300])
                if pc > 1:
                    if monitor is not None:
                        # a peer killed an instant ago still has a
                        # fresh heartbeat file; only after one full
                        # staleness window does the directory tell the
                        # dead from the living
                        time.sleep(monitor.timeout_s)
                        survivors = monitor.survivors()
                    else:
                        survivors = [jax.process_index()]
                    # classification -> confirmed shrink request,
                    # including the one-staleness-window survivor
                    # confirmation above (episode_t0 stamps the first
                    # classified failure of this episode)
                    detect_s = time.monotonic() - episode_t0
                    _record_event("shrink_required", kind=kind,
                                  survivors=survivors,
                                  detect_s=round(detect_s, 3))
                    raise ElasticShrinkRequired(
                        kind, survivors, detect_s, str(e)[:200]) from e
                if recover_budget > 0 and \
                        now - episode_t0 > recover_budget:
                    from ..utils.log import Log
                    Log.warning(
                        f"elastic: recovery budget "
                        f"({recover_budget:g}s) exhausted; giving up")
                    raise
                rung_attempts += 1
                # host_loss and sticky SDC shrink immediately: retrying
                # the same topology re-runs on the dead/suspect device
                if kind in ("host_loss", "sdc") or rung_attempts > retries:
                    new_topo = _shrunk(topo, kind)
                    if topo is not None and new_topo >= topo:
                        raise     # serial rung failed: ladder exhausted
                    topo = new_topo
                    rung_attempts = 0
                    report["shrinks"] += 1
                    reg.counter("elastic.shrinks").inc()
                    _record_event("shrink", to_devices=topo, kind=kind)
                    from ..utils.log import Log
                    Log.warning(
                        f"elastic: shrinking to "
                        f"{'serial' if topo <= 1 else f'{topo} devices'} "
                        f"after [{kind}] and resuming from the newest "
                        "snapshot")
                # jittered backoff before the next attempt
                delay = min(2.0, 0.1 * (2 ** len(report["failures"])))
                time.sleep(delay * (0.75 + 0.5 * random.random()))
                continue
            if span is not None:
                span.args["outcome"] = "ok"
                span.end()
            if episode_t0 is not None:
                rec_s = time.monotonic() - episode_t0
                report["recoveries"] += 1
                reg.counter("elastic.recoveries").inc()
                reg.histogram("elastic.recovery_seconds").observe(rec_s)
                _record_event("recovered", seconds=round(rec_s, 3),
                              topo=str(topo or "requested"))
            bst.elastic_report = report
            return bst
    finally:
        uninstall(ctx)
        ctx.close()
        if tracer is not None:
            tracer.flush()
