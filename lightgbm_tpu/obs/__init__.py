"""Training/inference observability subsystem.

Always-available, low-overhead telemetry for the training and serving
paths — the production counterpart of the reference's
``Common::Timer``/``FunctionTimer`` discipline (common.h:978-1056,
SURVEY.md §5) and of hand-rolled fences in profiling scripts:

- ``trace``     the one span API: spans with ``id``/``parent``,
                monotonic clocks, JSONL event sink, Chrome-/Perfetto-
                trace export, a mirror into the profiler's host plane
                that puts a span on the device trace's clock, and
                ``fence()`` — a device_get of a scalar derived from the
                timed work, which cannot return before that work is
                done.
- ``metrics``   counters/gauges/histograms with labels, deterministic
                snapshot-to-dict export, shard-aware aggregation.
- ``comm``      static bytes-on-the-wire accounting for the collective
                call sites of the distributed learners (no extra syncs:
                byte math is derived from traced shapes at compile
                time, arXiv:1706.08359's instrumentation discipline).
- ``flops``     the compute-side mirror of ``comm``: static FLOP + HBM
                byte accounting for the histogram/split/partition/
                score/traversal sites, per-model ``FlopLedger``.
- ``attrib``    roofline attribution: joins the flop ledger with the
                fenced phase spans and a per-device peak table into
                the ``perf.*`` keys (achieved FLOP/s, MFU,
                compute-vs-memory verdict).
- ``blackbox``  flight recorder: bounded ring of per-iteration
                records dumped as JSONL on exception / watchdog /
                finite-guard trigger.
- ``profiler``  opt-in ``jax.profiler`` capture of an iteration window.

``ObsSession`` ties them together for one booster; it is built by
``maybe_session(config)`` which returns None unless ``telemetry``
is enabled — the telemetry-off hot path stays a single attribute-load
+ is-None branch with zero host syncs and no per-iteration allocation.
``lgb.cv`` builds a fold's session before the fold's Booster (the
fold's set-up is the first thing to time) and hands it down:
``Booster(_obs=...)`` → ``create_boosting(obs=...)``.
"""

from __future__ import annotations

import itertools

from .metrics import (MetricsRegistry, aggregate_snapshots,
                      gather_snapshots)
from .profiler import ProfilerWindow
from .trace import Span, Tracer, fence, jsonl_to_chrome

__all__ = [
    "MetricsRegistry", "ObsSession", "ProfilerWindow", "Tracer",
    "aggregate_snapshots", "fence", "jsonl_to_chrome", "maybe_session",
]

# every span of a training session is named SPAN_PREFIX + <name>, on the
# profiler's host plane as in the JSONL, so that a trace reader tells the
# program's spans from jax's own events
SPAN_PREFIX = "lgbtpu."
# the per-booster set-up spans and the ``stage`` label each is observed
# under in ``train.setup_seconds{stage=}`` (docs/Observability.md)
SETUP_STAGES = {"cv.fold_setup": "fold_setup", "dataset.subset": "subset",
                "booster.init": "booster_init",
                "booster.mesh": "mesh",
                "booster.to_device": "to_device",
                "grower.make": "grower_make"}
_SESSION_IDS = itertools.count(1)


class ObsSession:
    """One booster's telemetry bundle: one tracer (optionally sinking
    JSONL), one metrics registry, one optional profiler window.

    The GBDT driver holds ``self._obs`` (None when ``telemetry=false``)
    and brackets its work through ``span`` + ``end_setup`` (once a
    booster), ``iter_begin`` / ``phase`` + ``end_phase`` / ``iter_end``
    (every iteration) and ``span`` + ``end_eval`` — see models/gbdt.py,
    booster.py, engine.cv.  A span goes to the tracer, to the profiler's
    host plane (all but the enclosing ``iter``) and, through its
    ``end_*``, to the registry.  All methods here may sync the device
    (that is their job: attributing time to phases needs fences); none
    of them run when telemetry is off.
    """

    def __init__(self, trace_file: str = "", profile_iters=None,
                 profile_dir: str = ""):
        self.id = next(_SESSION_IDS)    # the booster's identifier
        self.tracer = Tracer(sink_path=trace_file or None,
                             ctx={"booster": self.id})
        self.metrics = MetricsRegistry()
        self._iter = None               # the open ``iter`` span
        self.profiler = None
        if profile_iters:
            start, count = (list(profile_iters) + [1])[:2]
            self.profiler = ProfilerWindow(
                int(start), int(count),
                logdir=profile_dir or
                ((trace_file + ".profile") if trace_file
                 else "lgbtpu_profile"))
        self._comm_sites = ()
        self._flop_sites = None
        # (peak FLOP/s, peak HBM bytes/s) for the roofline join;
        # attached by the driver (obs/attrib.config_peaks)
        self.peaks = (None, None)
        _set_compile_watch_target(self)

    # -- spans -------------------------------------------------------------
    def span(self, name: str, mirror: bool = True, **args) -> Span:
        """Open ``lgbtpu.<name>`` on the tracer and, unless it encloses
        other spans' whole job (``iter``, a super-epoch:
        ``mirror=False``), on the profiler's host plane.  Close it
        through ``end_setup`` / ``end_phase`` / ``end_eval`` so that the
        registry sees it too."""
        return self.tracer.span(SPAN_PREFIX + name, mirror=mirror, **args)

    def end_setup(self, sp: Span, **args) -> float:
        """Close a per-booster set-up span (``SETUP_STAGES``); ``args``
        are what was only known at the end (bytes, the memo's answer)."""
        sp.args.update(args)
        dur = sp.end()
        stage = SETUP_STAGES[sp.name[len(SPAN_PREFIX):]]
        self.metrics.histogram("train.setup_seconds",
                               stage=stage).observe(dur)
        return dur

    def end_to_device(self, sp: Span, arrays) -> float:
        """Close a ``booster.to_device`` span and count the bytes of the
        placed ``arrays`` into ``xfer.h2d_bytes``.  Not fenced: the span
        is the host's part of the placement.  The transfer goes on behind
        the next fold's host work, and a fence here made the traced job
        of five folds longer on the v5e (PERF.md §3); the transfer's own
        time is the runtime's events on the profiler's host plane.  An
        array placed on several devices counts once for each: its shards'
        bytes, so four times its own where it is replicated over four."""
        import jax
        nbytes = sum(int(s.data.nbytes)
                     for a in jax.tree_util.tree_leaves(arrays)
                     if isinstance(a, jax.Array)
                     for s in a.addressable_shards)
        self.metrics.counter("xfer.h2d_bytes").inc(nbytes)
        return self.end_setup(sp, bytes=nbytes)

    # -- iteration lifecycle ---------------------------------------------
    def activate(self) -> None:
        """Route the process-wide trace and compile events
        (``jax.traces{name=}``, ``jax.compile_seconds``) to this session:
        the booster that runs, not the one built last (``lgb.cv`` builds
        five, then runs them in turn)."""
        _set_compile_watch_target(self)

    def iter_begin(self, it: int) -> None:
        self.activate()
        if self.profiler is not None:
            self.profiler.on_iter_begin(it)
        if self._iter is not None:
            self._iter.end()            # left open by an exception
        self._iter = self.span("iter", mirror=False, iteration=it)

    def iter_end(self, it: int, n_steps: int = 0) -> None:
        self.metrics.counter("train.iterations").inc()
        if n_steps:
            self.metrics.histogram("train.steps_per_tree").observe(n_steps)
        sp, self._iter = self._iter, None
        self.metrics.histogram("train.iter_seconds").observe(sp.end())
        self.record_comm(n_steps)
        self.record_flops(n_steps)
        if self.profiler is not None:
            self.profiler.on_iter_end(it)

    def phase(self, name: str, it: int = -1) -> Span:
        """Span for one iteration phase (grad, sample, grow, fetch,
        tree_host, score, valid_score); close with
        ``end_phase(span, device_value)`` so the fence attributes the
        wall time to the phase that queued the work, not to the next
        blocking call."""
        args = {"iteration": it} if it >= 0 else {}
        return self.span(name, **args)

    def end_phase(self, sp: Span, result=None) -> float:
        """Close an iteration phase.  The phases PARTITION the iteration
        (``train.iter_seconds`` less their sum is what no phase owns), so
        only a direct child of the ``iter`` span is observed into
        ``train.phase_seconds``; a span opened inside another phase goes
        to the tracer alone."""
        dur = sp.end(result)
        if self._iter is not None and sp.parent == self._iter.id:
            self.metrics.histogram(
                "train.phase_seconds",
                phase=sp.name[len(SPAN_PREFIX):]).observe(dur)
        return dur

    def end_eval(self, sp: Span) -> float:
        """Close an ``eval`` span (a metric evaluation and its fetch: it
        runs between iterations, so it has a family of its own)."""
        dur = sp.end()
        self.metrics.histogram("train.eval_seconds").observe(dur)
        return dur

    def adopt_construct_seconds(self, dataset) -> None:
        """The seconds ``Dataset.construct`` kept of its own stages, from
        the root of ``dataset``'s reference chain (a fold is a subset of
        it), as ``data.construct_seconds{stage=}``: one observation a
        booster, so a reader of several boosters takes ``sum / count``."""
        root = dataset.get_ref_chain()[-1]
        for stage, seconds in (getattr(root, "construct_seconds", None)
                               or {}).items():
            self.metrics.histogram("data.construct_seconds",
                                   stage=stage).observe(seconds)

    # -- comm accounting --------------------------------------------------
    def attach_comm_sites(self, sites) -> None:
        """Register the grower's static collective ledger (obs/comm.py);
        per-iteration byte counters are derived from it host-side."""
        self._comm_sites = sites

    def record_comm(self, n_steps: int) -> None:
        for site in (self._comm_sites.sites()
                     if self._comm_sites else ()):
            mult = n_steps if site.cadence == "step" else 1
            if mult <= 0:
                continue
            labels = dict(site=site.site, collective=site.collective)
            self.metrics.counter("comm.calls", **labels).inc(mult)
            self.metrics.counter("comm.payload_bytes", **labels).inc(
                site.payload_bytes * mult)
            self.metrics.counter("comm.bytes", **labels).inc(
                site.wire_bytes * mult)

    # -- compute accounting ------------------------------------------------
    def attach_flop_sites(self, ledger) -> None:
        """Register the driver's static compute ledger (obs/flops.py
        FlopLedger, built from LOGICAL GLOBAL shapes); per-iteration
        FLOP/HBM-byte counters are derived from it host-side.  Under
        multi-process training the driver attaches on process 0 only —
        the ledger already accounts the global work, so a per-process
        attach would multiply it by the process count at aggregation."""
        self._flop_sites = ledger

    def attach_peaks(self, peak_flops, peak_bw) -> None:
        self.peaks = (peak_flops, peak_bw)

    @property
    def flop_sites(self):
        return self._flop_sites

    def record_flops(self, n_steps: int) -> None:
        for site in (self._flop_sites.sites()
                     if self._flop_sites is not None else ()):
            mult = n_steps if site.cadence == "step" else 1
            if mult <= 0:
                continue
            labels = dict(phase=site.phase, site=site.site)
            self.metrics.counter("flops.total", **labels).inc(
                site.flops * mult)
            self.metrics.counter("flops.hbm_bytes", **labels).inc(
                site.hbm_bytes * mult)

    # -- snapshot / finish ------------------------------------------------
    def snapshot(self, gather: bool = True) -> dict:
        """Metrics snapshot as a plain dict; with ``gather`` (default)
        per-shard snapshots are gathered and merged on every process
        (host 0's view == everyone's view) under multi-process
        training."""
        snap = self.metrics.snapshot()
        if gather:
            snap = aggregate_snapshots(gather_snapshots(snap))
        return snap

    def finish(self) -> dict:
        """Stop any active profiler capture, flush the trace sink and
        return the final (gathered) metrics snapshot."""
        if self.profiler is not None:
            self.profiler.finish()
        self.tracer.flush()
        return self.snapshot()


# compile/cache events (utils/compile_cache.watch_compiles) go through
# one process-global indirection: jax.monitoring listeners cannot be
# unregistered, so they are registered ONCE and forward to the session
# constructed or activated last (None = drop)
_compile_watch_target = None
_compile_watch_installed = False


def _set_compile_watch_target(session: "ObsSession") -> None:
    global _compile_watch_target, _compile_watch_installed
    _compile_watch_target = session
    if _compile_watch_installed:
        return

    class _Fwd:
        """Registry/tracer proxies bound to the CURRENT target."""

        @staticmethod
        def histogram(name, /, **labels):
            t = _compile_watch_target
            return (t.metrics if t else MetricsRegistry()) \
                .histogram(name, **labels)

        @staticmethod
        def counter(name, /, **labels):
            t = _compile_watch_target
            return (t.metrics if t else MetricsRegistry()) \
                .counter(name, **labels)

        @staticmethod
        def instant(name, /, **args):
            t = _compile_watch_target
            if t is not None:
                t.tracer.instant(name, **args)

    from ..utils.compile_cache import watch_compiles
    _compile_watch_installed = watch_compiles(_Fwd, tracer=_Fwd)


def maybe_session(config) -> "ObsSession | None":
    """Build an ObsSession from Config telemetry params, or None when
    ``telemetry=false`` (the default) — the only thing the hot path
    ever does with telemetry off is test this None."""
    if not getattr(config, "telemetry", False):
        return None
    return ObsSession(
        trace_file=getattr(config, "telemetry_trace_file", "") or "",
        profile_iters=getattr(config, "telemetry_profile_iters", None))
