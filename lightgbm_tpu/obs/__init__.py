"""Training/inference observability subsystem.

Always-available, low-overhead telemetry for the training and serving
paths — the production counterpart of the reference's
``Common::Timer``/``FunctionTimer`` discipline (common.h:978-1056,
SURVEY.md §5) and of hand-rolled fences in profiling scripts:

- ``trace``     nested span/trace API: monotonic clocks, JSONL event
                sink, Chrome-/Perfetto-trace export, and ``fence()`` —
                a device_get of a scalar derived from the timed work,
                which cannot return before that work is done.
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

``ObsSession`` ties the four together for a training run; it is built
by ``maybe_session(config)`` which returns None unless ``telemetry``
is enabled — the telemetry-off hot path stays a single attribute-load
+ is-None branch with zero host syncs and no per-iteration allocation.
"""

from __future__ import annotations

from .metrics import (MetricsRegistry, aggregate_snapshots,
                      gather_snapshots)
from .profiler import ProfilerWindow
from .trace import Tracer, fence, jsonl_to_chrome

__all__ = [
    "MetricsRegistry", "ObsSession", "ProfilerWindow", "Tracer",
    "aggregate_snapshots", "fence", "jsonl_to_chrome", "maybe_session",
]


class ObsSession:
    """Per-training-run telemetry bundle: one tracer (optionally sinking
    JSONL), one metrics registry, one optional profiler window.

    The GBDT driver holds ``self._obs`` (None when ``telemetry=false``)
    and brackets its iteration phases through ``phase``/``iter_begin``/
    ``iter_end`` — see models/gbdt.py.  All methods here may sync the
    device (that is their job: attributing time to phases needs fences);
    none of them run when telemetry is off.
    """

    def __init__(self, trace_file: str = "", profile_iters=None,
                 profile_dir: str = ""):
        self.tracer = Tracer(sink_path=trace_file or None)
        self.metrics = MetricsRegistry()
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
        from ..utils import timer as _timer
        _timer.global_timer.enabled = True   # FunctionTimer scopes feed in
        _set_compile_watch_target(self)

    # -- iteration lifecycle ---------------------------------------------
    def iter_begin(self, it: int) -> float:
        if self.profiler is not None:
            self.profiler.on_iter_begin(it)
        return self.tracer.now()

    def iter_end(self, it: int, t0: float, n_steps: int = 0) -> None:
        self.metrics.counter("train.iterations").inc()
        if n_steps:
            self.metrics.histogram("train.steps_per_tree").observe(n_steps)
        self.metrics.histogram("train.iter_seconds").observe(
            self.tracer.now() - t0)
        self.record_comm(n_steps)
        self.record_flops(n_steps)
        if self.profiler is not None:
            self.profiler.on_iter_end(it)

    def phase(self, name: str, it: int = -1):
        """Span for one iteration phase (grad/grow/fetch/score); close
        with ``end(device_value)`` so the fence attributes the wall time
        to the phase that queued the work, not to the next blocking
        call."""
        args = {"iteration": it} if it >= 0 else {}
        return self.tracer.span(name, **args)

    def phase_metric(self, name: str, seconds: float) -> None:
        self.metrics.histogram("train.phase_seconds",
                               phase=name).observe(seconds)

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
            self.metrics.counter("comm.wire_bytes", **labels).inc(
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
        """Stop any active profiler capture, flush the trace sink, end
        the process-wide FunctionTimer feed this session switched on,
        and return the final (gathered) metrics snapshot."""
        if self.profiler is not None:
            self.profiler.finish()
        self.tracer.flush()
        from ..utils import timer as _timer
        _timer.global_timer.enabled = False
        return self.snapshot()


# compile/cache events (utils/compile_cache.watch_compiles) go through
# one process-global indirection: jax.monitoring listeners cannot be
# unregistered, so they are registered ONCE and forward to the most
# recently constructed session (latest wins; None = drop)
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
        def histogram(name, **labels):
            t = _compile_watch_target
            return (t.metrics if t else MetricsRegistry()) \
                .histogram(name, **labels)

        @staticmethod
        def counter(name, **labels):
            t = _compile_watch_target
            return (t.metrics if t else MetricsRegistry()) \
                .counter(name, **labels)

        @staticmethod
        def instant(name, **args):
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
