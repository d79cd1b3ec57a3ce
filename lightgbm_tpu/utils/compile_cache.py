"""Persistent XLA compilation cache + process-wide compile accounting.

Two concerns live here because they are two halves of one feature —
making compile time a managed, *measured* resource:

1. :func:`enable_persistent_cache` points jax at an on-disk compilation
   cache so later processes warm-start every compile (train -> serve
   included).  The directory is chosen so that whoever runs the program
   can place it from outside and every process agrees on it:
   ``JAX_COMPILATION_CACHE_DIR`` when set, else the ``compile_cache_dir``
   param, else ``<checkout>/.jax_cache`` (:func:`default_cache_dir`) —
   the path is part of jax's cache key, so a directory that differs
   between processes never hits.  Config wiring: ``compile_cache`` /
   ``compile_cache_dir`` / ``compile_cache_min_compile_s`` /
   ``compile_cache_min_entry_bytes`` (engine.train / Booster / cli /
   serve bring-up via :func:`maybe_enable_from_config`).

2. :func:`install_compile_counters` + :func:`trace_event` make
   warm-start observable instead of assumed: process-global counters of
   backend compiles / persistent-cache hits+misses / compile seconds
   (fed by ``jax.monitoring``), and named trace counters bumped at
   trace time by the library's jitted entry points (grower, super-epoch,
   traversal, forest).  Surfaced through
   ``Booster.telemetry_snapshot()`` and the serve ``/metrics`` endpoint,
   and pinned by tools/check_retraces.py.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Optional

def default_cache_dir() -> str:
    """``<checkout>/.jax_cache``: beside the package, identical in every
    process and on every host that runs this checkout."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def resolve_cache_dir(param_dir: Optional[str] = None) -> str:
    """The one directory rule shared by the compile cache and
    ``hist_tune.json`` (ops/hist_tune.py): ``JAX_COMPILATION_CACHE_DIR``
    > the ``compile_cache_dir`` param > :func:`default_cache_dir`."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or param_dir \
        or default_cache_dir()


def enable_persistent_cache(min_compile_secs: float = 0.5,
                            cache_dir: Optional[str] = None,
                            min_entry_bytes: int = 0) -> str:
    """Enable the persistent compilation cache; returns the path used
    (:func:`resolve_cache_dir` of ``cache_dir``).

    jax opens its cache once, on the first compile, and decides then
    whether the process uses one at all; a directory set afterwards is
    ignored.  So when the resolved path differs from what jax holds the
    cache is reset and reopens at the new path on the next compile.

    A persistence threshold pinned via its jax env var
    (``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` /
    ``JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES``) is left alone."""
    import jax
    path = resolve_cache_dir(cache_dir)
    if jax.config.jax_compilation_cache_dir != path:
        from jax.experimental.compilation_cache import compilation_cache
        jax.config.update("jax_compilation_cache_dir", path)
        compilation_cache.reset_cache()
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_secs))
    if "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES" not in os.environ:
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          int(min_entry_bytes))
    install_compile_counters()
    return path


def maybe_enable_from_config(config) -> Optional[str]:
    """Config-driven bring-up used by Booster / engine.train / cli /
    serve: enables the persistent cache when ``compile_cache`` is on
    (the default) and always installs the compile counters so
    ``compile.*`` telemetry works even with the cache disabled.
    Idempotent and cheap; returns the cache path or None."""
    install_compile_counters()
    if not getattr(config, "compile_cache", True):
        return None
    return enable_persistent_cache(
        min_compile_secs=getattr(config, "compile_cache_min_compile_s",
                                 0.5),
        cache_dir=getattr(config, "compile_cache_dir", "") or None,
        min_entry_bytes=getattr(config, "compile_cache_min_entry_bytes",
                                0))


# ---------------------------------------------------------------------------
# Process-wide compile accounting
# ---------------------------------------------------------------------------

# jax.monitoring event names jax 0.9.0 emits:
# /jax/core/compile/backend_compile_duration (jax/_src/dispatch.py
# BACKEND_COMPILE_EVENT), /jax/compilation_cache/cache_hits
# (jax/_src/compiler.py) and /jax/compilation_cache/cache_misses
# (jax/_src/compilation_cache.py).  Matched by substring; a renamed
# event counts nothing, which chip_smoke.py and
# tests/test_compile_cache.py refuse (both require non-zero counters
# after a compile).
_BACKEND_COMPILE = "backend_compile"
_CACHE_HIT = "cache_hits"
_CACHE_MISS = "cache_misses"

_STATS_LOCK = threading.Lock()
_COMPILE_STATS = {"count": 0, "seconds": 0.0,
                  "cache_hits": 0, "cache_misses": 0}
_COUNTERS_INSTALLED = [False]


def install_compile_counters() -> bool:
    """Register the process-global jax.monitoring listeners feeding
    :func:`compile_stats`.  Listeners cannot be unregistered, so this
    installs exactly once; returns False when the monitoring surface is
    unavailable."""
    if _COUNTERS_INSTALLED[0]:
        return True
    try:
        from jax import monitoring
    except Exception:
        return False

    def _on_duration(event: str, duration: float, **kw) -> None:
        if _BACKEND_COMPILE in event:
            with _STATS_LOCK:
                _COMPILE_STATS["count"] += 1
                _COMPILE_STATS["seconds"] += float(duration)

    def _on_event(event: str, **kw) -> None:
        if _CACHE_HIT in event:
            with _STATS_LOCK:
                _COMPILE_STATS["cache_hits"] += 1
        elif _CACHE_MISS in event:
            with _STATS_LOCK:
                _COMPILE_STATS["cache_misses"] += 1

    try:
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
    except Exception:
        return False
    _COUNTERS_INSTALLED[0] = True
    return True


def compile_stats() -> Dict[str, float]:
    """Snapshot of process-wide compile accounting: backend compile
    REQUESTS (count/seconds — jax emits the duration event on
    persistent-cache hits too, just with the near-zero load time) and
    persistent-cache hits/misses (``cache_misses`` is the
    fresh-compile count).  Zeros until
    :func:`install_compile_counters` ran (Booster/serve bring-up
    installs it)."""
    with _STATS_LOCK:
        return dict(_COMPILE_STATS)


# ---------------------------------------------------------------------------
# Named trace counters (retrace-budget lint)
# ---------------------------------------------------------------------------

_TRACE_COUNTS: Dict[str, int] = {}
_TRACE_PREFIX = "/lgbtpu/trace/"


def trace_event(name: str) -> None:
    """Record one TRACE of a named jitted program.  Called as a Python
    side effect from inside the traced function body, so it fires once
    per fresh jit cache entry and never per execution.  Mirrored into
    ``jax.monitoring`` under ``/lgbtpu/trace/<name>`` so external
    listeners (tools/check_retraces.py) can count without importing
    library internals."""
    with _STATS_LOCK:
        _TRACE_COUNTS[name] = _TRACE_COUNTS.get(name, 0) + 1
    try:
        from jax import monitoring
        monitoring.record_event(_TRACE_PREFIX + name)
    except Exception:
        pass


def trace_counts() -> Dict[str, int]:
    """Per-name trace counters for this process (deterministic: traces
    are independent of the persistent cache's disk state — a cache hit
    skips the COMPILE, never the trace)."""
    with _STATS_LOCK:
        return dict(_TRACE_COUNTS)


def trace_total() -> int:
    with _STATS_LOCK:
        return sum(_TRACE_COUNTS.values())


def compile_snapshot(traces: str = "total") -> Dict[str, object]:
    """The ``compile.*`` key block shared by every telemetry surface
    (``Booster.telemetry_snapshot`` and the serve ``/metrics``
    snapshot): compile requests, persistent-cache hits/misses, and the
    library trace counters — as a total (``traces="total"``) or the
    per-program breakdown (``traces="by_name"``)."""
    cs = compile_stats()
    return {
        "compile.count": cs["count"],
        "compile.seconds": cs["seconds"],
        "compile.cache_hits": cs["cache_hits"],
        "compile.cache_misses": cs["cache_misses"],
        "compile.traces": (trace_counts() if traces == "by_name"
                           else trace_total()),
    }


def watch_compiles(metrics, tracer=None) -> bool:
    """Feed XLA compile / compilation-cache events into an obs
    MetricsRegistry (+ optional Tracer instants): compile durations as
    a ``jax.compile_seconds`` histogram, cache hits/misses and other
    compile-adjacent counters as ``jax.events{event=...}``, the
    library's own trace events as ``jax.traces{name=...}``, and the traces
    of a site by implementation (``obs.flops.note_traced(impl=)``) as
    ``hist.contraction_traces{impl=...}``, with the tiles the kernel's plan
    chose (``obs.flops.note_kernel_plan``) as ``hist.kernel_plans{fpart=,
    parts=}``, and the rule a grower's row partition took
    (``obs.flops.note_partition_rule``) as ``grower.partition_rule{rule=}``
    with the ``[N]`` look-ups it keeps in ``grower.partition_row_gathers``.

    Uses ``jax.monitoring``'s public listener hooks; listeners are
    process-global and cannot be unregistered, so the registered
    closures forward to whatever registry/tracer was CURRENT at
    registration — callers register once per session (obs.ObsSession).
    Returns False when the monitoring surface is unavailable."""
    try:
        from jax import monitoring
    except Exception:
        return False
    from ..obs.flops import (IMPL_EVENT_PREFIX, PARTITION_EVENT_PREFIX,
                             PLAN_EVENT_PREFIX)

    def _on_duration(event: str, duration: float, **kw) -> None:
        if "compil" not in event:
            return
        metrics.histogram("jax.compile_seconds",
                          event=event).observe(duration)
        if tracer is not None:
            tracer.instant("jax_compile", event=event, seconds=duration)

    def _on_event(event: str, **kw) -> None:
        if event.startswith(_TRACE_PREFIX):
            metrics.counter("jax.traces",
                            name=event[len(_TRACE_PREFIX):]).inc()
            return
        if event.startswith(IMPL_EVENT_PREFIX):
            site, impl = event[len(IMPL_EVENT_PREFIX):].split("/")
            metrics.counter(f"{site}.contraction_traces", impl=impl).inc()
            return
        if event.startswith(PLAN_EVENT_PREFIX):
            site, tiles = event[len(PLAN_EVENT_PREFIX):].split("/")
            metrics.counter(f"{site}.kernel_plans", **dict(
                t.split("=") for t in tiles.split(","))).inc()
            return
        if event.startswith(PARTITION_EVENT_PREFIX):
            rule, gathers = event[len(PARTITION_EVENT_PREFIX):].split("/")
            metrics.counter("grower.partition_rule", rule=rule).inc()
            metrics.counter("grower.partition_row_gathers").inc(int(gathers))
            return
        if "compil" not in event and "cache" not in event:
            return
        metrics.counter("jax.events", event=event).inc()
        if tracer is not None and "cache" in event:
            tracer.instant("jax_cache", event=event)

    try:
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
    except Exception:
        return False
    return True
