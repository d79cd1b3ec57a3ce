"""Span/trace API: nested spans, JSONL sink, Perfetto export, fence().

The reference attributes time with RAII ``FunctionTimer`` scopes into a
``global_timer`` table (common.h:978-1056).  On an asynchronous XLA
runtime wall-clock scopes lie unless each span's device work is fenced.
``fence()`` below fences with a ``jax.device_get`` of a value *derived
from* the work being timed: a fetch is a sync that cannot complete
before its producer does, whatever the backend's dispatch model; every
hand-rolled copy of it (tools/profile_iter.py, bench_hist.py) should go
through here.

Event model: spans are Chrome-trace "complete" events (``ph": "X"``)
with microsecond ``ts``/``dur`` on the monotonic clock, written one
JSON object per line (JSONL) so a crash loses at most the line in
flight.  ``jsonl_to_chrome`` wraps the same records into the
``{"traceEvents": [...]}`` envelope Perfetto / chrome://tracing load
directly — the round trip is loss-free because the JSONL records ARE
trace events.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional


def fence(x: Any = None) -> Any:
    """Reliable device fence: block until every array in ``x`` has
    actually been computed, then return ``x`` unchanged (chainable).

    Fetches a tiny slice *derived from* each array: the transfer
    completes only after the producing computation does, so the fence
    holds on any backend without relying on how ``block_until_ready``
    is implemented there.  Cost: one scalar-sized host round trip, zero
    extra device compute beyond a 1-element slice.

    Arrays that are not fully addressable from this process (multi-host
    shards) fall back to ``block_until_ready`` — a cross-process fetch
    would turn the fence into a collective.
    """
    import jax
    import jax.numpy as jnp

    if x is None:
        # fence the whole stream: a fresh trivial computation is queued
        # behind everything already dispatched on the default device
        jax.device_get(jnp.zeros(()) + 0.0)
        return x
    probes = []
    for leaf in jax.tree_util.tree_leaves(x):
        if not isinstance(leaf, jax.Array):
            continue
        if getattr(leaf, "is_fully_addressable", True):
            # a 1-element corner slice, NOT ravel()[:1]: ravel of a 2-D
            # array is a real reshape that copies the whole buffer
            probes.append(leaf[(slice(0, 1),) * leaf.ndim])
        else:
            jax.block_until_ready(leaf)       # sync-ok: multi-host fallback
    if probes:
        jax.device_get(probes)
    return x


class Span:
    """One open span; closes via context-manager exit or ``end()``.

    ``end(result)`` fences ``result`` before taking the stop timestamp
    — a span that timed asynchronous device
    work must wait on a value derived from that work, or the time leaks
    into whoever blocks next.  ``end()`` with no result (and plain
    ``with``-exit) records wall time without touching the device."""

    __slots__ = ("tracer", "name", "args", "t0", "_done")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any]):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.t0 = tracer.now()
        self._done = False

    def __enter__(self) -> "Span":
        return self

    def end(self, result: Any = None) -> float:
        """Close the span, fencing ``result`` first when given; returns
        the span duration in seconds."""
        if self._done:
            return 0.0
        self._done = True
        if result is not None:
            fence(result)
        dur = self.tracer.now() - self.t0
        self.tracer._emit(self.name, self.t0, dur, self.args)
        return dur

    def __exit__(self, *exc) -> bool:
        self.end()
        return False


class Tracer:
    """Nested-span tracer with an optional JSONL sink.

    Spans nest naturally (the Chrome trace model infers nesting from
    containment of [ts, ts+dur) per tid), so no explicit stack is kept;
    ``span()`` is re-entrant and thread-safe.  Events are retained
    in-memory (for programmatic export) AND streamed to the sink the
    moment each span closes.
    """

    def __init__(self, sink_path: Optional[str] = None,
                 pid: Optional[int] = None):
        self.events: List[Dict[str, Any]] = []
        self._lock = threading.Lock()
        self._sink = None
        self._sink_path = sink_path
        if sink_path:
            d = os.path.dirname(os.path.abspath(sink_path))
            os.makedirs(d, exist_ok=True)
            self._sink = open(sink_path, "a", buffering=1)
        if pid is None:
            try:
                import jax
                pid = jax.process_index()
            except Exception:
                pid = 0
        self.pid = pid

    @staticmethod
    def now() -> float:
        """Monotonic seconds (perf_counter: highest-resolution monotonic
        clock Python exposes)."""
        return time.perf_counter()

    def span(self, name: str, **args: Any) -> Span:
        return Span(self, name, args)

    def instant(self, name: str, **args: Any) -> None:
        """Zero-duration marker event (``ph: "i"``)."""
        self._emit(name, self.now(), 0.0, args, ph="i")

    def _emit(self, name: str, t0: float, dur: float,
              args: Dict[str, Any], ph: str = "X") -> None:
        ev = {"name": name, "ph": ph, "ts": round(t0 * 1e6, 3),
              "dur": round(dur * 1e6, 3), "pid": self.pid,
              "tid": threading.get_ident() & 0xFFFF}
        if args:
            ev["args"] = args
        with self._lock:
            self.events.append(ev)
            if self._sink is not None:
                self._sink.write(json.dumps(ev) + "\n")

    def durations(self, name: str) -> List[float]:
        """All recorded durations (seconds) of spans named ``name``."""
        with self._lock:                  # _emit appends concurrently
            events = list(self.events)
        return [e["dur"] / 1e6 for e in events
                if e["name"] == name and e["ph"] == "X"]

    def flush(self) -> None:
        with self._lock:
            if self._sink is not None:
                self._sink.flush()

    def close(self) -> None:
        with self._lock:
            if self._sink is not None:
                self._sink.close()
                self._sink = None

    def export_chrome(self, path: str) -> None:
        """Write the in-memory events as a Chrome/Perfetto trace file."""
        with self._lock:
            events = list(self.events)
        _write_chrome(path, events)


def timed_fenced(fn, iters: int = 10, tracer: Optional[Tracer] = None,
                 name: str = "timed") -> tuple:
    """Run ``fn`` ``iters`` times, fencing its return value each rep;
    returns (min_seconds, avg_seconds).  The successor of the private
    ``bench_phase`` helpers in tools/ — one definition of "how we time a
    device-side phase"."""
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fence(fn())
        dt = time.perf_counter() - t0
        ts.append(dt)
        if tracer is not None:
            tracer._emit(name, t0, dt, {})
    return min(ts), sum(ts) / len(ts)


# -- JSONL <-> Perfetto ----------------------------------------------------

def read_jsonl(path: str) -> List[Dict[str, Any]]:
    """Parse a JSONL trace back into event dicts (skipping any torn
    trailing line from a crash)."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except ValueError:
                continue          # torn final line: crash mid-write
    return out


def _write_chrome(path: str, events: List[Dict[str, Any]]) -> None:
    with open(path, "w") as f:
        f.write(json.dumps({"traceEvents": events,
                            "displayTimeUnit": "ms"}))


def jsonl_to_chrome(src: str, dst: str) -> int:
    """Convert a JSONL event sink into a Chrome-trace JSON file that
    Perfetto (ui.perfetto.dev) and chrome://tracing load directly;
    returns the event count."""
    events = read_jsonl(src)
    _write_chrome(dst, events)
    return len(events)
