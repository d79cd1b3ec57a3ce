"""Span/trace API: nested spans, JSONL sink, Perfetto export, fence().

The reference attributes time with RAII ``FunctionTimer`` scopes into a
``global_timer`` table (common.h:978-1056).  On an asynchronous XLA
runtime wall-clock scopes lie unless each span's device work is fenced.
``fence()`` below fences with a ``jax.device_get`` of a value *derived
from* the work being timed: a fetch is a sync that cannot complete
before its producer does, whatever the backend's dispatch model; every
timed span should go through here rather than a hand-rolled copy.

Event model: spans are Chrome-trace "complete" events (``ph": "X"``)
with microsecond ``ts``/``dur`` on the monotonic clock, written one
JSON object per line (JSONL) so a crash loses at most the line in
flight.  Every span carries an ``id`` (unique in the process) and the
``parent`` id of the span that was open on its thread when it began, so
a layer's self time is its duration minus its children's; a tracer's
``ctx`` (the booster's identifier) rides on every record.
``jsonl_to_chrome`` wraps the same records into the
``{"traceEvents": [...]}`` envelope Perfetto / chrome://tracing load
directly — the round trip is loss-free because the JSONL records ARE
trace events.

One clock with the device: a span opened with ``mirror=True`` holds a
``jax.profiler.TraceAnnotation`` of its name for as long as it is open,
so any profiler session running meanwhile shows it on the host plane
beside the device's operations.  Only spans that PARTITION a job are
mirrored: a trace reader names an idle gap by the host event that
overlaps it most, and an annotation enclosing a whole iteration would
take every gap.
"""

from __future__ import annotations

import itertools
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


_SPAN_IDS = itertools.count(1)     # next() is atomic under the GIL


class Span:
    """One open span; closes via context-manager exit or ``end()``.

    ``end(result)`` fences ``result`` before taking the stop timestamp
    — a span that timed asynchronous device
    work must wait on a value derived from that work, or the time leaks
    into whoever blocks next.  ``end()`` with no result (and plain
    ``with``-exit) records wall time without touching the device.

    ``parent`` is the id of the span that was open on this thread (and
    this tracer) when this one began.  ``mirror`` enters a
    ``jax.profiler.TraceAnnotation`` of the same name until ``end()``."""

    __slots__ = ("tracer", "name", "args", "t0", "id", "parent",
                 "_open", "_ann", "_done")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any],
                 mirror: bool = False):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.id = next(_SPAN_IDS)
        self._open = tracer._open_spans()
        self.parent = self._open[-1].id if self._open else None
        self._open.append(self)
        self._done = False
        self._ann = None
        if mirror:
            import jax.profiler
            self._ann = jax.profiler.TraceAnnotation(name)
            self._ann.__enter__()
        self.t0 = tracer.now()

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
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        try:
            # with it go the spans above it, abandoned by an exception
            del self._open[self._open.index(self):]
        except ValueError:
            pass
        self.tracer._emit(self.name, self.t0, dur, self.args,
                          span_id=self.id, parent=self.parent)
        return dur

    def __exit__(self, *exc) -> bool:
        self.end()
        return False


class Tracer:
    """Nested-span tracer with an optional JSONL sink.

    Each thread keeps the list of its open spans, from which a new span
    takes its ``parent``; ``span()`` is re-entrant and thread-safe.
    Events are retained in-memory (for programmatic export) AND streamed
    to the sink the moment each span closes.  ``ctx`` is written into
    every record (the training session puts the booster's identifier
    there, so the spans of one booster are told from another's in a
    shared sink).
    """

    def __init__(self, sink_path: Optional[str] = None,
                 pid: Optional[int] = None,
                 ctx: Optional[Dict[str, Any]] = None):
        self.events: List[Dict[str, Any]] = []
        self.ctx = dict(ctx or {})
        self._local = threading.local()
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

    def _open_spans(self) -> List[Span]:
        try:
            return self._local.open
        except AttributeError:
            self._local.open = []
            return self._local.open

    def span(self, name: str, mirror: bool = False, **args: Any) -> Span:
        return Span(self, name, args, mirror)

    def instant(self, name: str, **args: Any) -> None:
        """Zero-duration marker event (``ph: "i"``)."""
        self._emit(name, self.now(), 0.0, args, ph="i")

    def _emit(self, name: str, t0: float, dur: float,
              args: Dict[str, Any], ph: str = "X",
              span_id: Optional[int] = None,
              parent: Optional[int] = None) -> None:
        ev = {"name": name, "ph": ph, "ts": round(t0 * 1e6, 3),
              "dur": round(dur * 1e6, 3), "pid": self.pid,
              "tid": threading.get_ident() & 0xFFFF}
        if span_id is not None:
            ev["id"] = span_id
            ev["parent"] = parent
        ev.update(self.ctx)
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


def jsonl_to_chrome(src: str, dst: str) -> int:
    """Convert a JSONL event sink into a Chrome-trace JSON file that
    Perfetto (ui.perfetto.dev) and chrome://tracing load directly;
    returns the event count."""
    events = read_jsonl(src)
    with open(dst, "w") as f:
        f.write(json.dumps({"traceEvents": events,
                            "displayTimeUnit": "ms"}))
    return len(events)
