"""From a ``jax.profiler`` trace (``*.xplane.pb``) to seconds.

Read with ``jax.profiler.ProfileData`` alone.  A device plane is one whose
name starts with ``/device:``; on it the line ``XLA Ops`` holds one event
per executed operation and ``XLA Modules`` one per executed program.  Host
planes start with ``/host:``.  All lines of one trace share a clock
(nanoseconds from the session's start) to within about a millisecond.

- busy time of a device is the union of its operation intervals; over
  several devices it is averaged;
- the window is the benchmark's own ``TraceAnnotation`` (``WINDOW_SPAN``) on
  the host plane, so the host's share of it (tracing, dispatch, the fetch)
  counts as idle;
- the longest idle gaps are named by the longest host event that overlaps
  them, and the operations that took most time are summed by name, each
  without the time of the operations nested in it.
"""

import re

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:"
HOST_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
TOP = 10


class Ops:
    """One device's operations in columns: ``names[name_id[i]]`` ran from
    ``start[i]`` to ``end[i]`` seconds.  A traced job is a million
    operations, so nothing here walks them one tuple at a time but
    ``self_seconds``."""

    def __init__(self, names, name_id, start, end):
        import numpy as np
        self.names = list(names)
        self.name_id = np.asarray(name_id, np.int64)
        self.start = np.asarray(start, np.float64)
        self.end = np.asarray(end, np.float64)

    @classmethod
    def of(cls, events) -> "Ops":
        """From ``[(name, start_s, end_s), ...]`` (or an ``Ops``)."""
        if isinstance(events, cls):
            return events
        ids = {}
        name_id = [ids.setdefault(n, len(ids)) for n, _, _ in events]
        return cls(ids, name_id, [s for _, s, _ in events],
                   [e for _, _, e in events])

    def __len__(self) -> int:
        return len(self.start)


def load(path: str) -> dict:
    """``columns`` of a trace file."""
    from jax.profiler import ProfileData
    return columns(ProfileData.from_file(path))


def columns(data) -> dict:
    """``{"devices": {plane: Ops}, "host": [(name, start_s, end_s), ...]}``
    of a ``jax.profiler.ProfileData``, with times in seconds on the
    trace's clock."""
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ids, name_id, start, dur = {}, [], [], []
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            if not lines:
                continue
            for line in lines:
                for ev in line.events:
                    name_id.append(ids.setdefault(ev.name, len(ids)))
                    start.append(ev.start_ns)
                    dur.append(ev.duration_ns)
            ops = Ops(ids, name_id, start, dur)
            ops.end = (ops.start + ops.end) * 1e-9
            ops.start = ops.start * 1e-9
            devices[plane.name] = ops
        elif plane.name.startswith(HOST_PREFIX):
            for line in plane.lines:
                host.extend((ev.name, ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9)
                            for ev in line.events if ev.duration_ns > 0)
    return {"devices": devices, "host": host}


def merge(intervals) -> list:
    """Sorted disjoint ``(start, end)`` covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_and_gaps(ops: Ops, lo: float, hi: float):
    """Seconds covered by the union of the operations' intervals, and the
    idle ``(start, end)`` stretches between ``lo`` and ``hi``."""
    import numpy as np
    order = np.argsort(ops.start, kind="stable")
    s, e = ops.start[order], ops.end[order]
    reach = np.maximum.accumulate(e)            # how far the union reaches
    before = np.concatenate([[lo], reach[:-1]])
    busy = float(np.clip(e - np.maximum(s, before), 0.0, None).sum())
    opens = np.flatnonzero(s > before)          # an idle stretch ends here
    gaps = list(zip(before[opens].tolist(), s[opens].tolist()))
    if hi > reach[-1]:
        gaps.append((float(reach[-1]), hi))
    return busy, gaps


def window_of(trace: dict, span: str = WINDOW_SPAN):
    """``(start, end)`` of the traced window: the benchmark's window
    annotation, widened to hold every device operation (the device's clock
    sits up to a millisecond off the host's, so an operation at the window's
    edge may start before the span that launched it)."""
    edges = [(s, e) for name, s, e in trace["host"] if name == span]
    for events in trace["devices"].values():
        ops = Ops.of(events)
        if len(ops):
            edges.append((float(ops.start.min()), float(ops.end.max())))
    if not edges:
        return None
    return min(s for s, _ in edges), max(e for _, e in edges)


_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")


def short_name(hlo: str) -> str:
    """``%fusion.15 fusion f32[3,1792]`` from an operation's whole HLO
    line, which is what the device plane gives as its name."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:80]
    shape = "tuple" if rest.startswith("(") else \
        rest.split("{")[0].split(" ")[0]
    op = _OPCODE.search(rest)
    return f"{head} {op.group(1) if op else '?'} {shape}"[:80]


def self_seconds(ops: Ops) -> dict:
    """Seconds by operation name with every nested operation's time taken
    off its parent (a ``while`` spans its body's operations)."""
    import numpy as np
    order = np.lexsort((-ops.end, ops.start))
    by_id = [0.0] * len(ops.names)
    stack = []                                  # (name id, end) of the open
    for i, s, e in zip(ops.name_id[order].tolist(),
                       ops.start[order].tolist(), ops.end[order].tolist()):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            by_id[stack[-1][0]] -= min(e, stack[-1][1]) - s
        by_id[i] += e - s
        stack.append((i, e))
    return {ops.names[i]: by_id[i] for i in set(ops.name_id.tolist())}


def reduce(trace: dict, span: str = WINDOW_SPAN) -> dict:
    """Busy seconds (mean over devices), window seconds, and the breakdown.
    Returns ``None`` where no operation ran on a device."""
    devices = [Ops.of(evs) for evs in trace["devices"].values()]
    win = window_of(trace, span)
    if win is None or not any(len(ops) for ops in devices):
        return None
    lo, hi = win
    busy, gaps, by_name = [], [], {}
    for ops in devices:
        if not len(ops):
            busy.append(0.0)
            gaps.append((lo, hi))
            continue
        b, g = busy_and_gaps(ops, lo, hi)
        busy.append(b)
        gaps += g
        for n, t in self_seconds(ops).items():
            by_name[n] = by_name.get(n, 0.0) + t
    n_dev = len(devices)
    host = [h for h in trace["host"] if h[0] != span]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        over = [(min(he, e) - max(hs, s), n) for n, hs, he in host
                if he > s and hs < e]
        named.append([max(over)[1] if over else "(no host event)", e - s])
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": sum(busy) / n_dev, "window_s": hi - lo,
            "devices": n_dev,
            "device_ops": [[short_name(n), t / n_dev] for n, t in ops],
            "idle_gaps": named}
