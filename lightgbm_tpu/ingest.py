"""Survivable out-of-core ingest: streaming, checkpointed, fault-injectable.

ROADMAP item 3's data path assumed every process could materialize its
full shard in host RAM and died on the first torn/corrupt/slow chunk —
none of the fault machinery training got (retry ladders, snapshots,
heartbeats, fault injection) guarded the loader.  This module applies
the same treatment to ingest, in the shape "Exact Distributed Training:
Random Forest with Billions of Examples" (arXiv:1804.06755) prescribes:
no host ever sees the full dataset; each process streams bounded-memory
chunks, folds them into mergeable per-feature quantile sketches
(:class:`binning.QuantileSketch`), and bin bounds come from the merged
sketches — arXiv:1611.01276's ship-summaries-not-samples argument
applied to binning.

Pipeline, per chunk (:class:`IngestRunner`):

1. **Resume probe** — if ``ingest_resume`` and the chunk's spool +
   manifest verify (manifest parses, spool sha256 matches), the spooled
   arrays are loaded and the source is never re-read: a killed or OOM'd
   loader resumes from the last COMPLETE chunk, byte-identically
   (tests/ingest_worker.py kills the loader between commits and the
   resumed model text equals the uninterrupted run's).
2. **Read + parse** under ``resilience.retry_call`` (jittered backoff,
   ``ingest_retries``) and a raise-mode ``resilience.Watchdog``
   (``ingest_read_timeout_s``): a reader hung on a dead filesystem is
   abandoned at the deadline and the WatchdogTimeout — like any
   transient read error — is retried; exhaustion raises
   ``ElasticFailure("ingest", ...)`` so the elastic recovery ladder
   classifies it instead of inheriting a stuck process.  Fault sites
   ``ingest_read`` / ``ingest_hang`` (utils/faultinject.py) fire here.
3. **Validate** — parse failure, row-count drift against the plan, and
   the ``ingest_checksum`` fault site classify the chunk CORRUPT (not
   transient): it is quarantined with a flight-recorder dump and the
   run either fails fast (``ingest_bad_chunk=raise``, default) or
   degrades with a dropped-row accounting (``skip``).
4. **Commit** — the parsed arrays spool to a DETERMINISTIC container
   (``.lgc`` — raw ``.npy`` segments, no zip timestamps, so the spool
   sha256 is reproducible) via ``resilience.atomic_write``, then the
   chunk manifest (sha256s, row span, byte offsets) is written LAST in
   the snapshot.py mold: its presence marks a complete chunk.
5. **Sketch** — each feature column folds into its QuantileSketch;
   after the last chunk ``binning.fit_mappers_from_sketches`` turns
   them into BinMappers in one pass, and :func:`ingest_dataset` hands
   a :class:`SpooledChunkSequence` (a ``dataset.Sequence``) plus the
   mappers to ``Dataset`` — construction bins chunk-by-chunk and the
   full raw matrix never exists in memory.

Liveness: when an elastic context is installed
(``parallel/elastic.install``) the per-process heartbeat thread keeps
beating through ingest and every chunk boundary calls
``elastic.check_peers()`` — a peer that died mid-ingest surfaces as a
classified ``host_loss`` at the next boundary, not at first collective.

Metrics (``metrics_snapshot()``): ``ingest.chunks{outcome=...}``,
``ingest.rows``, ``ingest.rows_dropped``, ``ingest.retries``,
``ingest.bytes_read``, ``ingest.chunk_s``.
"""

from __future__ import annotations

import dataclasses
import io
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .binning import BinMapper, QuantileSketch, fit_mappers_from_sketches
from .data_io import (_clean_line, detect_format, parse_csv_block,
                      parse_libsvm_block)
from .dataset import Sequence as DatasetSequence
from .obs import blackbox
from .obs.metrics import MetricsRegistry
from .utils import faultinject
from .utils.log import Log
from .utils.resilience import (RetryPolicy, Watchdog, atomic_write,
                               is_retryable_device_error, retry_call)

_FORMAT = 1
_SPOOL_MAGIC = b"LGIC\x01"

# module-level ingest metrics, the elastic.py registry pattern:
# always-on, host-side counter bumps per CHUNK (never per row).
# Lock contract (tools/analyze/check_races.py): _REGISTRY_LOCK guards:
# _REGISTRY.
_REGISTRY = MetricsRegistry()
_REGISTRY_LOCK = threading.Lock()


def metrics_snapshot() -> dict:
    """Deterministic dict snapshot of the ``ingest.*`` metrics."""
    return _REGISTRY.snapshot()


def reset_metrics() -> None:
    """Test hook: drop all ``ingest.*`` metric state."""
    global _REGISTRY
    with _REGISTRY_LOCK:
        _REGISTRY = MetricsRegistry()


def _metrics() -> MetricsRegistry:
    with _REGISTRY_LOCK:
        return _REGISTRY


class IngestError(RuntimeError):
    """Unrecoverable ingest failure (corrupt chunk under
    ``ingest_bad_chunk=raise``, malformed source).  Deliberately NOT
    classified retryable: bad data does not become good by waiting."""


class ChunkCorrupt(IngestError):
    """One chunk failed validation (sha mismatch, parse failure,
    row-count drift) — quarantine material, never retried."""

    def __init__(self, index: int, reason: str):
        self.index = index
        self.reason = reason
        super().__init__(f"chunk {index} corrupt: {reason}")


@dataclasses.dataclass
class ChunkPlan:
    """One chunk's slice of the source, fixed at plan time."""
    index: int
    path: str
    byte_start: int
    byte_end: int
    row_start: int
    rows: int            # data (non-blank) lines; -1 = unknown until read


@dataclasses.dataclass
class ChunkReport:
    """Per-chunk outcome for the run report / soak assertions."""
    index: int
    rows: int
    outcome: str          # "ok" | "resumed" | "quarantined"
    retries: int = 0
    reason: str = ""


@dataclasses.dataclass
class IngestResult:
    """Everything dataset construction needs, without the raw matrix."""
    sketches: List[QuantileSketch]
    sequence: "SpooledChunkSequence"
    label: Optional[np.ndarray]
    num_rows: int
    num_features: int
    dropped_rows: int
    reports: List[ChunkReport]
    spool_dir: str
    resumed_chunks: int

    def fit_bin_mappers(self, cfg, cat_idx: Optional[set] = None
                        ) -> List[BinMapper]:
        return fit_mappers_from_sketches(self.sketches, cfg, cat_idx)


# ---------------------------------------------------------------------------
# Planning: source -> chunk spans (bounded-memory scan)
# ---------------------------------------------------------------------------

def _scan_line_offsets(path: str, scan_libsvm_width: bool
                       ) -> Tuple[List[int], int, int]:
    """Stream the file once in 1 MiB blocks -> (offsets of each
    non-blank data line, total byte size, libsvm max feature index or
    -1).  Never holds more than one block; the scan is the one
    whole-file pass planning needs (the libsvm feature-space width must
    be global before any chunk densifies)."""
    offsets: List[int] = []
    max_feat = -1
    pos = 0
    carry = b""
    carry_off = 0
    with open(path, "rb") as f:
        while True:
            block = f.read(1 << 20)
            if not block:
                break
            data = carry + block
            start = 0
            while True:
                nl = data.find(b"\n", start)
                if nl < 0:
                    break
                line = data[start:nl]
                if line.strip(b"\r \t\xef\xbb\xbf"):
                    offsets.append(carry_off + start)
                    if scan_libsvm_width:
                        for tok in line.split()[1:]:
                            col, sep, _ = tok.partition(b":")
                            if sep:
                                try:
                                    max_feat = max(max_feat, int(col))
                                except ValueError:
                                    pass  # parse stage reports lineno
                start = nl + 1
            pos = carry_off + len(data)
            carry = data[start:]
            carry_off = pos - len(carry)
    if carry.strip(b"\r \t\xef\xbb\xbf"):
        offsets.append(carry_off)
    size = pos
    return offsets, size, max_feat


def _is_chunk_file(name: str) -> bool:
    return (not name.startswith(".") and not name.endswith(".tmp")
            and not name.endswith(".json"))


@dataclasses.dataclass
class IngestPlan:
    """The run-scoped chunking decision, persisted to ``run.json`` so a
    resumed loader can tell whether its spool is still valid."""
    source: str
    fmt: str
    has_header: bool
    label_column: str
    chunk_rows: int
    n_cols: int                    # libsvm feature-space width; -1 n/a
    header_line: str
    chunks: List[ChunkPlan]
    source_sizes: Dict[str, int]

    def signature(self) -> Dict[str, Any]:
        return {"format": _FORMAT, "source": os.path.abspath(self.source),
                "fmt": self.fmt, "has_header": self.has_header,
                "label_column": self.label_column,
                "chunk_rows": self.chunk_rows, "n_cols": self.n_cols,
                "num_chunks": len(self.chunks),
                "source_sizes": self.source_sizes}


def plan_chunks(source: str, chunk_rows: int, has_header: bool = False,
                fmt: Optional[str] = None,
                label_column: str = "") -> IngestPlan:
    """Chunk a source into bounded spans.  A directory is one chunk per
    (sorted) file — the sharded-dataset layout; a single file is split
    every ``chunk_rows`` data lines via a streaming offset scan."""
    if os.path.isdir(source):
        files = sorted(f for f in os.listdir(source) if _is_chunk_file(f))
        if not files:
            raise IngestError(f"ingest source dir {source!r} has no "
                              "chunk files")
        first = os.path.join(source, files[0])
        fmt = fmt or detect_format(first, has_header)
        n_cols = -1
        if fmt == "libsvm":
            n_cols = 0
            for fn in files:
                _, _, mf = _scan_line_offsets(os.path.join(source, fn),
                                              True)
                n_cols = max(n_cols, mf + 1)
        header_line = ""
        if has_header:
            with open(first, encoding="utf-8-sig") as f:
                header_line = _clean_line(f.readline())
        chunks, sizes = [], {}
        for i, fn in enumerate(files):
            p = os.path.join(source, fn)
            sz = os.path.getsize(p)
            sizes[fn] = sz
            chunks.append(ChunkPlan(i, p, 0, sz, -1, -1))
        return IngestPlan(source, fmt, has_header, label_column,
                          chunk_rows, n_cols, header_line, chunks, sizes)

    fmt = fmt or detect_format(source, has_header)
    offsets, size, max_feat = _scan_line_offsets(source, fmt == "libsvm")
    header_line = ""
    if has_header and offsets:
        with open(source, encoding="utf-8-sig") as f:
            header_line = _clean_line(f.readline())
        offsets = offsets[1:]
    chunks = []
    for i, lo in enumerate(range(0, len(offsets), chunk_rows)):
        rows = min(chunk_rows, len(offsets) - lo)
        end = (offsets[lo + rows] if lo + rows < len(offsets) else size)
        chunks.append(ChunkPlan(i, source, offsets[lo], end, lo, rows))
    if not chunks:
        raise IngestError(f"ingest source {source!r} has no data rows")
    return IngestPlan(source, fmt, has_header, label_column, chunk_rows,
                      max_feat + 1 if fmt == "libsvm" else -1,
                      header_line, chunks,
                      {os.path.basename(source): size})


# ---------------------------------------------------------------------------
# Deterministic spool container (.lgc): no zip timestamps -> stable sha
# ---------------------------------------------------------------------------

def _spool_encode(x: np.ndarray, y: Optional[np.ndarray]) -> bytes:
    segs = []
    for arr in (x, y):
        if arr is None:
            segs.append(b"")
            continue
        buf = io.BytesIO()
        np.save(buf, np.ascontiguousarray(arr), allow_pickle=False)
        segs.append(buf.getvalue())
    out = [_SPOOL_MAGIC]
    for s in segs:
        out.append(len(s).to_bytes(8, "little"))
        out.append(s)
    return b"".join(out)


def _spool_decode(blob: bytes) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    if blob[:len(_SPOOL_MAGIC)] != _SPOOL_MAGIC:
        raise IngestError("spool container magic mismatch")
    pos = len(_SPOOL_MAGIC)
    arrs: List[Optional[np.ndarray]] = []
    for _ in range(2):
        n = int.from_bytes(blob[pos:pos + 8], "little")
        pos += 8
        if n == 0:
            arrs.append(None)
        else:
            arrs.append(np.load(io.BytesIO(blob[pos:pos + n]),
                                allow_pickle=False))
            pos += n
    assert arrs[0] is not None
    return arrs[0], arrs[1]


# ---------------------------------------------------------------------------
# The runner
# ---------------------------------------------------------------------------

def _sha256(data: bytes) -> str:
    from .snapshot import sha256_hex
    return sha256_hex(data)


class IngestRunner:
    """Drives one source through the chunk pipeline (module docstring).

    ``cfg`` is duck-typed on the ``ingest_*`` config params plus the
    binning surface ``fit_bin_mappers`` needs; ``tracer`` (obs/trace)
    adds ``ingest.chunk`` spans when telemetry is on."""

    def __init__(self, source: str, cfg, spool_dir: str = "",
                 has_header: bool = False, label_column: str = "",
                 tracer=None):
        self.source = source
        self.cfg = cfg
        self.has_header = has_header
        self.label_column = label_column
        self.tracer = tracer
        self.spool_dir = (spool_dir or getattr(cfg, "ingest_dir", "")
                          or (source.rstrip("/\\") + ".ingest"))
        self._retry_policy = RetryPolicy(
            max_attempts=1 + int(cfg.ingest_retries),
            base_delay_s=float(cfg.ingest_retry_backoff_s),
            max_delay_s=max(1.0, float(cfg.ingest_retry_backoff_s) * 8))

    # -- paths -------------------------------------------------------------
    def _spool_path(self, i: int) -> str:
        return os.path.join(self.spool_dir, f"chunk_{i:06d}.lgc")

    def _manifest_path(self, i: int) -> str:
        return os.path.join(self.spool_dir, f"chunk_{i:06d}.manifest.json")

    def _run_manifest_path(self) -> str:
        return os.path.join(self.spool_dir, "run.json")

    # -- plan / resume ------------------------------------------------------
    def _load_or_make_plan(self) -> Tuple[IngestPlan, bool]:
        """(plan, resumable): the spool is resumable only when its
        ``run.json`` matches the freshly computed plan signature —
        changed chunking, source size or label column invalidates every
        spooled chunk (they were cut along different byte spans)."""
        plan = plan_chunks(self.source, int(self.cfg.ingest_chunk_rows),
                           self.has_header, None, self.label_column)
        rm = self._run_manifest_path()
        resumable = False
        if bool(self.cfg.ingest_resume) and os.path.exists(rm):
            try:
                with open(rm, encoding="utf-8") as f:
                    old = json.load(f)
                resumable = old == plan.signature()
            except (OSError, ValueError):
                resumable = False
            if not resumable:
                Log.warning(
                    f"ingest: spool {self.spool_dir} belongs to a "
                    "different plan (source/params changed); re-ingesting")
        if not resumable:
            # stale spool entries must not satisfy a future resume probe
            if os.path.isdir(self.spool_dir):
                for fn in os.listdir(self.spool_dir):
                    if fn.startswith("chunk_"):
                        try:
                            os.unlink(os.path.join(self.spool_dir, fn))
                        except OSError:
                            pass
            atomic_write(self._run_manifest_path(),
                         json.dumps(plan.signature(), indent=1,
                                    sort_keys=True))
        return plan, resumable

    def _try_resume_chunk(self, plan: ChunkPlan
                          ) -> Optional[Tuple[np.ndarray,
                                              Optional[np.ndarray]]]:
        """Load a chunk from its verified spool, or None.  Trust order
        is manifest-last: no manifest (or an unparsable one) means the
        chunk never committed; a manifest whose spool sha disagrees
        means torn spool debris — both re-ingest from source."""
        mp, sp = self._manifest_path(plan.index), self._spool_path(plan.index)
        try:
            with open(mp, encoding="utf-8") as f:
                man = json.load(f)
            with open(sp, "rb") as f:
                blob = f.read()
        except (OSError, ValueError):
            return None
        if man.get("format") != _FORMAT \
                or man.get("spool_sha256") != _sha256(blob):
            Log.warning(f"ingest: chunk {plan.index} spool fails its "
                        "manifest checksum; re-reading from source")
            return None
        try:
            return _spool_decode(blob)
        except (IngestError, ValueError):
            return None

    # -- read + parse (the retried, deadline-guarded stage) ----------------
    def _read_raw(self, plan: ChunkPlan) -> bytes:
        faultinject.check("ingest_read")
        faultinject.check("ingest_hang")
        with open(plan.path, "rb") as f:
            f.seek(plan.byte_start)
            return f.read(plan.byte_end - plan.byte_start)

    def _read_and_parse(self, plan: IngestPlan, cp: ChunkPlan, label_idx
                        ) -> Tuple[np.ndarray, Optional[np.ndarray], bytes]:
        timeout = float(self.cfg.ingest_read_timeout_s)
        wd = Watchdog(timeout, label=f"ingest chunk {cp.index}",
                      on_timeout="raise")
        raw = wd.run(self._read_raw, cp)
        _metrics().counter("ingest.bytes_read").inc(len(raw))
        try:
            # ingest_checksum models DATA corruption, not infra flakiness:
            # surface it as ChunkCorrupt so the retry loop won't re-read
            # (re-reading corrupt bytes yields the same corrupt bytes)
            faultinject.check("ingest_checksum")
        except faultinject.InjectedFault as e:
            raise ChunkCorrupt(cp.index, str(e)) from None
        first_lineno = (cp.row_start + (2 if plan.has_header else 1)
                        if cp.row_start >= 0 else 1)
        text = raw.decode("utf-8-sig", errors="strict")
        lines = text.splitlines()
        if cp.byte_start == 0 and plan.has_header and cp.rows < 0:
            lines = lines[1:]       # directory chunk carrying a header
        if plan.fmt == "libsvm":
            x, y = parse_libsvm_block(
                lines, path=cp.path, first_lineno=first_lineno,
                n_cols=plan.n_cols if plan.n_cols > 0 else None)
            return x, y, raw
        delim = "\t" if plan.fmt == "tsv" else ","
        data = parse_csv_block(lines, delim, path=cp.path,
                               first_lineno=first_lineno)
        if data.shape[1] < 2 or label_idx is None:
            return data, None, raw
        y = data[:, label_idx].astype(np.float32)
        x = np.delete(data, label_idx, axis=1)
        return x, y, raw

    def _label_idx(self, plan: IngestPlan) -> Optional[int]:
        if plan.fmt == "libsvm":
            return None
        lc = plan.label_column
        if lc.startswith("name:"):
            if not plan.has_header:
                raise IngestError(
                    "label_column by name requires header=true")
            delim = "\t" if plan.fmt == "tsv" else ","
            names = plan.header_line.rstrip(delim).split(delim)
            return names.index(lc[5:])
        return int(lc) if lc else 0

    # -- quarantine --------------------------------------------------------
    def _quarantine(self, cp: ChunkPlan, raw: Optional[bytes],
                    reason: str) -> None:
        qdir = os.path.join(self.spool_dir, "quarantine")
        os.makedirs(qdir, exist_ok=True)
        meta = {"chunk": cp.index, "path": cp.path,
                "byte_start": cp.byte_start, "byte_end": cp.byte_end,
                "reason": reason}
        if raw is not None:
            atomic_write(os.path.join(qdir, f"chunk_{cp.index:06d}.bin"),
                         raw, binary=True)
        atomic_write(os.path.join(qdir, f"chunk_{cp.index:06d}.json"),
                     json.dumps(meta, indent=1, sort_keys=True))
        blackbox.dump_all(f"ingest:quarantine:chunk{cp.index}")
        _metrics().counter("ingest.chunks", outcome="quarantined").inc()
        Log.warning(f"ingest: chunk {cp.index} quarantined ({reason}) "
                    f"-> {qdir}")

    # -- the run -----------------------------------------------------------
    def run(self, categorical_idx: Optional[set] = None) -> IngestResult:
        t_run = time.monotonic()
        plan, resumable = self._load_or_make_plan()
        label_idx = self._label_idx(plan)
        cat_idx = categorical_idx or set()
        sketches: List[QuantileSketch] = []
        reports: List[ChunkReport] = []
        chunk_meta: List[Tuple[str, int]] = []   # (spool path, rows)
        dropped = resumed = 0
        n_features = -1
        bad_policy = str(self.cfg.ingest_bad_chunk)

        from .parallel import elastic

        for cp in plan.chunks:
            t0 = time.monotonic()
            if elastic.current() is not None:
                # a peer that died mid-ingest surfaces at the next
                # chunk boundary as a classified host_loss
                elastic.check_peers()
            x = y = raw = None
            outcome = "ok"
            retries = 0
            if resumable:
                loaded = self._try_resume_chunk(cp)
                if loaded is not None:
                    x, y = loaded
                    outcome = "resumed"
                    resumed += 1
            if x is None:
                def _on_retry(_a, _d, _e):
                    nonlocal retries
                    retries += 1
                    _metrics().counter("ingest.retries").inc()
                try:
                    x, y, raw = retry_call(
                        self._read_and_parse, plan, cp, label_idx,
                        policy=self._retry_policy,
                        # corruption is never transient, whatever its
                        # message says — only infra errors are retried
                        classify=lambda e: (
                            not isinstance(e, ChunkCorrupt)
                            and is_retryable_device_error(e)),
                        on_retry=_on_retry,
                        label=f"ingest chunk {cp.index}")
                except ChunkCorrupt as e:
                    x = e
                except ValueError as e:
                    # parse failure: corrupt, not transient
                    x = ChunkCorrupt(cp.index, f"parse failure: {e}")
                except faultinject.InjectedFault as e:
                    # retry budget exhausted on a transient-classified
                    # fault: infra failure, not data corruption
                    raise elastic.ElasticFailure(
                        "ingest", f"chunk {cp.index} read failed after "
                        f"{self._retry_policy.max_attempts} attempts: "
                        f"{e}") from e
                except Exception as e:
                    if is_retryable_device_error(e):
                        raise elastic.ElasticFailure(
                            "ingest", f"chunk {cp.index} read failed "
                            f"after {self._retry_policy.max_attempts} "
                            f"attempts: {e}") from e
                    x = ChunkCorrupt(cp.index, str(e))
                if not isinstance(x, ChunkCorrupt) \
                        and cp.rows >= 0 and len(x) != cp.rows:
                    x = ChunkCorrupt(
                        cp.index, f"row-count drift: plan {cp.rows}, "
                        f"parsed {len(x)}")
            if isinstance(x, ChunkCorrupt):
                self._quarantine(cp, raw, x.reason)
                reports.append(ChunkReport(cp.index, max(cp.rows, 0),
                                           "quarantined", retries,
                                           x.reason))
                if bad_policy == "raise":
                    raise x
                dropped += max(cp.rows, 0)
                _metrics().counter("ingest.rows_dropped").inc(
                    max(cp.rows, 0))
                continue
            if n_features < 0:
                n_features = x.shape[1]
                cap = int(self.cfg.ingest_sketch_size)
                sketches = [QuantileSketch(cap, categorical=(f in cat_idx))
                            for f in range(n_features)]
            elif x.shape[1] != n_features:
                self._quarantine(
                    cp, raw, f"feature-count drift: expected "
                    f"{n_features}, got {x.shape[1]}")
                reports.append(ChunkReport(cp.index, len(x),
                                           "quarantined", retries,
                                           "feature-count drift"))
                if bad_policy == "raise":
                    raise ChunkCorrupt(cp.index, "feature-count drift")
                dropped += len(x)
                _metrics().counter("ingest.rows_dropped").inc(len(x))
                continue
            if outcome != "resumed":
                blob = _spool_encode(x, y)
                atomic_write(self._spool_path(cp.index), blob,
                             binary=True)
                man = {"format": _FORMAT, "chunk": cp.index,
                       "source": cp.path, "byte_start": cp.byte_start,
                       "byte_end": cp.byte_end, "row_start": cp.row_start,
                       "rows": int(len(x)),
                       "raw_sha256": _sha256(raw),
                       "spool_sha256": _sha256(blob)}
                # manifest LAST: its presence marks a complete chunk
                atomic_write(self._manifest_path(cp.index),
                             json.dumps(man, indent=1, sort_keys=True))
            span = (self.tracer.span("ingest.chunk", index=cp.index,
                                     rows=len(x))
                    if self.tracer is not None else None)
            for f, sk in enumerate(sketches):
                sk.update(x[:, f])
            if span is not None:
                span.end()
            chunk_meta.append((self._spool_path(cp.index), int(len(x))))
            reports.append(ChunkReport(cp.index, int(len(x)), outcome,
                                       retries))
            _metrics().counter("ingest.chunks", outcome=outcome).inc()
            _metrics().counter("ingest.rows").inc(len(x))
            _metrics().histogram("ingest.chunk_s").observe(
                time.monotonic() - t0)

        if n_features < 0:
            raise IngestError(
                f"ingest of {self.source!r}: every chunk quarantined")
        seq = SpooledChunkSequence(chunk_meta)
        label = seq.gather_labels()
        total = sum(r for _, r in chunk_meta)
        _metrics().gauge("ingest.run_s").set(time.monotonic() - t_run)
        Log.info(f"ingest: {total} rows / {len(chunk_meta)} chunks from "
                 f"{self.source} ({resumed} resumed, {dropped} rows "
                 f"dropped)")
        return IngestResult(sketches, seq, label, total, n_features,
                            dropped, reports, self.spool_dir, resumed)


# ---------------------------------------------------------------------------
# Spooled chunks as a dataset.Sequence (streaming construction)
# ---------------------------------------------------------------------------

class SpooledChunkSequence(DatasetSequence):
    """Random row access over the spooled chunks — a
    ``dataset.Sequence``, so ``Dataset`` routes it through the
    streaming ``_construct_from_seqs`` path.  At most ONE decoded chunk
    is resident; sequential access (the construction scan) decodes each
    spool file exactly once."""

    def __init__(self, chunk_meta: List[Tuple[str, int]]):
        self._meta = list(chunk_meta)
        self._bounds = np.concatenate(
            [[0], np.cumsum([r for _, r in self._meta])]).astype(np.int64)
        self._cache_idx = -1
        self._cache: Optional[Tuple[np.ndarray, Optional[np.ndarray]]] = None
        self.batch_size = max(int(r) for _, r in self._meta) \
            if self._meta else 4096

    def __len__(self) -> int:
        return int(self._bounds[-1])

    def _chunk(self, ci: int) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        if ci != self._cache_idx:
            with open(self._meta[ci][0], "rb") as f:
                self._cache = _spool_decode(f.read())
            self._cache_idx = ci
        assert self._cache is not None
        return self._cache

    def _rows(self, gidx: np.ndarray) -> np.ndarray:
        ci = np.searchsorted(self._bounds, gidx, side="right") - 1
        out = None
        for c in np.unique(ci):
            x, _ = self._chunk(int(c))
            sel = ci == c
            if out is None:
                out = np.empty((len(gidx), x.shape[1]), np.float64)
            out[sel] = x[gidx[sel] - self._bounds[c]]
        assert out is not None
        return out

    def __getitem__(self, idx):
        if isinstance(idx, (int, np.integer)):
            return self._rows(np.asarray([int(idx)]))[0]
        if isinstance(idx, slice):
            gidx = np.arange(*idx.indices(len(self)))
        else:
            gidx = np.asarray(list(idx), dtype=np.int64)
        return self._rows(gidx)

    def gather_labels(self) -> Optional[np.ndarray]:
        """Concatenated per-chunk labels (float32 — tiny next to the
        raw features), or None when the source had no label column."""
        parts = []
        for ci in range(len(self._meta)):
            _, y = self._chunk(ci)
            if y is None:
                return None
            parts.append(y)
        return np.concatenate(parts) if parts else None


# ---------------------------------------------------------------------------
# Front door
# ---------------------------------------------------------------------------

def ingest_dataset(source: str, params: Optional[Dict[str, Any]] = None,
                   has_header: bool = False, label_column: str = "",
                   categorical_idx: Optional[set] = None,
                   spool_dir: str = "", tracer=None, reference=None):
    """Stream ``source`` (file or directory of chunks) into a
    ``Dataset``: chunked ingest -> merged sketches -> BinMappers ->
    streaming binned construction.  The full raw matrix never exists in
    memory; peak RSS is bounded by one chunk.  With ``reference`` (a validation set binned
    against the training set) the reference's mappers are reused and no
    sketches are fitted."""
    from .config import Config
    from .dataset import Dataset
    cfg = Config(params or {})
    runner = IngestRunner(source, cfg, spool_dir=spool_dir,
                          has_header=has_header,
                          label_column=label_column, tracer=tracer)
    result = runner.run(categorical_idx=categorical_idx)
    mappers = (None if reference is not None
               else result.fit_bin_mappers(cfg, categorical_idx))
    ds = Dataset(result.sequence, label=result.label,
                 params=dict(params or {}), bin_mappers=mappers,
                 reference=reference)
    ds.ingest_report = {
        "num_rows": result.num_rows,
        "num_features": result.num_features,
        "dropped_rows": result.dropped_rows,
        "resumed_chunks": result.resumed_chunks,
        "quarantined": [dataclasses.asdict(r) for r in result.reports
                        if r.outcome == "quarantined"],
        "spool_dir": result.spool_dir,
    }
    return ds
