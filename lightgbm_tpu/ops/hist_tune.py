"""On-device (K, block_rows) autotuner for the histogram contraction.

The contraction's two tunables are structural constants of the grower
trace: the super-step width K (``split_batch`` — how many leaves share
one C=3K one-hot contraction) and the row-block size of the
``lax.scan`` (``hist_block_rows``'s budget heuristic, a number measured
once on one v5e and hard-coded since).  Neither is knowable from shapes
alone — the measured sweet spot moved between CPU and TPU and between
f32 and int8 operands — so this module
measures instead of guessing:

- **one-shot sweep** (:func:`tune`): time the SHIPPED
  ``compute_histogram`` (never a bench-local variant) over the eligible
  ``SPLIT_BATCH_SET`` widths x a small block_rows neighborhood of the
  budget heuristic, on a synthetic row sample bucketed from the real
  shape.  The score is **ms per leaf slot** (= ms/pass / K): a K=32
  pass may cost more wall time than a K=16 pass and still win, because
  it retires twice the leaves per binned-matrix load.
- **persisted next to the compile cache** (:func:`ensure`): the chosen
  record is keyed by (platform, pow2 row bucket, histogram columns,
  padded bins, vals itemsize, eligible-K ceiling) and merged into
  ``hist_tune.json`` in the same directory family as the persistent
  XLA compile cache (utils/compile_cache.py precedence), so the FIRST
  fit per (platform, shape-bucket) pays the sweep and every later
  process — including a fresh interpreter — reuses both the choice and
  the compiled traces it leads to (zero re-tune, zero re-compile;
  tests/test_zretrace.py pins it).
- ``hist_tune=off`` (the default) never calls into this module: shapes,
  traces and models are exactly the pre-tuner ones.

The tuned K feeds ``split_batch`` resolution (models/gbdt.py) and so
CHANGES THE GROWN TREES (a K-way super-step is a different — equally
valid — best-first growth order); ``hist_tune=on`` therefore trades
cross-platform model determinism for measured throughput.  The tuned
block_rows only re-partitions the scan, but f32 accumulation order
follows the partition, so it is applied the same way: only under
``hist_tune=on``, and recorded in bench extras for provenance.

``block_rows`` is the SCAN's row block.  Since PR 27 a TPU contracts
float32 accumulands in the kernel of ``ops/hist_kernel.py``, which takes
its tiles from the shapes and ignores ``block_rows``: there the sweep's
block_rows candidates time the same program, and only K is tuned.  The
block still means what it says on the CPU and for the integer
accumulands of ``quant_train``.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Dict, List, Optional

_LOCK = threading.Lock()
_COUNTS = {"sweeps": 0, "hits": 0}
_MEM: Dict[str, dict] = {}          # process-level merged table view

TUNE_FILE = "hist_tune.json"

# sweep bounds: the sample is big enough that the scan has multiple
# blocks at every candidate (block sizing is the thing under test) and
# small enough that a full sweep stays a few seconds on CPU
_SAMPLE_ROWS_CAP = 1 << 17
_SWEEP_REPS = 3


def tune_counts() -> Dict[str, int]:
    """Process-wide sweep/lookup counters — the warm-start test's
    instrument (a second process against a warm table must report
    ``sweeps == 0``)."""
    with _LOCK:
        return dict(_COUNTS)


def tune_dir(config=None) -> str:
    """Directory the tune table lives in: the compile cache's own
    (utils/compile_cache.resolve_cache_dir — the table's lifetime should
    match the traces its choices produce)."""
    from ..utils.compile_cache import resolve_cache_dir
    return resolve_cache_dir(getattr(config, "compile_cache_dir", ""))


def shape_key(platform: str, n_rows: int, n_cols: int, num_bins: int,
              itemsize: int, kmax: int) -> str:
    """Bucketed lookup key: rows round to pow2 (one sweep covers a
    whole row bucket, like every other trace-relevant dim in
    utils/shapes.py), the rest are exact trace constants."""
    from ..obs.flops import padded_bins
    from ..utils.shapes import round_up_pow2
    return (f"{platform}|r{round_up_pow2(max(int(n_rows), 1))}"
            f"|c{int(n_cols)}|b{padded_bins(num_bins)}"
            f"|i{int(itemsize)}|kmax{int(kmax)}")


def _load_table(path: str) -> Dict[str, dict]:
    try:
        with open(path) as f:
            obj = json.load(f)
        return obj if isinstance(obj, dict) else {}
    except (OSError, ValueError):
        return {}


def _store(dir_path: str, key: str, rec: dict) -> None:
    """Read-merge-replace under the process lock; atomic on disk
    (temp + os.replace) so concurrent writers can interleave but never
    tear the JSON."""
    from ..utils.resilience import atomic_write
    path = os.path.join(dir_path, TUNE_FILE)
    os.makedirs(dir_path, exist_ok=True)
    table = _load_table(path)
    table[key] = rec
    atomic_write(path, json.dumps(table, indent=1, sort_keys=True))


def candidate_widths(kmax: int) -> List[int]:
    """Eligible super-step widths: the shipped set above 1, capped by
    the leaf budget's ceiling (utils/shapes.fit_split_batch is the
    per-model clamp; ``kmax`` keys the sweep so 31-leaf and 255-leaf
    shapes tune their own eligible sets)."""
    from ..utils.shapes import SPLIT_BATCH_SET
    return [k for k in SPLIT_BATCH_SET if 1 < k <= int(kmax)]


def _block_candidates(n_cols: int, num_bins: int, itemsize: int,
                      k: int) -> List[int]:
    from ..obs.flops import padded_bins
    from ..ops.histogram import HIST_BLOCK_ROWS, hist_block_rows
    from ..utils.shapes import bucket_channels
    b0 = hist_block_rows(n_cols, padded_bins(num_bins), itemsize,
                         channels=bucket_channels(3 * k))
    cands = {b0, max(8, (b0 // 2) // 8 * 8),
             min(HIST_BLOCK_ROWS, b0 * 2)}
    return sorted(cands)


def _measure_ms(binned, vals, slot, k: int, block_rows: int,
                num_bins: int, reps: int) -> float:
    """Wall ms of one slotted pass, amortized over ``reps`` in-graph
    repetitions (one dispatch and one fetch per measurement) and closed
    with ``obs.trace.fence``."""
    import time

    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..obs.trace import fence
    from .histogram import compute_histogram

    @jax.jit
    def rep(b, v, s):
        def body(i, acc):
            h = compute_histogram(b, v, num_bins=num_bins,
                                  block_rows=block_rows, slot=s + 0 * i,
                                  num_slots=k)
            return acc + h.astype(jnp.float32)
        z = compute_histogram(b, v, num_bins=num_bins,
                              block_rows=block_rows, slot=s, num_slots=k)
        return lax.fori_loop(0, reps, body,
                             jnp.zeros_like(z, jnp.float32))

    fence(rep(binned, vals, slot))           # compile + warm
    t0 = time.perf_counter()
    fence(rep(binned, vals, slot))
    return (time.perf_counter() - t0) / reps * 1e3


def tune(n_rows: int, n_cols: int, num_bins: int, itemsize: int = 4,
         kmax: int = 64, reps: int = _SWEEP_REPS,
         sample_rows: Optional[int] = None) -> dict:
    """Run the sweep and return the winning record (no persistence —
    :func:`ensure` owns the table).  Synthetic operands at the training
    dtypes: uint8 bins, f32 or int8/int16 accumulands by ``itemsize``,
    uniform random slots so every width does real multi-leaf work."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..utils.shapes import round_up_pow2

    widths = candidate_widths(kmax)
    if not widths:
        raise ValueError(
            f"no eligible super-step width under kmax={kmax} (the leaf "
            "budget admits only strict growth — nothing to tune)")
    n = int(sample_rows) if sample_rows else \
        min(_SAMPLE_ROWS_CAP, round_up_pow2(max(int(n_rows), 1)))
    rng = np.random.RandomState(0)
    binned = jnp.asarray(rng.randint(0, max(int(num_bins), 2),
                                     size=(n, int(n_cols)),
                                     dtype=np.uint8))
    if int(itemsize) == 4:
        vals = jnp.asarray(rng.randn(n, 3).astype(np.float32))
    else:
        dt = np.int8 if int(itemsize) == 1 else np.int16
        vals = jnp.asarray(rng.randint(-100, 100, size=(n, 3), dtype=dt))
    best = None
    for k in widths:
        slot = jnp.asarray(rng.randint(0, k, size=n, dtype=np.int32))
        for blk in _block_candidates(n_cols, num_bins, itemsize, k):
            ms = _measure_ms(binned, vals, slot, k, blk, int(num_bins),
                             int(reps))
            if best is None or ms / k < best["ms_per_leaf"]:
                best = {"k": k, "block_rows": blk,
                        "ms_per_pass": round(ms, 4),
                        "ms_per_leaf": round(ms / k, 5)}
    best.update(platform=jax.devices()[0].platform,
                sample_rows=n, n_cols=int(n_cols),
                num_bins=int(num_bins), itemsize=int(itemsize),
                kmax=int(kmax), reps=int(reps))
    with _LOCK:
        _COUNTS["sweeps"] += 1
    return best


def ensure(n_rows: int, n_cols: int, num_bins: int, itemsize: int = 4,
           kmax: int = 64, dir_path: Optional[str] = None,
           config=None) -> dict:
    """Lookup-or-tune: the driver-facing entry.  Process memo → on-disk
    table → fresh sweep (persisted).  Returns the winning record; the
    caller snaps/clamps ``record["k"]`` through
    ``utils/shapes.fit_split_batch`` before use."""
    import jax
    d = dir_path or tune_dir(config)
    key = shape_key(jax.devices()[0].platform, n_rows, n_cols, num_bins,
                    itemsize, kmax)
    with _LOCK:
        rec = _MEM.get(key)
        if rec is not None:
            _COUNTS["hits"] += 1
            return rec
    table = _load_table(os.path.join(d, TUNE_FILE))
    rec = table.get(key)
    if isinstance(rec, dict) and "k" in rec and "block_rows" in rec:
        with _LOCK:
            _MEM[key] = rec
            _COUNTS["hits"] += 1
        return rec
    rec = tune(n_rows, n_cols, num_bins, itemsize=itemsize, kmax=kmax)
    _store(d, key, rec)
    with _LOCK:
        _MEM[key] = rec
    return rec
