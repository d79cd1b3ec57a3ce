"""Static FLOP + HBM-byte accounting for the compute hot paths.

The compute-side mirror of ``obs/comm.py``'s trace-time static
accounting trick: "GPU-acceleration for Large-scale Tree Boosting"
(arXiv:1706.08359) and "Booster" (arXiv:2011.02022) justify their
kernels with op-level FLOP/byte budgets; here the same numbers are
derived STATICALLY from shapes, in two complementary channels that
share ONE set of formula functions:

1. ``note_traced(site, ...)`` — called as a Python side effect inside
   the traced bodies of the histogram contraction
   (``ops/histogram.py``), the split scan (``ops/split.py``), the
   grower's row partition (``grower.py``), the score update
   (``models/gbdt.py``) and the tree/forest traversals
   (``predict_device.py``).  Fires once per fresh jit trace (never per
   execution), records (flops, hbm_bytes) for the shapes actually
   traced, and overwrites idempotently on retrace — zero runtime cost,
   zero extra syncs.  ``traced_sites()`` is the process-wide view.

2. ``FlopLedger`` — the per-model site table the GBDT driver builds
   from its LOGICAL GLOBAL shapes (rows x features x bins, independent
   of sharding), so the accounting is deterministic, identical between
   ``tree_learner=data`` and serial, and non-empty even when a warm jit
   cache means nothing re-traces.  ``obs.ObsSession.record_flops``
   turns the site table into per-iteration ``flops.*`` counters, and
   ``obs/attrib.py`` joins them with the fenced phase spans into
   ``perf.*`` roofline keys.

FLOP conventions (documented so the numbers are comparable run to
run, not because the constants are exact):

- histogram: 2 FLOPs per multiply-add of the one-hot contraction —
  ``2 * C * N * F * Bp`` per full-N pass (the MXU useful work; padded
  bins included because the hardware computes them).
- split scan / partition / traversal: elementwise-op estimates with
  per-cell constants documented at each formula.

HBM-byte convention: bytes that MUST cross HBM for the op — operand
reads + result writes, assuming perfect fusion of generated
intermediates.  For the histogram that holds of the TPU's kernel
(``ops/hist_kernel.py``: the one-hot lives in VMEM), not of the scan,
whose one-hot XLA writes to HBM and reads back (``ops/histogram.py``).
"""

from __future__ import annotations

import contextlib
import contextvars
import threading
from typing import Dict, NamedTuple, Tuple


class FlopSite(NamedTuple):
    site: str         # stable call-site name, e.g. "hist"
    phase: str        # iteration phase the time lands in (grad/grow/score)
    flops: int        # FLOPs per execution of the site
    hbm_bytes: int    # HBM bytes per execution (reads + writes)
    cadence: str      # "step" (per grower loop step) | "iter" (per iter)


def padded_bins(num_bins: int) -> int:
    """The histogram kernel's padded bin axis (ops/histogram.py pads to
    a multiple of 64 so the merge is a free relayout) — the bin width
    FLOP accounting must use, because the hardware computes the pad."""
    return max(64, -(-int(num_bins) // 64) * 64)


# ---------------------------------------------------------------------------
# Formula functions — the ONE definition each call site and the driver
# ledger share.  All return (flops, hbm_bytes) ints.
# ---------------------------------------------------------------------------

def hist_flops_bytes(n_rows: int, n_cols: int, num_bins: int,
                     channels: int = 3,
                     binned_itemsize: int = 1,
                     vals_itemsize: int = 4,
                     slotted: bool = None) -> Tuple[int, int]:
    """One full-N one-hot-contraction histogram pass over ``n_cols``
    binned columns (features, or EFB groups): ``hist[c, f*Bp] +=
    vals[c, n] @ onehot[n, f*Bp]`` — 2 FLOPs per MAC.  ``channels`` is
    the accumulated channel count (3 strict; 3K for the split_batch
    multi-leaf contraction).  Bytes: binned matrix read + the
    (grad, hess, weight) vals read AT THEIR STORED WIDTH
    (``vals_itemsize``: 4 for f32, 1/2 for the int8/int16 quantized
    packing — the per-dtype accounting the quant_train acceptance
    instrument reads) + the [N] int32 slot vector when the TRUE
    multi-slot expansion is active (``slotted``: num_slots > 1, the
    kernel passes it explicitly; defaults to ``channels > 3``) +
    histogram write (f32 and int32 are both 4-byte lanes); the one-hot
    is not counted (the kernel builds it in VMEM; the scan's, which does
    cross HBM, is the formulation's overhead, not the algorithm's bytes).

    Accounting convention for the strict hist_overlap path: its 1-slot
    mask is the in-graph ENCODING of the masked pass it is
    byte-identical to — like the ``vals * mask`` temp it replaces
    (which this model never counted under the perfect-fusion rule),
    the [N] mask carries no operand bytes here.  Only a real K-way
    slot expansion (num_slots > 1) adds the slot read, which keeps the
    quantized-training byte-cut instrument (docs/Quantized-Training.md
    ≥2x pin) calibrated identically across overlap on/off.

    ``channels`` is the USEFUL (logical) width: the MXU lane padding
    wide widths take (C = 3K > 48 buckets to 128 multiples,
    utils/shapes.bucket_channels) is NOT useful work, so its MACs are
    excluded here and accounted separately by
    :func:`hist_pad_flops_bytes` under the MFU-excluded ``pad`` phase
    — MFU from this site stays an honest useful-work fraction.  The
    histogram WRITE does cross HBM at the padded width (the padded
    accumulator is materialized before the in-kernel slice), so the
    write term uses the padded channel count."""
    from ..utils.shapes import bucket_channels
    if slotted is None:
        slotted = int(channels) > 3
    bp = padded_bins(num_bins)
    flops = 2 * int(channels) * int(n_rows) * int(n_cols) * bp
    hbm = (int(n_rows) * int(n_cols) * int(binned_itemsize)
           + int(n_rows) * 3 * int(vals_itemsize)
           + (int(n_rows) * 4 if slotted else 0)
           + bucket_channels(int(channels)) * int(n_cols) * bp * 4)
    return flops, hbm


def hist_pad_flops_bytes(n_rows: int, n_cols: int, num_bins: int,
                         channels: int = 3) -> Tuple[int, int]:
    """The lane-pad MACs of one wide histogram pass: the hardware
    multiplies the padded ``bucket_channels(C) - C`` zero columns too
    (ops/histogram.py), but they produce no useful result — recorded
    as the ``hist_pad`` site under ``phase="pad"``, which
    ``obs/attrib.perf_summary`` reports per-site but EXCLUDES from
    phase/total aggregation so MFU never counts padding as achieved
    work.  Zero bytes: the pad's operand columns are generated
    in-registers and its write share is already in the ``hist`` site's
    padded write term."""
    from ..utils.shapes import bucket_channels
    c = int(channels)
    pad = bucket_channels(c) - c
    bp = padded_bins(num_bins)
    return 2 * pad * int(n_rows) * int(n_cols) * bp, 0


# elementwise ops per (direction, feature, bin) cell of the numerical
# split scan: cumsum add, left/right sums (6), two leaf gains (~2x8),
# gain shift + subtract (3), six validity masks + where (~12), argmax
# compare (1) — a documented estimate, stable across runs
SPLIT_SCAN_OPS_PER_CELL = 40
# bytes per (feature, bin) cell: hist read [3] f32 + the two-direction
# gain tensor write+read [2 x 2] f32
SPLIT_SCAN_BYTES_PER_CELL = 4 * (3 + 4)


def split_scan_flops_bytes(n_feat: int, num_bins: int,
                           n_leaves: int = 1) -> Tuple[int, int]:
    """Best-split scan over ``n_leaves`` candidate leaves: the two
    directional scans over the ``[2, F, B]`` gain tensor
    (ops/split.py find_best_split), VPU elementwise work."""
    cells = 2 * int(n_feat) * int(num_bins) * int(n_leaves)
    return (SPLIT_SCAN_OPS_PER_CELL * cells,
            SPLIT_SCAN_BYTES_PER_CELL
            * int(n_feat) * int(num_bins) * int(n_leaves))


# per-row ops of one partition pass, a slot: the slot's compare and the
# selects that hand the row its slot's column, threshold, missing-value bin,
# default direction, new leaf and the two target slots
PARTITION_OPS_PER_ROW_SLOT = 8
# per-row ops past the slots: the missing-value test (2), the threshold
# compare, the direction, leaf and target-slot selects
PARTITION_OPS_PER_ROW = 6


def partition_flops_bytes(n_rows: int, binned_itemsize: int = 1,
                          slots: int = 1) -> Tuple[int, int]:
    """One row-partition pass (``grower._partition_rows``, a step of the
    strict grower or a super-step of the batched one): ``slots`` compares
    and selects a row hand it its slot's scalars, then one threshold
    compare rewrites ``leaf_of_row`` and, for the batched grower, writes
    the rows' contraction slot.  Bytes: the ``slots`` split columns read
    (one dynamic slice each; no gather), ``leaf_of_row`` read and written,
    ``tslot`` written where there is more than one slot (int32 each)."""
    n, k = int(n_rows), int(slots)
    return ((PARTITION_OPS_PER_ROW_SLOT * k + PARTITION_OPS_PER_ROW) * n,
            k * n * int(binned_itemsize) + (2 + (k > 1)) * n * 4)


# ops per quantized value: divide by scale, hash-uniform draw (~2 mixes
# amortized), add, floor, clip — a documented estimate (ops/quantize.py)
QUANTIZE_OPS_PER_VAL = 5


def quantize_flops_bytes(n_rows: int,
                         out_itemsize: int = 1) -> Tuple[int, int]:
    """One per-iteration grad/hess/weight packing pass (quant_train,
    ops/quantize.py): the [N, 3] f32 stack read + the int8/int16 stack
    written; the scale reduction's [N, 3] read fuses with it."""
    n3 = 3 * int(n_rows)
    return (QUANTIZE_OPS_PER_VAL * n3,
            n3 * 4 + n3 * int(out_itemsize))


def dequant_flops_bytes(n_cols: int, num_bins: int,
                        n_leaves: int = 1) -> Tuple[int, int]:
    """Split-scan-time dequantization (ops/split.py dequantize_hist):
    one int32->f32 widening multiply per (leaf, column, bin, channel)
    cell; int32 read + f32 write, both 4-byte lanes."""
    cells = 3 * int(n_cols) * int(num_bins) * int(n_leaves)
    return cells, 2 * 4 * cells


def score_update_flops_bytes(n_rows: int) -> Tuple[int, int]:
    """Per-iteration score update: ``score += leaf_value[leaf_of_row]``
    — one gather + one add per row; leaf_of_row read, score
    read-modify-write."""
    n = int(n_rows)
    return 2 * n, n * 4 + 2 * n * 4


def eval_flops_bytes(n_rows: int, n_entries: int) -> Tuple[int, int]:
    """Traced in-scan metric evaluation (metrics.traced_metric_fn,
    models/gbdt.py train_superepoch): ~8 ops per (valid row, metric
    entry) — transform, clip, weight, pad-mask, reduce — charged against
    the TRAIN row count as a conservative stand-in (valid sets are
    usually smaller).  Bytes: score/label/weight reads per entry."""
    n = int(n_rows) * max(int(n_entries), 1)
    return 8 * n, 3 * 4 * n


# per (row, tree, level) ops of the binned traversal: node gather,
# feature gather, bin gather, NaN test, rank gather, compare,
# child select, finished-row select
TRAVERSE_OPS_PER_STEP = 8
# bytes per (row, tree, level): ~6 gathered int32 words
TRAVERSE_BYTES_PER_STEP = 6 * 4


def traverse_flops_bytes(n_rows: int, n_trees: int, steps: int,
                         n_feat: int,
                         binned_itemsize: int = 1) -> Tuple[int, int]:
    """Fixed-depth binned traversal (predict_device.py): every row
    walks ``n_trees`` trees one level per step for ``steps`` levels.
    Bytes add one read of the binned matrix."""
    per_level = int(n_rows) * int(n_trees) * int(steps)
    return (TRAVERSE_OPS_PER_STEP * per_level,
            TRAVERSE_BYTES_PER_STEP * per_level
            + int(n_rows) * int(n_feat) * int(binned_itemsize))


def device_bin_flops_bytes(n_rows: int, n_feat: int,
                           thr_bins: int) -> Tuple[int, int]:
    """On-device model-derived binning (predict_device
    ``bin_rows_device*``): one compare+accumulate per (row, feature,
    threshold-table slot) — the searchsorted-as-comparison-sum.
    Bytes: raw f32 rows read + threshold tables read + binned write
    (the binned tensor stays in registers when fused ahead of the
    traversal, but the write is counted as the op's result)."""
    n, f, b = int(n_rows), int(n_feat), int(thr_bins)
    flops = 2 * n * f * b
    hbm = n * f * 4 + f * b * 4 + n * f * 4
    return flops, hbm


def fused_forest_flops_bytes(n_rows: int, n_trees: int, steps: int,
                             n_feat: int, thr_bins: int,
                             num_class: int = 1,
                             table_itemsize: int = 4) -> Tuple[int, int]:
    """One fused serve batch (predict_device.fused_forest_predict):
    on-device binning + whole-forest traversal + tree-order leaf-value
    accumulation (gather + multiply + add per (row, tree)) + objective
    transform (~4 elementwise ops per output).  ``table_itemsize`` is
    the PACKED node-table element width (serve_packed_tables), which
    scales the traversal's gather bytes; the final ``[rows, out]``
    score is the only tensor that crosses back to the host."""
    n, t, k = int(n_rows), int(n_trees), max(1, int(num_class))
    bf, bb = device_bin_flops_bytes(n, n_feat, thr_bins)
    per_level = n * t * int(steps)
    tf = TRAVERSE_OPS_PER_STEP * per_level
    tb = (TRAVERSE_BYTES_PER_STEP * per_level
          * int(table_itemsize)) // 4
    af = 3 * n * t + 4 * n * k
    ab = n * t * 4 + 2 * n * k * 4
    return bf + tf + af, bb + tb + ab


def train_hist_flops_per_iter(n_rows: int, n_feat: int, num_bins: int,
                              num_leaves: int) -> float:
    """Useful histogram FLOPs per boosting iteration: one C=3 full-N
    contraction per smaller-child pass, (num_leaves - 1) passes/tree,
    derived from the shared formula."""
    f, _ = hist_flops_bytes(n_rows, n_feat, num_bins, channels=3)
    return float(f) * (int(num_leaves) - 1)


# ---------------------------------------------------------------------------
# Channel 1: trace-time site notes (process-global, like trace_event)
# ---------------------------------------------------------------------------

_TRACED_LOCK = threading.Lock()
_TRACED: Dict[str, FlopSite] = {}
# traces by (site, implementation), and the ``jax.monitoring`` event that
# carries each to the running session's registry
_IMPLS: Dict[Tuple[str, str], int] = {}
IMPL_EVENT_PREFIX = "/lgbtpu/impl/"
PLAN_EVENT_PREFIX = "/lgbtpu/plan/"
PARTITION_EVENT_PREFIX = "/lgbtpu/partition/"

# ambient member-axis multiplier (fleet/trainer.py): while a fleet
# program traces, every site note fires ONCE (vmap traces the body once)
# but the compiled program executes it N times per dispatch — scale the
# note so perf.* / MFU stay truthful for the whole fleet.  A contextvar
# (not a global) so a concurrent solo trace in another thread is not
# contaminated.
_MEMBER_AXIS: "contextvars.ContextVar[int]" = contextvars.ContextVar(
    "lgbtpu_member_axis", default=1)


def _member_scale() -> int:
    return _MEMBER_AXIS.get()


@contextlib.contextmanager
def member_axis(n: int):
    """Scale every ``note_traced`` fired inside the context by ``n`` —
    wrap the fleet program's trace/dispatch so the process-wide traced
    ledger accounts all N members' work, not one lane's."""
    tok = _MEMBER_AXIS.set(max(1, int(n)))
    try:
        yield
    finally:
        _MEMBER_AXIS.reset(tok)


def note_traced(site: str, flops: int, hbm_bytes: int,
                phase: str = "", cadence: str = "step",
                impl: str = "") -> None:
    """Record a site's static accounting from TRACED shapes.  Called
    inside jitted function bodies, so it fires once per fresh trace and
    overwrites idempotently on retrace — the latest traced shapes win
    (the process-wide view; per-model attribution goes through the
    driver's FlopLedger, which never depends on jit-cache state).
    Under :func:`member_axis` the note is scaled by the fleet's member
    count — vmap traces the body once but runs it N-wide.

    ``impl`` names the implementation traced at a site that has more than
    one (the histogram contraction: ``vmem`` | ``scan``).  Each trace is
    counted, process-wide (:func:`traced_impls`) and, through
    ``jax.monitoring``, as ``<site>.contraction_traces{impl=}`` in the
    registry of the session that runs (``compile_cache.watch_compiles``)."""
    scale = _member_scale()
    with _TRACED_LOCK:
        _TRACED[site] = FlopSite(site=site, phase=phase,
                                 flops=int(flops) * scale,
                                 hbm_bytes=int(hbm_bytes) * scale,
                                 cadence=cadence)
        if impl:
            _IMPLS[(site, impl)] = _IMPLS.get((site, impl), 0) + 1
    if impl:
        from jax import monitoring
        monitoring.record_event(f"{IMPL_EVENT_PREFIX}{site}/{impl}")


def note_kernel_plan(site: str, **tiles: int) -> None:
    """Count a trace of ``site``'s kernel by the tiles its plan chose, as
    ``<site>.kernel_plans{<tile>=...}`` in the registry of the session that
    runs (through ``jax.monitoring``, like the count by implementation):
    ``hist.kernel_plans{fpart=32,parts=4}`` says the contraction's
    accumulator was held in four parts of 32 features."""
    from jax import monitoring
    monitoring.record_event(PLAN_EVENT_PREFIX + site + "/" + ",".join(
        f"{k}={int(v)}" for k, v in sorted(tiles.items())))


def note_partition_rule(rule: str, row_gathers: int) -> None:
    """Count a trace of a grower's row partition by the rule it took
    (``grower._partition_rows``: ``select``, ``select+rank`` or ``sparse``)
    as ``grower.partition_rule{rule=}``, and add the ``[N]`` look-ups that
    rule keeps a step to ``grower.partition_row_gathers``, in the registry
    of the session that runs (through ``jax.monitoring``, like the kernel's
    plan)."""
    from jax import monitoring
    monitoring.record_event(
        f"{PARTITION_EVENT_PREFIX}{rule}/{int(row_gathers)}")


def traced_sites() -> Dict[str, FlopSite]:
    """Process-wide snapshot of the trace-time site notes."""
    with _TRACED_LOCK:
        return dict(_TRACED)


def traced_impls() -> Dict[Tuple[str, str], int]:
    """Process-wide count of traces by (site, implementation)."""
    with _TRACED_LOCK:
        return dict(_IMPLS)


# ---------------------------------------------------------------------------
# Channel 2: the per-model ledger
# ---------------------------------------------------------------------------

class FlopLedger:
    """Per-model static compute ledger, the compute sibling of
    ``obs/comm.CommLedger``: a table of (site, phase, flops, hbm_bytes,
    cadence) built from LOGICAL GLOBAL shapes so serial and
    ``tree_learner=data`` produce byte-identical accounting."""

    def __init__(self):
        self._sites: Dict[str, FlopSite] = {}

    def add(self, site: str, phase: str, flops: int, hbm_bytes: int,
            cadence: str = "step") -> None:
        self._sites[site] = FlopSite(site=site, phase=phase,
                                     flops=int(flops),
                                     hbm_bytes=int(hbm_bytes),
                                     cadence=cadence)

    def sites(self) -> Tuple[FlopSite, ...]:
        return tuple(self._sites[k] for k in sorted(self._sites))

    def per_iteration(self, n_steps: int) -> Tuple[int, int]:
        """(flops, hbm_bytes) for one boosting iteration that ran
        ``n_steps`` grower loop steps."""
        f = b = 0
        for s in self.sites():
            mult = n_steps if s.cadence == "step" else 1
            f += s.flops * mult
            b += s.hbm_bytes * mult
        return f, b

    def flop_share(self, n_steps: int) -> Dict[str, float]:
        """Static per-site share of one iteration's FLOPs — the
        "where would the nanoseconds go on ideal hardware" split every
        bench point records alongside the measured rate."""
        total, _ = self.per_iteration(n_steps)
        if total <= 0:
            return {}
        return {s.site: round(s.flops
                              * (n_steps if s.cadence == "step" else 1)
                              / total, 4)
                for s in self.sites()}

    @classmethod
    def for_training(cls, n_rows: int, n_feat: int, num_bins: int,
                     split_batch: int = 1, hist_cols: int = None,
                     hist_bins: int = None, binned_itemsize: int = 1,
                     num_class: int = 1,
                     vals_itemsize: int = 4,
                     quant: bool = False) -> "FlopLedger":
        """The training-loop site table for the masked grower family.

        ``hist_cols``/``hist_bins``: the histogram pass's column/bin
        axes when they differ from the scan space (EFB bundles build
        G-column histograms at the max group-bin width, then expand to
        F features for the scan); default to ``n_feat``/``num_bins``.
        ``num_class``: trees grown per iteration — iter-cadence sites
        run once PER CLASS, so their per-iteration values carry the
        factor (step-cadence sites get it through the summed
        across-class step count the driver records).
        ``vals_itemsize``/``quant``: quantized training (quant_train)
        — the histogram passes read int8/int16 accumulands instead of
        f32, and the quantize/dequant sites appear so ``perf.hist.*``
        intensity/bound keys show the bound actually moving.  (The
        strict hist_overlap path's 1-slot mask is accounted as the
        masked pass it is byte-identical to — see
        :func:`hist_flops_bytes`.)  Sites:

        - ``hist``       smaller-child contraction, C=3K, per step
        - ``hist_pad``   MXU lane-pad MACs of the wide contraction
                         (C=3K > 48 buckets to 128 multiples), per
                         step — phase="pad", excluded from MFU
        - ``hist_root``  root contraction, C=3, per class per iter
        - ``split_scan`` 2K candidate leaves per step
        - ``split_root`` root scan, per class per iteration
        - ``partition``  one row pass per step
        - ``score``      leaf-gather score update, per class per iter
        - ``quantize``   grad/hess int packing, per class per iter
        - ``dequant``    scan-time int32->f32 widen, per step
        """
        k = max(1, int(split_batch))
        nc = max(1, int(num_class))
        hc = int(hist_cols) if hist_cols else int(n_feat)
        hb = int(hist_bins) if hist_bins else int(num_bins)
        led = cls()
        f, b = hist_flops_bytes(n_rows, hc, hb, channels=3 * k,
                                binned_itemsize=binned_itemsize,
                                vals_itemsize=vals_itemsize,
                                slotted=k > 1)
        led.add("hist", "grow", f, b, "step")
        f, b = hist_pad_flops_bytes(n_rows, hc, hb, channels=3 * k)
        if f:
            led.add("hist_pad", "pad", f, b, "step")
        f, b = hist_flops_bytes(n_rows, hc, hb, channels=3,
                                binned_itemsize=binned_itemsize,
                                vals_itemsize=vals_itemsize,
                                slotted=False)
        led.add("hist_root", "grow", f * nc, b * nc, "iter")
        f, b = split_scan_flops_bytes(n_feat, num_bins, n_leaves=2 * k)
        led.add("split_scan", "grow", f, b, "step")
        f, b = split_scan_flops_bytes(n_feat, num_bins, n_leaves=1)
        led.add("split_root", "grow", f * nc, b * nc, "iter")
        f, b = partition_flops_bytes(n_rows, binned_itemsize, slots=k)
        led.add("partition", "grow", f, b, "step")
        f, b = score_update_flops_bytes(n_rows)
        led.add("score", "score", f * nc, b * nc, "iter")
        if quant:
            f, b = quantize_flops_bytes(n_rows, vals_itemsize)
            led.add("quantize", "grow", f * nc, b * nc, "iter")
            f, b = dequant_flops_bytes(n_feat, num_bins, n_leaves=2 * k)
            led.add("dequant", "grow", f, b, "step")
        return led
