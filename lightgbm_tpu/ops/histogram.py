"""Histogram construction: the hot kernel of GBDT training.

Replaces the reference's histogram kernels — CPU
``DenseBin::ConstructHistogram`` (/root/reference/src/io/dense_bin.hpp),
CUDA ``CUDAConstructHistogramDenseKernel``
(/root/reference/src/treelearner/cuda/cuda_histogram_constructor.cu:18-70,
shared-memory atomicAdd per (bin, grad/hess)) — with a TPU-native
formulation: scatter-add has no fast TPU lowering, so the histogram is
computed as a **one-hot contraction on the MXU**:

    hist[f*B + b, c] = sum_n (binned[n, f] == b) * vals[n, c]

Two implementations of that contraction, one a regime; the call's backend,
dtypes and shapes choose (:func:`vmem_plan`), never a parameter:

- ``vmem`` (``ops/hist_kernel.py``): on a TPU, for float32 accumulands.  One
  Pallas kernel builds each 0/1 tile in VMEM and contracts it there, in
  three bfloat16 pieces of the accumuland that sum to it exactly; the
  one-hot never exists in HBM.
- ``scan`` (below): everywhere else (the CPU, with its byte-identity pins on
  block partitions; integer accumulands).  A ``[C, block] @ [block, F*B]``
  matmul per row block, accumulated over blocks with ``lax.scan``; the
  one-hot of a block is an array of its own.

The records, since this docstring once said otherwise.  On the TPU XLA does
**not** fuse the iota-compare into the matmul's operand load: it writes each
block's one-hot to HBM and reads it back.  At 2,000 features x 64 bins the
64 MB budget below clips the block to 128 rows, and a block cost 20.1 us
to write (16.4 MB at the chip's 819 GB/s) and 38.3 us to contract: a pass
over 320,000 rows x 16 slots 155.2 ms on the v5e (ledger, PR 26).  No XLA
formulation tried keeps it out of HBM (a batched 3-D contraction, bfloat16
pieces, rows on lanes: each compiles to a ``pred[block, F*Bp]`` fusion
output; TPU compiler in the sandbox, PR 27).  An earlier Pallas kernel read
8.2 against the scan's 4.7 ms a pass at 1M x 28 x 64 bins and was removed
(before PR 22, in an earlier round; neither its source nor its layout is on
record); the scan then ran at the backend's default precision, and PR 22,
which stated ``HIGHEST``, read it at 5.38 ms there.  PR 27's kernel against
the scan, one v5e, milliseconds a pass (builder's chip run, PR 27): 320,000
x 2,000 at 16 slots 70.5 against 156.3, with no slot 40.7 against 144.3; 1M
x 28 with no slot 2.74 against 6.05, at 16 slots 4.14 against 6.41.  The
kernel is the faster at both shapes, so the rule has no condition on width.
Channels C = (grad, hess, count).

All features share a uniform padded bin axis ``B`` (= dataset max_bin) so
shapes are static; per-feature valid-bin masking happens in the split scan.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


# Cap on the row-block (lax.scan chunk) size for the histogram pass.
# Measured on TPU v5e (1M x 28 x 63 bins): with the
# [C, rows] x [rows, F*B] orientation below, 8192-row blocks run ~1.8x
# faster than VMEM-sized 888-row blocks — XLA tiles the one-hot
# internally, so second-guessing VMEM only shrank the matmuls.
HIST_BLOCK_ROWS = 8192
# ...but the one-hot intermediate is block*F*Bp*itemsize bytes: keep it
# bounded so wide/high-bin datasets (e.g. Bosch-like 968 features x 256
# bins) don't materialize multi-GB scan blocks in HBM.
HIST_ONEHOT_BUDGET = 64 * 1024 * 1024


def hist_block_rows(num_features: int, padded_bins: int,
                    itemsize: int = 4, channels: int = 3) -> int:
    """Row-block size of the SCAN, bounded by the one-hot intermediate's
    byte budget.  The budget governs whoever takes the scan: the CPU, and
    on a TPU the integer accumulands of ``quant_train`` (and a shape whose
    accumulator the kernel cannot hold, ``hist_kernel.tile_plan``); the kernel's
    one-hot tile lives in VMEM and has tiles of its own.
    ``itemsize`` is the accumuland (vals) element width — the
    one-hot operand is generated at the SAME width so the dot's operand
    dtypes match, so int8-packed passes (quant_train, ops/quantize.py)
    get proportionally larger blocks than the f32 default.

    ``channels``: the slot-expanded (and lane-padded) accumuland width
    C = cv·K of the multi-leaf contraction.  Past the shipped ceiling
    (C = 48, K = 16) the budget must account the C·K expansion the old
    feature-only formula ignored — at K=64 on a wide dataset the scan
    working set silently overshot ``HIST_ONEHOT_BUDGET``:

    - the ``[C, F·Bp]`` ACCUMULATOR carry (4-byte lanes) is resident
      for the whole scan regardless of block size, so it is subtracted
      from the budget first (a carry alone past the budget floors the
      block at 8 rows rather than pretending the budget holds);
    - the per-block ``vals ⊗ onehot(slot)`` product adds
      ``block·C·itemsize`` alongside the one-hot's ``block·F·Bp``.

    At or below the shipped widths both terms are EXCLUDED so the
    regression-pinned block shapes (and therefore the f32 accumulation
    order — histograms are byte-identical only for identical block
    partitions) of split_batch ∈ {1, 8, 16} stay exactly as before."""
    per_row = num_features * padded_bins * int(itemsize)
    budget = HIST_ONEHOT_BUDGET
    from ..utils.shapes import HIST_CHANNEL_EXACT_MAX
    if int(channels) > HIST_CHANNEL_EXACT_MAX:
        per_row += int(channels) * int(itemsize)
        budget -= int(channels) * num_features * padded_bins * 4
    blk = max(budget, 0) // max(per_row, 1)
    return max(8, min(HIST_BLOCK_ROWS, blk // 8 * 8))


def pad_feature_axis(h: jax.Array, total: int) -> jax.Array:
    """Zero-pad the feature/group axis of a channel-major histogram
    ``[C, F, B]`` to ``total`` rows.  The owner-shard reduce-scatter
    (parallel/data_parallel.py) needs the histogram's chunk axis to
    divide evenly over the mesh; zero rows reduce to zero and are never
    scanned (their scan slots carry a False feature mask)."""
    pad = total - h.shape[1]
    if pad <= 0:
        return h
    return jnp.pad(h, ((0, 0), (0, pad), (0, 0)))


def compute_histogram(binned: jax.Array, vals: jax.Array, *, num_bins: int,
                      block_rows: int = 0, slot: Optional[jax.Array] = None,
                      num_slots: int = 1,
                      channel_major: bool = False) -> jax.Array:
    """hist[f, b, c] = sum over rows n of (binned[n,f]==b) * vals[n,c].

    binned: [N, F] integer bins (uint8/uint16/int32)
    vals:   [N, C] float32 per-row accumulands (grad, hess, count-weight);
            rows outside the target leaf / bag must already be zeroed.
            int8/int16 vals (quantized training, ops/quantize.py) take
            the integer contraction: the one-hot operand is generated at
            the vals dtype and the dot accumulates **exact int32**, so
            the returned histogram is int32 and cross-shard reductions
            of it are bitwise order-independent.
    returns [F, num_bins, C] float32 (int32 for integer vals) — with
    ``slot`` set, C becomes ``C * num_slots`` (channel ``c * num_slots +
    slot``).  ``channel_major`` returns ``[C, F, num_bins]`` instead, as
    both implementations accumulate it: the masked grower keeps its
    histograms so (grower.py), because an array whose minor axis is the
    3 channels is padded to 128 lanes wherever the TPU's compiler tiles
    it (42.8x; at 2,000 features x 255 bins one such copy was 15.6 GB,
    PERF.md §6, PR 30).

    slot/num_slots: per-row slot id in [0, num_slots) or negative for
    "no slot" (row contributes nothing).  The per-slot one-hot expansion
    ``vals ⊗ onehot(slot)`` is generated INSIDE the row-block scan, so
    the multi-leaf batched grower never materializes the [N, C*K]
    operand in HBM (at 10M rows x K=8 that buffer alone would be ~1 GB).

    ``block_rows`` is the scan's row block; the kernel takes its tiles
    from the shapes and ignores it.
    """
    plan = vmem_plan(binned, vals, num_bins=num_bins,
                     num_slots=num_slots if slot is not None else 1)
    if plan is not None:
        return _compute_histogram_vmem(binned, vals, num_bins=num_bins,
                                       plan=plan, slot=slot,
                                       num_slots=num_slots,
                                       channel_major=channel_major)
    return _compute_histogram_matmul(binned, vals, num_bins=num_bins,
                                     block_rows=block_rows, slot=slot,
                                     num_slots=num_slots,
                                     channel_major=channel_major)


def vmem_plan(binned: jax.Array, vals: jax.Array, *, num_bins: int,
              num_slots: int = 1):
    """The kernel's tiles where the kernel runs, else None (the scan): a
    TPU behind the call, float32 accumulands, integer bins, and an
    accumulator that ``hist_kernel.tile_plan`` can hold in VMEM.  Sparse rows
    never come here (``sparse_data.histogram``)."""
    if jax.default_backend() != "tpu" or vals.dtype != jnp.float32 \
            or not jnp.issubdtype(binned.dtype, jnp.integer):
        return None
    from .hist_kernel import tile_plan
    return tile_plan(*binned.shape, num_bins, vals.shape[1] * num_slots)


def _note_pass(binned, vals, num_bins: int, channels: int, slotted: bool,
               impl: str) -> None:
    """Static FLOP/byte accounting from the TRACED shapes (obs/flops.py; a
    Python side effect, so it fires once per fresh trace and costs nothing
    at runtime), and the count of contraction sites by implementation,
    ``hist.contraction_traces{impl=}``.  The "hist" site carries the USEFUL
    channels only."""
    from ..obs.flops import hist_flops_bytes, note_traced
    n, f = binned.shape
    note_traced("hist", *hist_flops_bytes(
        n, f, num_bins, channels=channels,
        binned_itemsize=getattr(binned.dtype, "itemsize", 1),
        vals_itemsize=getattr(vals.dtype, "itemsize", 4),
        slotted=slotted), phase="grow", impl=impl)


@functools.partial(jax.jit, static_argnames=("num_bins", "plan", "num_slots",
                                             "channel_major"))
@jax.named_scope("lgbtpu.hist")
def _compute_histogram_vmem(binned: jax.Array, vals: jax.Array, *,
                            num_bins: int, plan,
                            slot: Optional[jax.Array] = None,
                            num_slots: int = 1,
                            channel_major: bool = False) -> jax.Array:
    from .hist_kernel import hist_vmem
    k = num_slots if slot is not None else 1
    _note_pass(binned, vals, num_bins, vals.shape[1] * k,
               slot is not None and num_slots > 1, impl="vmem")
    from ..obs.flops import note_kernel_plan
    note_kernel_plan("hist", parts=plan.parts, fpart=plan.fpart)
    return hist_vmem(binned, vals, num_bins=num_bins, plan=plan, slot=slot,
                     num_slots=num_slots, channel_major=channel_major)


# device-phase names (metadata only): the pass is ``lgbtpu.hist``, and in
# it the making of the one-hot operand and the contraction, which in the
# scan are separate operations on the chip, carry a scope each (the kernel
# is one operation, ``lgbtpu.hist.contract``)
@functools.partial(jax.jit,
                   static_argnames=("num_bins", "block_rows", "num_slots",
                                    "channel_major"))
@jax.named_scope("lgbtpu.hist")
def _compute_histogram_matmul(binned: jax.Array, vals: jax.Array, *,
                              num_bins: int, block_rows: int = 0,
                              slot: Optional[jax.Array] = None,
                              num_slots: int = 1,
                              channel_major: bool = False) -> jax.Array:
    n, f = binned.shape
    c = vals.shape[1] * (num_slots if slot is not None else 1)
    # wide multi-leaf contractions (split_batch K ∈ {32, 64} → C = 3K
    # ∈ {96, 192}) pad the channel axis to MXU lane multiples of 128
    # (utils/shapes.bucket_channels) so the [block, C] accumuland
    # operand fills whole 128-lane tiles; the pad columns belong to
    # slots no row carries (exact zeros) and are sliced off below.
    # Shipped widths (C <= 48) keep their exact shapes.
    from ..utils.shapes import bucket_channels
    c_pad = bucket_channels(c)
    # integer accumulands (quantized training): int8/int16 operands,
    # exact int32 accumulation on the MXU's low-precision path
    integer = jnp.issubdtype(vals.dtype, jnp.integer)
    op_dt = vals.dtype if integer else jnp.float32
    acc_dt = jnp.int32 if integer else jnp.float32

    # the lane-pad MACs go to the MFU-excluded "hist_pad" site (phase="pad")
    _note_pass(binned, vals, num_bins, c, slot is not None and num_slots > 1,
               impl="scan")
    if c_pad > c:
        from ..obs.flops import hist_pad_flops_bytes, note_traced
        note_traced("hist_pad", *hist_pad_flops_bytes(n, f, num_bins,
                                                      channels=c),
                    phase="pad")

    # Pad the bin axis to a multiple of 64 so the [blk, F, Bp] -> [blk, F*Bp]
    # merge is a free relayout (the minor dim tiles onto the 128-lane
    # registers).  Measured on v5e: B=63 unpadded costs 14.3 ms/pass vs
    # 5.5 ms padded to 64; padding to 128 is SLOWER again (8.1 ms), and
    # even B=15 runs faster padded to 64 than to 16.  Padded bins compare
    # equal to nothing (bins < num_bins), so the extra columns stay zero
    # and are sliced off at the end.
    bp = max(64, -(-num_bins // 64) * 64)
    if block_rows <= 0:
        block_rows = hist_block_rows(f, bp,
                                     getattr(vals.dtype, "itemsize", 4),
                                     channels=c_pad)
    block_rows = min(block_rows, max(8, n))

    cv = vals.shape[1]                       # raw (unexpanded) channels
    pad = (-n) % block_rows
    if pad:
        binned = jnp.pad(binned, ((0, pad), (0, 0)))
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
        if slot is not None:
            slot = jnp.pad(slot, (0, pad), constant_values=-1)
    nblocks = (n + pad) // block_rows

    binned_b = binned.reshape(nblocks, block_rows, f)
    vals_b = vals.reshape(nblocks, block_rows, cv)
    iota = jnp.arange(bp, dtype=jnp.int32)
    xs = (binned_b, vals_b)
    if slot is not None:
        xs = xs + (slot.reshape(nblocks, block_rows),)
        kiota = jnp.arange(num_slots, dtype=jnp.int32)

    def body(acc, chunk):
        bins_blk, vals_blk = chunk[0], chunk[1]
        if slot is not None:
            # expand vals ⊗ onehot(slot) per block, fused into the scan:
            # the [N, cv*K] operand never exists in HBM.  The 0/1 slot
            # one-hot multiplies at the vals dtype (an int8 product of
            # an int8 value and {0, 1} cannot overflow)
            oh_s = (chunk[2][:, None] == kiota).astype(op_dt)
            vals_blk = (vals_blk[:, :, None] * oh_s[:, None, :]) \
                .reshape(block_rows, c)
        if c_pad > c:
            # lane-pad the accumuland operand: the extra columns are
            # exact zeros (no slot reaches them), sliced off after the
            # scan, so they cost MXU cycles, never numerics
            vals_blk = jnp.pad(vals_blk, ((0, 0), (0, c_pad - c)))
        with jax.named_scope("lgbtpu.hist.onehot"):
            onehot = (bins_blk.astype(jnp.int32)[:, :, None] == iota) \
                .astype(op_dt).reshape(block_rows, f * bp)
        # [C, block] x [block, F*Bp] -> [C, F*Bp]: the narrow C=3 axis maps
        # to output SUBLANES (padded 3->8) instead of lanes (3->128), a
        # measured ~2.2x win over the transposed orientation.
        # HIGHEST for f32 accumulands: at default precision a TPU rounds
        # f32 matmul operands to bf16 (8 mantissa bits), so every grad and
        # hess would be rounded before it is summed (observed on v5e:
        # rows of 1 + 2^-12 sum to the row count).  The 0/1 operand is
        # exact either way; HIGHEST carries all 24 bits of the accumuland
        # into the f32 accumulator.  Integer operands are exact as is.
        with jax.named_scope("lgbtpu.hist.contract"):
            h = lax.dot_general(
                vals_blk, onehot,
                dimension_numbers=(((0,), (0,)), ((), ())),
                precision=None if integer else lax.Precision.HIGHEST,
                preferred_element_type=acc_dt)
            return acc + h, None

    acc0 = jnp.zeros((c_pad, f * bp), dtype=acc_dt)
    acc, _ = lax.scan(body, acc0, xs)
    out = acc[:c].reshape(c, f, bp)[:, :, :num_bins]       # [C, F, B]
    return out if channel_major else out.transpose(1, 2, 0)


def masked_histogram(binned: jax.Array, vals: jax.Array, leaf_of_row: jax.Array,
                     leaf: jax.Array, *, num_bins: int, block_rows: int = 0) -> jax.Array:
    """Histogram over only the rows whose current leaf == ``leaf``.

    The masked-full-pass equivalent of the reference's gathered smaller-leaf
    construction (cuda_histogram_constructor.cu) — static shapes, mask folded
    into the accumulands.
    """
    mask = (leaf_of_row == leaf).astype(vals.dtype)[:, None]
    return compute_histogram(binned, vals * mask, num_bins=num_bins,
                             block_rows=block_rows)


def feature_totals_residual(hist: jax.Array, vals: jax.Array) -> jax.Array:
    """Max absolute residual of the histogram's defining invariant:
    summing a feature's bins must reproduce the column totals of the
    accumulands, ``sum_b hist[f, b, c] == sum_n vals[n, c]`` for every
    feature ``f`` — the one-hot rows partition the rows exactly once.

    A scalar 0 (int accumulands) or ~rounding-sized value (f32) on a
    healthy device; a bit flip anywhere in the contraction shows up as
    a residual the size of the flipped magnitude.  Used by the
    integrity layer (lightgbm_tpu/integrity.py) as an attribution probe
    when a sticky histogram mismatch is being blackbox-dumped, and by
    the unit tests as a direct oracle on :func:`compute_histogram`.
    """
    tot = jnp.sum(hist, axis=1)                     # [F, C]
    col = jnp.sum(vals.astype(hist.dtype), axis=0)  # [C]
    return jnp.max(jnp.abs(tot - col[None, :]))
