"""The histogram contraction as one TPU kernel: the one-hot operand is
built in VMEM by the kernel that contracts it and never exists in HBM.

    hist[c, f*Bp + b] = sum_n vals3[c, n] * (binned[n, f] == b)

The XLA scan of ``ops/histogram.py`` writes every block's one-hot to HBM
and reads it back (at 2,000 features x 64 bins: 16.4 MB for 128 rows, 64
bytes for every byte of binned matrix; PERF.md §6, PR 27).  Here a grid
step takes a ``[rows, Ft]`` block of the binned matrix as it is placed
(``uint8``/``uint16``, through its ``BlockSpec``), turns it in VMEM so
that rows lie on lanes, and for a few features at a time compares their
bins, broadcast along sublanes, against an iota: a 0/1 tile
``[features*Bp, rows]`` in bfloat16, contracted at once on its lane axis
with the accumulands (an "NT" matmul, as attention's q.kT).  The float32
accumulator of a feature tile stays in VMEM across the row axis, the
grid's last, and is written once.

**Exact float32.**  The 0/1 operand is exact in bfloat16.  The slot-
expanded accumuland ``v`` is split into three bfloat16 pieces, ``hi =
bf16(v)``, ``mid = bf16(v - hi)``, ``lo = bf16(v - hi - mid)`` (3 x 8 =
24 mantissa bits, so ``hi + mid + lo == v``), stacked along the channel
axis into one bfloat16 matmul with float32 accumulation; the three row
groups are added at the end.  Every product is exact and every sum is
float32: the numbers of ``precision=HIGHEST``, up to summation order.

The tiles are chosen from the shapes (``tile_plan``); the caller has no say.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.flops import padded_bins

ROWS = 512                      # rows a grid step (the matmul's depth) ...
MAX_ROWS = 2048                 # ... and of a block narrower than FEATURE_TILE
FEATURE_TILE = 128              # features a binned block (its lane width)
ONEHOT_ROWS = 512               # (feature, bin) rows built and contracted at once
ACC_BYTES = 6 * 1024 * 1024     # the resident accumulator of one feature part
VMEM_FLOOR = 32 * 1024 * 1024   # what the kernel asks for at the least ...
VMEM_LIMIT = 100 * 1024 * 1024  # ... and at the most, of the v5e's 128 MiB


class Plan(NamedTuple):
    rows: int       # rows a grid step
    ft: int         # features a binned block
    fpart: int      # features whose accumulator is resident at once
    parts: int      # parts of a block (the block is read once a part)
    fsub: int       # features a one-hot sub-tile
    cp: int         # slot-expanded channels, padded to bfloat16's 16 sublanes
    bp: int         # padded bins
    vmem: int       # bytes the kernel asks for


def tile_plan(n: int, f: int, num_bins: int, channels: int,
              rows: Optional[int] = None) -> Optional[Plan]:
    """Tiles for ``[n, f]`` bins and ``channels`` slot-expanded
    accumulands, or None where even eight features' accumulator does not
    fit the kernel's VMEM (the caller then takes the scan).  ``rows`` is
    for the tests, which want several row blocks of few rows.  Swept on
    the v5e at 320,000 x 2,000 x 16 slots (PERF.md §6, PR 27): 256 to 2,048
    rows a step 71.7 to 68.5 ms a pass, 128 to 2,048 one-hot rows at once
    71.2 to 70.4, 256 features a block 90.8 against 70.5."""
    bp = padded_bins(num_bins)
    cp = -(-int(channels) // 16) * 16
    ft = min(f, FEATURE_TILE)
    per_feature = 3 * cp * bp * 4
    # a block whose accumulator does not fit is contracted in parts, as few
    # as fit and all alike: at 256 bins x 16 slots 40 features fit, and four
    # parts of 32 contract the block's 128 where four of 40 contract 160
    most = max(8, ACC_BYTES // per_feature // 8 * 8)
    parts = -(-ft // most)
    fpart = ft if parts == 1 else -(-ft // (8 * parts)) * 8
    fsub = max(1, min(fpart, ONEHOT_ROWS // bp))
    # a narrow block takes more rows, so that a grid step's fixed cost is
    # spread over as much one-hot (PERF.md §6, PR 27: 1M x 28, 3.1 → 2.7 ms)
    if rows is None:
        rows = min(MAX_ROWS, ROWS * (FEATURE_TILE // ft))
    rows = min(rows, max(128, -(-n // 128) * 128))
    vmem = (fpart * per_feature                  # accumulator
            + 2 * cp * fpart * bp * 4            # the output block, twice
            + 2 * rows * max(ft, 128) * 4        # binned blocks (32-bit at most)
            + parts * fpart * rows * 4           # the turned block
            + 6 * fsub * bp * rows * 4           # one-hot sub-tile and its making
            + 8 * 3 * cp * rows * 4)             # accumulands and their pieces
    vmem = max(vmem + (4 << 20), VMEM_FLOOR)
    if vmem > VMEM_LIMIT:
        return None
    return Plan(rows, ft, fpart, parts, fsub, cp, bp, vmem)


def _kernel(bins_ref, vals_ref, slot_ref, out_ref, acc_ref, bt_ref, *,
            cv, k, p: Plan):
    part, r = pl.program_id(1), pl.program_id(2)
    rows, cp, bp = p.rows, p.cp, p.bp

    @pl.when(r == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # vals (x) onehot(slot), rows on lanes: channel c*k + slot of [cp, rows];
    # a slot outside [0, k) meets no row of the iota
    slot = slot_ref[...]
    slot = jnp.where((slot >= 0) & (slot < k), slot, -cp)
    j = lax.broadcasted_iota(jnp.int32, (cp, rows), 0)
    a = jnp.zeros((cp, rows), jnp.float32)
    for c in range(cv):
        a = jnp.where(j == slot + c * k, vals_ref[c:c + 1, :], a)
    hi = a.astype(jnp.bfloat16)
    rest = a - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    a3 = jnp.concatenate([hi, mid, lo], axis=0)             # [3cp, rows]

    # the block turned so that a feature's bins lie along lanes; the v5e's
    # VPU compares in 32 bits
    bt_ref[0:p.ft, :] = bins_ref[...].astype(jnp.int32).T
    if p.parts > 1:
        first = pl.multiple_of(part * p.fpart, 8)
        bins_t = bt_ref[pl.ds(first, p.fpart), :]
    else:
        bins_t = bt_ref[0:p.fpart, :]

    for s in range(0, p.fpart, p.fsub):
        fs = min(p.fsub, p.fpart - s)           # the last sub-tile may be short
        # (an iota hoisted out of the loop and sliced to fs aborts Mosaic)
        iota = lax.broadcasted_iota(jnp.int32, (fs, bp, rows), 1)
        sub = bins_t[s:s + fs, :]
        onehot = (sub[:, None, :] == iota) \
            .astype(jnp.float32).astype(jnp.bfloat16).reshape(fs * bp, rows)
        acc_ref[:, s * bp:(s + fs) * bp] += lax.dot_general(
            a3, onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(r == pl.num_programs(2) - 1)
    def _():
        out_ref[...] = (acc_ref[0:cp, :] + acc_ref[cp:2 * cp, :]) \
            + acc_ref[2 * cp:3 * cp, :]


def hist_vmem(binned: jax.Array, vals: jax.Array, *, num_bins: int,
              plan: Plan, slot: Optional[jax.Array] = None,
              num_slots: int = 1, interpret: bool = False,
              channel_major: bool = False) -> jax.Array:
    """``compute_histogram``'s result, ``[F, num_bins, C]`` float32
    (``[C, F, num_bins]``, as the kernel writes it, with ``channel_major``),
    for dense integer ``binned [N, F]`` and float32 ``vals [N, cv]``, by the
    tiles of :func:`tile_plan`.  ``interpret`` runs the kernel in Pallas's
    interpreter (the CPU's tests)."""
    p = plan
    n, f = binned.shape
    cv = vals.shape[1]
    k = num_slots if slot is not None else 1
    c = cv * k
    # rows on lanes; the pad rows are zero, so that whatever the last
    # block of the binned matrix holds past row n adds nothing
    pad = (-n) % p.rows
    vals_t = jnp.pad(vals.T, ((0, 0), (0, pad)))
    if slot is None:
        slot_t = jnp.zeros((1, n + pad), jnp.int32)
    else:
        slot_t = jnp.pad(slot.astype(jnp.int32), (0, pad),
                         constant_values=-1)[None, :]
    nf = -(-f // p.ft)
    width = p.fpart * p.bp
    with jax.named_scope("lgbtpu.hist.contract"):
        out = pl.pallas_call(
            functools.partial(_kernel, cv=cv, k=k, p=p),
            grid=(nf, p.parts, (n + pad) // p.rows),
            in_specs=[pl.BlockSpec((p.rows, p.ft), lambda i, q, r: (r, i)),
                      pl.BlockSpec((cv, p.rows), lambda i, q, r: (0, r)),
                      pl.BlockSpec((1, p.rows), lambda i, q, r: (0, r))],
            out_specs=pl.BlockSpec((p.cp, width),
                                   lambda i, q, r: (0, i * p.parts + q)),
            out_shape=jax.ShapeDtypeStruct((p.cp, nf * p.parts * width),
                                           jnp.float32),
            scratch_shapes=[pltpu.VMEM((3 * p.cp, width), jnp.float32),
                            pltpu.VMEM((p.parts * p.fpart, p.rows),
                                       jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=p.vmem),
            name="hist_vmem",
            interpret=interpret,
        )(binned, vals_t, slot_t)
    # a block's parts lie side by side, each fpart features wide: features
    # past the block's own ft (parts*fpart > ft) and past f are dropped
    out = out.reshape(p.cp, nf, p.parts * p.fpart, p.bp)[:c, :, :p.ft]
    out = out.reshape(c, nf * p.ft, p.bp)[:, :f, :num_bins]
    return out if channel_major else out.transpose(1, 2, 0)
