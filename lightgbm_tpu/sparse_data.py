"""Sparse binned storage: the TPU-native analog of the reference's
delta-encoded sparse bins (sparse_bin.hpp:73 SparseBin, row-wise
multi_val_sparse_bin.hpp MultiValSparseBin).

The dense binned matrix is ``[N, G]`` bytes of HBM; for wide-sparse data
(Allstate-class: 13.2M x 4228 dummy-encoded columns, docs/Experiments.rst:32)
that is 51.9 GB — infeasible on a 16 GB chip unless EFB compresses it.  The
reference's answer is per-feature delta-encoded (row, bin) streams; the
TPU-native answer here is a **padded k-hot row layout**:

    flat[n, k] = f * stride + b        for the k-th stored entry of row n
    flat[n, k] = -1                    padding

where an entry is stored only when its bin differs from the feature's
*default bin* (the bin that the absent value 0.0 maps to — the reference's
most_freq_bin discipline, bin.h).  K = max stored entries per row, so the
array is ``[N, K] int32``: static shapes for XLA, rows shard over a mesh
axis exactly like the dense matrix, and memory is ``4K`` bytes/row instead
of ``G`` — for Allstate-shaped data K ~= the number of original categorical
columns (~35), i.e. ~1.9 GB.

Histogram construction cannot ride the one-hot MXU contraction (its FLOP
cost is slot-count x output-size, independent of sparsity), so the sparse
path uses the formulation whose work IS O(nnz): a per-row-block
``segment_sum`` scatter-add keyed by ``flat`` (+ a slot offset for the
split_batch multi-histogram), followed by the reference's FixHistogram
subtraction (dataset.cpp:1292) to reconstruct the default bin from the
leaf totals.  Column access (row partitioning, traversal) is a K-wide
vectorized compare — O(N*K) VPU work, no gather.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


# row-block size for the segment_sum scan is chosen so a block carries
# ~ENTRY_BLOCK entries; bounds the [R*K, C] gathered-values buffer
ENTRY_BLOCK = 512 * 1024


@jax.tree_util.register_pytree_node_class
class SparseBinned:
    """Device-side padded k-hot binned matrix (pytree: jit-traceable).

    flat:        [N, K] int32, ``f * stride + b`` or -1 padding
    default_bin: [F] int32 — bin of the absent value, per used feature
    stride:      static bin-axis stride (>= every feature's num_bin)
    num_features: static F
    """

    def __init__(self, flat, default_bin, stride: int, num_features: int):
        self.flat = flat
        self.default_bin = default_bin
        self.stride = int(stride)
        self.num_features = int(num_features)

    @property
    def shape(self):
        """(N, F) — matches the dense binned matrix's shape contract."""
        return (self.flat.shape[0], self.num_features)

    @property
    def k(self) -> int:
        return self.flat.shape[1]

    def tree_flatten(self):
        return (self.flat, self.default_bin), (self.stride, self.num_features)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0], aux[1])

    def take_rows(self, idx) -> "SparseBinned":
        """Row gather (the child-histogram tier path's axis-0 take)."""
        return SparseBinned(jnp.take(self.flat, idx, axis=0),
                            self.default_bin, self.stride, self.num_features)


def column(sp: SparseBinned, feat) -> jax.Array:
    """Bin of feature ``feat`` (traced scalar) for every row — the sparse
    analog of ``jnp.take(binned, feat, axis=1)``."""
    lo = feat.astype(jnp.int32) * sp.stride if hasattr(feat, "astype") \
        else jnp.int32(feat) * sp.stride
    m = (sp.flat >= lo) & (sp.flat < lo + sp.stride)
    binv = jnp.sum(jnp.where(m, sp.flat - lo, 0), axis=1)
    return jnp.where(m.any(axis=1), binv, sp.default_bin[feat]) \
        .astype(jnp.int32)


def column_per_row(sp: SparseBinned, feat_r) -> jax.Array:
    """Per-row feature lookup: row n reads feature ``feat_r[n]`` — the
    sparse analog of ``take_along_axis(binned, feat_r[:, None], 1)``
    (batched-grower partitioning, tree traversal)."""
    lo = feat_r.astype(jnp.int32)[:, None] * sp.stride
    m = (sp.flat >= lo) & (sp.flat < lo + sp.stride)
    binv = jnp.sum(jnp.where(m, sp.flat - lo, 0), axis=1)
    return jnp.where(m.any(axis=1), binv, sp.default_bin[feat_r]) \
        .astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_bins", "num_slots"))
def histogram(sp: SparseBinned, vals: jax.Array, *, num_bins: int,
              slot: Optional[jax.Array] = None,
              num_slots: int = 1) -> jax.Array:
    """hist[f, b, c] over the sparse layout — same output contract as
    ops/histogram.compute_histogram: [F, num_bins, C] with C = cv*num_slots
    and channel index ``c * num_slots + s``.

    O(nnz) work: stored entries scatter-add per row-block; the default bin
    gets ``leaf_total - stored_mass`` per feature afterwards (FixHistogram,
    dataset.cpp:1292), which assigns every absent row in one subtraction.
    """
    n, k = sp.flat.shape
    f = sp.num_features
    cv = vals.shape[1]
    s = num_slots if slot is not None else 1
    nseg = s * f * sp.stride

    block_rows = max(8, min(n, ENTRY_BLOCK // max(k, 1)) // 8 * 8)
    pad = (-n) % block_rows
    flat_p, vals_p, slot_p = sp.flat, vals, slot
    if pad:
        flat_p = jnp.pad(flat_p, ((0, pad), (0, 0)), constant_values=-1)
        vals_p = jnp.pad(vals_p, ((0, pad), (0, 0)))
        if slot is not None:
            slot_p = jnp.pad(slot_p, (0, pad), constant_values=-1)
    nblocks = (n + pad) // block_rows

    xs = (flat_p.reshape(nblocks, block_rows, k),
          vals_p.reshape(nblocks, block_rows, cv))
    if slot is not None:
        xs = xs + (slot_p.reshape(nblocks, block_rows),)

    def body(acc, chunk):
        fl, vb = chunk[0], chunk[1]
        sid = fl.astype(jnp.int32)                       # [R, K]
        ok = sid >= 0
        if slot is not None:
            sb = chunk[2].astype(jnp.int32)              # [R]
            ok = ok & (sb >= 0)[:, None]
            sid = sid + jnp.maximum(sb, 0)[:, None] * (f * sp.stride)
        # invalid entries land in the overflow segment nseg (dropped)
        sid = jnp.where(ok, sid, nseg).reshape(-1)
        data = jnp.broadcast_to(vb[:, None, :], (block_rows, k, cv)) \
            .reshape(-1, cv)
        return acc + jax.ops.segment_sum(data, sid, num_segments=nseg + 1), \
            None

    acc0 = jnp.zeros((nseg + 1, cv), jnp.float32)
    acc, _ = lax.scan(body, acc0, xs)
    # [S, F, stride, cv] -> [F, stride, cv, S] -> [F, stride, cv*S]
    hist = acc[:nseg].reshape(s, f, sp.stride, cv).transpose(1, 2, 3, 0) \
        .reshape(f, sp.stride, cv * s)

    # FixHistogram: absent mass = per-slot totals - stored mass, added at
    # each feature's default bin.  Totals via an MXU contraction (onehot
    # fused into the dot) when slotted, a plain sum otherwise.
    if slot is not None:
        oh = (slot[:, None] == jnp.arange(num_slots, dtype=jnp.int32)) \
            .astype(jnp.float32)
        # HIGHEST: at default precision a TPU rounds the f32 accumulands
        # to bf16 before the sum (see ops/histogram.py)
        tot = lax.dot_general(vals, oh, (((0,), (0,)), ((), ())),
                              precision=lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)  # [cv, S]
        tot = tot.reshape(cv * s)
    else:
        tot = vals.sum(axis=0)
    absent = tot[None, :] - hist.sum(axis=1)             # [F, cv*S]
    hist = hist.at[jnp.arange(f), sp.default_bin].add(absent)
    return hist[:, :num_bins, :]


@functools.partial(jax.jit, static_argnames=("steps",))
def traverse_tree_sparse(sp: SparseBinned, split_feature, threshold_bin,
                         default_left, left_child, right_child, na_bin,
                         is_cat_node, cat_rank, *, steps: int):
    """Leaf index per row over the sparse layout — predict_device
    traverse_tree_binned with the gather replaced by column_per_row."""
    n = sp.flat.shape[0]
    node = jnp.zeros(n, jnp.int32)

    def body(_, node):
        internal = node >= 0
        nid = jnp.maximum(node, 0)
        fcol = split_feature[nid]
        v = column_per_row(sp, fcol)
        nb = na_bin[fcol]
        is_na = (nb >= 0) & (v == nb) & (~is_cat_node[nid])
        rank = cat_rank[nid, v]
        go_left = jnp.where(is_na, default_left[nid],
                            rank <= threshold_bin[nid])
        nxt = jnp.where(go_left, left_child[nid], right_child[nid])
        return jnp.where(internal, nxt, node)

    node = lax.fori_loop(0, steps, body, node)
    return (~node).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("steps",))
def add_tree_score_sparse(score, sp: SparseBinned, split_feature,
                          threshold_bin, default_left, left_child,
                          right_child, na_bin, is_cat_node, cat_rank,
                          leaf_value, weight, *, steps: int):
    """score += weight * tree(sparse rows)."""
    leaf = traverse_tree_sparse(sp, split_feature, threshold_bin,
                                default_left, left_child, right_child,
                                na_bin, is_cat_node, cat_rank, steps=steps)
    return score + weight * jnp.take(leaf_value, leaf)


# ----------------------------------------------------------------------
# host-side construction
# ----------------------------------------------------------------------

class SparseBinnedHost:
    """Construction product kept on the Dataset (numpy; device copies are
    made by the model)."""

    def __init__(self, flat: np.ndarray, default_bin: np.ndarray,
                 stride: int, num_features: int):
        self.flat = flat                    # [N, K] int32
        self.default_bin = default_bin      # [F] int32
        self.stride = int(stride)
        self.num_features = int(num_features)

    @property
    def k(self) -> int:
        return self.flat.shape[1]

    def nbytes(self) -> int:
        return self.flat.nbytes

    def to_device(self) -> SparseBinned:
        return SparseBinned(jnp.asarray(self.flat),
                            jnp.asarray(self.default_bin),
                            self.stride, self.num_features)

    def subset_rows(self, idx: np.ndarray) -> "SparseBinnedHost":
        return SparseBinnedHost(self.flat[idx], self.default_bin,
                                self.stride, self.num_features)

    def densify(self) -> np.ndarray:
        """[N, F] dense bins — for paths that need the flat layout
        (add_features_from, partitioned learner).  O(N*F) memory: callers
        guard on size."""
        n, _ = self.flat.shape
        dtype = np.uint8 if self.stride <= 256 else np.uint16
        out = np.broadcast_to(self.default_bin.astype(dtype),
                              (n, self.num_features)).copy()
        rows, ks = np.nonzero(self.flat >= 0)
        fl = self.flat[rows, ks]
        out[rows, fl // self.stride] = (fl % self.stride).astype(dtype)
        return out


def collect_entries_csc(csc, mappers, used_features, stride: int):
    """collect_entries straight off a scipy CSC layout — O(nnz_col) per
    column, no N-length dense intermediate (the LGBM_DatasetCreateFromCSC
    discipline, c_api.h:281)."""
    rows_l, flat_l = [], []
    default_bin = np.zeros(len(used_features), np.int32)
    for j, f in enumerate(used_features):
        m = mappers[f]
        db = int(m.value_to_bin(np.zeros(1))[0])
        default_bin[j] = db
        lo, hi = csc.indptr[f], csc.indptr[f + 1]
        idx, dat = csc.indices[lo:hi], np.asarray(csc.data[lo:hi],
                                                  np.float64)
        b = m.value_to_bin(dat).astype(np.int32)
        keep = np.nonzero(b != db)[0]
        if len(keep):
            rows_l.append(idx[keep].astype(np.int64))
            flat_l.append(j * stride + b[keep])
    if rows_l:
        rows = np.concatenate(rows_l)
        flat = np.concatenate(flat_l)
    else:
        rows = np.zeros(0, np.int64)
        flat = np.zeros(0, np.int32)
    return rows, flat, default_bin


def build_khot(rows: np.ndarray, flat: np.ndarray, default_bin: np.ndarray,
               num_data: int, stride: int, num_features: int,
               counts: Optional[np.ndarray] = None) -> SparseBinnedHost:
    """Assemble the padded [N, K] layout from entry streams.  ``counts``
    (per-row entry counts) may be passed by a caller that already
    bincounted the stream for the layout decision."""
    if counts is None:
        counts = np.bincount(rows, minlength=num_data) if len(rows) \
            else np.zeros(num_data, np.int64)
    k = int(max(counts.max() if num_data else 0, 1))
    out = np.full((num_data, k), -1, np.int32)
    if len(rows):
        order = np.argsort(rows, kind="stable")
        r_s, f_s = rows[order], flat[order]
        offs = np.zeros(num_data + 1, np.int64)
        np.cumsum(counts, out=offs[1:])
        pos = np.arange(len(r_s)) - offs[r_s]
        out[r_s, pos] = f_s
    return SparseBinnedHost(out, default_bin, stride, num_features)
