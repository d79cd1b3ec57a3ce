"""The algorithm's work, counted from shapes and from the grown trees.

A roofline has to read the same whatever implements the work, so nothing
here looks at the program (``obs/flops.py`` counts what the *implementation*
does: one-hot MACs, lane padding).  The model is the histogram algorithm of
the configuration's source: to grow one tree every row is scanned once for
the root's histogram and, at each split, the rows of the smaller child are
scanned again (the larger child's histogram is parent minus smaller).  A
scanned row reads its F bin codes and its gradient and hessian and adds
them into F histogram cells each; around the tree one pass over all rows
makes the gradients from the scores and one adds the leaf values back.

``least_seconds`` is the larger of operations over the chip's peak FLOP/s
and bytes over its peak HBM bytes/s; it says which of the two binds.
"""

GRAD_HESS_BYTES = 8          # one float32 gradient and one float32 hessian
SCORE_LABEL_BYTES = 8        # one float32 score and one float32 label
ADDS_PER_CELL = 2            # gradient and hessian; the count rides on them


def rows_scanned(n_rows: int, split_child_counts) -> int:
    """``N + sum over splits of the smaller child's row count``.

    ``split_child_counts`` is an iterable of ``(left_count, right_count)``,
    one pair per split of the tree."""
    return int(n_rows) + sum(min(int(a), int(b))
                             for a, b in split_child_counts)


def tree_work(n_rows: int, n_features: int, bin_bytes: int,
              split_child_counts) -> dict:
    """Operations and bytes of one boosting iteration that grows this tree."""
    scanned = rows_scanned(n_rows, split_child_counts)
    hist_bytes = scanned * (n_features * bin_bytes + GRAD_HESS_BYTES)
    hist_ops = scanned * n_features * ADDS_PER_CELL
    # gradient pass: read score and label, write gradient and hessian;
    # score update: read and write the score
    pass_bytes = n_rows * (SCORE_LABEL_BYTES + GRAD_HESS_BYTES) \
        + n_rows * 8
    pass_ops = n_rows * 8        # a sigmoid and two products, a few flops
    return {"rows_scanned": scanned,
            "ops": hist_ops + pass_ops,
            "bytes": hist_bytes + pass_bytes}


def hist_pass_work(n_rows: int, n_features: int, bin_bytes: int,
                   channels: int, padded_bins: int, slots: int) -> dict:
    """One full-N contraction: N x F bin codes and N x channels float32
    in (and N slot ids where rows are dealt to several slots), the
    histogram out; one add per cell and channel."""
    out_bytes = n_features * padded_bins * channels * slots * 4
    slot_bytes = n_rows * 4 if slots > 1 else 0
    return {"ops": n_rows * n_features * channels,
            "bytes": n_rows * n_features * bin_bytes
            + n_rows * channels * 4 + slot_bytes + out_bytes}


def least_seconds(work: dict, peak: dict) -> dict:
    """The least time the chip could take for ``work`` and what binds it."""
    t_ops = work["ops"] / peak["flops_per_s_bf16"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_ops, t_bytes),
            "bound": "compute" if t_ops > t_bytes else "memory"}


def split_child_counts(flat_tree) -> list:
    """``(left, right)`` row counts of every split of a dumped tree
    (``reference.FlatTree``): a child's count is its own ``internal_count``
    or its ``leaf_count``."""
    def count(ref: int) -> int:
        return int(flat_tree.leaf_count[~ref]) if ref < 0 \
            else int(flat_tree.internal_count[ref])
    return [(count(int(l)), count(int(r)))
            for l, r in zip(flat_tree.left, flat_tree.right)]
