"""The work-counting functions against counts made by hand."""

import numpy as np
import pytest

from bench_testlib import v5e_peak

from benchmarks import reference, work


def three_leaf_tree():
    """1000 rows: root splits 700 | 300, then the 700 split 450 | 250."""
    return {"num_leaves": 3, "shrinkage": 0.1, "tree_structure": {
        "split_index": 0, "split_feature": 2, "split_gain": 9.0,
        "threshold": 0.5, "decision_type": "<=", "internal_count": 1000,
        "default_left": True, "missing_type": "None",
        "left_child": {
            "split_index": 1, "split_feature": 0, "split_gain": 4.0,
            "threshold": -1.0, "decision_type": "<=", "internal_count": 700,
            "default_left": False, "missing_type": "NaN",
            "left_child": {"leaf_index": 0, "leaf_value": 0.1,
                           "leaf_count": 450},
            "right_child": {"leaf_index": 2, "leaf_value": -0.2,
                            "leaf_count": 250}},
        "right_child": {"leaf_index": 1, "leaf_value": 0.3,
                        "leaf_count": 300}}}


def test_rows_scanned_of_a_three_leaf_tree():
    flat = reference.flatten_tree(three_leaf_tree())
    pairs = work.split_child_counts(flat)
    assert sorted(pairs) == [(450, 250), (700, 300)]
    # the root's 1000, then the smaller child of each split: 300 and 250
    assert work.rows_scanned(1000, pairs) == 1000 + 300 + 250


def test_tree_work_by_hand():
    flat = reference.flatten_tree(three_leaf_tree())
    w = work.tree_work(1000, 28, 1, work.split_child_counts(flat))
    assert w["rows_scanned"] == 1550
    # a scanned row: 28 bin bytes, 8 bytes of gradient and hessian
    # around the tree: 16 bytes a row for the gradients, 8 for the scores
    assert w["bytes"] == 1550 * (28 + 8) + 1000 * 16 + 1000 * 8
    assert w["ops"] == 1550 * 28 * 2 + 1000 * 8


def test_hist_pass_work_by_hand():
    w = work.hist_pass_work(1000, 28, 1, 3, 64, 1)
    assert w["bytes"] == 1000 * 28 + 1000 * 12 + 28 * 64 * 3 * 4
    assert w["ops"] == 1000 * 28 * 3
    k = work.hist_pass_work(1000, 28, 1, 3, 64, 16)
    assert k["bytes"] == 1000 * 28 + 1000 * 12 + 1000 * 4 \
        + 28 * 64 * 3 * 16 * 4


@pytest.mark.parametrize("ops,nbytes,bound", [
    (197e12, 1.0, "compute"), (1.0, 819e9, "memory")])
def test_least_seconds_names_what_binds(ops, nbytes, bound):
    least = work.least_seconds({"ops": ops, "bytes": nbytes}, v5e_peak())
    assert least["bound"] == bound
    assert least["seconds"] == pytest.approx(1.0)


def test_flatten_and_route_the_three_leaf_tree():
    flat = reference.flatten_tree(three_leaf_tree())
    x = np.zeros((4, 3), np.float32)
    x[0] = (-2.0, 0, 0.0)      # left, left  -> leaf 0
    x[1] = (0.0, 0, 0.5)       # left (<=), right -> leaf 2
    x[2] = (0.0, 0, 0.6)       # right -> leaf 1
    x[3] = (-1.0, 0, -3.0)     # left, left (<=) -> leaf 0
    assert reference.route(flat, x).tolist() == [0, 2, 1, 0]


@pytest.mark.parametrize("chips", [1, 4])
def test_iteration_work_is_held_against_all_the_cells_chips(chips):
    """``train_iter_mfu`` on four chips: the same trees' least time is a
    quarter of one chip's, so the share of the four chips' peak reads a
    quarter of what one chip's would."""
    from benchmarks import run
    members = [{"n_rows": 1000,
                "model": {"tree_info": [three_leaf_tree()] * 2}}]
    one = run.iteration_work(members, 28, 1, v5e_peak(), 2.0)
    got = run.iteration_work(members, 28, 1, v5e_peak(), 2.0, chips)
    assert got["least_s"] == pytest.approx(one["least_s"] / chips)
    assert got["rows_scanned"] == [1550, 1550] and got["traced_s"] == 2.0
    w = work.tree_work(1000, 28, 1, [(700, 300), (450, 250)])
    assert one["least_s"] == pytest.approx(
        2 * w["bytes"] / v5e_peak()["hbm_bytes_per_s"])
