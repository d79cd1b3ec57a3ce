"""What decides ``correct``: sound runs pass, the lower-precision control
and each planted fault fail.  Every test drives ``run.run_cell`` past its look
for a chip, at a size the CPU holds, through the harness's own window,
comparison and limits; the faults break the timed path underneath it."""

import copy
import types

import numpy as np
import pytest

from bench_testlib import (CV_CELL, HIGGS_CELL, HIGGS_ON_CPU, HIGGS_SMALL,
                           ON_CPU, ROOT, SMALL, TRAIN_CELL,
                           root_with_train_cell, v5e_peak)

from benchmarks import reference, run


def drive(cell, seed, call=None, control=False, root=ROOT, sizes=SMALL,
          **params):
    import jax
    return run.run_cell(cell, seed, 0.0, False, control=control,
                        devices=jax.devices()[:1], peak=v5e_peak(),
                        sizes=sizes, extra_params=dict(ON_CPU, **params),
                        call=call, root=root)


def failed(result, root=ROOT):
    lim = run.load_cell(result["workload"], root)["limits"]
    return sorted(k for k, c in result["compared"].items()
                  if ("at_most" in lim[k] and c["value"] > c["limit"])
                  or ("at_least" in lim[k] and c["value"] < c["limit"]))


def small_data(seed, cell=CV_CELL, sizes=SMALL, root=ROOT):
    x, y, _, _ = run.make_data(run.load_cell(cell, root), seed, sizes, False)
    return x, y


def check_sound_and_control(r, root=ROOT):
    assert r["correct"] and not failed(r, root), r["compared"]
    assert r["compared"]["count_gap"]["value"] == 0
    assert "split_shortfall" in r["compared"]
    assert set(r["metrics"]) == {"train_iter_s", "setup_s"}
    assert list(r)[-1] == "compared"
    # the control: the reference's own arithmetic with every gradient and
    # hessian rounded to bfloat16, on the same trees, judged by the same
    # limits in the program's place, comes out not correct
    low = r["readings"]["control"]
    assert low["correct"] is False
    assert low["compared"]["leaf_gap_median"]["value"] \
        > 3 * low["compared"]["leaf_gap_median"]["limit"]
    assert low["compared"]["leaf_gap_median"]["value"] \
        > 30 * r["compared"]["leaf_gap_median"]["value"]
    # what a search that took the second-best feature would read
    assert r["readings"]["faults"]["second_best_feature"][
        "split_shortfall"] > r["compared"]["split_shortfall"]["limit"]


@pytest.mark.parametrize("cell,sizes,seed,params", [
    (CV_CELL, SMALL, 11, {}), (CV_CELL, SMALL, 2 ** 31 + 12, {}),
    (HIGGS_CELL, HIGGS_SMALL, 2 ** 31 + 14, HIGGS_ON_CPU)])
def test_sound_cv_run_is_correct_and_the_control_is_not(cell, sizes, seed,
                                                        params):
    """Both shapes: 40 of Epsilon's columns at 63 bins, and HIGGS's own 28
    at 255 bins with its three-valued columns."""
    r = drive(cell, seed, control=True, sizes=sizes, **params)
    check_sound_and_control(r)
    assert r["attempted"] == 2 * 5          # one job: two rounds, five folds
    config = run.load_cell(cell)["config"]
    assert config["params"]["max_bin"] == {CV_CELL: 63, HIGGS_CELL: 255}[cell]


@pytest.mark.parametrize("leaves", [31, 255])
def test_a_cell_on_the_train_mix_is_added_by_data_alone(tmp_path, leaves):
    """The mix that no cell uses yet, both growers: strict below 64 leaves,
    batched above."""
    root = root_with_train_cell(str(tmp_path))
    r = drive(TRAIN_CELL, 13 + leaves, control=True, root=root,
              num_leaves=leaves)
    check_sound_and_control(r, root)
    assert r["attempted"] == 2


class Doctored:
    """A booster whose dumped model was altered where it is produced."""

    def __init__(self, bst, alter):
        self._bst, self._alter = bst, alter

    def num_trees(self):
        return self._bst.num_trees()

    def dump_model(self):
        model = copy.deepcopy(self._bst.dump_model())
        self._alter(model)
        return model


def leaves_of(node):
    if "leaf_index" in node:
        yield node
    else:
        yield from leaves_of(node["left_child"])
        yield from leaves_of(node["right_child"])


def doctored_cv(lgb, alter_of_fold):
    """``lgb.cv`` whose boosters dump altered models: ``alter_of_fold(k,
    fold's train rows)`` gives fold ``k``'s alteration or None."""
    def cv(params, ds, num_boost_round, folds, **kw):
        out = lgb.cv(params, ds, num_boost_round=num_boost_round,
                     folds=folds, **kw)
        boosters = [Doctored(b, alter_of_fold(k, folds[k][0]))
                    if alter_of_fold(k, folds[k][0]) else b
                    for k, b in enumerate(out["cvbooster"].boosters)]
        return dict(out, cvbooster=types.SimpleNamespace(boosters=boosters))
    return cv


def state_unchanged(lgb, seed, cell, sizes, root=ROOT):
    """Every step returns its scores unchanged: each tree is grown from
    the first tree's gradients again."""
    _, y = small_data(seed, cell, sizes, root)

    def alter_of_fold(k, rows):
        s0 = reference.init_score(y[rows].astype(np.float64))

        def alter(model):
            again = copy.deepcopy(model["tree_info"][0])
            for leaf in leaves_of(again["tree_structure"]):
                leaf["leaf_value"] -= s0
            model["tree_info"][1:] = [copy.deepcopy(again) for _ in
                                      model["tree_info"][1:]]
        return alter
    return doctored_cv(lgb, alter_of_fold)


def half_batch(lgb, seed, cell, sizes, root=ROOT):
    """Half of every fold's rows left out; means are taken over the rest."""
    def cv(params, ds, num_boost_round, folds, **kw):
        halved = [(tr[:len(tr) // 2], te) for tr, te in folds]
        return lgb.cv(params, ds, num_boost_round=num_boost_round,
                      folds=halved, **kw)
    return cv


def answer_altered(lgb, seed, cell, sizes, root=ROOT):
    """One leaf of one fold's second tree says the opposite."""
    def alter(model):
        worst = max(leaves_of(model["tree_info"][1]["tree_structure"]),
                    key=lambda leaf: abs(leaf["leaf_value"]))
        worst["leaf_value"] = -worst["leaf_value"]
    return doctored_cv(lgb, lambda k, rows: alter if k == 3 else None)


def metric_altered(lgb, seed, cell, sizes, root=ROOT, off=1e-3):
    """The held-out layer: a mean AUC reported a thousandth off what the
    folds' trees give."""
    def cv(params, ds, num_boost_round, folds, **kw):
        out = lgb.cv(params, ds, num_boost_round=num_boost_round,
                     folds=folds, **kw)
        out["valid auc-mean"] = list(out["valid auc-mean"])
        out["valid auc-mean"][-1] += off
        return out
    return cv


def best_feature_overlooked(lgb, seed, cell, sizes, root=ROOT):
    """The split search never sees the feature that separates best: the
    program trains on a matrix whose strongest column is noise, the
    reference searches the true one."""
    x, y = small_data(seed, cell, sizes, root)
    p = float(y.mean())
    # under the cell's own floor: without one, under a rare label, the
    # "strongest" column is whichever has a failed part at its far end
    floor = run.load_cell(cell, root)["config"]["params"][
        "min_sum_hessian_in_leaf"]
    strongest = int(np.argmax(reference.best_exact_gains(
        x, np.arange(len(x)), p - y.astype(np.float64),
        np.full(len(x), p * (1 - p)), np.arange(x.shape[1]),
        min_hess=floor, min_data=1)))

    def cv(params, ds, num_boost_round, folds, **kw):
        blind = x.copy()
        blind[:, strongest] = np.random.default_rng(seed).standard_normal(
            len(x), dtype=np.float32)
        return lgb.cv(params, lgb.Dataset(blind, label=y, params=params),
                      num_boost_round=num_boost_round, folds=folds, **kw)
    return cv


@pytest.mark.parametrize("cell,sizes,params", [
    (CV_CELL, SMALL, {}), (HIGGS_CELL, HIGGS_SMALL, HIGGS_ON_CPU)],
    ids=["epsilon", "higgs"])
@pytest.mark.parametrize("fault,catches", [
    (state_unchanged, "leaf_gap_median"),
    (half_batch, "count_gap"),
    (answer_altered, "leaf_gap_max"),
    (metric_altered, "auc_gap"),
    (best_feature_overlooked, "split_shortfall")],
    ids=lambda p: getattr(p, "__name__", p))
def test_planted_fault_is_not_correct(fault, catches, cell, sizes, params):
    """Each fault a cell can have, at both shapes, under the cell's own
    limits."""
    import lightgbm_tpu as lgb
    seed = 21
    r = drive(cell, seed, call=fault(lgb, seed, cell, sizes), sizes=sizes,
              **params)
    assert not r["correct"]
    assert catches in failed(r), r["compared"]


def test_a_table_without_holes_reads_neither_fault_of_the_directions():
    """Neither fault is read where no node routes a missing value: the
    trees say so, no key of a cell or a configuration."""
    r = drive(CV_CELL, 11, control=True)
    assert set(r["readings"]["faults"]) == {
        "state_unchanged", "answer_altered", "half_batch",
        "second_best_feature"}


def test_altered_metric_value_on_the_train_mix_is_not_correct(tmp_path):
    """The held-out layer of ``lgb.train``: an AUC that the program reports
    a thousandth off what its trees give."""
    import lightgbm_tpu as lgb

    # the harness reads the curve from the dict it handed to
    # record_evaluation, so the fault wraps that callback
    real_record = lgb.record_evaluation

    def record(evals):
        cb = real_record(evals)

        def off(env):
            cb(env)
            evals["valid_0"]["auc"][-1] += 1e-3
        for attr in ("order", "before_iteration", "_replayable"):
            if hasattr(cb, attr):
                setattr(off, attr, getattr(cb, attr))
        return off
    root = root_with_train_cell(str(tmp_path))
    lgb.record_evaluation = record
    try:
        r = drive(TRAIN_CELL, 22, root=root)
    finally:
        lgb.record_evaluation = real_record
    assert not r["correct"] and failed(r, root) == ["auc_gap"], r["compared"]


def test_round_bfloat16_keeps_eight_bits():
    a = np.array([1.0 + 2.0 ** -12, 1.0 + 2.0 ** -7, -3.14159], np.float32)
    got = reference.round_bfloat16(a)
    assert got[0] == 1.0 and got[1] == 1.0 + 2.0 ** -7
    assert abs(got[2] + 3.14159) < 2.0 ** -7


def three_node_tree(default_left, missing_type):
    """Node 0 splits column 2 at 0.5 and its left child, node 1, column 0
    at -0.25; leaves 0, 1 (under node 1) and 2."""
    return reference.FlatTree(
        split_feature=np.array([2, 0]), threshold=np.array([0.5, -0.25]),
        left=np.array([1, ~0]), right=np.array([~2, ~1]),
        split_gain=np.ones(2), internal_count=np.array([500, 0]),
        leaf_value=np.zeros(3), leaf_count=np.zeros(3, np.int64),
        shrinkage=1.0, default_left=np.array(default_left),
        missing_type=np.array(missing_type))


def test_route_on_rows_is_route_on_their_copy():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((500, 6)).astype(np.float32)
    x[rng.random((500, 6)) < 0.3] = np.nan
    tree = three_node_tree([True, False], ["NaN", "NaN"])
    rows = rng.permutation(500)[:137]
    assert (reference.route(tree, x, rows)
            == reference.route(tree, x[rows])).all()
    assert reference.node_depths(tree).tolist() == [0, 1]


@pytest.mark.parametrize("default_left,missing_type,leaves", [
    # column 2 missing: left at node 0; then column 0 missing: right
    ([True, False], ["NaN", "NaN"], {"both": 1, "col2": 0, "col0": 1}),
    ([False, True], ["NaN", "NaN"], {"both": 2, "col2": 2, "col0": 0}),
    # a node of type None takes a NaN as 0.0: 0.0 <= 0.5 is left at node
    # 0 whatever its default_left says, 0.0 <= -0.25 is right at node 1
    ([False, True], ["None", "None"], {"both": 1, "col2": 0, "col0": 1}),
    ([False, False], ["None", "NaN"], {"both": 1, "col2": 0, "col0": 1})],
    ids=["left-right", "right-left", "none-none", "none-nan"])
def test_route_sends_a_missing_value_where_the_node_says(
        default_left, missing_type, leaves):
    """Upstream's ``NumericalDecision`` on three rows: column 2 and column
    0 missing; column 2 alone (column 0 is -1.0: left at node 1); column 0
    alone (column 2 is 0.0: left at node 0)."""
    nan = np.float32(np.nan)
    x = np.zeros((3, 6), np.float32)
    x[0, [0, 2]] = nan
    x[1, 2], x[1, 0] = nan, -1.0
    x[2, 0] = nan
    got = reference.route(three_node_tree(default_left, missing_type), x)
    assert got.tolist() == [leaves["both"], leaves["col2"], leaves["col0"]]


@pytest.mark.parametrize("default_left", [
    [False, False], [True, True], [True, False]], ids=str)
def test_route_on_a_table_without_holes_is_the_plain_walk(default_left):
    """Leaf for leaf what ``value <= threshold`` alone gives, whatever the
    nodes' directions and types say."""
    rng = np.random.default_rng(4)
    x = rng.standard_normal((700, 6)).astype(np.float32)
    x[:50, 2], x[50:90, 0] = 0.5, -0.25        # values on the thresholds
    want = np.where(x[:, 2] <= 0.5, np.where(x[:, 0] <= -0.25, 0, 1), 2)
    assert {0, 1, 2} == set(want[:90])
    for missing_type in (["NaN", "NaN"], ["None", "NaN"]):
        tree = three_node_tree(default_left, missing_type)
        assert (reference.route(tree, x) == want).all()


def dumped(missing_type):
    leaf = {"leaf_value": 0.5, "leaf_count": 3}
    return {"num_leaves": 2, "shrinkage": 0.1, "tree_structure": {
        "split_index": 0, "split_feature": 4, "split_gain": 2.0,
        "threshold": 1.5, "decision_type": "<=", "default_left": True,
        "missing_type": missing_type, "internal_count": 6,
        "left_child": dict(leaf, leaf_index=0),
        "right_child": dict(leaf, leaf_index=1)}}


@pytest.mark.parametrize("missing_type", ["NaN", "None"])
def test_flatten_tree_reads_each_nodes_direction_and_type(missing_type):
    tree = reference.flatten_tree(dumped(missing_type))
    assert tree.default_left.tolist() == [True]
    assert tree.missing_type.tolist() == [missing_type]
    assert (tree.split_feature[0], tree.threshold[0]) == (4, 1.5)


def test_flatten_tree_refuses_zero_as_missing():
    """One semantics a PR: ``zero_as_missing`` is not followed."""
    with pytest.raises(ValueError, match="not Zero"):
        reference.flatten_tree(dumped("Zero"))


@pytest.mark.parametrize("missing", [0, 37, 120])
@pytest.mark.parametrize("min_hess,min_data", [(0.0, 1), (5.0, 1), (0.0, 40)])
def test_best_exact_gains_against_every_threshold_tried(min_hess, min_data,
                                                        missing):
    """A loop over every threshold and, where the node has missing rows,
    over both sides for them (with them right, the last real value is a
    threshold too: all real rows against the missing ones); without any, a
    loop over ``value <= t`` alone."""
    rng = np.random.default_rng(5)
    n = 200
    x = np.round(rng.standard_normal((n, 3)), 1).astype(np.float32)  # ties
    # both signs of NaN: the one with its sign bit set sorts before -inf
    x[rng.permutation(n)[:missing], 0] = np.nan
    x[rng.permutation(n)[:missing], 2] = -np.float32(np.nan)
    g = rng.standard_normal(n)
    h = rng.uniform(0.05, 0.25, n)
    rows = rng.permutation(n)[:150]
    kw = dict(min_hess=min_hess, min_data=min_data)
    got = reference.best_exact_gains(x, rows, g[rows], h[rows], [0, 2], **kw)
    placed = reference.exact_gains(x, rows, g[rows], h[rows], [0, 2], **kw)
    assert (got == placed.max(axis=1)).all()
    for f, gain, (right, left) in zip([0, 2], got, placed):
        v, gg, hh = x[rows, f], g[rows], h[rows]
        nan = np.isnan(v)
        real = np.unique(v[~nan])
        best = {"right": -np.inf, "left": -np.inf}
        for side in (("right", "left") if nan.any() else ("right",)):
            cuts = real if side == "right" and nan.any() else real[:-1]
            for t in cuts:
                goes_left = (v <= t) | (nan & (side == "left"))
                if min(hh[goes_left].sum(), hh[~goes_left].sum()) \
                        < min_hess or min(goes_left.sum(),
                                          (~goes_left).sum()) < min_data:
                    continue
                best[side] = max(
                    best[side],
                    gg[goes_left].sum() ** 2 / hh[goes_left].sum()
                    + gg[~goes_left].sum() ** 2 / hh[~goes_left].sum()
                    - gg.sum() ** 2 / hh.sum())
        assert right == pytest.approx(best["right"], rel=1e-9)
        assert left == pytest.approx(best["left"], rel=1e-9)
        assert gain == max(best.values()) or gain == pytest.approx(
            max(best.values()), rel=1e-9)
        if not missing:
            assert left == -np.inf


@pytest.mark.parametrize("cell,scan_share,steps", [
    (CV_CELL, 0.0, None), (TRAIN_CELL, 100.0, 30.0)])
def test_traced_run_reports_what_its_readers_find(tmp_path, cell, scan_share,
                                                  steps):
    """Off the chip a trace holds no device plane: the counters' metrics
    are reported and those of the trace are left out, never 0.  The scan
    share is not capped: 100 on the super-epoch, 0 under ``lgb.cv``."""
    import jax
    root = root_with_train_cell(str(tmp_path))
    r = run.run_cell(cell, 31, 0.0, True, devices=jax.devices()[:1],
                     peak=v5e_peak(), sizes=SMALL, call=None, root=root,
                     extra_params=dict(ON_CPU, num_leaves=31))
    assert r["correct"], r["compared"]
    got = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(got) == {"grower_steps_per_tree", "scan_iters_share", "bin_s",
                        "compile_s"}
    assert got["scan_iters_share"] == scan_share
    assert steps is None or got["grower_steps_per_tree"] == steps
    assert "busy_s" not in r["device"] and "breakdown" not in r


@pytest.mark.parametrize("cell", [CV_CELL, TRAIN_CELL],
                         ids=["not-listed", "listed"])
def test_a_metric_that_lists_its_cells_is_reported_in_those_alone(tmp_path,
                                                                  cell):
    """``scan_iters_share`` given ``workloads`` of the train mix's cell
    alone, on a copied root: reported there, absent under ``lgb.cv``.
    (``compile_s`` is let be: it reads nothing where the test above has
    compiled the cell's programs in this process already.)"""
    import json
    import os
    import jax
    root = root_with_train_cell(str(tmp_path))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    next(e for e in m["per_layer"]
         if e["name"] == "scan_iters_share")["workloads"] = [TRAIN_CELL]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(m, fh)
    r = run.run_cell(cell, 31, 0.0, True, devices=jax.devices()[:1],
                     peak=v5e_peak(), sizes=SMALL, call=None, root=root,
                     extra_params=dict(ON_CPU, num_leaves=31))
    assert r["correct"], r["compared"]
    reported = {"grower_steps_per_tree", "bin_s"} | (
        {"scan_iters_share"} if cell == TRAIN_CELL else set())
    assert set(r["metrics"]) - {"compile_s"} == reported
