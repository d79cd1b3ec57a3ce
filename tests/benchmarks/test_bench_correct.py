"""What decides ``correct``: sound runs pass, the lower-precision control
and each planted fault fail.  Every test drives ``run.run_cell`` past its look
for a chip, at a size the CPU holds, through the harness's own window,
comparison and limits; the faults break the timed path underneath it."""

import copy
import types

import numpy as np
import pytest

from bench_testlib import (CV_CELL, ON_CPU, ROOT, SMALL, TRAIN_CELL,
                           root_with_train_cell, v5e_peak)

from benchmarks import reference, run


def drive(cell, seed, call=None, control=False, root=ROOT, **params):
    import jax
    return run.run_cell(cell, seed, 0.0, False, control=control,
                        devices=jax.devices()[:1], peak=v5e_peak(),
                        sizes=SMALL, extra_params=dict(ON_CPU, **params),
                        call=call, root=root)


def failed(result, root=ROOT):
    lim = run.load_cell(result["workload"], root)["limits"]
    return sorted(k for k, c in result["compared"].items()
                  if ("at_most" in lim[k] and c["value"] > c["limit"])
                  or ("at_least" in lim[k] and c["value"] < c["limit"]))


def small_data(seed):
    cell = run.load_cell(CV_CELL)
    x, y, _, _ = run.make_data(cell, seed, SMALL, False)
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


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 12])
def test_sound_cv_run_is_correct_and_the_control_is_not(seed):
    r = drive(CV_CELL, seed, control=True)
    check_sound_and_control(r)
    assert r["attempted"] == 2 * 5          # one job: two rounds, five folds


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


def state_unchanged(lgb, seed):
    """Every step returns its scores unchanged: each tree is grown from
    the first tree's gradients again."""
    _, y = small_data(seed)

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


def half_batch(lgb, seed):
    """Half of every fold's rows left out; means are taken over the rest."""
    def cv(params, ds, num_boost_round, folds, **kw):
        halved = [(tr[:len(tr) // 2], te) for tr, te in folds]
        return lgb.cv(params, ds, num_boost_round=num_boost_round,
                      folds=halved, **kw)
    return cv


def answer_altered(lgb, seed):
    """One leaf of one fold's second tree says the opposite."""
    def alter(model):
        worst = max(leaves_of(model["tree_info"][1]["tree_structure"]),
                    key=lambda leaf: abs(leaf["leaf_value"]))
        worst["leaf_value"] = -worst["leaf_value"]
    return doctored_cv(lgb, lambda k, rows: alter if k == 3 else None)


def metric_altered(lgb, seed):
    """The held-out layer: a mean AUC reported a thousandth off what the
    folds' trees give."""
    def cv(params, ds, num_boost_round, folds, **kw):
        out = lgb.cv(params, ds, num_boost_round=num_boost_round,
                     folds=folds, **kw)
        out["valid auc-mean"] = list(out["valid auc-mean"])
        out["valid auc-mean"][-1] += 1e-3
        return out
    return cv


def best_feature_overlooked(lgb, seed):
    """The split search never sees the feature that separates best: the
    program trains on a matrix whose strongest column is noise, the
    reference searches the true one."""
    x, y = small_data(seed)
    strongest = int(np.argmax(np.abs((x[:, :16] * (y[:, None] - 0.5))
                                     .mean(0))))

    def cv(params, ds, num_boost_round, folds, **kw):
        blind = x.copy()
        blind[:, strongest] = np.random.default_rng(seed).standard_normal(
            len(x), dtype=np.float32)
        return lgb.cv(params, lgb.Dataset(blind, label=y, params=params),
                      num_boost_round=num_boost_round, folds=folds, **kw)
    return cv


@pytest.mark.parametrize("fault,catches", [
    (state_unchanged, "leaf_gap_median"),
    (half_batch, "count_gap"),
    (answer_altered, "leaf_gap_max"),
    (metric_altered, "auc_gap"),
    (best_feature_overlooked, "split_shortfall")],
    ids=lambda p: getattr(p, "__name__", p))
def test_planted_fault_is_not_correct(fault, catches):
    import lightgbm_tpu as lgb
    seed = 21
    r = drive(CV_CELL, seed, call=fault(lgb, seed))
    assert not r["correct"]
    assert catches in failed(r), r["compared"]


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


def test_route_on_rows_is_route_on_their_copy():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((500, 6)).astype(np.float32)
    tree = reference.FlatTree(
        split_feature=np.array([2, 0]), threshold=np.array([0.1, -0.3]),
        left=np.array([1, ~0]), right=np.array([~1, ~2]),
        split_gain=np.ones(2), internal_count=np.array([500, 0]),
        leaf_value=np.zeros(3), leaf_count=np.zeros(3, np.int64),
        shrinkage=1.0)
    rows = rng.permutation(500)[:137]
    assert (reference.route(tree, x, rows)
            == reference.route(tree, x[rows])).all()
    assert reference.node_depths(tree).tolist() == [0, 1]


@pytest.mark.parametrize("min_hess,min_data", [(0.0, 1), (5.0, 1), (0.0, 40)])
def test_best_exact_gains_against_every_threshold_tried(min_hess, min_data):
    rng = np.random.default_rng(5)
    n = 200
    x = np.round(rng.standard_normal((n, 3)), 1).astype(np.float32)  # ties
    g = rng.standard_normal(n)
    h = rng.uniform(0.05, 0.25, n)
    rows = rng.permutation(n)[:150]
    got = reference.best_exact_gains(x, rows, g[rows], h[rows], [0, 2],
                                     min_hess=min_hess, min_data=min_data)
    for f, gain in zip([0, 2], got):
        v, gg, hh = x[rows, f], g[rows], h[rows]
        best = -np.inf
        for t in np.unique(v)[:-1]:
            left = v <= t
            if min(hh[left].sum(), hh[~left].sum()) < min_hess or \
                    min(left.sum(), (~left).sum()) < min_data:
                continue
            best = max(best, gg[left].sum() ** 2 / hh[left].sum()
                       + gg[~left].sum() ** 2 / hh[~left].sum()
                       - gg.sum() ** 2 / hh.sum())
        assert gain == pytest.approx(best, rel=1e-9)


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
