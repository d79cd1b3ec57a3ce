"""``tools/fp_digest.py``: the feature-parallel cell's models against the
serial cell's on one table, at a size the CPU holds on its virtual mesh of
four.  The benchmark's ``correct`` follows the trees a run grew, so it cannot
see a tree grown without the exchange between the workers (PERF.md section
7); this comparison can, and says where the trees part."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import fp_digest                                            # noqa: E402
from lightgbm_tpu.parallel import feature_parallel          # noqa: E402

SIZES = {"train_rows": 6000, "features": 40}
# the learner the chip's defaults pick, and a row block in which a worker's
# 10 columns and the serial learner's 40 sum alike (the CPU's contraction is
# a dot whose summation order follows its operands' widths)
ON_CPU = {"tpu_learner": "masked", "rows_per_block": 8, "verbosity": -1}


def compare(seed, **kw):
    return fp_digest.compare(seed, folds=2, sizes=SIZES,
                             extra_params=ON_CPU, **kw)


@pytest.fixture()
def exchange_left_out(monkeypatch):
    """Every worker keeps its own columns' best split: ``select_best``
    without ``gather_best``, in a program of its own (the memo of sharded
    growers is emptied before and after)."""
    feature_parallel._SHARED.clear()
    monkeypatch.setattr(feature_parallel, "gather_best",
                        lambda res, axis: res)
    yield
    feature_parallel._SHARED.clear()


@pytest.mark.parametrize("seed", [34, 2 ** 31 + 34])
def test_the_sharded_cells_models_are_the_serial_cells(seed):
    out = compare(seed)
    assert out["equal"] and out["first_difference"] is None
    assert out["curves_equal"]
    a, b = out[fp_digest.SHARDED], out[fp_digest.SERIAL]
    assert a == b and len(a["models"]) == 2 == out["folds"]


def test_a_tree_grown_without_the_exchange_is_told_apart(exchange_left_out):
    out = compare(34)
    assert not out["equal"]
    where = out["first_difference"]
    # the first tree of the first fold already parts from the serial one,
    # at a split that a column of another worker wins
    assert (where["fold"], where["tree"]) == (0, 0)
    assert where["field"] in ("num_leaves", "split_feature")
    assert out[fp_digest.SHARDED]["models"][0] \
        != out[fp_digest.SERIAL]["models"][0]


HEAD = "tree\nversion=v3"
TREE = "0\nnum_leaves=3\nsplit_feature=4 7\nthreshold=0.5 1.5"


@pytest.mark.parametrize("ours,theirs,where", [
    ([HEAD + "\nTree=" + TREE], [HEAD + "\nTree=" + TREE], None),
    ([HEAD + "\nTree=" + TREE],
     [HEAD + "\nTree=" + TREE.replace("4 7", "4 9")],
     {"fold": 0, "tree": 0, "field": "split_feature", "node": 1,
      "sharded": ["7"], "serial": ["9"]}),
    ([HEAD + "\nTree=" + TREE] * 2,
     [HEAD + "\nTree=" + TREE,
      HEAD + "\nTree=" + TREE + "\nTree=" + TREE.replace("1.5", "1.25")],
     {"fold": 1, "field": "trees"}),
    ([HEAD + "\nTree=" + TREE + "\nTree=" + TREE],
     [HEAD + "\nTree=" + TREE + "\nTree=" + TREE.replace("1.5", "1.25")],
     {"fold": 0, "tree": 1, "field": "threshold", "node": 1,
      "sharded": ["1.5"], "serial": ["1.25"]})],
    ids=["equal", "a_node", "a_tree_more", "second_tree"])
def test_first_difference_names_fold_tree_field_and_node(ours, theirs, where):
    assert fp_digest.first_difference(ours, theirs) == where


def test_the_command_prints_the_verdict_and_exits_by_it(monkeypatch, capsys):
    verdict = {"equal": False, "first_difference": {"fold": 0}}
    monkeypatch.setattr(fp_digest, "compare", lambda seed, **kw: verdict)
    assert fp_digest.main(["--seed", "7"]) == 1
    assert json.loads(capsys.readouterr().out.splitlines()[-1]) == verdict
    verdict["equal"] = True
    assert fp_digest.main(["--seed", "7", "--folds", "1"]) == 0
