"""A table with holes through the harness: 81% of the cells missing in
station blocks and a rare label (``generators/bosch_like.py``), as a cell
on a copied root (``bench_testlib.root_with_holes_config``) at a size the
CPU holds.  The reference routes a missing value by what each dumped node
says of it and places a node's missing rows on either side when it searches
splits itself, so a sound run is ``correct``; the bfloat16 control, the two
faults of the directions and the five planted faults are not.  (The rules
themselves, on hand-made trees and columns: ``test_bench_correct.py``.)"""

import numpy as np
import pytest

from bench_testlib import HOLES_CELL, root_with_holes_config
from test_bench_correct import (answer_altered, best_feature_overlooked,
                                drive, failed, half_batch, metric_altered,
                                state_unchanged)

from benchmarks import run


@pytest.fixture(scope="module")
def holes_root(tmp_path_factory):
    return root_with_holes_config(str(tmp_path_factory.mktemp("holes")))


@pytest.mark.parametrize("seed", [41, 2 ** 31 + 52, 46])
def test_sound_run_on_a_table_with_holes_is_correct_and_its_controls_are_not(
        holes_root, seed):
    """81% of the cells missing in station blocks, a rare label: the
    reference routes the missing rows by each node's dumped direction, so
    every leaf holds the program's count of rows and every gap is finite
    and within the (test's own) limits; the bfloat16 control is not
    correct; the trees followed with every direction cleared lose rows, and
    a search that never places the missing rows left falls short."""
    r = drive(HOLES_CELL, seed, control=True, root=holes_root, sizes={})
    assert r["correct"] and not failed(r, holes_root), r["compared"]
    assert r["compared"]["count_gap"]["value"] == 0
    assert set(r["compared"]) == set(run.load_cell(HOLES_CELL,
                                                   holes_root)["limits"])
    assert all(np.isfinite(c["value"]) for c in r["compared"].values())
    assert r["readings"]["control"]["correct"] is False
    faults = r["readings"]["faults"]
    assert faults["direction_ignored"]["count_gap"] > 1000
    limit = r["compared"]["split_shortfall"]["limit"]
    assert faults["one_direction_search"]["split_shortfall"] > limit
    assert faults["second_best_feature"]["split_shortfall"] > limit
    # a node whose missing rows lie right anyway loses nothing by it
    assert faults["one_direction_search"]["least_node"] == 0


def metric_altered_by_a_twentieth(lgb, seed, cell, sizes, root):
    """A thousandth, the fault's size elsewhere, is within what a sound run
    reads here (``bench_testlib.HOLES_LIMITS``)."""
    return metric_altered(lgb, seed, cell, sizes, root, off=0.05)


@pytest.mark.parametrize("fault,catches", [
    (state_unchanged, "leaf_gap_max"),
    (half_batch, "count_gap"),
    (answer_altered, "leaf_gap_max"),
    (metric_altered_by_a_twentieth, "auc_gap"),
    (best_feature_overlooked, "split_shortfall")],
    ids=lambda p: getattr(p, "__name__", p))
def test_planted_fault_on_a_table_with_holes_is_not_correct(holes_root, fault,
                                                            catches):
    """The five faults, on the table with holes, under the limits of
    ``CV_CELL`` but for ``bench_testlib.HOLES_LIMITS``.  A state left
    unchanged is the worst leaf's to catch: a leaf without a failed part says
    all but the same whatever the scores, so the median leaf's gap reads
    6e-4 on one seed of five and 1e-2 on the others."""
    import lightgbm_tpu as lgb
    seed = 43
    r = drive(HOLES_CELL, seed, root=holes_root, sizes={},
              call=fault(lgb, seed, HOLES_CELL, {}, holes_root))
    assert not r["correct"]
    assert catches in failed(r, holes_root), r["compared"]
