"""The third configuration, ``epsilon-b255`` (Epsilon at LightGBM's default
255 bins), and its cell ``epsilon-b255.cv5``: the manifest's entries and
files on the real tree, and the cell through the harness at a size the CPU
holds (``bench_testlib.SMALL``'s rows and columns at the configuration's own
255 bins and 255 leaves), under the cell's own limits: sound runs are
``correct``, the bfloat16 control and each planted fault are not."""

import json
import os

import pytest

from bench_testlib import CV_CELL, ROOT, SMALL, manifest
from test_bench_correct import (answer_altered, best_feature_overlooked,
                                check_sound_and_control, drive, failed,
                                half_batch, metric_altered, state_unchanged)
from test_bench_manifest import check_cell, check_config

from benchmarks import run

CONFIG = "epsilon-b255"
CELL = "epsilon-b255.cv5"
# As HIGGS_ON_CPU: at 16,000-row folds and 255 bins the trees are 6 to 9
# deep, on both sides of 8, and the held-out walk is one program for each
# power of two of the depth, which the warm-up's one fold need not show
# (PERF.md section 7, 0m); on the chip every tree is of one bucket.
DEPTH = {"max_depth": 8}


def entry(kind, name):
    return next(e for e in manifest()[kind] if e["name"] == name)


def test_the_manifest_has_the_configuration_its_cell_and_its_metric():
    m = manifest()
    check_config(ROOT, m, entry("configs", CONFIG))
    check_cell(ROOT, m, entry("workloads", CELL))
    cell = entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "cv5", 1)
    # added beside what was there, and before what came later: entries are
    # looked up by name, so a later cell or metric breaks nothing here
    for kind, name, later in (("configs", CONFIG, "epsilon-b255-fp4"),
                              ("workloads", CELL, "epsilon-b255-fp4.cv5"),
                              ("per_layer", "grower_temp_gib",
                               "sync_iter_ms")):
        names = [e["name"] for e in m[kind]]
        assert 0 < names.index(name) < names.index(later)
    assert entry("per_layer", "grower_temp_gib") == {
        "name": "grower_temp_gib", "unit": "GiB", "better": "lower",
        "source": "program_counter", "layer": "grower",
        "moves": "train_iter_s"}


def test_no_width_is_cut_and_only_the_bin_count_differs_from_epsilon_l255():
    with open(os.path.join(ROOT, entry("configs", CONFIG)["file"])) as fh:
        body = json.load(fh)
    with open(os.path.join(ROOT, entry("configs", "epsilon-l255")["file"])) \
            as fh:
        narrow = json.load(fh)
    assert body["reduced"] == ["num_iterations"] \
        == entry("configs", CONFIG)["reduced"]
    run_sizes = dict(body["data"], **body["params"])
    for key in ("train_rows", "valid_rows", "features", "max_bin",
                "num_leaves"):
        assert run_sizes[key] == body["published"][key]
    assert (body["published"]["features"], body["published"]["max_bin"],
            body["published"]["num_leaves"]) == (2000, 255, 255)
    assert (body["published"]["train_rows"],
            body["published"]["valid_rows"]) == (400_000, 100_000)
    # the same generator, rows, columns and parameter block: 255 bins for 63
    assert body["data"] == narrow["data"]
    assert dict(body["params"], max_bin=63) == narrow["params"]
    # two deployments of one document have sources of their own
    assert body["source"] != narrow["source"] and len(body["source"]) <= 200
    assert body["source"] == entry("configs", CONFIG)["source"]


def test_the_cell_is_found_by_name_with_limits_of_its_own():
    cell = run.load_cell(CELL)
    assert cell["rounds"] == 2 and cell["traffic"]["entry"] == "cv"
    assert cell["config"]["params"]["max_bin"] == 255
    assert set(cell["limits"]) == set(run.load_cell(CV_CELL)["limits"])
    assert "grower_temp_gib" in [m["name"] for m in cell["per_layer"]]


@pytest.mark.parametrize("seed", [31, 2 ** 31 + 32])
def test_sound_run_is_correct_and_the_control_is_not(seed):
    r = drive(CELL, seed, control=True, sizes=SMALL, **DEPTH)
    check_sound_and_control(r)
    assert r["attempted"] == 2 * 5          # one job: two rounds, five folds


@pytest.mark.parametrize("fault,catches", [
    (state_unchanged, "leaf_gap_median"),
    (half_batch, "count_gap"),
    (answer_altered, "leaf_gap_max"),
    (metric_altered, "auc_gap"),
    (best_feature_overlooked, "split_shortfall")],
    ids=lambda p: getattr(p, "__name__", p))
def test_planted_fault_is_not_correct(fault, catches):
    import lightgbm_tpu as lgb
    seed = 33
    r = drive(CELL, seed, call=fault(lgb, seed, CELL, SMALL), sizes=SMALL,
              **DEPTH)
    assert not r["correct"]
    assert catches in failed(r), r["compared"]


def test_grower_temp_gib_reads_the_mean_of_the_boosters_observations():
    read = run.load_reader(os.path.join(ROOT, "benchmarks"),
                           "grower_temp_gib")
    five = {"grower.temp_bytes": {"count": 5, "sum": 5 * 3 * 2.0 ** 30}}
    assert read({"trace": {"busy_s": 1.0}, "counters": five}) == 3.0
    # nothing from a program without the counter (the parent), from a run
    # without a device plane, from no run at all
    assert read({"trace": {"busy_s": 1.0}, "counters": {}}) is None
    assert read({"trace": None, "counters": five}) is None
    assert read({}) is None
