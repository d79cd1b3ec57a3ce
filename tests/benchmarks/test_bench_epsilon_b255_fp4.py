"""The fourth configuration, ``epsilon-b255-fp4`` (Epsilon at 255 bins under
the source's feature-parallel learner, four workers), and its cell
``epsilon-b255-fp4.cv5``, the benchmark's first on four chips: the
manifest's entries and files on the real tree, and the cell through the
harness at a size the CPU holds, on the CPU's virtual mesh of four, under
the cell's own limits: sound runs are ``correct``, the bfloat16 control and
each planted fault are not.  The cases are ``test_bench_epsilon_b255.py``'s
on this cell; what is new here is what crosses chips."""

import json
import os

import pytest

from bench_testlib import ROOT, SMALL, manifest
from test_bench_correct import (answer_altered, best_feature_overlooked,
                                check_sound_and_control, drive, failed,
                                half_batch, metric_altered, state_unchanged)
from test_bench_manifest import check_cell, check_config

from benchmarks import run

CONFIG, SERIAL = "epsilon-b255-fp4", "epsilon-b255"
CELL = "epsilon-b255-fp4.cv5"
NEW_METRICS = ("sync_iter_ms", "sync_kib_iter")
WORKERS = 4


def entry(kind, name):
    return next(e for e in manifest()[kind] if e["name"] == name)


def body_of(config):
    with open(os.path.join(ROOT, entry("configs", config)["file"])) as fh:
        return json.load(fh)


def test_the_manifest_has_the_configuration_its_cell_and_its_metrics():
    m = manifest()
    check_config(ROOT, m, entry("configs", CONFIG))
    check_cell(ROOT, m, entry("workloads", CELL))
    cell = entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "cv5", WORKERS)
    # the two metrics of what crosses chips are read in this cell; entries
    # are looked up by name, so a later cell or metric breaks nothing here
    units = {"sync_iter_ms": ("ms", "device_trace"),
             "sync_kib_iter": ("KiB", "program_counter")}
    for name in NEW_METRICS:
        unit, source = units[name]
        assert entry("per_layer", name) == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "mesh", "moves": "train_iter_s", "workloads": [CELL]}


def test_only_the_learner_and_the_worker_count_differ_from_epsilon_b255():
    body, serial = body_of(CONFIG), body_of(SERIAL)
    for key in ("task", "data", "published", "precision", "hist_slots",
                "reduced", "reduced_why"):
        assert body[key] == serial[key], key
    assert body["reduced"] == ["num_iterations"] \
        == entry("configs", CONFIG)["reduced"]
    added = {"tree_learner": "feature", "num_machines": WORKERS}
    assert body["params"] == dict(serial["params"], **added)
    assert set(body) - set(serial) == {"deployment"}
    # what the serial configuration assumed, and what this one adds to it
    assert body["assumed"][:len(serial["assumed"])] == serial["assumed"]
    deployment = body["deployment"]
    assert deployment["workers"] == WORKERS
    assert "serial learner's, tree for tree" in deployment["guarantee"]
    assert deployment["reference"].startswith("benchmarks/reference.py")
    # two deployments of one table have sources of their own
    assert body["source"] != serial["source"] and len(body["source"]) <= 200
    assert body["source"] == entry("configs", CONFIG)["source"]


def test_the_cell_is_found_by_name_with_limits_of_its_own():
    cell = run.load_cell(CELL)
    assert cell["rounds"] == 2 and cell["traffic"]["entry"] == "cv"
    assert cell["config"]["params"]["tree_learner"] == "feature"
    assert set(cell["limits"]) == set(run.load_cell(SERIAL + ".cv5")["limits"])
    # every metric the one-chip cell of this table reads, and the mesh's two
    names = {m["name"] for m in cell["per_layer"]}
    serial = {m["name"] for m in run.load_cell(SERIAL + ".cv5")["per_layer"]}
    assert serial | set(NEW_METRICS) <= names
    assert not set(NEW_METRICS) & serial


def learners_of(lgb):
    """``lgb.cv`` that notes what grew each booster."""
    seen = []

    def cv(params, ds, **kw):
        out = lgb.cv(params, ds, **kw)
        seen.extend((b._model._dist, b._model._mesh.size,
                     b._model._valid_followers() is not None)
                    for b in out["cvbooster"].boosters)
        return out
    return cv, seen


@pytest.mark.parametrize("seed", [41, 2 ** 31 + 42])
def test_sound_run_is_correct_and_the_control_is_not(seed):
    import lightgbm_tpu as lgb
    cv, seen = learners_of(lgb)
    # no depth is held here: the held-out rows ride the grower's partition,
    # so a tree of another depth than the warm-up's compiles nothing
    r = drive(CELL, seed, control=True, sizes=SMALL, call=cv)
    check_sound_and_control(r)
    assert r["attempted"] == 2 * 5          # one job: two rounds, five folds
    # the feature-parallel learner over four devices grew every booster,
    # the warm-up's too, and carried its held-out rows
    assert set(seen) == {("feature", WORKERS, True)} and len(seen) == 1 + 5


@pytest.mark.parametrize("fault,catches", [
    (state_unchanged, "leaf_gap_median"),
    (half_batch, "count_gap"),
    (answer_altered, "leaf_gap_max"),
    (metric_altered, "auc_gap"),
    (best_feature_overlooked, "split_shortfall")],
    ids=lambda p: getattr(p, "__name__", p))
def test_planted_fault_is_not_correct(fault, catches):
    import lightgbm_tpu as lgb
    seed = 43
    r = drive(CELL, seed, call=fault(lgb, seed, CELL, SMALL), sizes=SMALL)
    assert not r["correct"]
    assert catches in failed(r), r["compared"]


def reader(name):
    return run.load_reader(os.path.join(ROOT, "benchmarks"), name)


# what run.py hands a reader after a traced job of 10 iterations, 19 steps a
# tree, at the cell's width: the scopes' ms an iteration, and the five
# boosters' counters merged
STEP_BYTES, ROOT_BYTES = 102_336, 3_198
RECORDED = {
    "trace": {"busy_s": 15.2, "window_s": 22.0, "devices": 4},
    "scope_iter_ms": {"lgbtpu.hist.contract": 1315.0, "lgbtpu.sync": 7.25,
                      "lgbtpu.split": 28.0},
    "counters": {
        "train.iterations": {"value": 10.0},
        "comm.bytes{collective=all_gather,site=fp.best_split}":
            {"value": 10.0 * 19 * STEP_BYTES},
        "comm.bytes{collective=all_gather,site=fp.root_split}":
            {"value": 10.0 * ROOT_BYTES},
        "comm.calls{collective=all_gather,site=fp.best_split}":
            {"value": 190.0}}}


@pytest.mark.parametrize("name,reads", [
    ("sync_iter_ms", 7.25),
    ("sync_kib_iter", (19 * STEP_BYTES + ROOT_BYTES) / 1024)])
def test_new_reader_reads_a_recorded_context(name, reads):
    assert reader(name)(RECORDED) == pytest.approx(reads, rel=1e-12)


@pytest.mark.parametrize("name", NEW_METRICS)
@pytest.mark.parametrize("ctx", [
    {},
    {"trace": None, "counters": {}, "scope_iter_ms": {}},
    # a one-chip cell, or the parent: neither the scope nor the counter
    {"trace": {"busy_s": 1.0}, "scope_iter_ms": {"lgbtpu.split": 28.0},
     "counters": {"train.iterations": {"value": 10.0},
                  "comm.wire_bytes{collective=all_gather,site=fp.best_split}":
                      {"value": 1.0}}}],
    ids=["empty", "no_device", "no_scope_no_counter"])
def test_new_reader_reads_nothing_where_there_is_nothing(name, ctx):
    assert reader(name)(ctx) is None
