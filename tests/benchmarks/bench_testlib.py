"""Shared helpers for the benchmark's own tests (no test lives here)."""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SMALL = {"train_rows": 20000, "valid_rows": 2000, "features": 40}
ON_CPU = {"tpu_learner": "masked"}     # the learner the chip's defaults pick
CV_CELL = "epsilon-l255.cv5"
TRAIN_CELL = "epsilon-l255.train-eval"  # the mix BENCHMARK.json has no cell on
HIGGS_CELL = "higgs-l255.cv5"
HIGGS_SMALL = {"train_rows": 20000, "valid_rows": 2000}     # all 28 columns
# The program walks held-out rows for as many steps as the tree's depth
# rounded up to a power of two, one program each.  HIGGS_SMALL's trees are 6
# to 9 deep, on both sides of 8, and the warm-up's one fold need not show
# both programs, so the depth is held to 8 (on the chip the 255-leaf trees
# are 9 to 12 deep: PERF.md section 7).
HIGGS_ON_CPU = {"max_depth": 8}
SECOND_CELL = "wide-l63.cv5"            # root_with_second_config's
HOLES_CELL = "holes-l255.cv5"           # root_with_holes_config's


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def v5e_peak() -> dict:
    with open(os.path.join(ROOT, "benchmarks", "peaks.json")) as fh:
        return json.load(fh)["TPU v5 lite"]


def copied_root(tmp: str) -> dict:
    """The benchmark's files copied under ``tmp``; returns the manifest,
    for the caller to add to and write there."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(tmp, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return manifest()


def root_with_train_cell(tmp: str) -> str:
    """A copy of the benchmark's files with one cell more, added the way a
    later PR has to: one entry in ``workloads`` and one new file under
    ``cells/``, on the configuration and the traffic mix that are there."""
    m = copied_root(tmp)
    m["workloads"].append({
        "name": TRAIN_CELL, "config": "epsilon-l255",
        "traffic": "train-eval", "chips": 1,
        "why": "a test's cell: lgb.train with the held-out set, super-epoch"})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as fh:
        json.dump(m, fh)
    cells = os.path.join(tmp, "benchmarks", "cells")
    with open(os.path.join(cells, CV_CELL + ".json")) as fh:
        own = json.load(fh)
    with open(os.path.join(cells, TRAIN_CELL + ".json"), "w") as fh:
        json.dump(own, fh)
    return tmp


def root_with_config(tmp: str, name: str, cell: str, source: str, data: dict,
                     params: dict, num_iterations: int, limits=None) -> str:
    """A copy with one configuration more and a ``cv5`` cell on it, added
    the way a ``model_config`` PR has to: a new file under ``configs/`` and
    under ``cells/`` (``CV_CELL``'s, with ``limits`` in place of its own
    where given) and one new entry each in ``configs`` and ``workloads``; no
    file that is there is edited."""
    m = copied_root(tmp)
    body = {
        "source": source, "task": "train", "data": data, "params": params,
        "published": {**{k: data[k] for k in ("train_rows", "valid_rows",
                                              "features")},
                      "max_bin": params["max_bin"],
                      "num_leaves": params["num_leaves"],
                      "num_iterations": num_iterations},
        "hist_slots": 16, "assumed": [], "reduced": ["num_iterations"],
        "reduced_why": {"num_iterations":
                        f"{num_iterations} -> the cell's rounds"}}
    file = f"benchmarks/configs/{name}.json"
    with open(os.path.join(tmp, file), "w") as fh:
        json.dump(body, fh)
    m["configs"].append({
        "name": name, "source": source, "file": file,
        "reduced": ["num_iterations"], "why": "a test's: another shape"})
    m["workloads"].append({
        "name": cell, "config": name, "traffic": "cv5", "chips": 1,
        "why": "a test's cell on a configuration of its own"})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as fh:
        json.dump(m, fh)
    cells = os.path.join(tmp, "benchmarks", "cells")
    with open(os.path.join(cells, CV_CELL + ".json")) as fh:
        own = json.load(fh)
    own["limits"].update({k: {"at_most": v}
                          for k, v in (limits or {}).items()})
    with open(os.path.join(cells, cell + ".json"), "w") as fh:
        json.dump(own, fh)
    return tmp


def root_with_second_config(tmp: str) -> str:
    """A copy with one configuration more, of another shape than any the
    benchmark has."""
    return root_with_config(
        tmp, "wide-l63", SECOND_CELL,
        "a test's deployment: 30K train / 5K test x 120 features, binary; "
        "max_bin=15 num_leaves=63, 100 trees",
        {"generator": "epsilon_like", "train_rows": 30000,
         "valid_rows": 5000, "features": 120},
        {"objective": "binary", "num_leaves": 63, "max_bin": 15,
         "learning_rate": 0.1, "verbosity": -1}, 100)


# What a sound run reads at root_with_holes_config's size that CV_CELL's
# limits do not allow (five seeds on the CPU; PERF.md section 2).  A fold
# holds out 23 failed parts among 4,000 and most leaves of 350 rows hold
# none, so they say all but the same and their order, which the rounding
# decides, moves the AUC by up to 0.0048 (a state left unchanged reads
# 0.014-0.057; the held-out layer's fault is planted at 0.05).  The exact
# search reads up to 0.073 over the 255 bins of a 16,000-row fold; a search
# that takes the second-best feature or never places the missing rows left,
# 0.67 at the least.
HOLES_LIMITS = {"auc_gap": 0.03, "split_shortfall": 0.2}


def root_with_holes_config(tmp: str) -> str:
    """A copy with a configuration more whose table has holes: 81% of its
    cells missing in station blocks (``generators/bosch_like.py``), under
    the parameter block LightGBM's docs/GPU-Performance.rst runs Bosch with
    at the library's default 255 bins, its hessian floor cut as the rows are
    (100 a leaf of 800,000-row folds is 2 of 16,000-row ones: a leaf of 350
    rows at 0.58% positive)."""
    return root_with_config(
        tmp, "holes-l255", HOLES_CELL,
        "a test's deployment: 20K train / 4K test x 120 numeric in station "
        "blocks, 81% of cells missing, binary 0.58% positive; Bosch's block "
        "of LightGBM docs/GPU-Performance.rst",
        {"generator": "bosch_like", "train_rows": 20000, "valid_rows": 4000,
         "features": 120},
        {"objective": "binary", "num_leaves": 255, "max_bin": 255,
         "learning_rate": 0.1, "min_data_in_leaf": 1,
         "min_sum_hessian_in_leaf": 2, "verbosity": -1}, 500, HOLES_LIMITS)
