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


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def v5e_peak() -> dict:
    with open(os.path.join(ROOT, "benchmarks", "peaks.json")) as fh:
        return json.load(fh)["TPU v5 lite"]


def root_with_train_cell(tmp: str) -> str:
    """A copy of the benchmark's files with one cell more, added the way a
    later PR has to: one entry in ``workloads`` and one new file under
    ``cells/``, on the configuration and the traffic mix that are there."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(tmp, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    m = manifest()
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
