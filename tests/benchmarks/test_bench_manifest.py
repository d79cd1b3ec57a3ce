"""BENCHMARK.json against its contract and against the files it names."""

import importlib.util
import json
import os
import re

import pytest

from bench_testlib import (ROOT, SECOND_CELL, manifest,
                           root_with_second_config)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|head|expan")
# a deployment's widths are its shapes (never cut) and its scale is its rows
# (cut only where a chip or a run's time forces it); where each is run from
SHAPES = {"features": "data", "max_bin": "params", "num_leaves": "params"}
SCALE = {"train_rows": "data", "valid_rows": "data"}
HIGGS_CONFIG = "higgs-l255"     # the configuration that config_with edits


def metric_entries():
    m = manifest()
    return m["end_to_end"] + m["per_layer"]


def test_top_level_keys_and_sizes():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    assert 1 <= len(m["paths"]) <= 16
    for word in m["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in m["paths"]), word


@pytest.mark.parametrize("entry", metric_entries(),
                         ids=lambda e: e["name"])
def test_metric_entry(entry):
    m = manifest()
    per_layer = entry in m["per_layer"]
    keys = {"name", "unit", "better", "source"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert set(entry) - {"workloads"} == keys
    assert NAME.match(entry["name"]) and UNIT.match(entry["unit"])
    assert entry["better"] in ("lower", "higher")
    assert entry["source"] in SOURCES
    cells = {w["name"] for w in m["workloads"]}
    assert set(entry.get("workloads", cells)) <= cells
    if per_layer:
        assert entry["moves"] in {e["name"] for e in m["end_to_end"]}
        assert "\n" not in entry["layer"] and len(entry["layer"]) <= 200
        if entry["name"].endswith("_roofline") or "mfu" in entry["name"]:
            assert entry["unit"] == "%"
    else:
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.1


def test_names_are_unique_and_setup_is_there():
    m = manifest()
    for group in (metric_entries(), m["workloads"], m["configs"]):
        names = [g["name"] for g in group]
        assert len(names) == len(set(names))
    assert "setup_s" in {e["name"] for e in m["end_to_end"]}


def check_cell(root, m, cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key]), cell[key]
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    cfg = next(c for c in m["configs"] if c["name"] == cell["config"])
    bench = os.path.join(root, "benchmarks")
    traffic = os.path.join(bench, "traffic", cell["traffic"] + ".json")
    limits = os.path.join(bench, "cells", cell["name"] + ".json")
    for path in (os.path.join(root, cfg["file"]), traffic, limits):
        assert os.path.isfile(path), path
    with open(traffic) as fh:
        mix = json.load(fh)
    assert mix["kind"] == "train_jobs" and mix["entry"] in ("train", "cv")
    with open(limits) as fh:
        own = json.load(fh)
    assert own["rounds_per_job"] >= 2   # the score update is seen from tree 2
    for lim in own["limits"].values():
        assert set(lim) <= {"at_most", "at_least"} and len(lim) == 1


def check_config(root, m, cfg, body=None):
    """The entry against its file (or ``body`` in its place): what is run
    is what the source publishes, but for the keys in ``reduced``; a shape
    is never there, and a cut of scale says why."""
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert any(cfg["file"].startswith(p + "/") for p in m["paths"])
    if body is None:
        with open(os.path.join(root, cfg["file"])) as fh:
            body = json.load(fh)
    assert body["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert body["reduced"] == cfg["reduced"] and len(cfg["reduced"]) <= 16
    for key in cfg["reduced"]:
        assert NAME.match(key), key
        assert not WIDTH.search(key) and key not in SHAPES, \
            f"{key} is a width: never cut"
        assert key in body["published"] and key in body["reduced_why"]
    # no row, feature, bin or leaf count is cut from the source's, unless
    # the file says so
    for key, group in {**SHAPES, **SCALE}.items():
        assert key in body["published"], key
        if key not in cfg["reduced"]:
            assert body[group][key] == body["published"][key], \
                f"{key} is run at {body[group][key]}, the source has " \
                f"{body['published'][key]}, and reduced does not list it"
    assert "num_iterations" in body["published"]
    generator = os.path.join(root, "benchmarks", "generators",
                             body["data"]["generator"] + ".py")
    assert os.path.isfile(generator)


@pytest.mark.parametrize("cell", manifest()["workloads"],
                         ids=lambda w: w["name"])
def test_cell_names_files_that_exist(cell):
    check_cell(ROOT, manifest(), cell)


@pytest.mark.parametrize("cfg", manifest()["configs"],
                         ids=lambda c: c["name"])
def test_config_file_states_what_is_run(cfg):
    check_config(ROOT, manifest(), cfg)


def test_a_configuration_of_another_shape_is_added_by_data_alone(tmp_path):
    """New files and new entries, no file of the benchmark edited: the
    checks above pass on every cell and configuration of the copy, and the
    harness loads the new cell by name."""
    from benchmarks import run
    root = root_with_second_config(str(tmp_path))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    assert len(m["configs"]) == len(manifest()["configs"]) + 1
    for cell in m["workloads"]:
        check_cell(root, m, cell)
    for cfg in m["configs"]:
        check_config(root, m, cfg)
    cell = run.load_cell(SECOND_CELL, root)
    assert cell["config"]["data"]["features"] == 120
    assert cell["config"]["params"]["max_bin"] == 15


def config_with(**changes):
    """The entry and file body of ``HIGGS_CONFIG`` (the narrow table at 255
    bins: the cases below say what they change those from), with ``changes``
    to what it runs (``data`` or ``params`` keys) and to ``reduced``."""
    m = manifest()
    cfg = dict(next(c for c in m["configs"] if c["name"] == HIGGS_CONFIG))
    with open(os.path.join(ROOT, cfg["file"])) as fh:
        body = json.load(fh)
    for key, value in changes.items():
        if key == "reduced":
            cfg["reduced"] = body["reduced"] = value
            body["reduced_why"].update({k: "a test's" for k in value})
        else:
            body[{**SHAPES, **SCALE}[key]][key] = value
    return m, cfg, body


@pytest.mark.parametrize("changes,says", [
    ({"features": 14}, "features is run at 14"),
    ({"max_bin": 63}, "max_bin is run at 63"),
    ({"num_leaves": 31}, "num_leaves is run at 31"),
    ({"train_rows": 1_000_000}, "train_rows is run at 1000000"),
    ({"max_bin": 63, "reduced": ["num_iterations", "max_bin"]},
     "max_bin is a width"),
    ({"features": 14, "reduced": ["features"]}, "features is a width")],
    ids=lambda p: "-".join(p) if isinstance(p, dict) else None)
def test_a_cut_the_file_does_not_own_up_to_is_refused(changes, says):
    m, cfg, body = config_with(**changes)
    with pytest.raises(AssertionError, match=says):
        check_config(ROOT, m, cfg, body)


def test_a_cut_of_scale_that_says_why_is_admitted():
    m, cfg, body = config_with(train_rows=1_000_000,
                               reduced=["num_iterations", "train_rows"])
    check_config(ROOT, m, cfg, body)


@pytest.mark.parametrize("entry", manifest()["per_layer"],
                         ids=lambda e: e["name"])
def test_layer_metric_has_a_reader_that_reads_nothing_from_nothing(entry):
    """Unit, layer, source and what it moves are the manifest's alone; the
    reader's file holds the reader."""
    path = os.path.join(ROOT, "benchmarks", "layer_metrics",
                        entry["name"] + ".py")
    spec = importlib.util.spec_from_file_location("reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert not {"UNIT", "LAYER", "SOURCE", "MOVES", "BETTER"} & set(vars(mod))
    assert mod.read({}) is None


def test_four_chip_cells_stay_within_a_quarter():
    cells = manifest()["workloads"]
    four = sum(1 for c in cells if c["chips"] == 4)
    assert four <= max(1, len(cells) // 4)


def test_peaks_table_names_its_source():
    with open(os.path.join(ROOT, "benchmarks", "peaks.json")) as fh:
        peaks = json.load(fh)
    row = peaks["TPU v5 lite"]
    assert row["flops_per_s_bf16"] == 197e12 and row["ops_per_s_int8"] == 393e12
    assert row["hbm_bytes_per_s"] == 819e9 and "Google Cloud" in row["source"]
