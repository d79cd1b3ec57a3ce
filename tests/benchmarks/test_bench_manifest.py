"""BENCHMARK.json against its contract and against the files it names."""

import importlib.util
import json
import os
import re

import pytest

from bench_testlib import ROOT, manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|head|expan")


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


@pytest.mark.parametrize("cell", manifest()["workloads"],
                         ids=lambda w: w["name"])
def test_cell_names_files_that_exist(cell):
    m = manifest()
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key]), cell[key]
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    cfg = next(c for c in m["configs"] if c["name"] == cell["config"])
    bench = os.path.join(ROOT, "benchmarks")
    traffic = os.path.join(bench, "traffic", cell["traffic"] + ".json")
    limits = os.path.join(bench, "cells", cell["name"] + ".json")
    for path in (os.path.join(ROOT, cfg["file"]), traffic, limits):
        assert os.path.isfile(path), path
    with open(traffic) as fh:
        mix = json.load(fh)
    assert mix["kind"] == "train_jobs" and mix["entry"] in ("train", "cv")
    with open(limits) as fh:
        own = json.load(fh)
    assert own["rounds_per_job"] >= 2   # the score update is seen from tree 2
    for lim in own["limits"].values():
        assert set(lim) <= {"at_most", "at_least"} and len(lim) == 1


@pytest.mark.parametrize("cfg", manifest()["configs"],
                         ids=lambda c: c["name"])
def test_config_file_states_what_is_run(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert any(cfg["file"].startswith(p + "/") for p in manifest()["paths"])
    with open(os.path.join(ROOT, cfg["file"])) as fh:
        body = json.load(fh)
    assert body["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert body["reduced"] == cfg["reduced"] and len(cfg["reduced"]) <= 16
    for key in cfg["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key), key
        assert key in body["published"] and key in body["reduced_why"]
    # no row, feature, bin or leaf count is cut from the source's
    assert body["data"]["train_rows"] == 400_000
    assert body["data"]["valid_rows"] == 100_000
    assert body["data"]["features"] == 2000
    assert body["params"]["max_bin"] == 63
    assert body["params"]["num_leaves"] == 255
    generator = os.path.join(ROOT, "benchmarks", "generators",
                             body["data"]["generator"] + ".py")
    assert os.path.isfile(generator)


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
    assert "workloads" not in entry     # read in every cell, later ones too


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
