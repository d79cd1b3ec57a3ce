"""run.py off the chip: it refuses, and prints no result."""

import json
import types

import pytest

from bench_testlib import manifest

from benchmarks import run

CELL = manifest()["workloads"][0]["name"]
ARGS = ["--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"]


def fake_devices(platform, kind, n=1):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind,
                                memory_stats=lambda: {})
    return lambda *a, **k: [dev] * n


def test_refuses_the_cpu(capsys):
    assert run.main(ARGS) == run.EXIT_NO_CHIP
    out = capsys.readouterr()
    assert out.out == "" and "not 'tpu'" in out.err


@pytest.mark.parametrize("kind,count,says", [
    ("TPU v9 mega", 1, "not in benchmarks/peaks.json"),
    ("TPU v5 lite", 0, "asks for 1 chips")])
def test_refuses_an_unknown_kind_and_too_few_chips(monkeypatch, capsys,
                                                   kind, count, says):
    """``count`` 0 stands for a v5e machine whose chips are all taken: jax
    names the kind and lists fewer devices than the cell asks for."""
    import jax
    dev = fake_devices("tpu", kind)()[0]

    class Devices(list):
        def __getitem__(self, i):          # devices[0] names the kind
            return dev if isinstance(i, int) else list.__getitem__(self, i)
    monkeypatch.setattr(jax, "devices",
                        lambda *a, **k: Devices([dev] * count))
    assert run.main(ARGS) == run.EXIT_NO_CHIP
    out = capsys.readouterr()
    assert out.out == "" and says in out.err


def test_unknown_workload_is_an_error():
    with pytest.raises(SystemExit) as e:
        run.load_cell("no-such-cell")
    assert "unknown workload" in str(e.value)


def test_every_cell_loads_by_name():
    m = manifest()
    for w in m["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell["rounds"] >= 2
        assert {e["name"] for e in cell["end_to_end"]} >= {"train_iter_s",
                                                           "setup_s"}
        for metric in cell["per_layer"]:
            assert callable(run.load_reader(cell["bench_dir"],
                                            metric["name"]))
    assert json.dumps(m)      # plain data


@pytest.mark.parametrize("n,nfold", [(400_000, 5), (20_000, 5), (1003, 3)])
def test_seeded_folds_part_the_rows(n, nfold):
    import numpy as np
    folds = run.seeded_folds(n, nfold, 2 ** 31 + 7)
    assert len(folds) == nfold
    held = np.concatenate([te for _, te in folds])
    assert sorted(held.tolist()) == list(range(n))
    for tr, te in folds:
        assert len(tr) + len(te) == n and not set(tr[:50]) & set(te)
    again = run.seeded_folds(n, nfold, 2 ** 31 + 7)
    assert all((a[1] == b[1]).all() for a, b in zip(folds, again))


def test_data_comes_from_the_seed_alone():
    import numpy as np
    cell = run.load_cell(CELL)
    small = {"train_rows": 300, "valid_rows": 50, "features": 20}
    x, y, xv, yv = run.make_data(cell, 2 ** 31 + 99, small, True)
    x2, y2, none_x, none_y = run.make_data(cell, 2 ** 31 + 99, small, False)
    assert x.shape == (300, 20) and xv.shape == (50, 20) and len(yv) == 50
    assert none_x is None and none_y is None and x2.shape == (300, 20)
    assert (x2 == x).all() and set(np.unique(y)) <= {0.0, 1.0}
    x3, _, _, _ = run.make_data(cell, 2 ** 31 + 100, small, False)
    assert (x3 != x).any()


def test_merged_counters_add_up_the_boosters():
    a = {"train.iterations": {"type": "counter", "value": 2.0},
         "train.steps_per_tree": {"type": "histogram", "count": 2,
                                  "sum": 38.0, "min": 19, "max": 19},
         "enabled": 1}
    got = run.merged_counters([a, a, a])
    assert got["train.iterations"]["value"] == 6.0
    assert got["train.steps_per_tree"] == {"count": 6, "sum": 114.0}


def test_an_entry_the_harness_does_not_drive_is_an_error(tmp_path):
    import os
    import shutil
    from bench_testlib import ROOT
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    mix = tmp_path / "benchmarks" / "traffic" / "cv5.json"
    body = json.loads(mix.read_text())
    body["entry"] = "predict"
    mix.write_text(json.dumps(body))
    with pytest.raises(SystemExit) as e:
        run.load_cell(CELL, str(tmp_path))
    assert "predict" in str(e.value)
