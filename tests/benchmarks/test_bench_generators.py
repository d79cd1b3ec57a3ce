"""The seeded generators: the same seed gives the same rows, and the rows
have the column kinds the configuration's source has."""

import importlib.util
import os

import numpy as np
import pytest

from bench_testlib import ROOT, manifest

from benchmarks import run

HIGGS = next(w["name"] for w in manifest()["workloads"]
             if w["config"] == "higgs-l255")


def higgs_like():
    return run.load_by_name(run.load_cell(HIGGS)["bench_dir"], "generators",
                            "higgs_like", "make")


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 1234])
def test_higgs_like_comes_from_its_seed_alone(seed):
    make = higgs_like()
    x, y = make(3000, 28, seed)
    x2, y2 = make(3000, 28, seed)
    assert x.dtype == np.float32 and x.shape == (3000, 28)
    assert (x == x2).all() and (y == y2).all()
    x3, _ = make(3000, 28, seed + 1)
    assert (x3 != x).any()


@pytest.mark.parametrize("n_features", [8, 28])
def test_higgs_like_has_higgs_column_kinds(n_features):
    x, y = higgs_like()(40000, n_features, 7)
    distinct = [len(np.unique(x[:, j])) for j in range(n_features)]
    assert [j for j, d in enumerate(distinct) if d == 3] == [4, 5, 6, 7]
    assert min(d for j, d in enumerate(distinct) if j not in (4, 5, 6, 7)) \
        > 30000
    assert set(np.unique(y)) == {0.0, 1.0} and 0.45 <= y.mean() <= 0.6
    assert (x[:, 0] > 0).all() and (x[:, 2] > 0).all()       # momenta, masses
    assert x[:, 0].max() > 5 * np.median(x[:, 0])            # a long tail
    assert abs(np.median(x[:, 1])) < 0.05 and np.abs(x[:, 3]).max() < 3.1416


def test_higgs_like_rows_cross_chunks_and_threads_alike():
    """More rows than a chunk: the chunks' own streams give the same rows
    whatever thread draws them."""
    import importlib.util
    import os
    path = os.path.join(run.load_cell(HIGGS)["bench_dir"], "generators",
                        "higgs_like.py")
    spec = importlib.util.spec_from_file_location("higgs_like", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.CHUNK_ROWS = 1000
    x, y = mod.make(4500, 8, 2 ** 31 + 3)
    mod.THREADS = 1
    x1, y1 = mod.make(4500, 8, 2 ** 31 + 3)
    assert (x == x1).all() and (y == y1).all()
    assert len(np.unique(x[:, 0])) > 4400        # no chunk drawn twice


def test_higgs_like_refuses_fewer_columns_than_its_label_reads():
    with pytest.raises(ValueError, match="at least 8"):
        higgs_like()(100, 7, 1)


# -- bosch_like: a table with holes -----------------------------------------------

def bosch_like_module():
    """A copy of the module of the test's own, to turn its constants."""
    path = os.path.join(ROOT, "benchmarks", "generators", "bosch_like.py")
    spec = importlib.util.spec_from_file_location("bosch_like", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def same(a, b):
    return ((a == b) | (np.isnan(a) & np.isnan(b))).all()


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 1234])
def test_bosch_like_comes_from_its_seed_alone(seed):
    make = run.load_by_name(os.path.join(ROOT, "benchmarks"), "generators",
                            "bosch_like", "make")
    x, y = make(3000, 120, seed)
    x2, y2 = make(3000, 120, seed)
    assert x.dtype == np.float32 and x.shape == (3000, 120)
    assert y.dtype == np.float32 and set(np.unique(y)) <= {0.0, 1.0}
    assert same(x, x2) and (y == y2).all()
    x3, _ = make(3000, 120, seed + 1)
    assert not same(x3, x)


def test_bosch_like_rows_cross_chunks_and_threads_alike():
    mod = bosch_like_module()
    mod.CHUNK_ROWS = 1000
    x, y = mod.make(4500, 64, 2 ** 31 + 3)
    mod.THREADS = 1
    x1, y1 = mod.make(4500, 64, 2 ** 31 + 3)
    assert same(x, x1) and (y == y1).all()
    present = x[~np.isnan(x)]
    assert len(np.unique(present)) > 0.999 * len(present)   # no chunk twice


@pytest.mark.parametrize("n_rows,n_features", [
    (200_000, 64), (200_000, 120), (65_536, 968)], ids=str)
def test_bosch_like_has_boschs_holes_and_its_rare_label(n_rows, n_features):
    """About 81% of the cells missing and about 0.58% of the parts failing,
    at a test's widths and at Bosch's own (one chunk of rows there)."""
    mod = bosch_like_module()
    x, y = mod.make(n_rows, n_features, 7)
    assert 0.78 <= np.isnan(x).mean() <= 0.84
    assert 0.004 <= y.mean() <= 0.008
    assert np.isfinite(x[~np.isnan(x)]).all()
    assert mod.stations_of(n_features) == {64: 8, 120: 15, 968: 52}[
        n_features]


def test_bosch_like_misses_whole_stations_and_the_label_reads_both():
    mod = bosch_like_module()
    x, y = mod.make(200_000, 120, 11)
    starts, line = mod.layout(120)
    assert len(line) == 15 and sorted(set(line)) == [0, 1, 2, 3]
    missing = np.isnan(x)
    for s in range(len(line)):        # a station's columns go together
        block = missing[:, starts[s]:starts[s + 1]]
        assert (block == block[:, :1]).all()
    # stations differ from row to row, and no row passed them all
    assert len(np.unique(missing[:, starts[:-1]], axis=0)) > 1000
    assert missing.any(axis=1).all()
    # a high reading at HIGH_FAILS' first station fails more often than a
    # low one, with the missing rows in between; having passed REWORK's more
    # often than not
    col = starts[mod.place(mod.HIGH_FAILS[0][0], len(line))]
    high, low = x[:, col] > 1, x[:, col] < -1
    assert y[high].mean() > 3 * y[missing[:, col]].mean() \
        > 3 * y[low].mean()
    col = starts[mod.place(mod.REWORK[0][0], len(line))]
    assert y[~missing[:, col]].mean() > 1.5 * y[missing[:, col]].mean()


def test_bosch_like_refuses_fewer_columns_than_its_label_reads():
    mod = bosch_like_module()
    with pytest.raises(ValueError, match="at least 32"):
        mod.make(100, 31, 1)
    x, _ = mod.make(100, 32, 1)
    assert x.shape == (100, 32)
