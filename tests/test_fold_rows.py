"""``lgb.cv`` places its training set's binned matrix once a call and gathers
each fold's training and held-out rows from it on the device
(``lightgbm_tpu/fold_rows.py``): the fold's matrices are the host path's to
the byte, on the serial learner, over EFB groups and on the feature-sharded
learner's virtual mesh of four; the other learners and layouts keep the
host's path; nothing compiles in a job after a warm-up of one fold, and
nothing of the placement outlives the call."""

import gc

import jax
import numpy as np
import pytest
import scipy.sparse as sps

import lightgbm_tpu as lgb
from lightgbm_tpu.fold_rows import FoldRows

N, NFOLD = 2000, 5
BASE = dict(objective="binary", num_leaves=15, max_bin=63, learning_rate=0.1,
            min_data_in_leaf=5, verbosity=-1, tpu_learner="masked",
            metric="auc", telemetry=True)
# the learners and layouts whose folds are gathered on the device; the
# bundled table under ``feature`` is placed unbundled, its 15 columns
# padded to 16 for four workers by the gather
DEVICE = {"serial": ("dense", {}),
          "efb": ("onehot", {}),
          "feature": ("onehot", dict(tree_learner="feature", num_machines=4))}
# and those whose folds keep the host's path
HOST = {"sparse": ("sparse", {}),
        "data": ("dense", dict(tree_learner="data", num_machines=4)),
        "voting": ("dense", dict(tree_learner="voting", num_machines=4)),
        "fpreproc": ("dense", {})}


def table(kind: str):
    rng = np.random.default_rng(38)
    if kind == "sparse":
        rows = np.repeat(np.arange(N), 30)
        cols = rng.integers(0, 600, size=N * 30)
        _, first = np.unique(rows * 600 + cols, return_index=True)
        x = sps.csr_matrix((rng.integers(1, 16, len(first)).astype(float),
                            (rows[first], cols[first])), shape=(N, 600))
        y = (np.asarray(x[:, :5].sum(axis=1)).ravel()
             + rng.standard_normal(N) > 20).astype(np.float32)
        return x, y
    dense = rng.standard_normal((N, 10 if kind == "dense" else 3))
    if kind == "onehot":
        cat = rng.integers(0, 12, N)
        onehot = np.zeros((N, 12))
        onehot[np.arange(N), cat] = 1.0
        x = np.column_stack([dense, onehot])
        y = dense[:, 0] + (cat % 3 == 0) + 0.2 * rng.standard_normal(N) > 0.5
    else:
        x = dense
        y = dense[:, 0] + 0.5 * dense[:, 1] * dense[:, 2] \
            + 0.3 * rng.standard_normal(N) > 0
    return x, y.astype(np.float32)


def folds_of(n: int = N):
    rows = np.random.default_rng(7).permutation(n)
    return [(np.setdiff1d(np.arange(n), part), np.sort(part))
            for part in np.array_split(rows, NFOLD)]


def run_cv(kind, extra, folds=None, rounds=2, **kw):
    x, y = table(kind)
    params = dict(BASE, **extra)
    ds = lgb.Dataset(x, label=y, params=params).construct()
    out = lgb.cv(params, ds, num_boost_round=rounds,
                 folds=folds or folds_of(), return_cvbooster=True, **kw)
    return ds, out["cvbooster"].boosters, out["valid auc-mean"]


def host_path(monkeypatch):
    """Every fold's rows from the host, as before the device's gather."""
    monkeypatch.setattr(FoldRows, "serves", lambda self, ds: False)


def counted(bst, source):
    rec = bst.telemetry_snapshot().get(f"cv.fold_rows{{source={source}}}")
    return 0 if rec is None else rec["value"]


def same_array(a, b):
    """Same bytes, shape, dtype and placement."""
    assert a.shape == b.shape and a.dtype == b.dtype
    assert a.sharding == b.sharding and a.committed == b.committed
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("case", DEVICE)
def test_a_folds_device_matrices_are_the_host_subsets_to_the_byte(case):
    kind, extra = DEVICE[case]
    ds, boosters, _ = run_cv(kind, extra)
    params = dict(BASE, **extra)
    for (tr, te), bst in zip(folds_of(), boosters):
        model = bst._model
        assert counted(bst, "device") == 2 and counted(bst, "host") == 0
        ref = lgb.Booster(params=params, train_set=ds.subset(tr))
        ref.add_valid(ds.subset(te), "valid")
        same_array(model.binned_dev, ref._model.binned_dev)
        # the held-out rows padded to their bucket, pad rows bin 0
        held, held_ref = model.valid_sets[0][1], ref._model.valid_sets[0][1]
        assert held.shape[0] == 512 > len(te)
        same_array(held, held_ref)
    if case == "efb":
        assert model._use_efb and ds.efb is not None
        assert model.binned_dev.shape[1] < ds.num_features
    if case == "feature":
        assert model._dist == "feature" and model._feat_pad == 1
        assert len(model.binned_dev.sharding.device_set) == 4


@pytest.mark.parametrize("case", DEVICE)
def test_the_boosters_and_curve_are_the_host_paths(case, monkeypatch):
    kind, extra = DEVICE[case]
    _, device, curve = run_cv(kind, extra)
    with monkeypatch.context() as m:
        host_path(m)
        _, host, curve_host = run_cv(kind, extra)
    assert curve == curve_host
    for a, b in zip(device, host):
        assert counted(b, "host") == 2 and counted(b, "device") == 0
        assert a.model_to_string() == b.model_to_string()


def other_datasets(tr, te, params):
    """An fpreproc that hands the folds Datasets of its own."""
    return (tr.subset(np.arange(tr.num_data)),
            te.subset(np.arange(te.num_data)), params)


@pytest.mark.parametrize("case", HOST)
def test_the_other_paths_count_every_fold_from_the_host(case):
    kind, extra = HOST[case]
    _, boosters, _ = run_cv(
        kind, extra, rounds=1,
        fpreproc=other_datasets if case == "fpreproc" else None)
    assert len(boosters) == NFOLD
    for bst in boosters:
        assert counted(bst, "host") == 2 and counted(bst, "device") == 0
    if case == "sparse":
        assert bst._model._sparse
    if case in ("data", "voting"):
        assert bst._model._dist == case


def test_a_subset_reads_the_bins_it_always_read():
    x, y = table("dense")
    ds = lgb.Dataset(x, label=y).construct()
    idx = np.random.default_rng(3).permutation(N)[:700]
    sub = ds.subset(idx)
    assert sub._binned_rows is not None          # nothing gathered yet
    expect = ds.binned[np.sort(idx)]
    np.testing.assert_array_equal(sub.binned, expect)
    assert sub._binned_rows is None and sub.binned is sub.binned
    np.testing.assert_array_equal(sub.get_label(), y[np.sort(idx)])
    # a subset of a subset, and a matrix set by hand, read as before
    inner = sub.subset(np.arange(0, 700, 7))
    np.testing.assert_array_equal(inner.binned, expect[::7])
    # rows given sorted are kept as given, and a copy of them: the caller's
    # array may change after
    rows = np.sort(idx)
    kept = ds.subset(rows)
    rows[:] = 0
    np.testing.assert_array_equal(kept.binned, expect)
    fresh = ds.subset(idx)
    fresh.binned = expect[:, :2]
    assert fresh._binned_rows is None
    np.testing.assert_array_equal(fresh.binned, expect[:, :2])
    np.testing.assert_array_equal(fresh.feature_binned(), expect[:, :2])


def test_a_job_after_a_one_fold_warm_up_compiles_nothing_and_keeps_nothing():
    from lightgbm_tpu.utils.compile_cache import (compile_stats,
                                                  install_compile_counters)
    install_compile_counters()
    x, y = table("dense")
    params = dict(BASE, telemetry=False)
    ds = lgb.Dataset(x, label=y, params=params).construct()
    folds = folds_of()
    # the benchmark's warm-up: one fold of each size (all five are alike)
    lgb.cv(params, ds, num_boost_round=2, folds=folds[:1])
    before = compile_stats()["count"]
    out = lgb.cv(params, ds, num_boost_round=2, folds=folds,
                 return_cvbooster=True)
    assert compile_stats()["count"] == before
    boosters = out["cvbooster"].boosters
    # the call's placement is gone: no Dataset points at it, and no array
    # of the whole matrix's shape is alive
    for bst in boosters:
        held = bst._model.valid_sets[0][0]
        assert bst.train_set._fold_rows is None and held._fold_rows is None
    assert ds._fold_rows is None
    gc.collect()
    assert not [a for a in jax.live_arrays() if a.shape == ds.binned.shape]


def test_a_jobs_transfers_are_one_matrix_and_the_row_state():
    ds, boosters, _ = run_cv("dense", {})
    for fold, bst in enumerate(boosters):
        model, snap = bst._model, bst.telemetry_snapshot()
        held = model.valid_sets[0]
        state = [model.score, model.objective.label, held[2]]
        rows = model.num_data + held[1].shape[0]       # the row numbers
        # and the held-out AUC's class codes, one byte a held-out row
        assert snap["xfer.h2d_bytes"]["value"] == 4 * rows + sum(
            int(a.nbytes) for a in state) + held[2].shape[0] \
            + (int(ds.binned.nbytes) if fold == 0 else 0)
        # the placement is a span of the first fold's set-up, under it
        spans = {e["id"]: e for e in model._obs.tracer.events
                 if e["ph"] == "X"}
        parent = [e for e in spans.values()
                  if e["name"] == "lgbtpu.booster.to_device"
                  and e["args"]["what"] == "parent"]
        assert len(parent) == (1 if fold == 0 else 0)
        for e in parent:
            up = e
            while up.get("parent") is not None:
                up = spans[up["parent"]]
            assert up["name"] == "lgbtpu.cv.fold_setup"
            setup = snap["train.setup_seconds{stage=fold_setup}"]
            assert setup["count"] == 1
            assert setup["sum"] * 1e6 >= e["dur"]
