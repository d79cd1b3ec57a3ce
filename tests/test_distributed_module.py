"""User-facing cluster orchestration e2e (VERDICT r3 task 9): >= 2 REAL
coordinated processes spawned THROUGH ``lightgbm_tpu.distributed.run``
(the dask.py:393-810 _train analog: port allocation, machines parameter,
one trainer per worker), each training via ``distributed.train`` with
row sharding + distributed binning + data-parallel growth, then the
replicated model must agree across ranks and match single-process
training quality."""

import os

import numpy as np
import pytest

from lightgbm_tpu import distributed

HERE = os.path.dirname(os.path.abspath(__file__))
PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
          "min_data_in_leaf": 5, "verbosity": -1}
ROUNDS = 8


def test_run_spawns_coordinated_workers():
    results = distributed.run(
        "dist_worker:worker", num_workers=2,
        args={"params": PARAMS, "rounds": ROUNDS, "weighted": True},
        extra_pythonpath=[HERE], timeout=420)
    assert [r["rank"] for r in results] == [0, 1]
    # the machines parameter followed the reference conventions
    assert results[0]["machines"].count(",") == 1
    assert all(m.startswith("127.0.0.1:")
               for m in results[0]["machines"].split(","))
    # replicated model: byte-identical across ranks
    assert results[0]["model"] == results[1]["model"]
    np.testing.assert_allclose(results[0]["pred_head"],
                               results[1]["pred_head"], rtol=1e-6)

    # quality sanity vs a single-process run on the same global data
    from dist_worker import _global_data
    import sys
    sys.path.insert(0, HERE)
    import lightgbm_tpu as lgb
    from lightgbm_tpu.metrics import _auc
    x, y = _global_data()
    bst = lgb.train(dict(PARAMS), lgb.Dataset(x, label=y),
                    num_boost_round=ROUNDS)
    auc_single = _auc(y, bst.predict(x, raw_score=True), None)

    from lightgbm_tpu.booster import Booster
    dist_bst = Booster(model_str=results[0]["model"])
    auc_dist = _auc(y, dist_bst.predict(x, raw_score=True), None)
    assert auc_dist > 0.9
    assert abs(auc_single - auc_dist) < 0.05


def test_multi_host_emits_commands():
    with pytest.raises(SystemExit) as ei:
        distributed.run("dist_worker:worker", hosts=["10.0.0.1", "10.0.0.2"])
    msg = str(ei.value)
    assert "-m lightgbm_tpu.distributed" in msg
    assert "--machines 10.0.0.1:12400,10.0.0.2:12400" in msg


@pytest.mark.parametrize("hosts", [None, ["127.0.0.1", "localhost"]])
def test_local_workers_refused_unless_pinned_to_cpu(hosts):
    """N local processes that leave device selection to JAX would each
    claim every chip of the host: refused before anything is spawned."""
    with pytest.raises(ValueError, match="a chip belongs to one process"):
        distributed.run("dist_worker:worker", num_workers=2, hosts=hosts,
                        backend="")


ESTIMATOR_PARAMS = dict(num_leaves=15, max_bin=63, min_data_in_leaf=5,
                        n_estimators=8, verbosity=-1)


def test_estimator_classifier_prepartitioned():
    """Estimator-level distributed API (VERDICT r4 task 9, the
    dask.py:1092-1417 DaskLGBMClassifier analog): fit on PRE-PARTITIONED
    per-worker data — one part per worker, never concatenated on any
    host — over 2 real coordinated processes; the fitted estimator then
    predicts locally and matches single-process quality."""
    rng = np.random.RandomState(6)
    n, f = 4000, 10
    x = rng.randn(n, f)
    y = np.where(x[:, 0] - 0.7 * x[:, 1] > 0, "pos", "neg")

    parts_x = [x[:n // 2], x[n // 2:]]
    parts_y = [y[:n // 2], y[n // 2:]]
    clf = distributed.DistributedLGBMClassifier(
        n_workers=2, timeout=420, **ESTIMATOR_PARAMS)
    # eval_set carries the RAW (string) labels — they must go through
    # the fitted class encoding, not a float cast
    clf.fit(parts_x, parts_y, eval_set=[(x[:400], y[:400])])

    assert "valid_0" in clf.evals_result_
    assert list(clf.classes_) == ["neg", "pos"]
    assert clf.n_features_ == f
    pred = clf.predict(x)
    acc = (pred == y).mean()
    assert acc > 0.93, acc
    proba = clf.predict_proba(x)
    assert proba.shape == (n, 2)

    # single-process reference point: same params, plain sklearn API
    from lightgbm_tpu.sklearn import LGBMClassifier
    ref = LGBMClassifier(**ESTIMATOR_PARAMS).fit(x, (y == "pos"))
    acc_ref = (ref.predict(x) == (y == "pos")).mean()
    assert abs(acc - acc_ref) < 0.03

    # to_local: the plain estimator carries the fitted model
    local = clf.to_local()
    assert type(local) is LGBMClassifier
    np.testing.assert_array_equal(local.predict(x), pred)


def test_estimator_regressor_global_with_eval():
    """Global-array input is partitioned for the caller; eval_set is
    replicated per worker and the metric history comes back."""
    rng = np.random.RandomState(7)
    x = rng.randn(3000, 8)
    y = 2.0 * x[:, 0] - x[:, 1] + 0.1 * rng.randn(3000)
    reg = distributed.DistributedLGBMRegressor(
        n_workers=2, timeout=420, **ESTIMATOR_PARAMS)
    reg.fit(x, y, eval_set=[(x[:500], y[:500])], eval_names=["held"])
    assert "held" in reg.evals_result_
    assert len(reg.evals_result_["held"]["l2"]) == 8
    r2 = 1.0 - np.mean((reg.predict(x) - y) ** 2) / np.var(y)
    assert r2 > 0.7, r2  # 8 rounds at lr 0.1 — fit quality, not convergence


def test_estimator_ranker_group_aligned():
    """Ranker partitioning respects query-group boundaries (dask requires
    group-aligned partitions the same way)."""
    rng = np.random.RandomState(8)
    n_q, qsize, f = 60, 25, 6
    n = n_q * qsize
    x = rng.randn(n, f)
    rel = (x[:, 0] + 0.3 * rng.randn(n) > 0.5).astype(np.float32)
    group = np.full(n_q, qsize)
    rk = distributed.DistributedLGBMRanker(
        n_workers=2, timeout=420, **ESTIMATOR_PARAMS)
    rk.fit(x, rel, group=group)
    s = rk.predict(x)
    # ranking signal present: relevant rows score higher on average
    assert s[rel > 0].mean() > s[rel == 0].mean() + 0.5


def test_estimator_rejects_feature_parallel():
    clf = distributed.DistributedLGBMClassifier(
        n_workers=2, tree_learner="feature")
    with pytest.raises(ValueError, match="tree_learner=feature"):
        clf.fit(np.zeros((10, 2)), np.zeros(10))


def test_estimator_sparse_input():
    """scipy-sparse global input rides the estimator layer row-sliced
    (never densified on the host), reaching the Dataset's native
    CSR/CSC binning — the wide-sparse path the k-hot storage exists
    for."""
    import scipy.sparse as sp
    rng = np.random.RandomState(9)
    n, f = 3000, 40
    dense = rng.randn(n, f) * (rng.rand(n, f) < 0.1)
    dense[:, 0] = rng.randn(n)                    # informative + dense
    y = (dense[:, 0] > 0).astype(np.float32)
    x = sp.csr_matrix(dense)
    clf = distributed.DistributedLGBMClassifier(
        n_workers=2, timeout=420, **ESTIMATOR_PARAMS)
    clf.fit(x, y)
    acc = (clf.predict(dense) == y).mean()
    assert acc > 0.9, acc
