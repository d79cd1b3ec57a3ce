"""split_batch (K-way super-step grower, grower.py grow_tree_batched).

The batched grower splits the top-K leaves per step and builds all K child
histograms in one C=3K one-hot contraction (the histogram
matmul is sublane-bound at M=3, so batching is the only way past that
ceiling).  K=1 keeps exact strict leaf-wise reference semantics; K>1 is a
best-first variant between LightGBM's leaf-wise and XGBoost's depth-wise
growth.  These tests pin: model validity, near-parity of quality, exact
fused==per-iteration equality, and serial==distributed agreement.
"""

import pytest

pytestmark = pytest.mark.slow   # exhaustive sweep tier (docs/Testing.md)


import numpy as np
from sklearn.metrics import roc_auc_score

import lightgbm_tpu as lgb


def _assert_same_model(bst_a, bst_b):
    assert len(bst_a.trees) == len(bst_b.trees)
    for ts, td in zip(bst_a.trees, bst_b.trees):
        np.testing.assert_array_equal(ts.split_feature, td.split_feature)
        np.testing.assert_array_equal(ts.left_child, td.left_child)
        np.testing.assert_allclose(ts.leaf_value, td.leaf_value,
                                   rtol=1e-4, atol=1e-6)


def _params(sb, **kw):
    p = {"objective": "binary", "num_leaves": 31, "min_data_in_leaf": 5,
         "learning_rate": 0.1, "verbose": -1, "split_batch": sb,
         "tpu_learner": "masked", "fused_chunk": 0}
    p.update(kw)
    return p


@pytest.fixture(scope="module")
def data():
    rs = np.random.RandomState(7)
    n, f = 4000, 20
    x = rs.randn(n, f)
    x[rs.rand(n, f) < 0.05] = np.nan
    logit = (np.nan_to_num(x[:, 0]) * 1.5 - np.nan_to_num(x[:, 1])
             + 0.5 * np.nan_to_num(x[:, 2] * x[:, 3]) + 0.3 * rs.randn(n))
    y = (logit > 0).astype(np.float32)
    return x, y


def _train(x, y, params, rounds=20, max_bin=63):
    ds = lgb.Dataset(x, label=y, params={"max_bin": max_bin})
    return lgb.train(params, ds, num_boost_round=rounds)


def test_batched_auc_near_strict(data):
    """K>1 changes growth order, not model quality."""
    x, y = data
    auc = {}
    for sb in (1, 4, 8):
        bst = _train(x, y, _params(sb))
        auc[sb] = roc_auc_score(y, bst.predict(x))
    assert auc[4] > auc[1] - 0.01
    assert auc[8] > auc[1] - 0.02


def test_batched_model_roundtrip(data):
    x, y = data
    bst = _train(x, y, _params(4))
    bst2 = lgb.Booster(model_str=bst.model_to_string())
    np.testing.assert_allclose(bst2.predict(x), bst.predict(x), rtol=1e-6)


def test_batched_fused_equals_per_iter(data):
    """The fused lax.scan chunk path must be bit-identical to the
    per-iteration path under batching (same RNG/semantics)."""
    x, y = data
    b_it = _train(x, y, _params(4))
    b_fu = _train(x, y, _params(4, fused_chunk=10))
    np.testing.assert_array_equal(b_it.predict(x), b_fu.predict(x))


def test_batched_exhausts_splits_like_strict(data):
    """Batched growth must still stop cleanly and FILL up to num_leaves
    when gains allow: the super-step count accounts for the exponential
    ramp-up (step s can split at most min(K, leaves) leaves), so K=8
    cannot silently cap a 15-leaf tree at 2 steps = 3 nodes."""
    x, y = data
    bst = _train(x, y, _params(8, num_leaves=15, min_data_in_leaf=2))
    assert max(t.num_leaves for t in bst.trees) == 15
    for t in bst.trees:
        assert t.num_leaves <= 15
        # children pointers well-formed: every internal node referenced once
        lc, rc = np.asarray(t.left_child), np.asarray(t.right_child)
        nn = t.num_leaves - 1
        refs = [c for c in list(lc[:nn]) + list(rc[:nn]) if c >= 0]
        assert sorted(refs) == list(range(1, nn))


def test_reset_parameter_invalidates_scan_program(data):
    """reset_parameter must retrace the scanned program — the old
    jitted closure has the previous learning rate baked in."""
    x, y = data
    ds = lgb.Dataset(x, label=y, params={"max_bin": 63})
    bst = lgb.train(_params(4, fused_chunk=5), ds, num_boost_round=5)
    bst.reset_parameter({"learning_rate": 0.77})
    bst.update_superepoch(5, 5)  # must NOT reuse the lr=0.1 jitted scan
    shr = {t.shrinkage for t in bst.trees}
    assert 0.77 in shr and 0.1 in shr
    # device score must agree with the host trees' raw predictions
    raw = bst.predict(x, raw_score=True)
    dev = np.asarray(bst._model.train_score())[:, 0]
    np.testing.assert_allclose(raw, dev, rtol=1e-4, atol=1e-5)


def test_batched_feature_fraction_and_goss(data):
    x, y = data
    bst = _train(x, y, _params(4, feature_fraction=0.7,
                               data_sample_strategy="goss",
                               top_rate=0.3, other_rate=0.2))
    assert roc_auc_score(y, bst.predict(x)) > 0.85


def test_batched_efb(data):
    """EFB bundled layout under the batched grower (bundle-column decode in
    the one-pass partition)."""
    x, y = data
    rs = np.random.RandomState(3)
    # append sparse mutually-exclusive features so EFB actually bundles
    extra = np.zeros((x.shape[0], 6))
    for j in range(6):
        rows = rs.choice(x.shape[0], 200, replace=False)
        extra[rows, j] = rs.randn(200)
    xw = np.column_stack([np.nan_to_num(x), extra])
    b1 = _train(xw, y, _params(1, enable_bundle=True))
    b4 = _train(xw, y, _params(4, enable_bundle=True))
    assert roc_auc_score(y, b4.predict(xw)) > \
        roc_auc_score(y, b1.predict(xw)) - 0.02


def test_batched_categorical(data):
    x, y = data
    rs = np.random.RandomState(5)
    xc = np.nan_to_num(x).copy()
    cat = rs.randint(0, 8, x.shape[0]).astype(float)
    y2 = ((cat >= 4) ^ (np.nan_to_num(x[:, 0]) > 0)).astype(np.float32)
    xc[:, 5] = cat
    ds = lgb.Dataset(xc, label=y2, params={"max_bin": 63},
                     categorical_feature=[5])
    bst = lgb.train(_params(4, min_data_per_group=5), ds, num_boost_round=20)
    assert roc_auc_score(y2, bst.predict(xc)) > 0.9


@pytest.mark.skipif(
    __import__("jax").device_count() < 8,
    reason="needs the 8-device virtual mesh")
class TestDistributedBatched:
    def test_data_parallel_matches_serial(self, data):
        x, y = data
        b_s = _train(x, y, _params(2, num_leaves=15), rounds=8)
        p = _params(2, num_leaves=15)
        p.pop("tpu_learner")
        p["tree_learner"] = "data"
        b_d = _train(x, y, p, rounds=8)
        assert b_d._model._dist == "data"
        _assert_same_model(b_s, b_d)
        np.testing.assert_allclose(b_s.predict(x), b_d.predict(x),
                                   rtol=1e-4, atol=1e-6)

    def test_feature_parallel_matches_serial(self, data):
        x, y = data
        b_s = _train(x, y, _params(2, num_leaves=15), rounds=8)
        p = _params(2, num_leaves=15)
        p.pop("tpu_learner")
        p["tree_learner"] = "feature"
        b_f = _train(x, y, p, rounds=8)
        assert b_f._model._dist == "feature"
        _assert_same_model(b_s, b_f)

    def test_auto_split_batch_above_64_leaves(self, data):
        x, y = data
        bst = _train(x, y, _params(0, num_leaves=64,
                                   min_data_in_leaf=2), rounds=3)
        assert bst._model._split_batch == 8
        bst2 = _train(x, y, _params(0, num_leaves=31), rounds=3)
        assert bst2._model._split_batch == 1
