"""Scan parity without a valid set: k train_one_iter calls == one scan of k.

The super-epoch with an empty eval tail (GBDTModel.train_superepoch, which
``lgb.train`` takes when ``fused_chunk`` > 1) must produce byte-identical
model strings to the per-iteration path — same grower, same RNG streams
(feature masks pre-drawn host-side, GOSS keys seeded by iteration index
in-graph).
"""

import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb


def _data(n=1200, f=12, seed=3):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, f).astype(np.float32)
    y = (x[:, 0] - 0.5 * x[:, 1] + 0.3 * rng.randn(n) > 0).astype(np.float32)
    return x, y


def _train(params, x, y, rounds=23):
    ds = lgb.Dataset(x, label=y)
    return lgb.train(dict(params), ds, num_boost_round=rounds)


def _norm(model_str):
    """Model string minus the recorded fused_chunk param (the one line
    that legitimately differs between the two paths)."""
    return "\n".join(l for l in model_str.splitlines()
                     if not l.startswith("[fused_chunk:"))


BASE = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.1,
        "max_bin": 31, "min_data_in_leaf": 5, "verbosity": -1,
        "tpu_learner": "masked"}


@pytest.mark.parametrize("extra", [
    {},
    {"feature_fraction": 0.6},
    {"data_sample_strategy": "goss", "top_rate": 0.3, "other_rate": 0.3},
    {"objective": "regression"},
])
def test_fused_matches_per_iter(extra):
    x, y = _data()
    p_fused = dict(BASE, fused_chunk=10, **extra)
    p_plain = dict(BASE, fused_chunk=0, **extra)
    b_fused = _train(p_fused, x, y)
    b_plain = _train(p_plain, x, y)
    assert len(b_fused.trees) == len(b_plain.trees)
    assert _norm(b_fused.model_to_string()) == _norm(b_plain.model_to_string())
    pred_f = b_fused.predict(x)
    pred_p = b_plain.predict(x)
    np.testing.assert_allclose(pred_f, pred_p, rtol=1e-6)


def test_fused_stump_stops_training():
    # constant labels -> no split possible -> both paths stop with the
    # same single stump tree
    x, _ = _data(400, 6)
    y = np.ones(400, np.float32)
    b_fused = _train(dict(BASE, fused_chunk=8, objective="regression"),
                     x, y, rounds=16)
    b_plain = _train(dict(BASE, fused_chunk=0, objective="regression"),
                     x, y, rounds=16)
    assert len(b_fused.trees) == len(b_plain.trees)
    assert _norm(b_fused.model_to_string()) == _norm(b_plain.model_to_string())


def test_fused_mid_chunk_stump_parity():
    # feature_fraction can draw an unsplittable mask mid-chunk (stump);
    # per-iter semantics stop training THERE.  The fused scan must not let
    # later iterations (whose masks could split) leak deltas into the
    # score (code-review r3 finding: dead-flag in the scan carry).
    rng = np.random.RandomState(0)
    n = 2000
    x = np.column_stack([rng.randn(n), rng.randn(n)]).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    p = dict(BASE, num_leaves=7, feature_fraction=0.5,
             min_gain_to_split=50.0, min_data_in_leaf=5)
    b_fused = _train(dict(p, fused_chunk=10), x, y, rounds=20)
    b_plain = _train(dict(p, fused_chunk=0), x, y, rounds=20)
    # the uninformative feature's mask must have produced a stump early
    assert len(b_plain.trees) < 20, \
        "test setup: expected an early stump under feature_fraction"
    assert len(b_fused.trees) == len(b_plain.trees)
    assert _norm(b_fused.model_to_string()) == _norm(b_plain.model_to_string())
    np.testing.assert_allclose(
        np.asarray(b_fused._model.train_score()),
        np.asarray(b_plain._model.train_score()), rtol=1e-6)


def test_fused_respects_remainder():
    # rounds not divisible by the epoch: the remainder is a shorter scan,
    # total tree count must still be exact
    x, y = _data()
    b = _train(dict(BASE, fused_chunk=10), x, y, rounds=17)
    assert len(b.trees) == 17


def test_fused_bagging_parity():
    # bagging masks are drawn IN-GRAPH keyed by the refresh epoch
    # (gbdt.cpp:230-264 analog), so bagging configs scan and the scan
    # reproduces the per-iteration models exactly
    x, y = _data()
    p = dict(BASE, bagging_freq=2, bagging_fraction=0.7)
    b_fused = _train(dict(p, fused_chunk=6), x, y, rounds=12)
    b_plain = _train(dict(p, fused_chunk=0), x, y, rounds=12)
    assert b_fused._model.supports_fused()
    assert len(b_fused.trees) == 12
    assert _norm(b_fused.model_to_string()) == _norm(b_plain.model_to_string())
    np.testing.assert_allclose(
        np.asarray(b_fused._model.train_score()),
        np.asarray(b_plain._model.train_score()), rtol=1e-6)


def test_fused_pos_neg_bagging_parity():
    # pos/neg bagging (binary objective) routes through the same in-graph
    # draw with the device label vector
    x, y = _data()
    p = dict(BASE, bagging_freq=1, pos_bagging_fraction=0.8,
             neg_bagging_fraction=0.5)
    b_fused = _train(dict(p, fused_chunk=5), x, y, rounds=10)
    b_plain = _train(dict(p, fused_chunk=0), x, y, rounds=10)
    assert b_fused._model.supports_fused()
    assert _norm(b_fused.model_to_string()) == _norm(b_plain.model_to_string())


def test_bagging_mask_refresh_epochs():
    # same mask within a bagging_freq window, different across windows
    x, y = _data()
    p = dict(BASE, bagging_freq=3, bagging_fraction=0.6)
    b = _train(p, x, y, rounds=1)
    m = b._model
    w0 = np.asarray(m._bagging_w(jnp.int32(0)))
    w2 = np.asarray(m._bagging_w(jnp.int32(2)))
    w3 = np.asarray(m._bagging_w(jnp.int32(3)))
    np.testing.assert_array_equal(w0, w2)
    assert (w0 != w3).any()
    frac = w0.mean()
    assert 0.5 < frac < 0.7
