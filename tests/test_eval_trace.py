"""The held-out evaluation where it runs: the ``eval`` span split into its
two parts, ``eval.fetch`` (the score's transfer to the host) and
``eval.metric`` (the host metric), the bytes every host fetch brings back
(``xfer.d2h_bytes{site=}``)."""

import json

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.utils.shapes import bucket_rows

PARAMS = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
          "min_data_in_leaf": 5, "verbosity": 0, "fused_chunk": 0,
          "tpu_learner": "masked", "metric": "auc"}
ROUNDS, FOLDS, ROWS = 2, 2, 1200


def _data(n=ROWS, f=8, seed=3):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, f)
    y = (x[:, 0] - 0.5 * x[:, 1] > 0).astype(np.float32)
    return x, y


def _cv(telemetry=True, **extra):
    """A two-fold, two-round ``lgb.cv``: its curve and the folds'
    boosters."""
    x, y = _data()
    params = dict(PARAMS, telemetry=telemetry, **extra)
    out = lgb.cv(params, lgb.Dataset(x, label=y, params=params),
                 num_boost_round=ROUNDS, nfold=FOLDS, stratified=False,
                 return_cvbooster=True)
    return out.pop("cvbooster").boosters, out


@pytest.fixture(scope="module")
def traced():
    return _cv()


def _spans(bst):
    return [e for e in bst._model._obs.tracer.events if e["ph"] == "X"]


def _children(spans, parent):
    return [e for e in spans if e["parent"] == parent["id"]]


def test_every_eval_is_its_fetch_then_its_metric(traced):
    boosters, _ = traced
    for bst in boosters:
        spans = _spans(bst)
        evals = [e for e in spans if e["name"] == "lgbtpu.eval"]
        assert len(evals) == ROUNDS
        for ev in evals:
            kids = _children(spans, ev)
            assert [k["name"] for k in kids] \
                == ["lgbtpu.eval.fetch", "lgbtpu.eval.metric"]
            # inside the parent, one after the other, adding up to no more
            assert all(ev["ts"] <= k["ts"] and k["ts"] + k["dur"]
                       <= ev["ts"] + ev["dur"] + 1e-3 for k in kids)
            assert kids[0]["ts"] + kids[0]["dur"] <= kids[1]["ts"] + 1e-3
            assert sum(k["dur"] for k in kids) <= ev["dur"]
        # the parts are nowhere else
        assert all(by["name"] == "lgbtpu.eval" for e in spans
                   if e["name"].startswith("lgbtpu.eval.")
                   for by in spans if by["id"] == e["parent"])


def test_the_registry_counts_a_fetch_and_a_metric_an_evaluation(traced):
    boosters, _ = traced
    for bst in boosters:
        snap = bst.telemetry_snapshot()
        assert snap["train.eval_seconds"]["count"] == ROUNDS
        for part in ("fetch", "metric"):
            h = snap[f"train.eval_part_seconds{{part={part}}}"]
            assert h["count"] == ROUNDS
        parts = sum(snap[f"train.eval_part_seconds{{part={p}}}"]["sum"]
                    for p in ("fetch", "metric"))
        assert 0 < parts <= snap["train.eval_seconds"]["sum"]


def test_d2h_bytes_of_the_held_out_scores_are_exact(traced):
    """A float32 score a held-out row, with its row bucket's padding, once
    an evaluation."""
    boosters, _ = traced
    held_out = ROWS // FOLDS
    for bst in boosters:
        snap = bst.telemetry_snapshot()
        assert snap["xfer.d2h_bytes{site=eval}"]["value"] \
            == bucket_rows(held_out, min_bucket=256) * 4 * ROUNDS
        # and the tree's fields, once an iteration
        assert snap["xfer.d2h_bytes{site=fetch}"]["value"] > 0
        assert {k for k in snap if k.startswith("xfer.d2h_bytes{")} \
            == {"xfer.d2h_bytes{site=eval}", "xfer.d2h_bytes{site=fetch}"}


def test_the_training_rows_evaluation_is_split_too():
    boosters, _ = _cv(lambda_l2=0.0627)
    for bst in boosters:
        bst.eval_train()
        spans = _spans(bst)
        ev = [e for e in spans if e["name"] == "lgbtpu.eval"][-1]
        assert ev["args"] == {"rows": ROWS // FOLDS}
        assert [k["name"] for k in _children(spans, ev)] \
            == ["lgbtpu.eval.fetch", "lgbtpu.eval.metric"]
        snap = bst.telemetry_snapshot()
        assert snap["xfer.d2h_bytes{site=eval}"]["value"] \
            == (bucket_rows(ROWS // FOLDS, min_bucket=256) * ROUNDS
                + ROWS // FOLDS) * 4


def test_the_traced_metric_has_a_fetch_and_no_host_metric():
    """``fused_eval=true``: the metric runs on the device, and the host's
    part of the evaluation is the fetch of its values."""
    x, y = _data()
    params = dict(PARAMS, telemetry=True, fused_eval="true", superepoch=-1)
    ds = lgb.Dataset(x[:900], label=y[:900], params=params)
    dv = lgb.Dataset(x[900:], label=y[900:], reference=ds)
    bst = lgb.train(params, ds, num_boost_round=ROUNDS, valid_sets=[dv])
    spans = _spans(bst)
    evals = [e for e in spans if e["name"] == "lgbtpu.eval"]
    assert len(evals) == ROUNDS
    for ev in evals:
        assert [k["name"] for k in _children(spans, ev)] \
            == ["lgbtpu.eval.fetch"]
    snap = bst.telemetry_snapshot()
    assert snap["train.eval_part_seconds{part=fetch}"]["count"] == ROUNDS
    assert "train.eval_part_seconds{part=metric}" not in snap
    # one float32 value a metric an evaluation, and no score's fetch
    assert snap["xfer.d2h_bytes{site=traced_eval}"]["value"] == 4 * ROUNDS
    assert "xfer.d2h_bytes{site=eval}" not in snap


def test_a_custom_objectives_fetch_is_not_an_evaluations():
    """``Booster.update(fobj=)`` fetches the training scores outside any
    evaluation: no ``eval`` span and no ``site=eval`` bytes."""
    x, y = _data()
    params = dict(PARAMS, telemetry=True, objective="none", metric="None")

    def logistic(preds, _ds):
        p = 1.0 / (1.0 + np.exp(-preds))
        return p - y, p * (1.0 - p)
    ds = lgb.Dataset(x, label=y, params=params).construct()
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(ROUNDS):
        bst.update(fobj=logistic)
    assert not [e for e in _spans(bst) if e["name"].startswith("lgbtpu.eval")]
    assert {k for k in bst.telemetry_snapshot()
            if k.startswith("xfer.d2h_bytes{")} \
        == {"xfer.d2h_bytes{site=fetch}"}


def test_telemetry_off_has_no_span_no_counter_and_the_same_curve(traced):
    boosters, curve = _cv(telemetry=False)
    for bst in boosters:
        assert bst._model._obs is None
        assert not [k for k in bst.telemetry_snapshot()
                    if k.startswith(("xfer.", "train."))]
    _, traced_curve = traced
    assert json.dumps(curve, sort_keys=True) \
        == json.dumps(traced_curve, sort_keys=True)
