"""The held-out evaluation where it runs: the ``eval`` span split into its
two parts, ``eval.fetch`` (the score's transfer to the host, or the wait for
the device's AUC) and ``eval.metric`` (the host metric), the bytes every
host fetch brings back (``xfer.d2h_bytes{site=}``), and the AUC ranked on
the device (``metrics.auc_rank_parts``): the host's ``_auc`` to the bit,
and the route each AUC took (``eval.auc_route{route=}``)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.metrics import (_auc, _auc_block, auc_classes,
                                  auc_from_parts, auc_rank_parts)
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


def _cv(telemetry=True, weight=None, **extra):
    """A two-fold, two-round ``lgb.cv``: its curve and the folds'
    boosters."""
    x, y = _data()
    params = dict(PARAMS, telemetry=telemetry, **extra)
    out = lgb.cv(params, lgb.Dataset(x, label=y, weight=weight,
                                     params=params),
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


def _parts_bytes(rows):
    """What ``auc_rank_parts`` sends the host over a ``rows``-row score:
    an int32 a block, and the two int32 class counts."""
    parts = jax.eval_shape(auc_rank_parts,
                           jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                           jax.ShapeDtypeStruct((rows,), jnp.int8))
    padded = -(-rows // 1024) * 1024
    assert parts[0].size == padded // _auc_block(padded)
    return sum(p.size * p.dtype.itemsize for p in parts)


def test_d2h_bytes_of_the_held_out_scores_are_exact(traced):
    """The held-out AUC is ranked on the device: its parts, over the row
    bucket's padding too, once an evaluation, and no score."""
    boosters, _ = traced
    held_out = ROWS // FOLDS
    for bst in boosters:
        snap = bst.telemetry_snapshot()
        assert snap["xfer.d2h_bytes{site=eval}"]["value"] \
            == _parts_bytes(bucket_rows(held_out, min_bucket=256)) * ROUNDS
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
        # the held-out AUC's parts, then the training rows' scores, whose
        # AUC the host ranks
        assert snap["xfer.d2h_bytes{site=eval}"]["value"] \
            == _parts_bytes(bucket_rows(ROWS // FOLDS, min_bucket=256)) \
            * ROUNDS + ROWS // FOLDS * 4
        assert snap["eval.auc_route{route=host}"]["value"] == 1


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


# -- the AUC ranked on the device ------------------------------------------

def _scores(kind, n, rs):
    if kind == "leaves":                # a handful of leaf values: ties
        return rs.randn(7).astype(np.float32)[rs.randint(0, 7, n)]
    if kind == "zeros":                 # -0.0 and 0.0 are one tie
        return rs.choice(np.array([-0.0, 0.0, 0.5, -0.5], np.float32), n)
    if kind == "nans":                  # every NaN is one tie, the last
        s = rs.choice(np.array([np.nan, 1.0, -1.0, 0.0], np.float32), n)
        s[:n // 3] = rs.randn(n // 3)
        return s
    return rs.randn(n).astype(np.float32)


# (scores, real rows, padded rows, labels)
AUC_CASES = {
    "ties": ("leaves", 5000, 5000, "binary"),
    "continuous": ("randn", 5000, 5000, "binary"),
    "bucket_padding": ("leaves", 80_000, 131_072, "binary"),
    "rows_not_a_multiple_of_the_block": ("randn", 3000, 3001, "binary"),
    "a_smaller_block": ("leaves", 1_100_000, 1_100_000, "binary"),
    "signed_zeros": ("zeros", 4000, 4096, "binary"),
    "nans": ("nans", 4000, 4000, "binary"),
    "single_class_positive": ("randn", 300, 512, "ones"),
    "single_class_negative": ("leaves", 300, 300, "zeros"),
    "real_valued_labels": ("leaves", 2000, 2048, "real"),
    "one_row": ("randn", 1, 1, "binary"),
    "two_rows": ("randn", 2, 2, "binary"),
}


@pytest.mark.parametrize("case", list(AUC_CASES))
def test_auc_rank_parts_is_the_hosts_auc_to_the_bit(case):
    """``auc_rank_parts`` then ``auc_from_parts`` give ``_auc``'s float64
    value itself, compared with ``==``; pad rows (class 0) carry scores of
    their own and change nothing."""
    kind, n, rows, labels = AUC_CASES[case]
    rs = np.random.RandomState(len(case))
    score = _scores(kind, n, rs)
    y = {"binary": lambda: (rs.rand(n) < 0.4).astype(np.float32),
         "ones": lambda: np.ones(n, np.float32),
         "zeros": lambda: np.zeros(n, np.float32),
         "real": lambda: rs.choice(np.array([-1.0, 0.0, 0.25, 2.0],
                                            np.float32), n)}[labels]()
    padded = np.concatenate([score, rs.randn(rows - n).astype(np.float32)])
    classes = np.zeros(rows, np.int8)
    classes[:n] = auc_classes(y)
    if case == "a_smaller_block":
        assert _auc_block(rows) < 1024
    if case == "rows_not_a_multiple_of_the_block":
        assert rows % _auc_block(rows)
    parts = jax.device_get(auc_rank_parts(jnp.asarray(padded)[:, None],
                                          jnp.asarray(classes)))
    got, want = auc_from_parts(*parts), _auc(y, score, None)
    assert got == want
    if case.startswith("single_class"):
        assert got == 1.0


@pytest.mark.parametrize("learner", ["serial", "feature"])
def test_unit_weights_take_the_host_and_give_the_devices_curve(learner):
    """Unit weights given explicitly keep the host's ``_auc``; no weights
    rank on the device: the same ``valid auc-mean`` and ``-stdv`` to the
    bit, on one device and on the virtual mesh of four."""
    extra = {"tree_learner": "feature", "num_machines": 4} \
        if learner == "feature" else {}
    on_device, curve = _cv(**extra)
    on_host, host_curve = _cv(weight=np.ones(ROWS), **extra)
    assert json.dumps(curve, sort_keys=True) \
        == json.dumps(host_curve, sort_keys=True)
    rows = bucket_rows(ROWS // FOLDS, min_bucket=256)
    for bst in on_device:
        snap = bst.telemetry_snapshot()
        assert snap["eval.auc_route{route=device}"]["value"] == ROUNDS
        assert "eval.auc_route{route=host}" not in snap
        assert snap["xfer.d2h_bytes{site=eval}"]["value"] \
            == _parts_bytes(rows) * ROUNDS
    for bst in on_host:
        snap = bst.telemetry_snapshot()
        assert snap["eval.auc_route{route=host}"]["value"] == ROUNDS
        assert "eval.auc_route{route=device}" not in snap
        assert snap["xfer.d2h_bytes{site=eval}"]["value"] \
            == rows * 4 * ROUNDS


HOST_ROUTES = {
    "weighted": {"weight": np.random.RandomState(5).rand(ROWS) + 0.5},
    "rf": {"boosting": "rf", "bagging_freq": 1, "bagging_fraction": 0.7},
}


@pytest.mark.parametrize("case", list(HOST_ROUTES))
def test_weighted_and_rf_sets_rank_on_the_host(case):
    boosters, curve = _cv(**HOST_ROUTES[case])
    assert len(curve["valid auc-mean"]) == ROUNDS
    for bst in boosters:
        assert all(m.classes is None for m in bst._valid_metrics[0])
        snap = bst.telemetry_snapshot()
        assert snap["eval.auc_route{route=host}"]["value"] == ROUNDS
        assert "eval.auc_route{route=device}" not in snap
