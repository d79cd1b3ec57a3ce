"""Super-epoch training parity (GBDTModel.train_superepoch).

The whole-run on-device path — ``lax.scan`` over k FULL boosting
iterations with in-scan valid scoring, traced eval and the early-stop
vote, ONE host fetch per epoch — must be byte-identical to the
per-iteration path: same trees, same ``best_iteration``, same
``record_evals`` values (the per-iteration twin evaluates through the
SAME jitted program via ``fused_eval=true`` — metrics.build_traced_eval;
the host f64 metrics are a different contract by construction).
"""

import glob
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.models.gbdt import GBDTModel

BASE = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.1,
        "max_bin": 31, "min_data_in_leaf": 5, "verbosity": -1,
        "tpu_learner": "masked", "metric": ["binary_logloss", "auc"]}

# param lines that legitimately differ between the two paths' saved
# parameter sections (the trees must still match byte-for-byte)
_PATH_PARAMS = ("[superepoch:", "[fused_eval:", "[fused_chunk:")


def _norm(model_str):
    return "\n".join(l for l in model_str.splitlines()
                     if not l.startswith(_PATH_PARAMS))


def _data(n=2400, f=12, seed=7, n_class=1):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, f).astype(np.float32)
    raw = x[:, 0] - 0.5 * x[:, 1] + 0.4 * x[:, 2] * x[:, 3] \
        + 0.3 * rng.randn(n)
    if n_class > 1:
        y = (np.digitize(raw, np.quantile(raw, [0.33, 0.66]))
             .astype(np.float32))
    elif BASE["objective"] == "binary":
        y = (raw > 0).astype(np.float32)
    else:
        y = raw.astype(np.float32)
    return x, y


def _run(params, rounds=40, n_valid=1, es=5, seed=7, n_class=1,
         binary=True):
    """Train with ``n_valid`` valid sets and (optionally) an
    early-stopping callback; returns (booster, record, n_superepochs)."""
    calls = [0]
    orig = GBDTModel.train_superepoch

    def spy(self, *a, **k):
        calls[0] += 1
        return orig(self, *a, **k)

    x, y = _data(seed=seed, n_class=n_class)
    if not binary:
        y = (x[:, 0] - 0.5 * x[:, 1] + 0.3
             * np.random.RandomState(seed).randn(len(y))).astype(
                 np.float32)
    dtr = lgb.Dataset(x[:1600], label=y[:1600])
    vs, vn = [], []
    for vi in range(n_valid):
        lo = 1600 + 400 * vi
        vs.append(lgb.Dataset(x[lo:lo + 400], label=y[lo:lo + 400],
                              reference=dtr))
        vn.append(f"v{vi}")
    rec = {}
    # always present: a replay-safe callback, which the plan must admit
    cbs = [lgb.record_evaluation(rec)]
    if es and n_valid:
        cbs.append(lgb.early_stopping(es, verbose=False))
    GBDTModel.train_superepoch = spy
    try:
        bst = lgb.train(dict(params), dtr, num_boost_round=rounds,
                        valid_sets=vs, valid_names=vn, callbacks=cbs)
    finally:
        GBDTModel.train_superepoch = orig
    return bst, rec, calls[0]


def _assert_identical(pa, pb, **kw):
    ba, ra, na = _run(pa, **kw)
    bb, rb, nb = _run(pb, **kw)
    assert nb == 0, "reference run must not take the super-epoch path"
    assert ba.best_iteration == bb.best_iteration
    assert ra == rb                       # exact float equality, nested
    assert _norm(ba.model_to_string()) == _norm(bb.model_to_string())
    assert ba.best_score == bb.best_score
    return na


MATRIX = {
    "binary_es": ({}, dict(es=5, n_valid=1)),
    "binary_no_es": ({}, dict(es=0, n_valid=1)),
    "binary_two_valids": ({}, dict(es=5, n_valid=2)),
    "binary_quant_int8": ({"quant_train": True, "quant_bits": 8},
                          dict(es=0, n_valid=1)),
    "binary_bagging": ({"bagging_freq": 2, "bagging_fraction": 0.7},
                       dict(es=5, n_valid=1)),
    "regression_es": ({"objective": "regression", "metric": ["l2"]},
                      dict(es=5, n_valid=1, binary=False)),
    "regression_l1_rmse": ({"objective": "regression",
                            "metric": ["rmse", "l1"]},
                           dict(es=0, n_valid=1, binary=False)),
}


@pytest.mark.parametrize("name", list(MATRIX))
def test_superepoch_byte_identity(name):
    extra, kw = MATRIX[name]
    pa = dict(BASE, fused_chunk=8, **extra)
    pb = dict(BASE, fused_chunk=8, superepoch=-1, fused_eval="true",
              **extra)
    n_epochs = _assert_identical(pa, pb, **kw)
    assert n_epochs >= 1, "super-epoch path must actually engage"


def test_superepoch_explicit_k():
    # explicit superepoch overrides the auto (fused_chunk / ES) sizing
    pa = dict(BASE, fused_chunk=0, superepoch=16)
    pb = dict(BASE, fused_chunk=0, superepoch=-1, fused_eval="true")
    n_epochs = _assert_identical(pa, pb, es=0, n_valid=1, rounds=32)
    assert n_epochs == 2


def test_superepoch_no_valid_sets():
    # no valid sets + a replayable callback: epochs run with an empty
    # eval_spec
    pa = dict(BASE, fused_chunk=8)
    pb = dict(BASE, fused_chunk=0, superepoch=-1)
    ba, _, na = _run(pa, es=0, n_valid=0, rounds=24)
    bb, _, nb = _run(pb, es=0, n_valid=0, rounds=24)
    assert na >= 1 and nb == 0
    assert _norm(ba.model_to_string()) == _norm(bb.model_to_string())


def test_superepoch_multiclass_falls_back():
    # num_class > 1 is unfusable: the plan must decline (fused_reasons
    # names the blocker) and the per-iteration fallback still matches
    # a plain per-iteration run exactly
    extra = {"objective": "multiclass", "num_class": 3,
             "metric": ["multi_logloss"]}
    pa = dict(BASE, fused_chunk=8, **extra)
    pb = dict(BASE, fused_chunk=0, superepoch=-1, **extra)
    ba, ra, na = _run(pa, es=5, n_valid=1, rounds=20, n_class=3)
    bb, rb, nb = _run(pb, es=5, n_valid=1, rounds=20, n_class=3)
    assert na == 0 and nb == 0
    assert ba.best_iteration == bb.best_iteration
    assert ra == rb
    assert _norm(ba.model_to_string()) == _norm(bb.model_to_string())


def test_superepoch_one_sync_per_epoch():
    # the acceptance pin: with a valid set AND early stopping active,
    # a super-epoch issues exactly ONE jax.device_get per epoch (the
    # fused_fetch in GBDTModel._eget) — 32 rounds / k=8 -> 4 epochs,
    # 4 device_gets, nothing else in the training loop syncs
    import jax
    x, y = _data()
    dtr = lgb.Dataset(x[:1600], label=y[:1600])
    dva = lgb.Dataset(x[1600:2000], label=y[1600:2000], reference=dtr)
    # construct up front so binning/bring-up work is outside the count
    dtr.construct()
    dva.construct()
    count = [0]
    orig = jax.device_get

    def counting(v):
        count[0] += 1
        return orig(v)

    p = dict(BASE, fused_chunk=8)
    jax.device_get = counting
    try:
        bst = lgb.train(p, dtr, num_boost_round=32, valid_sets=[dva],
                        valid_names=["va"],
                        callbacks=[lgb.early_stopping(50, verbose=False)])
    finally:
        jax.device_get = orig
    assert len(bst.trees) == 32
    assert count[0] == 4, \
        f"expected 1 host sync per epoch (4 epochs), got {count[0]}"


def test_superepoch_kill_resume_at_epoch_boundary(tmp_path):
    # epoch sizing clips to the snapshot boundary, so a crash+resume at
    # an epoch edge reproduces the straight run byte-for-byte
    out = str(tmp_path / "m.txt")
    p = dict(BASE, fused_chunk=8, snapshot_freq=8, output_model=out)
    x, y = _data()
    dtr = lgb.Dataset(x[:1600], label=y[:1600])
    dva = lgb.Dataset(x[1600:2000], label=y[1600:2000], reference=dtr)

    def mk():
        d = lgb.Dataset(x[:1600], label=y[:1600])
        v = lgb.Dataset(x[1600:2000], label=y[1600:2000], reference=d)
        return d, [v]

    d0, v0 = mk()
    straight = lgb.train(dict(p), d0, num_boost_round=24, valid_sets=v0,
                         valid_names=["va"],
                         callbacks=[lgb.record_evaluation({})])
    s_straight = straight.model_to_string()
    for f in glob.glob(out + "*"):
        os.unlink(f)

    # "crash" after 16 of 24 rounds (two full epochs, snapshot at 16)
    d1, v1 = mk()
    lgb.train(dict(p), d1, num_boost_round=16, valid_sets=v1,
              valid_names=["va"], callbacks=[lgb.record_evaluation({})])
    d2, v2 = mk()
    resumed = lgb.train(dict(p, resume=True), d2, num_boost_round=24,
                        valid_sets=v2, valid_names=["va"],
                        callbacks=[lgb.record_evaluation({})])
    assert resumed.model_to_string() == s_straight


def test_unfusable_superepoch_error_names_blocker():
    # train_superepoch called on an unfusable model raises with the
    # specific blocker (fused_reasons), not a generic message
    x, y = _data()
    p = dict(BASE, objective="multiclass", num_class=3,
             metric=["multi_logloss"], fused_chunk=8)
    ds = lgb.Dataset(x[:1600], label=y[:1600] % 3)
    bst = lgb.train(p, ds, num_boost_round=2,
                    keep_training_booster=True)
    with pytest.raises(ValueError, match="num_class"):
        bst._model.train_superepoch(4, 0)


# -- a run without a valid set is all super-epochs (PR 32) -----------------

NOVALID = dict(BASE, fused_chunk=8, metric="None")


def _train_novalid(seed, params=NOVALID, rounds=8):
    x, y = _data(n=1600, seed=seed)
    return lgb.train(dict(params), lgb.Dataset(x, label=y),
                     num_boost_round=rounds)


def test_train_without_valid_set_compiles_once_for_two_datasets():
    # the scanned program takes the dataset as an argument and is shared
    # process-wide, so a second call on other data of the same shape
    # traces nothing and compiles nothing that the cache does not hold
    from lightgbm_tpu.utils.compile_cache import (compile_stats,
                                                  trace_counts)
    _train_novalid(seed=11)
    traces, comp = trace_counts(), compile_stats()
    assert traces.get("superepoch", 0) >= 1
    bst = _train_novalid(seed=12)
    assert bst.num_trees() == 8
    assert trace_counts() == traces
    after = compile_stats()
    assert after["cache_misses"] == comp["cache_misses"]
    assert after["count"] - comp["count"] \
        == after["cache_hits"] - comp["cache_hits"]


def test_scanned_program_holds_no_dataset_constant(monkeypatch):
    # lower the program lgb.train dispatches and hold every constant of
    # its HLO under the binned matrix's N x F bytes: a dataset baked into
    # the executable makes every call compile anew
    import re

    import jax
    from lightgbm_tpu.models import gbdt
    dispatched = []
    build = GBDTModel._build_superepoch

    def recording_build(self, *a, **k):
        fn = build(self, *a, **k)

        def call(*args):
            dispatched.append((fn, jax.tree.map(
                lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), args)))
            return fn(*args)
        return call
    monkeypatch.setattr(GBDTModel, "_build_superepoch", recording_build)
    monkeypatch.setattr(gbdt, "_SE_CACHE", type(gbdt._SE_CACHE)())
    bst = _train_novalid(seed=13)
    assert len(dispatched) == 1, "lgb.train dispatched no scanned program"
    fn, shapes = dispatched[0]
    n_rows, n_feat = bst._model.binned_dev.shape
    assert (n_rows, n_feat) == (1600, 12)
    text = fn.lower(*shapes).as_text()
    sizes = []          # bytes of every constant: tensor<2x3xui8>, <f32>
    for m in re.finditer(
            r"stablehlo\.constant[^\n]*?: tensor<((?:\d+x)*)[a-z]+(\d+)>",
            text):
        dims = [int(d) for d in m.group(1).split("x")[:-1]]
        sizes.append(int(np.prod(dims)) * max(int(m.group(2)) // 8, 1))
    assert sizes and max(sizes) < n_rows * n_feat, max(sizes)


def test_run_without_valid_set_is_all_superepochs():
    bst = _train_novalid(seed=14, params=dict(NOVALID, telemetry=True),
                         rounds=24)
    snap = bst.telemetry_snapshot()
    assert snap["train.superepochs"]["value"] == 3.0
    assert snap["train.iterations"]["value"] == 24.0
    assert not [key for key in snap if "chunk" in key], snap.keys()
    plain = _train_novalid(seed=14, params=dict(NOVALID, telemetry=True,
                                                superepoch=-1), rounds=24)
    assert "train.superepochs" not in plain.telemetry_snapshot()
    assert _norm(bst.model_to_string()) == _norm(plain.model_to_string())
