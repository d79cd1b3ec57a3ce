"""Aux subsystem tests: logging, dump_model, refit, pred early stop
(test_utilities.py / SURVEY.md §5 analog)."""

import json

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.utils import Log, register_log_callback


class TestChooseParamValue:
    """ADVICE r5 #4: the canonical key wins by PRESENCE — an explicitly
    set None must not be overridden by an alias (the reference returns
    immediately when main_param_name is in params)."""

    def test_explicit_none_canonical_beats_alias(self):
        from lightgbm_tpu.basic import _choose_param_value
        out = _choose_param_value(
            "num_iterations",
            {"num_iterations": None, "n_estimators": 77}, 100)
        assert out["num_iterations"] is None
        assert "n_estimators" not in out

    def test_alias_wins_over_default(self):
        from lightgbm_tpu.basic import _choose_param_value
        out = _choose_param_value("num_iterations",
                                  {"n_estimators": 77}, 100)
        assert out["num_iterations"] == 77
        assert "n_estimators" not in out

    def test_canonical_value_wins_over_alias(self):
        from lightgbm_tpu.basic import _choose_param_value
        out = _choose_param_value(
            "num_iterations",
            {"num_iterations": 5, "n_estimators": 77}, 100)
        assert out["num_iterations"] == 5

    def test_default_when_absent(self):
        from lightgbm_tpu.basic import _choose_param_value
        out = _choose_param_value("num_iterations", {"max_bin": 3}, 100)
        assert out["num_iterations"] == 100
        assert out["max_bin"] == 3


class TestLog:
    def test_callback_sink(self):
        msgs = []
        register_log_callback(lambda m: msgs.append(m))
        # the level is process-global and driven by Config verbosity
        # (reference semantics) — pin it for the assertion
        old = Log.level
        Log.level = 1
        try:
            Log.info("hello")
            Log.warning("warn")
            assert any("hello" in m for m in msgs)
            assert any("warn" in m for m in msgs)
        finally:
            Log.level = old
            register_log_callback(None)

    def test_fatal_raises(self):
        with pytest.raises(RuntimeError):
            Log.fatal("boom")


class TestDumpModel:
    def test_json_dump(self, binary_data):
        x, y = binary_data
        p = {"objective": "binary", "num_leaves": 7, "max_bin": 31}
        bst = lgb.train(p, lgb.Dataset(x, label=y), num_boost_round=3)
        d = bst.dump_model()
        s = json.dumps(d)  # must be JSON-serializable
        assert d["num_class"] == 1
        assert len(d["tree_info"]) == 3
        t0 = d["tree_info"][0]["tree_structure"]
        assert "split_feature" in t0
        assert "left_child" in t0

    def test_pred_early_stop(self, binary_data):
        x, y = binary_data
        p = {"objective": "binary", "num_leaves": 15, "max_bin": 63}
        bst = lgb.train(p, lgb.Dataset(x, label=y), num_boost_round=30)
        full = bst.predict(x[:200], raw_score=True)
        es = bst.predict(x[:200], raw_score=True, pred_early_stop=True,
                         pred_early_stop_freq=5, pred_early_stop_margin=2.0)
        # early-stopped rows keep the same SIGN (classification unchanged)
        assert ((full > 0) == (es > 0)).mean() > 0.98


class TestRefit:
    def test_refit_api(self, binary_data):
        x, y = binary_data
        p = {"objective": "binary", "num_leaves": 7, "max_bin": 31}
        bst = lgb.train(p, lgb.Dataset(x, label=y), num_boost_round=5)
        refitted = bst.refit(x, y, decay_rate=0.5)
        assert refitted.num_trees() == bst.num_trees()
        from lightgbm_tpu.metrics import _auc
        assert _auc(y, refitted.predict(x, raw_score=True), None) > 0.9


class TestSnapshot:
    def test_snapshot_freq(self, binary_data, tmp_path):
        x, y = binary_data
        out = str(tmp_path / "m.txt")
        p = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
             "snapshot_freq": 2, "output_model": out}
        lgb.train(p, lgb.Dataset(x, label=y), num_boost_round=4)
        import os
        assert os.path.exists(out + ".snapshot_iter_2")
        assert os.path.exists(out + ".snapshot_iter_4")
        snap = lgb.Booster(model_file=out + ".snapshot_iter_2")
        assert snap.num_trees() == 2
