"""Compile-wall management (ROADMAP item 4; docs/Compile-Cache.md):

- shared shape-bucketing policy units (utils/shapes.py);
- persistent-cache placement: JAX_COMPILATION_CACHE_DIR, else the
  compile_cache_dir param, else <checkout>/.jax_cache, the same in every
  process; the persistence thresholds are parameters;
- the leaf-budget bucket: num_leaves 31/40/63 train through ONE padded
  L=64 grower trace with models byte-identical to the unbucketed
  per-shape path, across strict/batched growth and bagging/GOSS;
- compile accounting surfaces through Booster.telemetry_snapshot()
  and the serve /metrics snapshot.

The cross-process pieces (second-process warm start, the retrace-
budget lint subprocess, dp parity) live in tests/test_zretrace.py —
they spawn fresh interpreters and run late in the suite.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.utils import shapes
from lightgbm_tpu.utils.compile_cache import (compile_stats,
                                              enable_persistent_cache,
                                              trace_counts)


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tree_text(model_str: str) -> str:
    """Model text minus the parameters section (which records the
    trace_buckets flag itself and therefore legitimately differs)."""
    return model_str.split("end of parameters", 1)[-1]


def _sweep_params(nl, tb, **over):
    p = {"objective": "binary", "num_leaves": nl, "verbosity": 0,
         "min_data_in_leaf": 5, "max_bin": 15, "tpu_learner": "masked",
         "fused_chunk": 0, "trace_buckets": tb}
    p.update(over)
    return p


@pytest.fixture(scope="module")
def sweep_data():
    rs = np.random.RandomState(7)
    x = rs.randn(700, 10)
    y = (x[:, 0] * 1.5 - x[:, 1] + 0.4 * rs.randn(700) > 0)
    return x, y.astype(np.float32)


def _train_text(x, y, nl, tb, rounds=3, **over):
    p = _sweep_params(nl, tb, **over)
    ds = lgb.Dataset(x, label=y, params=p)
    return _tree_text(lgb.train(p, ds, num_boost_round=rounds)
                      .model_to_string())


class TestShapes:
    def test_round_up_pow2(self):
        assert [shapes.round_up_pow2(v) for v in (1, 2, 3, 17, 64, 65)] \
            == [1, 2, 4, 32, 64, 128]

    def test_bucket_rows_floor_and_cap(self):
        assert shapes.bucket_rows(3) == 16
        assert shapes.bucket_rows(17) == 32
        assert shapes.bucket_rows(300, min_bucket=256) == 512
        assert shapes.bucket_rows(5000, cap=1024) == 1024

    def test_bucket_leaves(self):
        # the headline consolidation: the common 31..63 budgets share
        # one bucket; larger budgets pow2 up
        assert [shapes.bucket_leaves(v) for v in (2, 31, 40, 63, 64)] \
            == [64, 64, 64, 64, 64]
        assert shapes.bucket_leaves(127) == 128
        assert shapes.bucket_leaves(255) == 256

    def test_snap_split_batch(self):
        # ISSUE 15 extended the shipped set to {1, 8, 16, 32, 64}: an
        # off-set request still rounds UP within the set, and values
        # past the widest snap down to it
        assert [shapes.snap_split_batch(v) for v in (0, 1, 2, 4, 8, 9,
                                                     16, 40, 64, 99)] \
            == [0, 1, 8, 8, 8, 16, 16, 64, 64, 64]

    def test_serve_engine_uses_shared_policy(self, sweep_data):
        from lightgbm_tpu.serve.engine import PredictorEngine
        x, y = sweep_data
        p = _sweep_params(7, True)
        ds = lgb.Dataset(x, label=y, params=p)
        bst = lgb.train(p, ds, num_boost_round=2)
        eng = PredictorEngine.from_booster(bst, max_batch=64,
                                           min_bucket=16)
        assert eng._bucket(3) == shapes.bucket_rows(3, 16, 64) == 16
        assert eng._bucket(500) == shapes.bucket_rows(500, 16, 64) == 64


# a fresh interpreter that trains two rounds and reports where jax's
# cache, the tune table and the compile counters ended up
_CHILD_TRAIN = """
import json, sys
import numpy as np
import jax
import lightgbm_tpu as lgb
from lightgbm_tpu.ops.hist_tune import tune_dir
from lightgbm_tpu.utils.compile_cache import compile_stats
rs = np.random.RandomState(0)
x = rs.randn(300, 5)
y = (x[:, 0] > 0).astype(np.float32)
p = {"objective": "binary", "num_leaves": 4, "max_bin": 15, "verbosity": -1,
     "min_data_in_leaf": 5, "compile_cache_min_compile_s": 0.0}
p.update(json.loads(sys.argv[1]))
lgb.train(p, lgb.Dataset(x, label=y, params=p), num_boost_round=2)
print(json.dumps({"cache_dir": jax.config.jax_compilation_cache_dir,
                  "tune_dir": tune_dir(lgb.Config(p)),
                  "stats": compile_stats()}))
"""

_CHILD_DEFAULT = """
import jax
from lightgbm_tpu.utils.compile_cache import enable_persistent_cache
assert enable_persistent_cache() == jax.config.jax_compilation_cache_dir
print(jax.config.jax_compilation_cache_dir)
"""


def _child_env(**over):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **over)
    return env


@pytest.fixture(scope="module")
def cache_children(tmp_path_factory):
    """Three fresh interpreters, started together (each pays seconds of
    imports): one trains with the variable set AND compile_cache_dir
    passed, two only resolve the default from different directories."""
    tmp = tmp_path_factory.mktemp("cache_children")
    env_dir, param_dir = str(tmp / "env"), str(tmp / "param")

    def spawn(script, *args, cwd, **env_over):
        return subprocess.Popen(
            [sys.executable, "-c", script, *args], env=_child_env(**env_over),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=cwd)

    procs = {
        "train": spawn(_CHILD_TRAIN,
                       json.dumps({"compile_cache_dir": param_dir,
                                   "tpu_learner": "masked"}),
                       cwd=str(tmp), JAX_COMPILATION_CACHE_DIR=env_dir),
        "default_tmp": spawn(_CHILD_DEFAULT, cwd=str(tmp)),
        "default_repo": spawn(_CHILD_DEFAULT, cwd=REPO),
    }
    last = {}
    for name, proc in procs.items():
        out, err = proc.communicate(timeout=300)
        assert proc.returncode == 0, (name, err[-2000:])
        last[name] = out.strip().splitlines()[-1]
    return {"env_dir": env_dir, "param_dir": param_dir, **last}


class TestPersistentCacheConfig:
    def test_precedence_env_over_param_over_default(self, tmp_path,
                                                    monkeypatch):
        """JAX_COMPILATION_CACHE_DIR > compile_cache_dir > the checkout's
        .jax_cache; conftest set the variable for the suite."""
        import jax
        from lightgbm_tpu.utils.compile_cache import (default_cache_dir,
                                                      resolve_cache_dir)
        suite_dir = os.environ["JAX_COMPILATION_CACHE_DIR"]
        explicit = str(tmp_path / "explicit")
        try:
            assert enable_persistent_cache(cache_dir=explicit) == suite_dir
            assert jax.config.jax_compilation_cache_dir == suite_dir
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
            assert enable_persistent_cache(
                cache_dir=explicit, min_compile_secs=0.0) == explicit
            assert jax.config.jax_compilation_cache_dir == explicit
            # jax had already opened its cache at the suite's directory:
            # the move must take effect, not only change the config value
            jax.jit(lambda a: a * 3 + 1)(np.arange(7.0))
            assert os.listdir(explicit)
            assert resolve_cache_dir() == default_cache_dir() \
                == os.path.join(REPO, ".jax_cache")
        finally:
            monkeypatch.undo()
            enable_persistent_cache()
        assert jax.config.jax_compilation_cache_dir == suite_dir

    def test_env_var_outranks_compile_cache_dir_param(self,
                                                      cache_children):
        """With the variable set, a training process keeps its cache and
        its tune table there even when compile_cache_dir is passed, and
        the compile counters see the cache traffic (a renamed
        jax.monitoring event would leave them at zero)."""
        env_dir = cache_children["env_dir"]
        rep = json.loads(cache_children["train"])
        assert rep["cache_dir"] == env_dir and rep["tune_dir"] == env_dir
        assert os.listdir(env_dir)
        assert not os.path.exists(cache_children["param_dir"])
        assert rep["stats"]["count"] > 0
        assert rep["stats"]["cache_hits"] + rep["stats"]["cache_misses"] > 0

    def test_default_dir_is_the_checkouts_in_every_process(self,
                                                           cache_children):
        """Without the variable two processes started from different
        directories agree on <checkout>/.jax_cache."""
        assert cache_children["default_tmp"] \
            == cache_children["default_repo"] \
            == os.path.join(REPO, ".jax_cache")

    def test_thresholds_are_parameters(self):
        import jax
        try:
            enable_persistent_cache(min_compile_secs=1.25,
                                    min_entry_bytes=123)
            assert jax.config.jax_persistent_cache_min_compile_time_secs \
                == 1.25
            assert jax.config.jax_persistent_cache_min_entry_size_bytes \
                == 123
        finally:
            enable_persistent_cache()     # restore conftest thresholds

    def test_config_rejects_negative_thresholds(self):
        with pytest.raises(ValueError):
            lgb.Config({"compile_cache_min_compile_s": -1.0})
        with pytest.raises(ValueError):
            lgb.Config({"compile_cache_min_entry_bytes": -1})


class TestLeafBucketing:
    def test_sweep_shares_one_trace_and_is_byte_identical(self,
                                                          sweep_data):
        """num_leaves 31/40/63 (strict growth) compile exactly one
        padded L=64 grower trace, and every model matches the
        unbucketed per-shape path byte-for-byte."""
        from lightgbm_tpu.grower import grower_trace_count
        x, y = sweep_data
        t0 = grower_trace_count()
        bucketed = {nl: _train_text(x, y, nl, True) for nl in (31, 40, 63)}
        # <= 1, not == 1: an earlier test in this module may already
        # have traced the bucket's shared grower (the memo working
        # across tests); the strict ==1 pin for a FRESH process is
        # tools/check_retraces.py's leaf_sweep scenario
        assert grower_trace_count() - t0 <= 1
        for nl in (31, 40, 63):
            assert bucketed[nl] == _train_text(x, y, nl, False), \
                f"bucketed num_leaves={nl} diverged from exact path"

    @pytest.mark.parametrize("extra", [
        {"bagging_fraction": 0.7, "bagging_freq": 1},
        {"data_sample_strategy": "goss"},
        {"split_batch": 8},
    ], ids=["bagging", "goss", "batched"])
    def test_sampling_and_batched_parity(self, sweep_data, extra):
        x, y = sweep_data
        assert _train_text(x, y, 40, True, **extra) \
            == _train_text(x, y, 40, False, **extra)

    def test_sampling_reuses_the_sweep_trace(self, sweep_data):
        """Bagging/GOSS change histogram VALUES, never shapes: the
        process-level grower memo must serve them from the already-
        traced config (zero fresh grower traces)."""
        from lightgbm_tpu.grower import grower_trace_count
        x, y = sweep_data
        _train_text(x, y, 40, True)          # ensure the config is traced
        t0 = grower_trace_count()
        _train_text(x, y, 40, True, bagging_fraction=0.7, bagging_freq=1)
        _train_text(x, y, 40, True, data_sample_strategy="goss")
        assert grower_trace_count() - t0 == 0

    def test_explicit_split_batch_snaps_to_shipped_set(self, sweep_data):
        x, y = sweep_data
        p = _sweep_params(40, True, split_batch=4)
        ds = lgb.Dataset(x, label=y, params=p)
        bst = lgb.train(p, ds, num_boost_round=1)
        assert bst._model._split_batch == 8
        p = _sweep_params(40, False, split_batch=4)
        ds = lgb.Dataset(x, label=y, params=p)
        bst = lgb.train(p, ds, num_boost_round=1)
        assert bst._model._split_batch == 4    # escape hatch honored

    def test_valid_row_bucketing_metrics_identical(self, sweep_data):
        import lightgbm_tpu.callback as cb
        x, y = sweep_data
        recs = []
        for tb in (True, False):
            p = _sweep_params(15, tb, metric=["binary_logloss"])
            ds = lgb.Dataset(x, label=y, params=p)
            v1 = lgb.Dataset(x[:200], label=y[:200], params=p,
                             reference=ds)
            v2 = lgb.Dataset(x[200:430], label=y[200:430], params=p,
                             reference=ds)
            rec = {}
            lgb.train(p, ds, num_boost_round=3, valid_sets=[v1, v2],
                      callbacks=[cb.record_evaluation(rec)])
            recs.append(rec)
        assert recs[0] == recs[1]


class TestCompileTelemetry:
    def test_booster_snapshot_has_compile_keys(self, sweep_data):
        x, y = sweep_data
        p = _sweep_params(7, True)
        ds = lgb.Dataset(x, label=y, params=p)
        bst = lgb.train(p, ds, num_boost_round=1)
        snap = bst.telemetry_snapshot()
        for k in ("compile.count", "compile.seconds",
                  "compile.cache_hits", "compile.cache_misses",
                  "compile.traces"):
            assert k in snap
        # the suite has been compiling all along — the process counters
        # must have seen it
        assert snap["compile.count"] > 0
        assert snap["compile.traces"] > 0

    def test_serve_metrics_snapshot_has_compile_keys(self, sweep_data):
        from lightgbm_tpu.serve.server import Server
        x, y = sweep_data
        p = _sweep_params(7, True)
        ds = lgb.Dataset(x, label=y, params=p)
        bst = lgb.train(p, ds, num_boost_round=1)
        srv = Server(params=p, booster=bst)
        try:
            srv.predict(x[:8])
            snap = srv.metrics_snapshot()
            for k in ("compile.count", "compile.cache_hits",
                      "compile.seconds", "compile.traces"):
                assert k in snap
            assert isinstance(snap["compile.traces"], dict)
        finally:
            srv.close()

    def test_trace_counters_monotone_and_named(self):
        tc = trace_counts()
        assert tc.get("grower", 0) >= 1        # this suite trained
        cs = compile_stats()
        assert set(cs) == {"count", "seconds", "cache_hits",
                           "cache_misses"}
