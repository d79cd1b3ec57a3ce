"""Cross-process compile-wall coverage (runs late in the suite — the
'z' keeps the subprocess-heavy pieces at the alphabetical tail):

- second-process warm start: replaying the canonical train+predict in a
  FRESH interpreter against the same persistent cache logs zero fresh
  compiles (pure cache hits);
- the retrace-budget lint (tools/check_retraces.py) is green against
  the pinned tools/retrace_budget.txt, catches a tampered budget, and
  reports stale entries;
- tree_learner=data: the leaf-bucketed (L=64-padded) trace trains
  byte-identical models to the unbucketed per-shape path.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT = os.path.join(REPO, "tools", "check_retraces.py")
BUDGET = os.path.join(REPO, "tools", "retrace_budget.txt")

# the canonical warm-start workload: train + engine-routed predict in a
# fresh interpreter, reporting the process compile/cache counters.
# min_compile_s=0 persists every compile so the second process can hit
# on all of them.
_WARM_SCRIPT = r"""
import json, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import lightgbm_tpu as lgb
from lightgbm_tpu.utils.compile_cache import compile_stats
rs = np.random.RandomState(0)
x = rs.randn(300, 8)
y = (x[:, 0] - x[:, 1] + 0.2 * rs.randn(300) > 0).astype(np.float32)
p = {"objective": "binary", "num_leaves": 31, "verbosity": 0,
     "min_data_in_leaf": 5, "max_bin": 15, "tpu_learner": "masked",
     "fused_chunk": 0, "predict_bucketed": "true",
     "compile_cache_min_compile_s": 0.0}
ds = lgb.Dataset(x, label=y, params=p)
bst = lgb.train(p, ds, num_boost_round=2)
pred = bst.predict(x[:50])
print("STATS " + json.dumps(compile_stats()))
print("PRED " + json.dumps(np.asarray(pred)[:4].round(8).tolist()))
"""


def _run_warm(cache_dir: str) -> dict:
    # a private cache, placed the way the driver places one
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache_dir)
    out = subprocess.run(
        [sys.executable, "-c", _WARM_SCRIPT],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    stats = pred = None
    for line in out.stdout.splitlines():
        if line.startswith("STATS "):
            stats = json.loads(line[6:])
        elif line.startswith("PRED "):
            pred = json.loads(line[5:])
    assert stats is not None, out.stdout
    stats["pred"] = pred
    return stats


class TestWarmStart:
    def test_second_process_pays_no_fresh_compiles(self, tmp_path):
        cache = str(tmp_path / "cache")
        cold = _run_warm(cache)
        warm = _run_warm(cache)
        # cold process: real compiles, all written to the empty cache
        assert cold["count"] > 0
        assert cold["cache_misses"] > 0
        # warm process: every compile request is served from disk —
        # zero fresh compiles (cache_misses IS the fresh-compile
        # counter; `count` tallies requests and ticks on hits too),
        # with hits covering the cold misses
        assert warm["cache_misses"] == 0, warm
        assert warm["cache_hits"] >= cold["cache_misses"]
        # and the warm-started model predicts identically
        assert warm["pred"] == cold["pred"]


# autotuner warm start (ISSUE 15, ops/hist_tune.py): the FIRST process
# pays the (K, block_rows) sweep and persists both the choice
# (hist_tune.json) and the compiled traces it leads to; a SECOND
# process against the same directory must re-tune zero times and
# compile zero times.
_TUNE_SCRIPT = r"""
import json, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import lightgbm_tpu as lgb
from lightgbm_tpu.ops import hist_tune
from lightgbm_tpu.utils.compile_cache import compile_stats
rs = np.random.RandomState(0)
x = rs.randn(400, 6)
y = (x[:, 0] - x[:, 1] + 0.2 * rs.randn(400) > 0).astype(np.float32)
p = {"objective": "binary", "num_leaves": 33, "verbosity": 0,
     "min_data_in_leaf": 5, "max_bin": 15, "tpu_learner": "masked",
     "fused_chunk": 0, "hist_tune": "on", "split_batch": 0,
     "compile_cache_min_compile_s": 0.0}
ds = lgb.Dataset(x, label=y, params=p)
bst = lgb.train(p, ds, num_boost_round=2)
rec = {"sweeps": hist_tune.tune_counts()["sweeps"],
       "pred": np.asarray(bst.predict(x[:4])).round(8).tolist()}
rec.update(compile_stats())
print("TUNE " + json.dumps(rec))
"""


class TestAutotunerWarmStart:
    def test_second_process_reuses_choice_and_traces(self, tmp_path):
        cache = str(tmp_path / "cache")

        def run():
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       JAX_COMPILATION_CACHE_DIR=cache)
            out = subprocess.run(
                [sys.executable, "-c", _TUNE_SCRIPT],
                capture_output=True, text=True, timeout=420, env=env,
                cwd=REPO)
            assert out.returncode == 0, out.stderr[-3000:]
            for line in out.stdout.splitlines():
                if line.startswith("TUNE "):
                    return json.loads(line[5:])
            raise AssertionError(out.stdout)

        cold = run()
        warm = run()
        # first fit per (platform, shape bucket): exactly one sweep,
        # persisted next to the compile cache
        assert cold["sweeps"] == 1
        assert os.path.exists(os.path.join(cache, "hist_tune.json"))
        # second process: zero re-tune, zero re-compile (the sweep's
        # own traces AND the tuned grower all hit the persistent
        # cache), and the tuned choice reproduces the same model
        assert warm["sweeps"] == 0, warm
        assert warm["cache_misses"] == 0, warm
        assert warm["pred"] == cold["pred"]


class TestRetraceLint:
    """The lint re-runs the whole canonical matrix in a fresh
    subprocess (~15 s with a warm persistent cache — which tier-1's own
    earlier compiles populate — minutes stone-cold).  The GREEN run now
    rides the unified driver (`python tools/lint.py`,
    tests/test_zlint.py — ISSUE 12 replaced the separate sync/retrace
    invocations); this class keeps the standalone entry point's
    tamper/stale sensitivity, slow-marked."""

    def _run(self, *args, timeout=600):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        return subprocess.run([sys.executable, LINT, *args],
                              capture_output=True, text=True,
                              timeout=timeout, env=env, cwd=REPO)

    @pytest.mark.slow
    def test_tampered_budget_is_caught(self, tmp_path):
        import re
        tampered = tmp_path / "budget.txt"
        text = open(BUDGET).read()
        # violate the headline pin AND leave a stale entry behind
        text = re.sub(r"leaf_sweep.grower = \d+",
                      "leaf_sweep.grower = 0", text)
        tampered.write_text(text + "ghost.scenario = 9\n")
        out = self._run("--budget", str(tampered))
        assert out.returncode == 1
        assert "trace budget violated: leaf_sweep.grower" in out.stderr
        assert "stale budget entry" in out.stderr


class TestBudgetFile:
    def test_budget_is_pinned_and_parses(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        from check_retraces import load_budget
        budget = load_budget(BUDGET)
        # the headline pins: one grower trace for the whole leaf sweep,
        # and the unbucketed negative control measurably above it
        assert budget.get("leaf_sweep.grower") == 1
        assert budget.get("negative_unbucketed.grower", 0) > 1
        # two valid-set sizes of one row bucket, in either order: the
        # grower that follows them is traced once, and no walk at all
        assert budget.get("valid_sizes.grower") == 1
        assert "valid_sizes.add_tree_score" not in budget
        assert "serve_buckets.forest" in budget


class TestDataParallelBucketing:
    def test_dp_bucketed_equals_unbucketed(self):
        import jax
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device CPU mesh")
        rs = np.random.RandomState(3)
        x = rs.randn(1600, 10)
        y = (x[:, 0] - 0.5 * x[:, 1] + 0.3 * rs.randn(1600) > 0) \
            .astype(np.float32)
        texts = []
        for tb in (True, False):
            p = {"objective": "binary", "num_leaves": 31, "verbosity": 0,
                 "min_data_in_leaf": 5, "max_bin": 15,
                 "tree_learner": "data", "split_batch": 1,
                 "fused_chunk": 0, "trace_buckets": tb}
            ds = lgb.Dataset(x, label=y, params=p)
            bst = lgb.train(p, ds, num_boost_round=3)
            texts.append(bst.model_to_string()
                         .split("end of parameters", 1)[-1])
        assert texts[0] == texts[1]
