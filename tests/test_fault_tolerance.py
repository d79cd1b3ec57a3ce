"""Fault-tolerance suite (ISSUE 2): device-claim retry/backoff +
serial fallback, finite guards in the boosting loop, atomic snapshots
with auto-resume (crash+resume == train-straight, byte-identical), and
the named fault-injection sites that drive it all.

Every injection site (device claim, collective, snapshot write,
kill-before-rename, NaN grads) has a test proving its configured policy
(retry / fallback / skip / raise) engages — the acceptance bar of the
issue.  Injection specs are installed programmatically via
``faultinject.configure`` and always cleared by the autouse fixture.
"""

import glob
import logging
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.basic import LightGBMError
from lightgbm_tpu.utils import faultinject
from lightgbm_tpu.utils.faultinject import InjectedFault, InjectedKill
from lightgbm_tpu.utils.resilience import (RetryPolicy, Watchdog,
                                           atomic_write,
                                           is_retryable_device_error,
                                           retry_call)

_rs = np.random.RandomState(7)
X = _rs.randn(600, 10)
Y = (2.0 * X[:, 0] - X[:, 1] + 0.1 * _rs.randn(600)).astype(np.float32)

BASE = {"objective": "regression", "num_leaves": 7, "max_bin": 31,
        "min_data_in_leaf": 5}


@pytest.fixture(autouse=True)
def _clear_faults():
    """No injection spec may leak between tests."""
    faultinject.clear()
    yield
    faultinject.clear()


def _ds():
    return lgb.Dataset(X, label=Y)


# ---------------------------------------------------------------------------
# resilience primitives
# ---------------------------------------------------------------------------

class TestRetryPrimitives:
    def test_classifier_retryable_vs_fatal(self):
        assert is_retryable_device_error(
            RuntimeError("UNAVAILABLE: claim hung"))
        assert is_retryable_device_error(
            OSError("connection refused by relay"))
        assert is_retryable_device_error(
            RuntimeError("DEADLINE_EXCEEDED: barrier timed out"))
        assert not is_retryable_device_error(TypeError("unavailable"))
        assert not is_retryable_device_error(ValueError("bad argument"))
        assert not is_retryable_device_error(
            RuntimeError("some unrelated assertion"))
        # InjectedFault deliberately matches the retryable patterns
        assert is_retryable_device_error(InjectedFault("device_claim", 1))

    def test_retry_succeeds_after_transient(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise RuntimeError("UNAVAILABLE: transient")
            return "ok"

        out = retry_call(flaky, policy=RetryPolicy(max_attempts=4,
                                                   base_delay_s=0.001))
        assert out == "ok" and len(calls) == 3

    def test_fatal_error_not_retried(self):
        calls = []

        def broken():
            calls.append(1)
            raise TypeError("programming error")

        with pytest.raises(TypeError):
            retry_call(broken, policy=RetryPolicy(max_attempts=5,
                                                  base_delay_s=0.001))
        assert len(calls) == 1

    def test_attempts_exhausted_reraises_last(self):
        with pytest.raises(RuntimeError, match="UNAVAILABLE"):
            retry_call(lambda: (_ for _ in ()).throw(
                RuntimeError("UNAVAILABLE")),
                policy=RetryPolicy(max_attempts=2, base_delay_s=0.001))

    def test_hard_deadline_stops_backoff(self):
        calls = []

        def always_down():
            calls.append(1)
            raise RuntimeError("UNAVAILABLE")

        # first backoff (10 s) would blow the 0.2 s deadline -> exactly
        # one attempt, immediate re-raise instead of sleeping
        with pytest.raises(RuntimeError):
            retry_call(always_down,
                       policy=RetryPolicy(max_attempts=5, base_delay_s=10.0,
                                          deadline_s=0.2))
        assert len(calls) == 1

    def test_watchdog_arms_and_cancels(self):
        # smoke: arming must not dump for a fast call, and a zero
        # timeout must be a no-op
        with Watchdog(60.0, label="test"):
            pass
        with Watchdog(0.0, label="disabled"):
            pass


class TestFaultSpecParsing:
    def test_grammar(self):
        faultinject.configure("device_claim:1-2,nan_grads:3,"
                              "snapshot_write:4-:exit")
        assert faultinject.enabled()
        faultinject.clear()
        assert not faultinject.enabled()

    @pytest.mark.parametrize("bad", ["nope:1", "device_claim",
                                     "device_claim:0", "device_claim:2-1",
                                     "device_claim:1:explode"])
    def test_bad_specs_rejected(self, bad):
        with pytest.raises(ValueError):
            faultinject.configure(bad)
        faultinject.clear()

    def test_hit_window(self):
        faultinject.configure("collective:2-3")
        assert not faultinject.fires("collective")      # hit 1
        assert faultinject.fires("collective")          # hit 2
        assert faultinject.fires("collective")          # hit 3
        assert not faultinject.fires("collective")      # hit 4
        assert faultinject.hits("collective") == 4


# ---------------------------------------------------------------------------
# atomic persistence
# ---------------------------------------------------------------------------

class TestAtomicWrites:
    def test_kill_before_rename_preserves_old_file(self, tmp_path):
        path = str(tmp_path / "f.txt")
        atomic_write(path, "old contents")
        faultinject.configure("snapshot_kill:1")
        with pytest.raises(InjectedKill):
            atomic_write(path, "new contents")
        faultinject.clear()
        # old file intact; the temp debris a real crash leaves is ignored
        with open(path) as f:
            assert f.read() == "old contents"

    def test_save_model_atomic(self, tmp_path):
        bst = lgb.train(dict(BASE), _ds(), num_boost_round=2)
        path = str(tmp_path / "m.txt")
        bst.save_model(path)
        first = open(path).read()
        faultinject.configure("snapshot_kill:1")
        with pytest.raises(InjectedKill):
            bst.save_model(path)
        faultinject.clear()
        assert open(path).read() == first

    def test_save_binary_atomic_and_exact_filename(self, tmp_path):
        ds = _ds()
        ds.construct(lgb.Config(dict(BASE)))
        path = str(tmp_path / "cache.bin")
        ds.save_binary(path)
        assert os.path.exists(path)            # no surprise '.npz' suffix
        good = open(path, "rb").read()
        faultinject.configure("snapshot_kill:1")
        with pytest.raises(InjectedKill):
            ds.save_binary(path)
        faultinject.clear()
        assert open(path, "rb").read() == good
        assert lgb.Dataset.load_binary(path).num_data == len(X)

    def test_snapshot_parent_dir_created(self, tmp_path, monkeypatch):
        # a RELATIVE output_model in a fresh working dir used to make
        # every snapshot write raise (engine.py satellite)
        monkeypatch.chdir(tmp_path)
        p = dict(BASE, snapshot_freq=2, output_model="out/nested/m.txt")
        lgb.train(p, _ds(), num_boost_round=2)
        assert os.path.exists("out/nested/m.txt.snapshot_iter_2")


# ---------------------------------------------------------------------------
# injection sites: device claim (retry / fallback), collective (raise),
# snapshot write (skip)
# ---------------------------------------------------------------------------

class TestDeviceClaimSite:
    DP = dict(BASE, tree_learner="data", dist_init_timeout_s=5.0)

    def test_retry_engages_and_training_proceeds(self):
        faultinject.configure("device_claim:1-2")
        bst = lgb.train(dict(self.DP, dist_init_retries=3), _ds(),
                        num_boost_round=2)
        assert bst.num_trees() == 2
        # two injected failures + the successful third attempt
        assert faultinject.hits("device_claim") == 3

    def test_exhausted_retries_raise_without_fallback(self):
        faultinject.configure("device_claim:1-")
        with pytest.raises(InjectedFault):
            lgb.train(dict(self.DP, dist_init_retries=1), _ds(),
                      num_boost_round=2)

    def test_fallback_serial_degrades_gracefully(self, caplog):
        faultinject.configure("device_claim:1-")
        with caplog.at_level(logging.WARNING, logger="lightgbm_tpu"):
            bst = lgb.train(dict(self.DP, dist_init_retries=1,
                                 dist_fallback_serial=True), _ds(),
                            num_boost_round=2)
        assert bst.num_trees() == 2
        assert any("falling back to the serial learner" in r.message
                   for r in caplog.records)

    def test_launch_init_retries_then_single_process_fallback(self):
        from lightgbm_tpu.parallel import launch
        was_done = getattr(launch.init, "_done", False)
        launch.init._done = False
        try:
            faultinject.configure("device_claim:1-2")
            # after the injected transients pass, the real auto-detect
            # initialize fails fatally on this CPU harness and the
            # documented single-process fallback engages — the assertion
            # is that the RETRY layer ran first
            launch.init(retries=3, timeout_s=5.0)
            assert faultinject.hits("device_claim") == 3
        finally:
            launch.init._done = was_done


class TestCollectiveSite:
    def test_collective_failure_surfaces_promptly(self):
        faultinject.configure("collective:1")
        with pytest.raises(InjectedFault, match="collective"):
            lgb.train(dict(BASE, tree_learner="data"), _ds(),
                      num_boost_round=2)


class TestSnapshotWriteSite:
    def test_failed_snapshot_skips_and_training_survives(self, tmp_path,
                                                         caplog):
        out = str(tmp_path / "m.txt")
        faultinject.configure("snapshot_write:1-")
        with caplog.at_level(logging.WARNING, logger="lightgbm_tpu"):
            bst = lgb.train(dict(BASE, snapshot_freq=2, output_model=out),
                            _ds(), num_boost_round=5)
        assert bst.num_trees() == 5
        assert any("training continues" in r.message
                   for r in caplog.records)
        # atomicity: the failed writes left no partial snapshot files
        assert not [f for f in os.listdir(tmp_path)
                    if not f.endswith(".tmp")]


# ---------------------------------------------------------------------------
# finite guards
# ---------------------------------------------------------------------------

class TestFiniteGuard:
    P = dict(BASE, finite_check_freq=1)

    def test_nan_grads_raise(self):
        faultinject.configure("nan_grads:3")
        with pytest.raises(LightGBMError, match="iteration 3"):
            lgb.train(dict(self.P, finite_check_policy="raise"), _ds(),
                      num_boost_round=5)

    def test_nan_grads_skip_iter(self, caplog):
        faultinject.configure("nan_grads:3")
        with caplog.at_level(logging.WARNING, logger="lightgbm_tpu"):
            bst = lgb.train(dict(self.P, finite_check_policy="skip_iter"),
                            _ds(), num_boost_round=5)
        # the poisoned iteration contributes a zero stump; training
        # recovers (gradients are recomputed from the untouched score)
        leaves = [t.num_leaves for t in bst.trees]
        assert bst.num_trees() == 5
        assert leaves[2] == 1 and float(bst.trees[2].leaf_value[0]) == 0.0
        assert all(nl > 1 for i, nl in enumerate(leaves) if i != 2)
        assert np.isfinite(bst.predict(X[:16])).all()
        assert any("skip_iter" in r.message for r in caplog.records)
        # the skipped stump round-trips through model text
        reloaded = lgb.Booster(model_str=bst.model_to_string())
        assert reloaded.trees[2].num_leaves == 1

    def test_nan_grads_clamp_trains_through(self):
        faultinject.configure("nan_grads:3")
        bst = lgb.train(dict(self.P, finite_check_policy="clamp"), _ds(),
                        num_boost_round=5)
        assert all(t.num_leaves > 1 for t in bst.trees)
        assert np.isfinite(np.concatenate(
            [t.leaf_value for t in bst.trees])).all()

    def test_check_freq_cadence(self):
        # with freq=2 the checks run at iterations 2/4/6 only: a NaN at
        # a check iteration raises there; the same NaN at an off-cadence
        # iteration is freq>1's documented blind spot (on this learner
        # it degenerates to a harmless stump — NaN gains never win a
        # split — so training neither raises nor corrupts)
        faultinject.configure("nan_grads:4")
        with pytest.raises(LightGBMError, match="iteration 4"):
            lgb.train(dict(self.P, finite_check_freq=2,
                           finite_check_policy="raise"), _ds(),
                      num_boost_round=6)
        faultinject.clear()
        faultinject.configure("nan_grads:3")
        bst = lgb.train(dict(self.P, finite_check_freq=2,
                             finite_check_policy="raise"), _ds(),
                        num_boost_round=6)
        assert np.isfinite(np.concatenate(
            [t.leaf_value for t in bst.trees])).all()

    # -- scan compatibility (the guard flags ride the one host
    #    sync per epoch) — NaN is seeded into the device score because
    #    labels are AvoidInf-sanitized at ingestion ------------------------
    FUSED = dict(BASE, tpu_learner="masked", boost_from_average=False,
                 finite_check_freq=1)

    def _poisoned(self, policy, fused_chunk):
        import jax.numpy as jnp
        bst = lgb.Booster(params=dict(self.FUSED, fused_chunk=fused_chunk,
                                      finite_check_policy=policy),
                          train_set=_ds())
        bst._model.score = bst._model.score.at[0, 0].set(jnp.nan)
        return bst

    def test_fused_raise(self):
        bst = self._poisoned("raise", 8)
        assert bst._model.supports_fused()
        with pytest.raises(LightGBMError, match="iteration 1"):
            bst.update_superepoch(8, 0)

    def test_fused_skip_iter_stumps_then_heals(self):
        # iteration 1 trips the check -> zero stump AND the score carry
        # is sanitized, so iterations 2..8 recover and train real trees
        bst = self._poisoned("skip_iter", 8)
        out = bst.update_superepoch(8, 0)
        assert out["done"] == 8 and not out["stump"]
        leaves = [t.num_leaves for t in bst.trees]
        assert leaves[0] == 1 and float(bst.trees[0].leaf_value[0]) == 0.0
        assert all(nl > 1 for nl in leaves[1:])
        # ...and the fused path matches the per-iteration path exactly
        bp = self._poisoned("skip_iter", 0)
        for _ in range(8):
            bp.update()

        def strip(s):
            return "\n".join(l for l in s.splitlines()
                             if not l.startswith("[fused_chunk:"))
        assert strip(bst.model_to_string()) == strip(bp.model_to_string())

    def test_fused_clamp_matches_per_iteration_clamp(self):
        bf = self._poisoned("clamp", 8)
        bf.update_superepoch(8, 0)
        bp = self._poisoned("clamp", 0)
        for _ in range(8):
            bp.update()

        def strip(s):     # fused_chunk is the one differing param line
            return "\n".join(l for l in s.splitlines()
                             if not l.startswith("[fused_chunk:"))
        assert strip(bf.model_to_string()) == strip(bp.model_to_string())
        assert all(t.num_leaves > 1 for t in bf.trees)


# ---------------------------------------------------------------------------
# crash/resume equivalence (the acceptance bar): kill-before-rename at the
# second snapshot, auto-resume from the first — byte-identical model text
# ---------------------------------------------------------------------------

CONFIGS = {
    "serial": {},
    "data_parallel": {"tree_learner": "data"},
    "ffrac_bagging": {"feature_fraction": 0.7, "bagging_fraction": 0.8,
                      "bagging_freq": 2},
    "goss": {"data_sample_strategy": "goss"},
}


class TestCrashResume:
    @pytest.mark.parametrize("cfg_name", list(CONFIGS))
    def test_kill_and_resume_byte_identical(self, cfg_name, tmp_path):
        out = str(tmp_path / "m.txt")
        p = dict(BASE, snapshot_freq=3, output_model=out,
                 **CONFIGS[cfg_name])
        straight = lgb.train(dict(p), _ds(), num_boost_round=7)
        s_straight = straight.model_to_string()
        for f in glob.glob(out + "*"):
            os.unlink(f)

        # run A dies mid-write of the iteration-6 snapshot's model file
        # (snapshot 3 = atomic_write hits 1-3; snapshot 6's model = hit 4)
        faultinject.configure("snapshot_kill:4")
        with pytest.raises(InjectedKill):
            lgb.train(dict(p), _ds(), num_boost_round=7)
        faultinject.clear()
        names = os.listdir(tmp_path)
        assert "m.txt.snapshot_iter_3.manifest.json" in names
        assert "m.txt.snapshot_iter_6" not in names   # old state, no hybrid

        # run B auto-resumes from iteration 3 and matches byte-for-byte
        resumed = lgb.train(dict(p, resume=True), _ds(), num_boost_round=7)
        assert resumed.model_to_string() == s_straight

    def test_resume_without_snapshot_trains_from_scratch(self, tmp_path):
        out = str(tmp_path / "m.txt")
        p = dict(BASE, snapshot_freq=3, output_model=out)
        straight = lgb.train(dict(p), _ds(), num_boost_round=5)
        for f in glob.glob(out + "*"):
            os.unlink(f)
        fresh = lgb.train(dict(p, resume=True), _ds(), num_boost_round=5)
        assert fresh.model_to_string() == straight.model_to_string()

    def test_resume_rejects_changed_params(self, tmp_path, caplog):
        out = str(tmp_path / "m.txt")
        p = dict(BASE, snapshot_freq=2, output_model=out)
        lgb.train(dict(p), _ds(), num_boost_round=4)
        with caplog.at_level(logging.WARNING, logger="lightgbm_tpu"):
            bst = lgb.train(dict(p, resume=True, learning_rate=0.05),
                            _ds(), num_boost_round=4)
        assert any("training parameters differ" in r.message
                   for r in caplog.records)
        assert bst.num_trees() == 4        # full retrain, nothing spliced

    def test_resume_accepts_changed_bringup_knobs(self, tmp_path):
        # raising the retry/timeout knobs is the NATURAL response to the
        # crash being resumed from — they never affect the trained model
        # and must not invalidate the snapshot (params_signature excludes
        # them); only the recorded parameters section may differ
        out = str(tmp_path / "m.txt")
        p = dict(BASE, snapshot_freq=2, output_model=out)
        straight = lgb.train(dict(p), _ds(), num_boost_round=4)
        resumed = lgb.train(dict(p, resume=True, dist_init_retries=9,
                                 dist_init_timeout_s=900.0), _ds(),
                            num_boost_round=4)

        def core(s):
            return s[s.index("tree_sizes="):s.index("\nparameters:")]

        assert resumed.num_trees() == 4
        assert core(resumed.model_to_string()) == \
            core(straight.model_to_string())

    def test_resume_rejects_changed_data(self, tmp_path, caplog):
        out = str(tmp_path / "m.txt")
        p = dict(BASE, snapshot_freq=2, output_model=out)
        lgb.train(dict(p), _ds(), num_boost_round=4)
        y2 = Y.copy()
        y2[0] += 1.0
        with caplog.at_level(logging.WARNING, logger="lightgbm_tpu"):
            lgb.train(dict(p, resume=True), lgb.Dataset(X, label=y2),
                      num_boost_round=4)
        assert any("dataset fingerprint differs" in r.message
                   for r in caplog.records)

    def test_interrupted_snapshot_resumes_from_previous(self, tmp_path):
        # a model file with NO manifest (crash between model write and
        # manifest write) must be walked past, not trusted
        out = str(tmp_path / "m.txt")
        p = dict(BASE, snapshot_freq=2, output_model=out)
        straight = lgb.train(dict(p), _ds(), num_boost_round=6)
        s_straight = straight.model_to_string()
        for f in glob.glob(out + "*"):
            os.unlink(f)
        # die on snapshot 4's STATE write (hits: s2=1,2,3; s4 model=4,
        # state=5) -> snapshot_iter_4 model exists, manifest does not
        faultinject.configure("snapshot_kill:5")
        with pytest.raises(InjectedKill):
            lgb.train(dict(p), _ds(), num_boost_round=6)
        faultinject.clear()
        names = os.listdir(tmp_path)
        assert "m.txt.snapshot_iter_4" in names
        assert "m.txt.snapshot_iter_4.manifest.json" not in names
        resumed = lgb.train(dict(p, resume=True), _ds(), num_boost_round=6)
        assert resumed.model_to_string() == s_straight

    def test_snapshot_keep_prunes_old(self, tmp_path):
        out = str(tmp_path / "m.txt")
        lgb.train(dict(BASE, snapshot_freq=1, snapshot_keep=2,
                       output_model=out), _ds(), num_boost_round=5)
        import re
        models = sorted(os.path.basename(m)
                        for m in glob.glob(out + ".snapshot_iter_*")
                        if re.search(r"snapshot_iter_\d+$", m))
        assert models == ["m.txt.snapshot_iter_4", "m.txt.snapshot_iter_5"]
        # sidecars pruned with their models
        assert not os.path.exists(out + ".snapshot_iter_3.manifest.json")
        assert os.path.exists(out + ".snapshot_iter_5.manifest.json")

    def test_save_period_alias(self, tmp_path):
        # satellite: snapshot_freq's reference alias must reach the
        # snapshot machinery end to end
        assert lgb.Config({"save_period": 2}).snapshot_freq == 2
        out = str(tmp_path / "m.txt")
        lgb.train(dict(BASE, save_period=2, output_model=out), _ds(),
                  num_boost_round=4)
        assert os.path.exists(out + ".snapshot_iter_2")
        assert os.path.exists(out + ".snapshot_iter_4.manifest.json")

    def test_trees_and_importances_roundtrip_byte_stable(self):
        # save -> load -> save keeps the tree blocks AND the importance
        # section byte-stable, full and SUBSET saves alike: importances
        # are summed over the written trees at the written %g precision.
        # (feature_infos/parameters legitimately differ on a loaded
        # model — no train_set / raw_params — so compare from the trees
        # through the importance section.)
        def core(s):
            return s[s.index("tree_sizes="):s.index("\nparameters:")]

        bst = lgb.train(dict(BASE), _ds(), num_boost_round=6)
        for kw in ({}, {"num_iteration": 3}, {"start_iteration": 2}):
            s1 = bst.model_to_string(**kw)
            s2 = lgb.Booster(model_str=s1).model_to_string()
            assert core(s1) == core(s2), f"round-trip drift for {kw}"

    def test_resume_not_recorded_in_model_params(self, tmp_path):
        out = str(tmp_path / "m.txt")
        p = dict(BASE, snapshot_freq=2, output_model=out)
        bst = lgb.train(dict(p, resume=True), _ds(), num_boost_round=2)
        assert "[resume:" not in bst.model_to_string()


# ---------------------------------------------------------------------------
# early-stopping NaN poisoning (callback.py satellite)
# ---------------------------------------------------------------------------

class TestEarlyStoppingNonFinite:
    def test_nan_metric_is_not_an_unbeatable_best(self):
        # a custom metric that is NaN for the first 3 iterations, then
        # improves: the old code recorded the first NaN as best_score
        # forever (every later comparison with NaN is False)
        def feval(preds, ds):
            it = len(history)
            history.append(it)
            val = float("nan") if it < 3 else 1.0 / (1.0 + it)
            return ("custom", val, False)

        history = []
        res = {}
        bst = lgb.train(dict(BASE, metric="custom"), _ds(),
                        num_boost_round=10,
                        valid_sets=[_ds()], valid_names=["v"],
                        feval=feval,
                        callbacks=[lgb.early_stopping(3, verbose=False),
                                   lgb.record_evaluation(res)])
        assert bst.best_iteration > 0
        best = bst.best_score["v"]["custom"]
        assert np.isfinite(best)           # NaN never became "best"

    def test_all_nan_metric_stops_cleanly(self):
        def feval(preds, ds):
            return ("custom", float("nan"), False)

        bst = lgb.train(dict(BASE, metric="custom"), _ds(),
                        num_boost_round=10,
                        valid_sets=[_ds()], valid_names=["v"],
                        feval=feval,
                        callbacks=[lgb.early_stopping(2, verbose=False)])
        # stops after the patience window without crashing on the
        # never-recorded best_score_list
        assert bst.best_iteration == 1
