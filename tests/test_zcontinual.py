"""Continual boosting pipeline (ISSUE 11): the freshness-guaranteed
train -> publish -> serve loop with shadow-parity gating and automatic
rollback (lightgbm_tpu/pipeline/continual.py), plus its satellites —
snapshot-prune TOCTOU pinning, registry in-flight guards, absolute
``best_iteration`` for continued runs, and the kill -9 stage-boundary
matrix proving restart converges byte-identically.
"""

import glob
import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.pipeline.continual import (ContinualTrainer,
                                             gate_metric_value,
                                             lineage_gate_reason,
                                             score_gate_reason,
                                             shadow_parity_probe)
from lightgbm_tpu.utils import faultinject

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_rs = np.random.RandomState(11)


def _chunk(n, seed=None, n_feat=6):
    rs = np.random.RandomState(seed) if seed is not None else _rs
    x = rs.randn(n, n_feat)
    return x, x[:, 0] + 0.5 * x[:, 1] + 0.05 * rs.randn(n)


BASE = {"objective": "regression", "num_leaves": 7, "max_bin": 31,
        "min_data_in_leaf": 5, "verbosity": -1, "continual_rounds": 3}


@pytest.fixture(autouse=True)
def _clear_faults():
    faultinject.clear()
    yield
    faultinject.clear()


def _params(tmp_path, **kw):
    p = dict(BASE, output_model=str(tmp_path / "m.txt"))
    p.update(kw)
    return p


# ---------------------------------------------------------------------------
# gate primitives
# ---------------------------------------------------------------------------

class TestGatePrimitives:
    def test_probability_drift_is_absolute(self):
        a = np.array([0.5, 0.6])
        assert score_gate_reason("binary", a, a + 0.05, 0.1) is None
        r = score_gate_reason("binary", a, a + 0.2, 0.1)
        assert r is not None and "probability drift" in r

    def test_regression_drift_is_relative(self):
        inc = np.array([100.0, 200.0])
        # 5 absolute on a scale of 200 = 2.5% relative: inside 10%
        assert score_gate_reason("regression", inc + 5.0, inc, 0.1) is None
        r = score_gate_reason("regression", inc + 50.0, inc, 0.1)
        assert r is not None and "relative score drift" in r

    def test_non_finite_and_shape_refused(self):
        inc = np.array([1.0, 2.0])
        assert "non-finite" in score_gate_reason(
            "regression", np.array([1.0, np.nan]), inc, 10.0)
        assert "shape" in score_gate_reason(
            "regression", np.array([1.0]), inc, 10.0)

    def test_degraded_incumbent_does_not_blind_the_gate(self):
        # NaN in the INCUMBENT poisons max(): every NaN comparison is
        # False, which used to pass ANY candidate exactly when serving
        # was already sick — the gate must judge on the finite entries
        inc = np.array([np.nan, 1.0, 2.0])
        cand = np.array([5.0, 1.0, 500.0])
        r = score_gate_reason("regression", cand, inc, 0.5)
        assert r is not None and "drift" in r
        # all-NaN incumbent: nothing sane to compare against — pass
        assert score_gate_reason(
            "regression", cand, np.full(3, np.nan), 0.5) is None

    def test_gate_metric_values(self):
        y = np.array([0.0, 1.0])
        name, v, hib = gate_metric_value("binary",
                                         np.array([0.1, 0.9]), y)
        assert name == "binary_logloss" and not hib
        assert v == pytest.approx(-np.mean([np.log(0.9), np.log(0.9)]))
        name, v, _ = gate_metric_value("regression",
                                       np.array([1.0, 3.0]),
                                       np.array([1.0, 1.0]))
        assert name == "l2" and v == pytest.approx(2.0)

    def test_lineage_gate_catches_tampered_prefix(self):
        x, y = _chunk(300, seed=1)
        m1 = lgb.train(dict(BASE), lgb.Dataset(x, label=y),
                       num_boost_round=3)
        m2 = lgb.train(dict(BASE), lgb.Dataset(x, label=y),
                       num_boost_round=3, init_model=m1)
        rows = x[:32]
        assert lineage_gate_reason(m2, m1, rows, 1.0, 1e-9) is None
        # corrupt one leading tree (on a text round-trip copy — the
        # merged booster SHARES tree objects with m1): the continuation
        # claim is now false
        m2 = lgb.Booster(model_str=m2.model_to_string())
        m2.trees[0].leaf_value = m2.trees[0].leaf_value + 0.5
        m2._drop_predict_cache()
        r = lineage_gate_reason(m2, m1, rows, 1.0, 1e-9)
        assert r is not None and "lineage parity violated" in r

    def test_lineage_gate_respects_decay(self):
        x, y = _chunk(300, seed=2)
        m1 = lgb.train(dict(BASE), lgb.Dataset(x, label=y),
                       num_boost_round=3)
        m2 = lgb.Booster(model_str=m1.model_to_string())
        for t in m2.trees:
            t.shrink(0.5)
        m2._drop_predict_cache()
        rows = x[:16]
        assert lineage_gate_reason(m2, m1, rows, 0.5, 1e-9) is None
        assert lineage_gate_reason(m2, m1, rows, 1.0, 1e-9) is not None

    def test_probe_timeout_is_a_failure(self):
        class Slow:
            trees = []

            def predict(self, rows):
                time.sleep(5.0)
                return np.zeros(len(rows))

        cfg = lgb.Config(dict(BASE))
        out = shadow_parity_probe(Slow(), Slow(),
                                  [np.zeros((4, 6))], cfg,
                                  timeout_s=0.2)
        assert not out["ok"] and "continual_timeout_s" in out["reason"]


# ---------------------------------------------------------------------------
# standalone trainer loop
# ---------------------------------------------------------------------------

class TestContinualStandalone:
    def test_generations_publish_and_freshen(self, tmp_path):
        p = _params(tmp_path)
        tr = ContinualTrainer(p, *_chunk(300, seed=3))
        reports = [tr.run_generation()]
        for s in (4, 5):
            reports.append(tr.run_generation(*_chunk(120, seed=s)))
        assert [r["status"] for r in reports] == ["published"] * 3
        assert [r["iteration"] for r in reports] == [3, 6, 9]
        assert tr.generation == 3
        # the newest complete snapshot is the freshest generation
        from lightgbm_tpu.snapshot import find_latest_complete_snapshot
        it, path = find_latest_complete_snapshot(p["output_model"])
        assert it == 9
        snap = tr.metrics.snapshot()
        assert snap["continual.published"]["value"] == 3
        assert snap["continual.rollbacks"]["value"] == 0
        assert snap["continual.freshness_lag_s"]["value"] > 0
        assert reports[-1]["freshness_lag_s"] > 0
        assert tr.freshness_lag_s() == pytest.approx(
            reports[-1]["freshness_lag_s"], abs=1e-6)

    def test_decay_shrinks_carried_trees(self, tmp_path):
        p = _params(tmp_path, continual_decay=0.5)
        tr = ContinualTrainer(p, *_chunk(300, seed=6))
        tr.run_generation()
        gen1 = lgb.Booster(model_file=p["output_model"]
                           + ".snapshot_iter_3")
        tr.run_generation(*_chunk(100, seed=7))
        gen2 = lgb.Booster(model_file=p["output_model"]
                           + ".snapshot_iter_6")
        # the carried trees' leaf values decayed by exactly 0.5
        for t1, t2 in zip(gen1.trees, gen2.trees[:3]):
            np.testing.assert_allclose(np.asarray(t2.leaf_value),
                                       0.5 * np.asarray(t1.leaf_value),
                                       rtol=1e-12)

    def test_decay_refused_for_linear_trees(self, tmp_path):
        p = _params(tmp_path, continual_decay=0.5, linear_tree=True)
        tr = ContinualTrainer(p, *_chunk(300, seed=8))
        tr.run_generation()
        rep = tr.run_generation(*_chunk(100, seed=9))
        assert rep["status"] == "rolled_back"
        assert "linear-tree" in rep["reason"]

    def test_gate_failure_rolls_back_and_quarantines(self, tmp_path):
        p = _params(tmp_path)
        tr = ContinualTrainer(p, *_chunk(300, seed=10))
        assert tr.run_generation()["status"] == "published"
        incumbent_text = tr._incumbent.model_to_string()
        faultinject.configure("shadow_probe:1-")
        rep = tr.run_generation(*_chunk(100, seed=11))
        faultinject.clear()
        assert rep["status"] == "rolled_back"
        assert rep["stage"] == "shadow_probe"
        # the incumbent is untouched and still the newest snapshot
        assert tr._incumbent.model_to_string() == incumbent_text
        from lightgbm_tpu.snapshot import find_latest_complete_snapshot
        assert find_latest_complete_snapshot(p["output_model"])[0] == 3
        # the candidate is quarantined with a blackbox dump
        q = tr.quarantine_dir
        names = os.listdir(q)
        assert "m.txt.snapshot_iter_6" in names
        assert "m.txt.snapshot_iter_6.manifest.json" in names
        bb = json.load(open(os.path.join(
            q, "m.txt.snapshot_iter_6.blackbox.json")))
        assert bb["stage"] == "shadow_probe"
        assert "shadow_probe" in bb["reason"] or "injected" in bb["reason"]
        snap = tr.metrics.snapshot()
        assert snap["continual.rollbacks"]["value"] == 1
        assert snap["continual.quarantined"]["value"] == 1
        # ...and the NEXT generation recovers from the incumbent
        rep2 = tr.run_generation(*_chunk(100, seed=12))
        assert rep2["status"] == "published"
        assert rep2["iteration"] == 6      # boosted from iter 3, not 6

    def test_transient_stage_faults_retried(self, tmp_path):
        # one trainer, one site per generation: each stage's retry must
        # carry its generation through a single transient fault
        p = _params(tmp_path, continual_retries=2)
        tr = ContinualTrainer(p, *_chunk(260, seed=13))
        assert tr.run_generation()["status"] == "published"
        for i, site in enumerate(["continual_append", "continual_boost",
                                  "continual_publish",
                                  "continual_promote"]):
            # arm AFTER the previous generation (configure resets hit
            # counters): the next occurrence of the site is hit 1
            faultinject.configure(f"{site}:1")
            rep = tr.run_generation(*_chunk(90, seed=14 + i))
            assert rep["status"] == "published", (site, rep)
            assert faultinject.hits(site) >= 2   # fault + retry
        assert tr.metrics.snapshot()["continual.rollbacks"]["value"] == 0

    def test_exhausted_retries_roll_back(self, tmp_path):
        p = _params(tmp_path, continual_retries=1)
        tr = ContinualTrainer(p, *_chunk(260, seed=15))
        assert tr.run_generation()["status"] == "published"
        faultinject.configure("continual_boost:1-")
        rep = tr.run_generation(*_chunk(90, seed=16))
        faultinject.clear()
        assert rep["status"] == "rolled_back"
        assert rep["stage"] == "boost"
        from lightgbm_tpu.snapshot import find_latest_complete_snapshot
        assert find_latest_complete_snapshot(p["output_model"])[0] == 3

    def test_probe_fault_is_gate_failure_not_retry(self, tmp_path):
        # a fault INSIDE the probe is conservative: never promote on an
        # unproven probe — rollback, even though retries remain
        p = _params(tmp_path, continual_retries=3)
        tr = ContinualTrainer(p, *_chunk(260, seed=17))
        assert tr.run_generation()["status"] == "published"
        faultinject.configure("shadow_probe:1")
        rep = tr.run_generation(*_chunk(90, seed=18))
        assert rep["status"] == "rolled_back"
        assert rep["stage"] == "shadow_probe"

    def test_snapshot_keep_clamped_above_one(self, tmp_path):
        tr = ContinualTrainer(_params(tmp_path, snapshot_keep=1),
                              *_chunk(100, seed=19))
        assert tr.config.snapshot_keep == 2


# ---------------------------------------------------------------------------
# serving integration: registry gate, /promote, /freshness
# ---------------------------------------------------------------------------

class TestServeIntegration:
    def _server(self, tmp_path, **kw):
        from lightgbm_tpu.serve.server import Server
        return Server(_params(tmp_path, serve_max_wait_ms=0.5, **kw))

    def test_pipeline_promotes_into_registry(self, tmp_path):
        srv = self._server(tmp_path)
        try:
            tr = ContinualTrainer(srv.config, *_chunk(300, seed=20),
                                  server=srv)
            r0 = tr.run_generation()
            assert r0["status"] == "published"
            assert srv.registry.current().version == r0["version"]
            # live traffic fills the shadow ring; the next gate replays it
            for _ in range(4):
                srv.predict(_rs.randn(8, 6))
            assert len(srv.shadow_batches()) == 4
            r1 = tr.run_generation(*_chunk(140, seed=21))
            assert r1["status"] == "published"
            assert srv.registry.current().version == r1["version"]
            assert r1["gate"]["probe"]["batches"] == 4
            fresh = srv.freshness()
            assert fresh["model_version"] == r1["version"]
            assert fresh["generation"] == 2
            assert fresh["generations_published"] == 2
            assert fresh["freshness_lag_s"] > 0
            # residency hygiene: with no serve_max_resident cap the
            # displaced incumbent is unloaded after the swap — a
            # long-running pipeline must not accumulate generations
            versions = [v["version"] for v in srv.registry.versions()]
            assert versions == [r1["version"]]
        finally:
            srv.close()

    def test_gate_failure_keeps_incumbent_serving(self, tmp_path):
        srv = self._server(tmp_path)
        try:
            tr = ContinualTrainer(srv.config, *_chunk(300, seed=22),
                                  server=srv)
            r0 = tr.run_generation()
            before = srv.predict(np.zeros((2, 6)))
            faultinject.configure("shadow_probe:1-")
            rep = tr.run_generation(*_chunk(100, seed=23))
            faultinject.clear()
            assert rep["status"] == "rolled_back"
            # the refused candidate is gone from the registry and the
            # incumbent answers byte-identically
            versions = [v["version"] for v in srv.registry.versions()]
            assert rep.get("version_refused") not in versions
            assert srv.registry.current().version == r0["version"]
            np.testing.assert_array_equal(
                srv.predict(np.zeros((2, 6))), before)
            assert srv.freshness()["generations_rolled_back"] == 1
        finally:
            srv.close()

    def test_http_promote_and_freshness(self, tmp_path):
        from lightgbm_tpu.serve.server import start_http
        srv = self._server(tmp_path)
        fe = start_http(srv, port=0)
        base = f"http://127.0.0.1:{fe.port}"

        def post(path, body):
            req = urllib.request.Request(
                base + path, data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            return json.loads(urllib.request.urlopen(req).read())

        try:
            tr = ContinualTrainer(srv.config, *_chunk(300, seed=24),
                                  server=srv)
            tr.run_generation()
            tr.run_generation(*_chunk(120, seed=25))
            out = str(tmp_path / "m.txt")
            # GET /freshness
            f = json.loads(urllib.request.urlopen(
                base + "/freshness").read())
            assert f["generation"] == 2 and f["freshness_lag_s"] > 0
            assert f["generations_published"] == 2
            # POST /promote of the newest artifact: gate passes
            ok = post("/promote", {"snapshot": out})
            assert ok["model_version"]
            assert ok["gate"]["probe"]["ok"] is True
            # POST /promote with a wrong pin: 409, reason + incumbent
            cur = srv.registry.current().version
            with pytest.raises(urllib.error.HTTPError) as ei:
                post("/promote", {"snapshot": out, "sha256": "0" * 64})
            assert ei.value.code == 409
            body = json.loads(ei.value.read())
            assert "checksum mismatch" in body["reason"]
            assert body["current_version"] == cur
            assert srv.registry.current().version == cur
        finally:
            fe.close()
            srv.close()

    def test_http_reload_409_carries_reason(self, tmp_path):
        from lightgbm_tpu.serve.server import start_http
        x, y = _chunk(200, seed=26)
        bst = lgb.train(dict(BASE), lgb.Dataset(x, label=y),
                        num_boost_round=2)
        mf = str(tmp_path / "m1.txt")
        bst.save_model(mf)
        from lightgbm_tpu.serve.server import Server
        srv = Server({"verbosity": -1}, booster=bst)
        fe = start_http(srv, port=0)
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{fe.port}/reload",
                data=json.dumps({"model_file": mf,
                                 "sha256": "f" * 64}).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req)
            assert ei.value.code == 409
            body = json.loads(ei.value.read())
            # satellite: the 409 BODY carries the verification failure
            # reason and the version still serving, not a bare status
            assert "checksum mismatch" in body["reason"]
            assert body["verification"] == "failed"
            assert body["current_version"] == "v1"
        finally:
            fe.close()
            srv.close()

    def test_unrelated_incumbent_skips_lineage_not_stuck(self, tmp_path):
        # an operator hot-swaps an UNRELATED hotfix model in: the next
        # generation is a continuation of the SNAPSHOT lineage, not of
        # the incumbent — the lineage gate must stand down (checksum
        # mismatch) instead of quarantining every generation forever.
        # (metric tolerance loosened: whether the candidate BEATS the
        # hotfix is the metric gate's call, not lineage's)
        srv = self._server(tmp_path, shadow_probe_metric_tolerance=10.0)
        try:
            tr = ContinualTrainer(srv.config, *_chunk(300, seed=29),
                                  server=srv)
            assert tr.run_generation()["status"] == "published"
            x, y = _chunk(300, seed=29)
            hotfix = lgb.train(dict(BASE, num_leaves=12),
                               lgb.Dataset(x, label=y),
                               num_boost_round=7)
            srv.reload(booster=hotfix)            # unpinned, unrelated
            rep = tr.run_generation(*_chunk(140, seed=30))
            assert rep["status"] == "published", rep
        finally:
            srv.close()

    def test_probe_batches_zero_disables_replay(self, tmp_path):
        srv = self._server(tmp_path, shadow_probe_batches=0)
        try:
            tr = ContinualTrainer(srv.config, *_chunk(300, seed=31),
                                  server=srv)
            assert tr.run_generation()["status"] == "published"
            srv.predict(_chunk(8, seed=31)[0])
            assert srv.shadow_batches() == []     # ring stays empty
            rep = tr.run_generation(*_chunk(120, seed=32))
            assert rep["status"] == "published"
            assert rep["gate"]["probe"]["batches"] == 0
        finally:
            srv.close()

    def test_self_check_failure_refuses_promotion(self, tmp_path):
        # serve_self_check fault: plain serving demotes to the host
        # walk; the continual gate REFUSES the candidate instead
        srv = self._server(tmp_path)
        try:
            tr = ContinualTrainer(srv.config, *_chunk(300, seed=27),
                                  server=srv)
            r0 = tr.run_generation()
            assert r0["status"] == "published"
            faultinject.configure("serve_self_check:1-")
            rep = tr.run_generation(*_chunk(100, seed=28))
            faultinject.clear()
            assert rep["status"] == "rolled_back"
            assert rep["stage"] == "self_check"
            assert srv.registry.current().version == r0["version"]
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# satellite: registry in-flight guards
# ---------------------------------------------------------------------------

class TestRegistryInflight:
    def _boosters(self, n=3):
        x, y = _chunk(200, seed=30)
        return [lgb.train(dict(BASE), lgb.Dataset(x, label=y),
                          num_boost_round=r) for r in range(2, 2 + n)]

    def test_unload_current_refused_force_allowed(self):
        from lightgbm_tpu.serve.registry import ModelRegistry, NoModelError
        reg = ModelRegistry(build_engine=False)
        b = self._boosters(1)[0]
        v = reg.load(booster=b)
        with pytest.raises(ValueError, match="current"):
            reg.unload(v)
        reg.unload(v, force=True)
        with pytest.raises(NoModelError):
            reg.current()

    def test_shadow_load_into_empty_registry_takes_no_traffic(self):
        # a gate candidate shadow-loaded into a model-less registry
        # must NOT auto-activate: the gated-promotion invariant is that
        # a refused candidate served ZERO requests, including during
        # the gate window before refusal
        from lightgbm_tpu.serve.registry import ModelRegistry, NoModelError
        reg = ModelRegistry(build_engine=False)
        v = reg.load(booster=self._boosters(1)[0], activate=False)
        with pytest.raises(NoModelError):
            reg.current()
        reg.activate(v)
        assert reg.current().version == v

    def test_eviction_skips_inflight_versions(self):
        from lightgbm_tpu.serve.registry import ModelRegistry
        b1, b2, b3 = self._boosters(3)
        reg = ModelRegistry(build_engine=False, max_resident=2)
        v1 = reg.load(booster=b1)                     # current
        v2 = reg.load(booster=b2, activate=False)     # shadow
        # a batch is mid-flight on the shadow version: the next load
        # would evict it (oldest non-current) — it must be skipped
        reg.get(v2).begin_request()
        v3 = reg.load(booster=b3, activate=False)
        versions = {v["version"] for v in reg.versions()}
        assert v2 in versions and v1 in versions and v3 in versions
        # batch finished: the NEXT load may evict it again
        reg.get(v2).end_request()
        b4 = self._boosters(1)[0]
        reg.load(booster=b4, activate=False)
        versions = {v["version"] for v in reg.versions()}
        assert v2 not in versions

    def test_inflight_counter_brackets_serving(self, tmp_path):
        from lightgbm_tpu.serve.server import Server
        x, y = _chunk(150, seed=31)
        bst = lgb.train(dict(BASE), lgb.Dataset(x, label=y),
                        num_boost_round=2)
        srv = Server({"verbosity": -1, "serve_max_wait_ms": 0.5},
                     booster=bst)
        try:
            srv.predict(x[:4])
            served = srv.registry.current()
            assert served.inflight == 0          # bracketed, not leaked
            assert served.describe()["inflight"] == 0
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# satellite: snapshot prune TOCTOU
# ---------------------------------------------------------------------------

class TestSnapshotPinning:
    def _make_snapshots(self, tmp_path, rounds=(2, 4, 6)):
        out = str(tmp_path / "m.txt")
        x, y = _chunk(200, seed=32)
        lgb.train(dict(BASE, snapshot_freq=2, snapshot_keep=0,
                       output_model=out),
                  lgb.Dataset(x, label=y), num_boost_round=max(rounds))
        return out

    def test_pinned_generation_survives_prune(self, tmp_path):
        from lightgbm_tpu.snapshot import pin_snapshot, prune_snapshots
        out = self._make_snapshots(tmp_path)
        oldest = out + ".snapshot_iter_2"
        with pin_snapshot(oldest):
            prune_snapshots(out, 1)
            assert os.path.exists(oldest)            # pinned: held
            assert not os.path.exists(out + ".snapshot_iter_4")
        prune_snapshots(out, 1)                      # unpinned: goes
        assert not os.path.exists(oldest)
        assert os.path.exists(out + ".snapshot_iter_6")

    def test_registry_rescans_once_on_pruned_snapshot(self, tmp_path,
                                                      monkeypatch):
        from lightgbm_tpu import snapshot as snap_mod
        from lightgbm_tpu.serve.registry import ModelRegistry
        out = self._make_snapshots(tmp_path)
        real = snap_mod.find_latest_complete_snapshot
        stale_path = out + ".snapshot_iter_9"        # never existed
        calls = []

        def finder(output_model, verify=True):
            calls.append(1)
            if len(calls) == 1:
                # the TOCTOU: the finder located a generation that a
                # concurrent prune deletes before the reader opens it
                return 9, stale_path
            return real(output_model, verify)

        monkeypatch.setattr(snap_mod, "find_latest_complete_snapshot",
                            finder)
        reg = ModelRegistry(build_engine=False)
        v = reg.load_snapshot(out)
        assert len(calls) == 2                       # re-scanned ONCE
        assert "snapshot_iter_6" in reg.get(v).source

    def test_resume_rescans_once_on_pruned_snapshot(self, tmp_path,
                                                    monkeypatch):
        out = str(tmp_path / "m.txt")
        x, y = _chunk(200, seed=33)
        p = dict(BASE, snapshot_freq=2, snapshot_keep=0,
                 output_model=out)
        straight = lgb.train(dict(p), lgb.Dataset(x, label=y),
                             num_boost_round=6)
        from lightgbm_tpu import snapshot as snap_mod
        real = snap_mod.find_latest_snapshot
        calls = []

        def finder(output_model, signature, train_set):
            calls.append(1)
            found = real(output_model, signature, train_set)
            if len(calls) == 1 and found is not None:
                it, path, score = found
                return it, str(tmp_path / "vanished.snapshot"), score
            return found

        monkeypatch.setattr(snap_mod, "find_latest_snapshot", finder)
        resumed = lgb.train(dict(p, resume=True),
                            lgb.Dataset(x, label=y), num_boost_round=6)
        assert len(calls) == 2
        assert resumed.model_to_string() == straight.model_to_string()


# ---------------------------------------------------------------------------
# satellite: best_iteration is absolute for continued runs
# ---------------------------------------------------------------------------

class TestBestIterationContinuation:
    def _stopping_feval(self, best_at):
        """Deterministic custom metric: improves until ``best_at`` calls,
        then worsens — early stopping fires with a known best."""
        calls = []

        def feval(preds, ds):
            it = len(calls)
            calls.append(it)
            return ("gate", abs(it - best_at) + 1.0, False)

        return feval

    def test_best_iteration_includes_init_model_trees(self, tmp_path):
        x, y = _chunk(400, seed=34)
        ds = lgb.Dataset(x, label=y, free_raw_data=False)
        m1 = lgb.train(dict(BASE), ds, num_boost_round=5)
        vs = lgb.Dataset(x[:100], label=y[:100])
        m2 = lgb.train(dict(BASE, metric="custom"),
                       lgb.Dataset(x, label=y, free_raw_data=False),
                       num_boost_round=10, valid_sets=[vs],
                       valid_names=["v"],
                       feval=self._stopping_feval(2), init_model=m1,
                       callbacks=[lgb.early_stopping(2, verbose=False)])
        # best is the continued run's 3rd iteration == absolute 5 + 3
        assert m2.best_iteration == 8
        # predict's best-iteration default slices the merged forest:
        # identical to an explicit absolute slice, and NOT to the
        # run-relative (wrong) slice
        np.testing.assert_array_equal(
            m2.predict(x[:50]), m2.predict(x[:50], num_iteration=8))
        assert not np.array_equal(
            m2.predict(x[:50]), m2.predict(x[:50], num_iteration=3))

    def test_save_continue_save_roundtrip_consistent(self, tmp_path):
        x, y = _chunk(400, seed=35)
        m1 = lgb.train(dict(BASE),
                       lgb.Dataset(x, label=y, free_raw_data=False),
                       num_boost_round=4)
        p1 = str(tmp_path / "m1.txt")
        m1.save_model(p1)
        vs = lgb.Dataset(x[:100], label=y[:100])
        m2 = lgb.train(dict(BASE, metric="custom"),
                       lgb.Dataset(x, label=y, free_raw_data=False),
                       num_boost_round=8, valid_sets=[vs],
                       valid_names=["v"],
                       feval=self._stopping_feval(1), init_model=p1,
                       callbacks=[lgb.early_stopping(2, verbose=False)])
        assert m2.best_iteration == 4 + 2
        # save at best -> reload -> predictions match the live booster's
        # best-sliced predictions (the round-trip the satellite pins)
        p2 = str(tmp_path / "m2.txt")
        m2.save_model(p2, num_iteration=m2.best_iteration)
        reloaded = lgb.Booster(model_file=p2)
        np.testing.assert_array_equal(reloaded.predict(x[:64]),
                                      m2.predict(x[:64]))

    def test_resume_best_iteration_unchanged(self, tmp_path):
        # a RESUMED run's loop index is already absolute — the offset
        # must not double-count (regression guard for the fix)
        out = str(tmp_path / "m.txt")
        x, y = _chunk(300, seed=36)
        # metric in BOTH runs' params: the resume's params signature
        # must match the snapshot writer's or nothing resumes
        p = dict(BASE, snapshot_freq=2, output_model=out,
                 metric="custom")
        lgb.train(dict(p), lgb.Dataset(x, label=y), num_boost_round=4)
        vs = lgb.Dataset(x[:80], label=y[:80])
        m = lgb.train(dict(p, resume=True),
                      lgb.Dataset(x, label=y), num_boost_round=10,
                      valid_sets=[vs], valid_names=["v"],
                      feval=self._stopping_feval(1),
                      callbacks=[lgb.early_stopping(2, verbose=False)])
        # resume continues at iteration 4; the feval's first call is
        # iteration 5 (env.iteration 4), best at its 2nd call -> abs 6
        assert m.best_iteration == 6


# ---------------------------------------------------------------------------
# satellite: kill -9 matrix at every stage boundary
# ---------------------------------------------------------------------------

class TestKillMatrix:
    N_CHUNKS = 1    # two generations: incumbent + the one under fire
    WORKER = os.path.join(REPO, "tests", "continual_worker.py")

    def _spawn(self, outdir, faults=None):
        env = dict(os.environ)
        env.pop("LGBM_TPU_FAULTS", None)
        if faults:
            env["LGBM_TPU_FAULTS"] = faults
        return subprocess.Popen(
            [sys.executable, self.WORKER, str(outdir),
             str(self.N_CHUNKS)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env)

    @staticmethod
    def _wait(procs, timeout=240):
        """{name: (returncode, output)} for a batch of concurrent
        workers (the matrix runs its independent dirs in parallel to
        stay inside the tier-1 wall-clock budget)."""
        out = {}
        for name, p in procs.items():
            try:
                stdout, _ = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                p.kill()
                stdout, _ = p.communicate()
                stdout = (stdout or "") + "\n<worker timed out>"
            out[name] = (p.returncode, stdout)
        return out

    def _run_worker(self, outdir, faults=None, timeout=240):
        p = self._spawn(outdir, faults=faults)
        rc, stdout = self._wait({"one": p}, timeout=timeout)["one"]

        class R:
            returncode, output = rc, stdout

        return R

    def _audit_disk(self, outdir):
        """After a kill, every COMPLETE snapshot must verify and a
        serving bring-up from disk must succeed — the dead pipeline
        never leaves serving without a verified incumbent."""
        from lightgbm_tpu.serve.registry import ModelRegistry
        from lightgbm_tpu.snapshot import (find_latest_complete_snapshot,
                                           verify_snapshot_artifacts)
        out = os.path.join(str(outdir), "m.txt")
        for man in glob.glob(out + ".snapshot_iter_*.manifest.json"):
            path = man[:-len(".manifest.json")]
            with open(man, encoding="utf-8") as f:
                assert verify_snapshot_artifacts(
                    path, json.load(f), state=True) is None, path
        found = find_latest_complete_snapshot(out)
        if found is not None:
            reg = ModelRegistry(build_engine=False)
            reg.load_snapshot(out)
            assert reg.current() is not None

    @staticmethod
    def _normalize(text):
        """The one legitimately path-dependent byte of a published
        model: its own output_model parameter line."""
        return "\n".join(ln for ln in text.splitlines()
                         if not ln.startswith("[output_model:"))

    def test_kill_exit_matrix_converges_byte_identical(self, tmp_path):
        # the clean reference run goes first, alone — it also warms the
        # persistent compile cache for the concurrent batches below
        clean = tmp_path / "clean"
        clean.mkdir()
        r = self._run_worker(clean)
        assert r.returncode == 0, r.output
        final_clean = self._normalize(
            open(clean / "final.txt", encoding="utf-8").read())
        # fault spec per stage boundary: hit indices target the SECOND
        # generation (the base generation must land so there is an
        # incumbent to protect); snapshot_kill:5 dies mid-publish
        # between the model and manifest writes — the torn-write window
        matrix = {
            "continual_append": "continual_append:1:exit",
            "continual_boost": "continual_boost:2:exit",
            "continual_publish": "continual_publish:2:exit",
            "continual_promote": "continual_promote:2:exit",
            "shadow_probe": "shadow_probe:1:exit",
            "publish_torn_write": "snapshot_kill:5:exit",
        }
        for name in matrix:
            (tmp_path / name).mkdir()
        # batch 1: every stage-boundary kill, concurrently (independent
        # dirs; serializing 12 jax subprocesses would not fit tier-1)
        killed = self._wait({name: self._spawn(tmp_path / name,
                                               faults=spec)
                             for name, spec in matrix.items()})
        for name, (rc, output) in killed.items():
            assert rc == 23, (f"{name}: expected injected exit(23), "
                              f"got {rc}\n{output}")
            # serving invariant while the pipeline is dead
            self._audit_disk(tmp_path / name)
        # batch 2: restart every dir with no faults — byte-identical
        # convergence with the uninterrupted run
        resumed = self._wait({name: self._spawn(tmp_path / name)
                              for name in matrix})
        for name, (rc, output) in resumed.items():
            assert rc == 0, f"{name}: restart failed\n{output}"
            final = self._normalize(
                open(tmp_path / name / "final.txt",
                     encoding="utf-8").read())
            assert final == final_clean, \
                f"{name}: restart did not converge byte-identically"


# ---------------------------------------------------------------------------
# chaos soak (tools/soak_serve.py --continual) — short tier-1 run
# ---------------------------------------------------------------------------

class TestContinualSoak:
    def test_short_continual_soak_with_gate_failure(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import soak_serve
        report = soak_serve.run_continual_soak(
            duration_s=1.5, clients=2, generations=2, seed=0,
            gate_failure=True)
        assert report["violations"] == [], report
        gens = report["generations"]
        assert gens[0]["status"] == "published"      # base incumbent
        assert gens[1]["status"] == "rolled_back"    # injected gate fail
        assert gens[2]["status"] == "published"      # recovery
        assert report["metrics"]["continual.rollbacks"]["value"] == 1
        assert report["freshness"]["generations_published"] == 2
        assert report["counts"].get("hung", 0) == 0
        assert report["counts"]["ok"] > 0


# ---------------------------------------------------------------------------
# CLI task=continual
# ---------------------------------------------------------------------------

class TestContinualCLI:
    def test_task_continual_end_to_end(self, tmp_path, capsys):
        from lightgbm_tpu.cli import run as cli_run

        def write_csv(path, n, seed):
            x, y = _chunk(n, seed=seed, n_feat=4)
            np.savetxt(path, np.column_stack([y, x]), delimiter=",",
                       fmt="%.8g")

        base = str(tmp_path / "base.csv")
        c1 = str(tmp_path / "c1.csv")
        c2 = str(tmp_path / "c2.csv")
        write_csv(base, 200, 40)
        write_csv(c1, 80, 41)
        write_csv(c2, 80, 42)
        out = str(tmp_path / "m.txt")
        rc = cli_run(["task=continual", f"data={base}",
                      f"continual_data={c1},{c2}", f"output_model={out}",
                      "continual_rounds=2", "num_leaves=6",
                      "min_data_in_leaf=5", "verbosity=-1"])
        assert rc == 0
        lines = capsys.readouterr().out.strip().splitlines()
        reports = [json.loads(ln) for ln in lines
                   if ln.startswith("{")]
        assert len(reports) == 3
        assert all(r["status"] == "published" for r in reports)
        assert [r["iteration"] for r in reports] == [2, 4, 6]
        from lightgbm_tpu.snapshot import find_latest_complete_snapshot
        assert find_latest_complete_snapshot(out)[0] == 6

    def test_bare_continual_token(self, tmp_path):
        from lightgbm_tpu.cli import _load_params
        p = _load_params(["continual", "data=x.csv"])
        assert p["task"] == "continual"
