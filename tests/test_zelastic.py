"""Elastic pod-scale training (parallel/elastic.py; ISSUE 14).

Covers the liveness layer (watchdog cancel-and-raise mode, heartbeats,
collective deadline), the shrink-to-survive recovery ladder (chaos soak
via tools/soak_train.py), the topology-volatile snapshot signature, the
``launch.init`` success-only latch, and the kill -9 subprocess matrix:
a 2-process ``jax.distributed`` run losing a worker mid-iteration must
detect the loss within the heartbeat deadline, persist the shrink
request, and — relaunched shrunk — converge byte-identically (int32
quant path) to an uninterrupted serial run."""

import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.parallel import elastic
from lightgbm_tpu.utils import faultinject
from lightgbm_tpu.utils.resilience import (Watchdog, WatchdogTimeout,
                                           is_retryable_device_error)

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(autouse=True)
def _clean_faults():
    faultinject.clear()
    yield
    faultinject.clear()


def _small_data(n=300, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, 6)
    y = (x[:, 0] - x[:, 1] > 0).astype("float32")
    return x, y


def _trees(bst_or_text):
    text = bst_or_text if isinstance(bst_or_text, str) \
        else bst_or_text.model_to_string()
    return text.split("parameters:")[0].split("feature_infos")[1]


# ---------------------------------------------------------------------------
# Watchdog cancel-and-raise mode (utils/resilience.py)
# ---------------------------------------------------------------------------

class TestWatchdogRaiseMode:
    def test_deadline_raises_classified_timeout_in_waiting_thread(self):
        wd = Watchdog(0.3, label="hung call", on_timeout="raise")
        t0 = time.monotonic()
        with pytest.raises(WatchdogTimeout) as ei:
            wd.run(time.sleep, 5.0)
        assert time.monotonic() - t0 < 3.0       # not the sleep's 5 s
        assert "hung call" in str(ei.value)
        # the classifier must treat the abandoned call as transient so
        # retry/backoff and the elastic ladder re-attempt it
        assert is_retryable_device_error(ei.value)

    def test_raise_mode_returns_value_and_relays_exceptions(self):
        wd = Watchdog(5.0, on_timeout="raise")
        assert wd.run(lambda a, b=0: a + b, 2, b=3) == 5
        with pytest.raises(KeyError):
            Watchdog(5.0, on_timeout="raise").run(
                lambda: (_ for _ in ()).throw(KeyError("x")))

    def test_dump_only_stays_default(self):
        # REGRESSION CONTRACT: the historical dump-only behavior is the
        # default — run() executes inline and NEVER raises on overrun
        wd = Watchdog(0.05)
        assert wd.on_timeout == "dump"
        t0 = time.monotonic()
        assert wd.run(lambda: (time.sleep(0.2), "done")[1]) == "done"
        assert time.monotonic() - t0 >= 0.2      # ran to completion
        with Watchdog(0.05, label="cm"):         # CM form unchanged
            time.sleep(0.1)

    def test_disabled_timeout_runs_inline(self):
        assert Watchdog(0.0, on_timeout="raise").run(lambda: 7) == 7

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError):
            Watchdog(1.0, on_timeout="explode")


# ---------------------------------------------------------------------------
# Fault-injection sites: hang action, new site defaults
# ---------------------------------------------------------------------------

class TestHangSites:
    def test_hang_is_default_for_wedge_sites_and_bounded(self, monkeypatch):
        monkeypatch.setenv(faultinject.HANG_ENV_VAR, "0.2")
        faultinject.configure("collective_hang:1")
        t0 = time.monotonic()
        faultinject.check("collective_hang")     # blocks ~0.2 s, no raise
        assert 0.15 <= time.monotonic() - t0 < 2.0

    def test_claim_wedge_known_and_hangs(self, monkeypatch):
        monkeypatch.setenv(faultinject.HANG_ENV_VAR, "0.1")
        faultinject.configure("claim_wedge:1")
        t0 = time.monotonic()
        faultinject.check("claim_wedge")
        assert time.monotonic() - t0 >= 0.05

    def test_explicit_actions_still_validated(self):
        with pytest.raises(ValueError):
            faultinject.configure("collective_hang:1:melt")
        faultinject.configure("collective_hang:1:raise")
        with pytest.raises(faultinject.InjectedFault):
            faultinject.check("collective_hang")


# ---------------------------------------------------------------------------
# Liveness: heartbeat writer + staleness monitor + guarded fetch
# ---------------------------------------------------------------------------

class TestLiveness:
    def test_heartbeat_and_monitor_detect_stale_peer(self, tmp_path):
        hb1 = elastic.Heartbeat(str(tmp_path), 1, interval_s=0.1).start()
        mon = elastic.HeartbeatMonitor(str(tmp_path), 0, timeout_s=0.6,
                                       interval_s=0.1)
        try:
            deadline = time.monotonic() + 3.0
            while 1 not in mon.peers() and time.monotonic() < deadline:
                mon.check()                     # registers the live peer
                time.sleep(0.05)
            assert mon.peers() == [1]
            mon.check()                         # fresh: no failure
        finally:
            hb1.stop()                          # the "kill"
        t0 = time.monotonic()
        with pytest.raises(elastic.ElasticFailure) as ei:
            while True:
                time.sleep(0.05)
                mon.check()
                if time.monotonic() - t0 > 5.0:
                    break
        assert ei.value.kind == "host_loss"
        # detected within the heartbeat deadline (+ slack for the scan
        # rate limit)
        assert time.monotonic() - t0 < 2.5

    def test_monitor_skew_immune_progress_based(self, tmp_path):
        # liveness is judged by observed mtime PROGRESS on the
        # monitor's monotonic clock, not by now - mtime: a live peer
        # whose host (or fileserver) clock is far behind must register
        # and stay fresh, while a relic file that never advances must
        # never become a peer
        mon = elastic.HeartbeatMonitor(str(tmp_path), 0, timeout_s=0.5,
                                       interval_s=0.1)
        path = os.path.join(str(tmp_path), "hb_7.json")
        skew = 120.0                      # absolute mtimes hopelessly stale

        def beat(k):
            with open(path, "w", encoding="utf-8") as f:
                f.write("{}")
            t = time.time() - skew + 0.05 * k
            os.utime(path, (t, t))

        beat(0)
        assert mon._scan() == ([], [])    # relic so far: not a peer
        for k in range(1, 4):             # advancing = alive, just skewed
            time.sleep(0.02)
            mon._scan()
            beat(k)
        fresh, lost = mon._scan()
        assert (fresh, lost) == ([7], [])
        t0 = time.monotonic()             # stops beating -> lost
        with pytest.raises(elastic.ElasticFailure) as ei:
            while time.monotonic() - t0 < 5.0:
                time.sleep(0.05)
                mon.check()
        assert ei.value.kind == "host_loss"
        assert time.monotonic() - t0 < 2.5

    def test_survivors_include_self_and_fresh_peers(self, tmp_path):
        hb = elastic.Heartbeat(str(tmp_path), 3, interval_s=0.1).start()
        try:
            mon = elastic.HeartbeatMonitor(str(tmp_path), 0,
                                           timeout_s=5.0, interval_s=0.1)
            assert mon.survivors() == [0, 3]
        finally:
            hb.stop()

    def test_guarded_get_bounds_a_hung_fetch(self, monkeypatch):
        import jax.numpy as jnp
        monkeypatch.setenv(faultinject.HANG_ENV_VAR, "5")
        faultinject.configure("collective_hang:1")
        t0 = time.monotonic()
        with pytest.raises(elastic.ElasticFailure) as ei:
            elastic.guarded_get(jnp.ones(3), 0.3, site="fetch")
        assert ei.value.kind == "collective_timeout"
        assert time.monotonic() - t0 < 3.0
        faultinject.clear()
        out = elastic.guarded_get(jnp.arange(3), 5.0)
        np.testing.assert_array_equal(out, [0, 1, 2])

    def test_check_peers_host_loss_injection(self):
        faultinject.configure("host_loss:1")
        with pytest.raises(elastic.ElasticFailure) as ei:
            elastic.check_peers()
        assert ei.value.kind == "host_loss"
        faultinject.clear()
        elastic.check_peers()                   # disarmed: no-op

    def test_failure_kind_classification(self):
        assert elastic.failure_kind(
            elastic.ElasticFailure("host_loss")) == "host_loss"
        assert elastic.failure_kind(
            WatchdogTimeout("x", 1.0)) == "collective_timeout"
        assert elastic.failure_kind(
            RuntimeError("UNAVAILABLE: claim hung")) == "bringup"
        assert elastic.failure_kind(TypeError("bug")) is None


# ---------------------------------------------------------------------------
# Config + snapshot-signature contracts
# ---------------------------------------------------------------------------

class TestElasticConfig:
    def test_validation(self):
        from lightgbm_tpu.config import Config
        with pytest.raises(ValueError):
            Config({"elastic_heartbeat_interval_s": 0})
        with pytest.raises(ValueError):
            Config({"elastic_heartbeat_interval_s": 2.0,
                    "elastic_heartbeat_timeout_s": 1.0})
        with pytest.raises(ValueError):
            Config({"elastic_retries": -1})
        with pytest.raises(ValueError):
            Config({"elastic_collective_timeout_s": -1})
        Config({"elastic_enable": True})        # defaults coherent

    def test_signature_topology_volatile_only_under_elastic(self):
        from lightgbm_tpu.snapshot import params_signature
        base = {"objective": "binary", "num_leaves": 15}
        el = dict(base, elastic_enable=True)
        # elastic: topology + every elastic_* knob is run control
        assert params_signature(dict(el, tree_learner="data",
                                     mesh_shape=[8])) \
            == params_signature(dict(el, tree_learner="serial"))
        assert params_signature(
            dict(el, elastic_collective_timeout_s=7.0)) \
            == params_signature(el)
        # non-elastic: topology stays signature-relevant
        assert params_signature(dict(base, tree_learner="data")) \
            != params_signature(dict(base, tree_learner="serial"))
        # the model surface still invalidates under elastic
        assert params_signature(dict(el, num_leaves=31)) \
            != params_signature(el)

    def test_disabled_elastic_is_byte_identical(self):
        x, y = _small_data()
        p = {"objective": "binary", "num_leaves": 8, "max_bin": 31,
             "min_data_in_leaf": 5, "verbosity": -1}
        b_off = lgb.train(dict(p), lgb.Dataset(x, label=y),
                          num_boost_round=4)
        b_on = lgb.train(dict(p, elastic_enable=True),
                         lgb.Dataset(x, label=y), num_boost_round=4)
        assert _trees(b_off) == _trees(b_on)


class TestMultiProcessResumeContract:
    def test_global_fp_override_and_score_slicing(self, tmp_path):
        # the survivors>1 relaunch contract, unit-level: a SHARD
        # dataset carrying elastic_global_fingerprint must match a
        # manifest stamped with the GLOBAL fingerprint, and engine
        # resume must slice the global score to elastic_row_range —
        # without both, a multi-process relaunch silently restarts
        # from iteration 0 (or crashes feeding a global score to a
        # shard-sized dataset)
        from lightgbm_tpu import engine
        from lightgbm_tpu.dataset import fingerprint_arrays
        from lightgbm_tpu.snapshot import find_latest_snapshot
        x, y = _small_data(200)
        p = {"objective": "binary", "num_leaves": 8, "max_bin": 31,
             "min_data_in_leaf": 5, "verbosity": -1,
             "elastic_enable": True, "snapshot_freq": 2,
             "output_model": str(tmp_path / "m.txt")}
        lgb.train(dict(p), lgb.Dataset(x, label=y), num_boost_round=4)
        # forge the global-state manifest a pc>1 run would write: the
        # serial snapshot's score/fingerprint ARE global here (pc=1),
        # so only the shard side of the contract needs exercising
        shard = lgb.Dataset(x[:50], label=y[:50])
        from lightgbm_tpu.snapshot import params_signature
        sig = params_signature(dict(p))
        # the shard's own fingerprint must NOT match the manifest
        assert find_latest_snapshot(str(tmp_path / "m.txt"), sig,
                                    shard) is None
        shard.elastic_global_fingerprint = fingerprint_arrays(y, None)
        found = find_latest_snapshot(str(tmp_path / "m.txt"), sig,
                                     shard)
        assert found is not None and found[0] >= 2
        assert found[2].shape[0] == 200          # global rows
        # engine resume on the shard: global score sliced to [0, 50) —
        # an unsliced 200-row init score would raise on the 50-row set
        shard.elastic_row_range = (0, 50)
        bst = engine.train(dict(p, resume=True), shard,
                           num_boost_round=4)
        assert len(bst.trees) >= 4


class TestLaunchLatch:
    def test_done_latched_only_on_success(self, monkeypatch):
        from lightgbm_tpu.parallel import launch
        import jax
        monkeypatch.delattr(launch.init, "_done", raising=False)
        launch.init._fail_t = None
        calls = {"n": 0}

        def failing_init(**kw):
            calls["n"] += 1
            raise RuntimeError("UNAVAILABLE: coordination service down")

        monkeypatch.setattr(jax.distributed, "initialize", failing_init)
        launch.init(retries=0, timeout_s=0)     # auto path: warn + solo
        # the failed bring-up must NOT latch: a later attempt retries
        assert not getattr(launch.init, "_done", False)
        monkeypatch.setattr(jax.distributed, "initialize",
                            lambda **kw: None)
        launch.init(retries=0, timeout_s=0)
        assert launch.init._done is True
        assert launch.init._fail_t is None
        assert calls["n"] == 1
        monkeypatch.delattr(launch.init, "_done", raising=False)

    def test_auto_failure_cooldown_skips_reattempt(self, monkeypatch):
        # the pre-elastic code latched _done permanently after a failed
        # AUTO bring-up; elastic made it retryable — but a cooldown of
        # one deadline must keep a permanently-down coordination
        # service from re-burning the full retry budget on EVERY
        # train() call
        from lightgbm_tpu.parallel import launch
        import jax
        monkeypatch.delattr(launch.init, "_done", raising=False)
        launch.init._fail_t = None
        calls = {"n": 0}

        def failing_init(**kw):
            calls["n"] += 1
            raise RuntimeError("UNAVAILABLE: coordination service down")

        monkeypatch.setattr(jax.distributed, "initialize", failing_init)
        launch.init(retries=0, timeout_s=30.0)   # fails, stamps _fail_t
        assert calls["n"] == 1
        launch.init(retries=0, timeout_s=30.0)   # inside cooldown: solo
        assert calls["n"] == 1
        launch.init._fail_t = time.monotonic() - 60.0   # cooldown over
        launch.init(retries=0, timeout_s=30.0)   # retried
        assert calls["n"] == 2
        launch.init._fail_t = None
        monkeypatch.delattr(launch.init, "_done", raising=False)


# ---------------------------------------------------------------------------
# Recovery ladder: in-process chaos soak (tools/soak_train.py)
# ---------------------------------------------------------------------------

class TestRecoveryLadder:
    def test_chaos_soak_shrinks_and_matches_serial(self, tmp_path):
        sys.path.insert(0, os.path.join(os.path.dirname(HERE), "tools"))
        import soak_train
        elastic.reset_metrics()
        rep = soak_train.run_soak_train(
            rounds=10, n_rows=350, mesh=4, hang_s=4.0,
            collective_timeout_s=0.8, budget_s=180.0,
            workdir=str(tmp_path))
        assert rep["violations"] == [], rep
        assert rep["report"]["shrinks"] >= 1
        assert rep["report"]["recoveries"] >= 1
        kinds = {f["kind"] for f in rep["report"]["failures"]}
        assert "collective_timeout" in kinds
        # failure events persisted next to the model
        ev_path = os.path.join(str(tmp_path),
                               "soak_model.txt.elastic.jsonl")
        events = [json.loads(ln)
                  for ln in open(ev_path, encoding="utf-8")]
        assert any(e["event"] == "shrink" for e in events)
        assert any(e["event"] == "recovered" for e in events)

    def test_ladder_reraises_unclassified_errors(self, tmp_path):
        x, y = _small_data(120)
        # CEGB is unsupported under tree_learner=data: a programming /
        # configuration error the ladder must surface, never retry
        p = {"objective": "binary", "tree_learner": "data",
             "mesh_shape": [2], "cegb_penalty_split": 0.5,
             "verbosity": -1,
             "output_model": str(tmp_path / "m.txt")}
        with pytest.raises(ValueError) as ei:
            elastic.elastic_train(p, x, y, num_boost_round=2)
        assert elastic.failure_kind(ei.value) is None


# ---------------------------------------------------------------------------
# kill -9 of a mesh worker mid-iteration (2 REAL jax.distributed
# processes, gloo collectives), then shrunk-relaunch convergence
# ---------------------------------------------------------------------------

def _free_ports(n):
    socks, ports = [], []
    try:
        for _ in range(n):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
            ports.append(s.getsockname()[1])
    finally:
        for s in socks:
            s.close()
    return ports


class TestKillMeshWorker:
    def test_kill9_detect_shrink_resume_bitwise(self, tmp_path):
        import elastic_worker as ew
        outdir = str(tmp_path)
        env = dict(os.environ, ELASTIC_WORKER_QUANT="1")
        env.pop("XLA_FLAGS", None)      # workers pin their own topology
        worker = os.path.join(HERE, "elastic_worker.py")
        ports = _free_ports(2)
        machines = ",".join(f"127.0.0.1:{p}" for p in ports)

        logs = [open(os.path.join(outdir, f"w{r}.log"), "w+")
                for r in (0, 1)]
        procs = [subprocess.Popen(
            [sys.executable, worker, outdir, "worker", str(r), machines],
            env=env, stdout=logs[r], stderr=subprocess.STDOUT)
            for r in (0, 1)]
        t0 = time.monotonic()
        rcs = [p.wait(timeout=240) for p in procs]
        wall = time.monotonic() - t0
        outs = []
        for lg in logs:
            lg.flush()
            lg.seek(0)
            outs.append(lg.read())
            lg.close()
        # rank 1 SIGKILLed itself mid-iteration
        assert "WORKER_KILLING_SELF" in outs[1], outs[1][-2000:]
        assert rcs[1] == -9, (rcs, outs[1][-500:])
        # rank 0 classified the loss and requested a shrink
        assert rcs[0] == ew.SHRINK_RC, (rcs, outs[0][-3000:])
        marker = json.load(open(os.path.join(outdir, "shrink_0.json"),
                                encoding="utf-8"))
        assert marker["kind"] in ("host_loss", "collective_timeout",
                                  "bringup")
        assert marker["survivors"] == [0]
        # detection bounded by the liveness deadlines (heartbeat 2 s /
        # collective 4 s), not by the 240 s harness timeout
        assert marker["detect_s"] < 15.0, marker
        assert wall < 200.0
        # a COMPLETE snapshot from before the kill exists with GLOBAL
        # state (full-data fingerprint + full-row score)
        from lightgbm_tpu.snapshot import find_latest_complete_snapshot
        found = find_latest_complete_snapshot(
            os.path.join(outdir, "m.txt"))
        assert found is not None and found[0] >= ew.SNAPSHOT_FREQ
        man = json.load(open(found[1] + ".manifest.json",
                             encoding="utf-8"))
        assert man["num_data"] == 320   # global rows, not a shard

        # shrunk relaunch (the pod-launcher contract): must resume the
        # 2-process snapshot and finish the remaining rounds
        r = subprocess.run([sys.executable, worker, outdir, "resume"],
                           env=env, capture_output=True, text=True,
                           timeout=240)
        assert "WORKER_DONE resume" in r.stdout, \
            r.stdout[-2000:] + r.stderr[-3000:]
        # uninterrupted serial oracle
        r2 = subprocess.run([sys.executable, worker, outdir, "serial"],
                            env=env, capture_output=True, text=True,
                            timeout=240)
        assert "WORKER_DONE serial" in r2.stdout, r2.stderr[-3000:]
        final = open(os.path.join(outdir, "final.txt"),
                     encoding="utf-8").read()
        serial = open(os.path.join(outdir, "serial.txt"),
                      encoding="utf-8").read()
        # int32 quant path: dp histograms == serial bitwise, so the
        # kill + shrink + resume run is BYTE-IDENTICAL to never failing
        assert _trees(final) == _trees(serial)
