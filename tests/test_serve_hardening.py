"""Serving hardening tests (ISSUE 5, docs/Serving.md "Hardening").

Deadlines enforced before device work (fail-fast admission + queue
shedding, HTTP 504), the serving circuit breaker (admission-time 503 +
Retry-After while the device side fails, half-open recovery, request
errors never trip it), graceful drain (queued work answered, new work
refused, readiness flips), verified artifacts (manifest SHA-256
checksums, refuse-don't-load on mismatch, engine byte-parity self-check
with host-walk fallback), and the chaos-injection soak harness
(tools/soak_serve.py) run short and deterministic in tier-1.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.serve import (ArtifactVerificationError, BatcherDraining,
                                CircuitOpen, DeadlineExceeded, MicroBatcher,
                                ModelRegistry, PredictorEngine, Server,
                                start_http)
from lightgbm_tpu.utils.resilience import CircuitBreaker

TOOLS = os.path.join(os.path.dirname(__file__), "..", "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)


def _train(rounds=8, seed=0, n=300, f=5):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, f)
    y = x[:, 0] + 0.5 * x[:, 1]
    return lgb.train({"objective": "regression", "verbosity": -1,
                      "num_leaves": 8}, lgb.Dataset(x, label=y),
                     num_boost_round=rounds)


@pytest.fixture(scope="module")
def booster():
    return _train()


# ---------------------------------------------------------------------------
# circuit breaker: state machine (utils/resilience.py)
# ---------------------------------------------------------------------------

class TestCircuitBreakerUnit:
    def _cb(self, **kw):
        clock = {"t": 0.0}
        cb = CircuitBreaker(clock=lambda: clock["t"], **kw)
        return cb, clock

    def test_trips_after_consecutive_failures_only(self):
        cb, _ = self._cb(failure_threshold=3, cooldown_s=1.0)
        for _ in range(2):
            cb.record_failure()
        cb.record_success()              # resets the consecutive count
        for _ in range(2):
            cb.record_failure()
        assert cb.state() == "closed" and cb.allow()
        cb.record_failure()              # 3rd consecutive: trip
        assert cb.state() == "open" and not cb.allow()
        assert cb.opens == 1
        assert 0 < cb.retry_after_s() <= 1.0

    def test_half_open_probe_success_closes_and_resets_cooldown(self):
        cb, clock = self._cb(failure_threshold=1, cooldown_s=1.0,
                             cooldown_max_s=8.0)
        cb.record_failure()
        assert not cb.allow()
        clock["t"] = 1.1
        assert cb.state() == "half_open" and cb.allow()
        cb.record_success()
        assert cb.state() == "closed"
        assert cb.describe()["cooldown_s"] == 1.0

    def test_half_open_failure_doubles_cooldown_capped(self):
        cb, clock = self._cb(failure_threshold=1, cooldown_s=1.0,
                             cooldown_max_s=4.0)
        cb.record_failure()              # open, cooldown 1
        expected = [2.0, 4.0, 4.0]       # doubles, then the cap holds
        for cd in expected:
            clock["t"] += 10.0
            assert cb.allow()            # half-open probe
            cb.record_failure()          # probe fails: re-open
            assert cb.state() == "open"
            assert cb.describe()["cooldown_s"] == cd
        assert cb.opens == 1 + len(expected)

    def test_open_late_failures_do_not_extend_cooldown(self):
        cb, clock = self._cb(failure_threshold=1, cooldown_s=1.0)
        cb.record_failure()
        until = cb.retry_after_s()
        cb.record_failure()              # in-flight straggler
        assert cb.retry_after_s() == until
        assert cb.opens == 1

    def test_half_open_admits_exactly_one_probe(self):
        cb, clock = self._cb(failure_threshold=1, cooldown_s=1.0)
        cb.record_failure()
        clock["t"] = 1.5
        assert cb.allow()                # THE probe
        assert not cb.allow()            # burst behind it: rejected
        assert not cb.allow()
        cb.record_success()              # probe outcome lands
        assert cb.allow() and cb.allow()     # closed: everyone admitted

    def test_abandoned_probe_expires(self):
        cb, clock = self._cb(failure_threshold=1, cooldown_s=1.0)
        cb.record_failure()
        clock["t"] = 1.5
        assert cb.allow()                # probe... whose outcome is lost
        assert not cb.allow()
        clock["t"] = 3.0                 # > probe start + cooldown
        assert cb.allow()                # a new probe may try

    def test_zero_cooldown_floored_still_rejects(self):
        # cooldown 0 must not degenerate into everyone-is-the-probe
        cb, clock = self._cb(failure_threshold=1, cooldown_s=0.0)
        cb.record_failure()
        assert not cb.allow()            # OPEN for the floored cooldown
        clock["t"] = 0.01                # past the floor: HALF_OPEN
        assert cb.allow()                # the single probe
        assert not cb.allow()            # everyone else still rejected

    def test_disabled_breaker_is_inert(self):
        cb, _ = self._cb(failure_threshold=0)
        for _ in range(10):
            cb.record_failure()
        assert cb.allow() and cb.state() == "closed"


# ---------------------------------------------------------------------------
# deadlines: fail-fast admission + queue shedding, never device work
# ---------------------------------------------------------------------------

class TestDeadlines:
    def test_lapsed_deadline_shed_before_dispatch(self):
        from lightgbm_tpu.obs import MetricsRegistry
        m = MetricsRegistry()
        hold = threading.Event()
        seen = []

        def fn(rows):
            seen.append(len(rows))
            hold.wait(10)
            return rows[:, 0]

        gate = MicroBatcher(fn, max_batch=4, max_wait_ms=0.0, metrics=m)
        try:
            f1 = gate.submit(np.zeros((1, 2)))
            time.sleep(0.05)             # worker stuck on batch 1
            f2 = gate.submit(np.zeros((2, 2)), deadline_ms=60.0)
            time.sleep(0.15)             # deadline lapses while queued
            hold.set()
            with pytest.raises(DeadlineExceeded) as ei:
                f2.result(5)
            assert ei.value.where == "queue"
            assert ei.value.waited_ms >= 60.0
            f1.result(5)
        finally:
            hold.set()
            gate.close()
        # the shed request NEVER reached the predict function
        assert seen == [1]
        assert m.snapshot()["serve.deadline_shed"]["value"] == 1

    def test_hopeless_deadline_rejected_at_admission(self):
        hold = threading.Event()
        gate = MicroBatcher(lambda r: (hold.wait(10), r[:, 0])[1],
                            max_batch=2, max_wait_ms=100.0)
        try:
            f1 = gate.submit(np.zeros((2, 1)))
            time.sleep(0.05)
            f2 = gate.submit(np.zeros((2, 1)))   # one pending batch:
            # estimated wait is >= the 100 ms window
            with pytest.raises(DeadlineExceeded) as ei:
                gate.submit(np.zeros((1, 1)), deadline_ms=50.0)
            assert ei.value.where == "admission"
            # a deadline the estimate can meet is admitted
            f3 = gate.submit(np.zeros((1, 1)), deadline_ms=5000.0)
            hold.set()
            for f in (f1, f2, f3):
                f.result(5)
        finally:
            hold.set()
            gate.close()

    def test_admission_floor_uses_measured_service_time(self):
        # full batches dispatch on FILL, so the coalescing window is
        # not a wait floor for them: once a batch has completed, the
        # estimate is measured service time — a queue that drains in
        # ~1ms must not 504 a deadline the window heuristic exceeds
        hold = threading.Event()
        seen = []

        def fn(rows):
            seen.append(len(rows))
            if len(seen) == 2:
                hold.wait(10)
            return rows[:, 0]

        b = MicroBatcher(fn, max_batch=2, max_wait_ms=100.0)
        try:
            b.submit(np.zeros((2, 1))).result(5)   # trains the EWMA
            f1 = b.submit(np.zeros((2, 1)))        # dispatches; blocks
            time.sleep(0.05)
            f2 = b.submit(np.zeros((2, 1)))        # one batch pending
            # window heuristic: 1 batch x 100ms window > 90ms deadline
            # -> the pre-fix code rejected at admission; the measured
            # sub-ms service floor admits it
            f3 = b.submit(np.zeros((1, 1)), deadline_ms=90.0)
            hold.set()
            f3.result(5)
            f1.result(5)
            f2.result(5)
        finally:
            hold.set()
            b.close()

    def test_server_default_deadline_from_config(self, booster):
        srv = Server({"serve_deadline_ms": 60.0, "serve_max_wait_ms": 0.0},
                     booster=booster)
        hold = threading.Event()
        real = srv.batcher.predict_fn
        srv.batcher.predict_fn = lambda rows: (hold.wait(10),
                                               real(rows))[1]
        try:
            f1 = srv.submit(np.zeros((1, 5)))
            time.sleep(0.15)
            f2 = srv.submit(np.zeros((1, 5)))   # inherits the default
            time.sleep(0.15)
            hold.set()
            f1.result(5)
            with pytest.raises(DeadlineExceeded):
                f2.result(5)
            # an explicit per-request deadline overrides the default
            assert srv.predict(np.zeros((1, 5)), timeout=5,
                               deadline_ms=30000.0) is not None
        finally:
            hold.set()
            srv.close()

    def test_http_504_on_deadline(self, booster):
        srv = Server({"serve_max_wait_ms": 0.0}, booster=booster)
        hold = threading.Event()
        real = srv.batcher.predict_fn
        srv.batcher.predict_fn = lambda rows: (hold.wait(10),
                                               real(rows))[1]
        fe = start_http(srv, port=0)
        try:
            f1 = srv.submit(np.zeros((1, 5)))
            time.sleep(0.1)
            req = urllib.request.Request(
                f"http://127.0.0.1:{fe.port}/predict",
                data=json.dumps({"rows": [[0.0] * 5],
                                 "deadline_ms": 80.0}).encode(),
                headers={"Content-Type": "application/json"})
            t0 = time.perf_counter()

            def release():
                time.sleep(0.3)
                hold.set()

            threading.Thread(target=release, daemon=True).start()
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 504
            body = json.loads(ei.value.read())
            assert body["deadline_ms"] == pytest.approx(80.0)
            assert time.perf_counter() - t0 < 8.0
            f1.result(5)
        finally:
            hold.set()
            fe.close()
            srv.close()


# ---------------------------------------------------------------------------
# circuit breaker: serving semantics
# ---------------------------------------------------------------------------

class TestServingBreaker:
    def _failing_server(self, booster, **params):
        srv = Server({"serve_retries": 0, "serve_breaker_failures": 2,
                      "serve_breaker_cooldown_ms": 150.0,
                      "serve_max_wait_ms": 0.0, **params},
                     booster=booster)
        return srv

    def test_opens_rejects_and_recovers(self, booster):
        srv = self._failing_server(booster)
        real = srv.batcher.predict_fn

        def boom(rows):
            raise RuntimeError("device UNAVAILABLE (simulated wedge)")

        srv.batcher.predict_fn = boom
        x = np.zeros((1, 5))
        try:
            for _ in range(2):
                with pytest.raises(RuntimeError):
                    srv.predict(x, timeout=5)
            with pytest.raises(CircuitOpen) as ei:
                srv.submit(x)
            assert ei.value.retry_after_ms > 0
            h = srv.health()
            # degraded stays READY: the half-open probe is an ordinary
            # request, so an LB must keep routing some traffic here
            assert h["status"] == "degraded" and h["ready"] is True
            assert h["breaker"]["state"] == "open"
            snap = srv.metrics_snapshot()
            assert snap["serve.breaker_opens"]["value"] == 1
            assert snap["serve.breaker_rejected"]["value"] >= 1
            assert snap["serve.breaker_state"]["value"] == 2
            # recovery: fix the device, wait out the cooldown, and the
            # half-open probe closes the circuit
            srv.batcher.predict_fn = real
            deadline = time.time() + 10
            while True:
                try:
                    srv.predict(x, timeout=5)
                    break
                except CircuitOpen:
                    assert time.time() < deadline, "breaker never half-opened"
                    time.sleep(0.03)
            assert srv.breaker.describe()["state"] == "closed"
            assert srv.health()["status"] == "ok"
        finally:
            srv.close()

    def test_request_scoped_errors_never_trip(self, booster):
        srv = self._failing_server(booster)
        x = np.zeros((1, 5))
        try:
            # wrong feature count -> LightGBMError (ValueError family):
            # each request fails alone, the breaker must not move
            for _ in range(4):
                with pytest.raises(Exception):
                    srv.predict(np.zeros((1, 2)), timeout=5)
            assert srv.breaker.describe()["state"] == "closed"
            assert srv.predict(x, timeout=5) is not None
        finally:
            srv.close()

    def test_http_503_with_retry_after(self, booster):
        srv = self._failing_server(booster)
        srv.batcher.predict_fn = \
            lambda rows: (_ for _ in ()).throw(RuntimeError("UNAVAILABLE"))
        fe = start_http(srv, port=0)
        base = f"http://127.0.0.1:{fe.port}"
        try:
            for _ in range(2):
                with pytest.raises(urllib.error.HTTPError):
                    self._post(base, {"rows": [[0.0] * 5]})
            with pytest.raises(urllib.error.HTTPError) as ei:
                self._post(base, {"rows": [[0.0] * 5]})
            assert ei.value.code == 503
            assert int(ei.value.headers["Retry-After"]) >= 1
            assert json.loads(ei.value.read())["retry_after_ms"] > 0
            # healthz stays 200 while merely degraded (alive, LBs may
            # deprioritize via the body) — not 503
            h = json.loads(urllib.request.urlopen(base + "/healthz").read())
            assert h["status"] == "degraded"
        finally:
            fe.close()
            srv.close()

    @staticmethod
    def _post(base, payload):
        req = urllib.request.Request(
            base + "/predict", data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        return json.loads(urllib.request.urlopen(req, timeout=10).read())

    def test_breaker_disabled_by_config(self, booster):
        srv = Server({"serve_breaker_failures": 0}, booster=booster)
        try:
            assert srv.breaker is None
            assert "breaker" not in srv.health()
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# graceful drain
# ---------------------------------------------------------------------------

class TestDrain:
    def test_drain_answers_queued_then_refuses_new(self, booster):
        srv = Server({"serve_max_batch": 2, "serve_max_wait_ms": 0.0},
                     booster=booster)
        hold = threading.Event()
        real = srv.batcher.predict_fn
        srv.batcher.predict_fn = lambda rows: (hold.wait(10),
                                               real(rows))[1]
        x = np.zeros((2, 5))
        f1 = srv.submit(x)
        time.sleep(0.05)
        f2 = srv.submit(x)               # queued behind the wedge
        result = {}

        def drain():
            result.update(srv.drain(10.0))

        t = threading.Thread(target=drain, daemon=True)
        t.start()
        time.sleep(0.05)
        # draining: new work refused, health flips, old work completes
        with pytest.raises(BatcherDraining):
            srv.submit(x)
        h = srv.health()
        assert h["status"] == "draining" and h["ready"] is False
        hold.set()
        t.join(10)
        assert result["drained"] is True and result["leftover_rows"] == 0
        f1.result(5), f2.result(5)
        assert srv.batcher.depth_rows == 0
        srv.close()

    def test_drain_prompt_when_last_round_all_shed(self):
        """A drain whose final collect round sheds EVERYTHING (all
        deadlines lapsed, nothing dispatched) must still wake
        wait_idle immediately, not sleep out the full budget.

        The deadline must lapse while the worker is BUSY with an
        earlier batch — the coalescing window itself closes before a
        queued deadline, so an idle batcher dispatches in time instead
        of shedding."""
        hold = threading.Event()

        def fn(rows):
            hold.wait(5.0)
            return rows[:, 0]

        gate = MicroBatcher(fn, max_batch=8, max_wait_ms=10.0)
        f1 = gate.submit(np.zeros((2, 1)))      # occupies the worker
        time.sleep(0.05)                        # worker now inside fn
        f2 = gate.submit(np.zeros((2, 1)), deadline_ms=60.0)
        time.sleep(0.1)                         # f2 lapses while queued
        gate.begin_drain()
        hold.set()
        t0 = time.perf_counter()
        assert gate.wait_idle(5.0) is True
        assert time.perf_counter() - t0 < 2.0   # shed wakes it, not 5s
        np.testing.assert_array_equal(f1.result(1), np.zeros(2))
        with pytest.raises(DeadlineExceeded):
            f2.result(1)
        gate.close()

    def test_http_drain_and_healthz_503(self, booster):
        srv = Server({}, booster=booster)
        fe = start_http(srv, port=0)
        base = f"http://127.0.0.1:{fe.port}"
        try:
            h = json.loads(urllib.request.urlopen(base + "/healthz").read())
            assert h["ready"] is True
            req = urllib.request.Request(base + "/drain", data=b"{}")
            resp = json.loads(urllib.request.urlopen(req, timeout=10).read())
            assert resp["drained"] is True
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + "/healthz")
            assert ei.value.code == 503
            assert json.loads(ei.value.read())["status"] == "draining"
            # predict during drain: 503, not a hang
            with pytest.raises(urllib.error.HTTPError) as ei:
                TestServingBreaker._post(base, {"rows": [[0.0] * 5]})
            assert ei.value.code == 503
            assert json.loads(ei.value.read())["draining"] is True
        finally:
            fe.close()
            srv.close()

    def test_cli_sigterm_drains_gracefully(self, tmp_path):
        model = str(tmp_path / "m.txt")
        _train().save_model(model)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, "-m", "lightgbm_tpu", "serve",
             f"input_model={model}", "serve_port=0", "serve_drain_s=5"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
            cwd=os.path.join(os.path.dirname(__file__), ".."))
        try:
            t0 = time.time()
            line = b""
            while time.time() - t0 < 90:
                line = proc.stdout.readline()
                if b"serving" in line:
                    break
            assert b"serving" in line, "server never came up"
            proc.send_signal(signal.SIGTERM)
            out, _ = proc.communicate(timeout=60)
            assert proc.returncode == 0, out.decode()
            assert b"draining" in out and b"drain complete" in out, \
                out.decode()
        finally:
            if proc.poll() is None:
                proc.kill()


# ---------------------------------------------------------------------------
# verified artifacts
# ---------------------------------------------------------------------------

class TestVerifiedArtifacts:
    def _snapshots(self, tmp_path, rounds=6):
        rs = np.random.RandomState(3)
        x = rs.randn(300, 5)
        y = x[:, 0]
        out = str(tmp_path / "model.txt")
        lgb.train({"objective": "regression", "verbosity": -1,
                   "output_model": out, "snapshot_freq": 2,
                   "snapshot_keep": 0}, lgb.Dataset(x, label=y),
                  num_boost_round=rounds)
        return out

    def test_manifest_records_matching_checksums(self, tmp_path):
        from lightgbm_tpu.snapshot import file_sha256
        out = self._snapshots(tmp_path)
        path = out + ".snapshot_iter_6"
        with open(path + ".manifest.json") as f:
            man = json.load(f)
        assert man["model_sha256"] == file_sha256(path)
        assert man["state_sha256"] == file_sha256(path + ".state.npz")

    def test_corrupted_snapshot_skipped_for_older(self, tmp_path):
        from lightgbm_tpu.snapshot import (find_latest_complete_snapshot,
                                           verify_snapshot_artifacts)
        out = self._snapshots(tmp_path)
        newest = out + ".snapshot_iter_6"
        with open(newest, "a") as f:
            f.write("\ncorruption")      # bit rot / torn write
        with open(newest + ".manifest.json") as f:
            assert "checksum mismatch" in \
                verify_snapshot_artifacts(newest, json.load(f))
        it, path = find_latest_complete_snapshot(out)
        assert it == 4                   # fell back past the corruption
        reg = ModelRegistry()
        v = reg.load_snapshot(out)
        assert "iter 4" in reg.get(v).source

    def test_snapshot_load_honors_caller_pin(self, tmp_path):
        # a caller pin on the SNAPSHOT form must be enforced, not
        # silently replaced by the manifest's self-checksum
        from lightgbm_tpu.snapshot import (file_sha256,
                                           find_latest_complete_snapshot)
        out = self._snapshots(tmp_path)
        reg = ModelRegistry()
        with pytest.raises(ArtifactVerificationError):
            reg.load_snapshot(out, expected_sha256="a" * 64)
        assert reg.versions() == []
        _, path = find_latest_complete_snapshot(out)
        v = reg.load_snapshot(out,
                              expected_sha256=file_sha256(path))
        assert reg.get(v).version == v

    def test_corrupted_state_skipped_for_training_resume(self, tmp_path):
        from lightgbm_tpu.snapshot import verify_snapshot_artifacts
        out = self._snapshots(tmp_path)
        newest = out + ".snapshot_iter_6"
        with open(newest + ".state.npz", "ab") as f:
            f.write(b"xx")
        with open(newest + ".manifest.json") as f:
            err = verify_snapshot_artifacts(newest, json.load(f))
        assert err and "state.npz" in err

    def test_registry_refuses_checksum_mismatch(self, tmp_path, booster):
        path = str(tmp_path / "m.txt")
        booster.save_model(path)
        reg = ModelRegistry()
        with pytest.raises(ArtifactVerificationError):
            reg.load(model_file=path, expected_sha256="0" * 64)
        assert reg.versions() == []      # nothing half-registered
        from lightgbm_tpu.snapshot import file_sha256, sha256_hex
        v = reg.load(model_file=path,
                     expected_sha256=file_sha256(path))
        assert reg.get(v).version == v
        # model_str pins verify against the string's bytes
        s = booster.model_to_string()
        with pytest.raises(ArtifactVerificationError):
            reg.load(model_str=s, expected_sha256="1" * 64)
        reg.load(model_str=s, expected_sha256=sha256_hex(s))
        # a live booster has no byte artifact: the pin is refused, not
        # silently ignored
        with pytest.raises(ValueError, match="expected_sha256"):
            reg.load(booster=booster, expected_sha256=sha256_hex(s))

    def test_http_reload_409_on_bad_sha(self, tmp_path, booster):
        path = str(tmp_path / "m.txt")
        booster.save_model(path)
        srv = Server({}, booster=booster)
        fe = start_http(srv, port=0)
        base = f"http://127.0.0.1:{fe.port}"
        try:
            req = urllib.request.Request(
                base + "/reload",
                data=json.dumps({"model_file": path,
                                 "sha256": "f" * 64}).encode())
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 409
            # the current version keeps serving
            assert srv.health()["model"]["version"] == "v1"
            assert srv.metrics_snapshot()["serve.reload_failures"][
                "value"] == 1
            ok = TestServingBreaker._post(
                base, {"rows": np.zeros((1, 5)).tolist()})
            assert ok["model_version"] == "v1"
        finally:
            fe.close()
            srv.close()

    def test_self_check_covers_device_binning_path(self, booster):
        from lightgbm_tpu.serve.engine import EngineUnsupported
        # the path serve_device_binning actually serves must be part of
        # the verification gate, on rows where f32 == f64 binning
        eng = PredictorEngine.from_booster(booster)
        assert eng.self_check(device_binning=True) is True
        assert eng._f32_consensus_mask(
            np.zeros((4, booster.num_feature()))).all()
        # categoricals device-bin integer-exactly since ISSUE 10 (the
        # fused serve path needs them): the check covers that path too
        rs = np.random.RandomState(11)
        x = np.column_stack([rs.randint(0, 4, 200).astype(np.float64),
                             rs.randn(200)])
        cat = lgb.train({"objective": "regression", "verbosity": -1,
                         "num_leaves": 6, "min_data_per_group": 1},
                        lgb.Dataset(x, label=x[:, 1] + (x[:, 0] == 2),
                                    categorical_feature=[0]),
                        num_boost_round=4)
        ceng = PredictorEngine.from_booster(cat)
        assert ceng.self_check() is True
        assert ceng.self_check(device_binning=True) is True
        # ...but categories beyond f32's exact integer range (>= 2^24)
        # would misroute in the f32 compare: the check raises (registry
        # treats an erroring probe as failed -> host-walk fallback)
        big = np.column_stack([
            np.repeat([1.0, float(1 << 24) + 2.0], 100), rs.randn(200)])
        bigm = lgb.train({"objective": "regression", "verbosity": -1,
                          "num_leaves": 4, "min_data_per_group": 1,
                          "min_data_in_leaf": 5},
                         lgb.Dataset(big, label=big[:, 1]
                                     + (big[:, 0] > 2),
                                     categorical_feature=[0]),
                         num_boost_round=4)
        beng = PredictorEngine.from_booster(bigm)
        if beng._device_bin_err is None:
            pytest.skip("model grew no >=2^24 categorical split")
        assert not beng.fused_ok
        with pytest.raises(EngineUnsupported):
            beng.self_check(device_binning=True)

    def test_empty_sha256_pin_refused(self, tmp_path, booster):
        # an empty pin is an unset deploy-script variable, never a
        # request to skip verification
        path = str(tmp_path / "m.txt")
        booster.save_model(path)
        reg = ModelRegistry()
        with pytest.raises(ValueError, match="non-empty"):
            reg.load(model_file=path, expected_sha256="")
        assert reg.versions() == []

    def test_engine_self_check_catches_corruption(self, booster):
        eng = PredictorEngine.from_booster(booster)
        assert eng.self_check() is True
        # corrupt the DEVICE-side SoA the traversal actually reads:
        # shifting every threshold bin flips the probe's exact-tie rows
        eng._dev["threshold_bin"] = eng._dev["threshold_bin"] + 1
        assert eng.self_check() is False

    def test_registry_falls_back_when_self_check_fails(self, booster,
                                                       monkeypatch):
        monkeypatch.setattr(PredictorEngine, "self_check",
                            lambda self, **kw: False)
        reg = ModelRegistry()
        v = reg.load(booster=booster)
        served = reg.get(v)
        assert served.engine is None     # discarded, host walk serves
        x = np.random.RandomState(5).randn(7, 5)
        assert np.array_equal(served.booster.predict(x),
                              _train().predict(x))

    def test_failed_reload_keeps_current_serving(self, booster):
        from lightgbm_tpu.utils import faultinject
        srv = Server({}, booster=booster)
        x = np.zeros((3, 5))
        try:
            ref = srv.predict(x, timeout=10)
            faultinject.configure("serve_reload:1")
            with pytest.raises(Exception, match="injected"):
                srv.reload(booster=_train(rounds=3, seed=9))
            faultinject.clear()
            assert srv.health()["model"]["version"] == "v1"
            assert np.array_equal(srv.predict(x, timeout=10), ref)
        finally:
            faultinject.clear()
            srv.close()


# ---------------------------------------------------------------------------
# chaos soak (tools/soak_serve.py) — short tier-1 run
# ---------------------------------------------------------------------------

class TestChaosSoak:
    def test_short_soak_no_violations(self):
        import soak_serve
        report = soak_serve.run_soak(duration_s=1.2, clients=3,
                                     chaos=True, seed=1)
        assert report["violations"] == [], report
        assert report["counts"]["ok"] > 0
        assert report["recovered"] is True
        assert report["drain"]["drained"] is True

    def test_soak_without_chaos_is_clean_and_error_free(self):
        import soak_serve
        report = soak_serve.run_soak(duration_s=0.8, clients=2,
                                     chaos=False, seed=2)
        assert report["violations"] == [], report
        assert report["counts"].get("error", 0) == 0
        assert report["counts"].get("reload_failed", 0) == 0


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------

class TestHardeningConfig:
    def test_defaults_and_validation(self):
        from lightgbm_tpu.config import Config
        cfg = Config({})
        assert cfg.serve_deadline_ms == 0.0
        assert cfg.serve_breaker_failures == 5
        assert cfg.serve_breaker_cooldown_ms == 1000.0
        assert cfg.serve_drain_s == 5.0
        assert cfg.serve_verify_artifacts is True
        assert Config({"serve_default_deadline_ms": 250.0}
                      ).serve_deadline_ms == 250.0
        for bad in ({"serve_deadline_ms": -1},
                    {"serve_breaker_failures": -1},
                    {"serve_breaker_cooldown_ms": -1},
                    # 0 would make every caller the half-open probe —
                    # an open circuit that never rejects anything
                    {"serve_breaker_cooldown_ms": 0},
                    {"serve_drain_s": -0.5}):
            with pytest.raises(ValueError):
                Config(bad)

    def test_new_fault_sites_known(self):
        from lightgbm_tpu.utils import faultinject
        assert "serve_batch" in faultinject.KNOWN_SITES
        assert "serve_reload" in faultinject.KNOWN_SITES
        faultinject.configure("serve_batch:2")
        try:
            faultinject.check("serve_batch")       # hit 1: no fire
            with pytest.raises(faultinject.InjectedFault):
                faultinject.check("serve_batch")   # hit 2: fires
        finally:
            faultinject.clear()
