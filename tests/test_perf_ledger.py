"""Perf ledger + flight recorder (ISSUE 9).

- FlopLedger formulas vs brute-force op counts on tiny shapes;
- trace-time site registration (obs/flops.note_traced) agrees with the
  driver ledger's formulas for the shapes actually trained;
- telemetry_snapshot(): perf.* roofline keys (flops / hbm_bytes /
  achieved FLOP/s / mfu / bound), deep-copy isolation, dp == serial
  static identity, telemetry=false carries no perf keys;
- flight recorder: JSONL dump of the last-K ring on an injected
  nan_grads fault, watchdog-fire dump, serve batch-failure dump,
  zero-cost (no ring, no file) when disabled;
- Prometheus text exposition of the metrics snapshot + the serve
  ``/metrics?format=prom`` endpoint.
"""

import json
import os
import time
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs.flops import (FlopLedger, hist_flops_bytes,
                                    padded_bins, partition_flops_bytes,
                                    score_update_flops_bytes,
                                    split_scan_flops_bytes,
                                    traced_sites,
                                    train_hist_flops_per_iter)


def _small_data(n=1200, f=8, seed=3):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, f)
    y = (x[:, 0] - 0.5 * x[:, 1] > 0).astype(np.float32)
    return x, y


def _train(params, n_iter=3, x=None, y=None):
    if x is None:
        x, y = _small_data()
    base = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
            "verbosity": 0, "fused_chunk": 0, "max_bin": 31,
            "tpu_learner": "masked"}
    base.update(params)
    ds = lgb.Dataset(x, label=y, params=base)
    ds.construct()
    bst = lgb.Booster(params=base, train_set=ds)
    for _ in range(n_iter):
        bst.update()
    return bst


# -- formulas vs brute force -----------------------------------------------

class TestFlopFormulas:
    def test_padded_bins_matches_hist_kernel_policy(self):
        # ops/histogram.py pads the bin axis to max(64, ceil(B/64)*64)
        assert padded_bins(15) == 64
        assert padded_bins(63) == 64
        assert padded_bins(64) == 64
        assert padded_bins(65) == 128
        assert padded_bins(255) == 256

    def test_hist_flops_match_brute_force(self):
        n, f, b, c = 5, 3, 7, 3
        flops, hbm = hist_flops_bytes(n, f, b, channels=c,
                                      binned_itemsize=1)
        # the one-hot contraction is 2 FLOPs (mul + add) per
        # (row, column, padded bin, channel) cell
        count = 0
        for _ in range(n):
            for _ in range(f):
                for _ in range(padded_bins(b)):
                    for _ in range(c):
                        count += 2
        assert flops == count
        # bytes: binned read + (g, h, w) read + histogram write
        assert hbm == n * f * 1 + n * 3 * 4 + c * f * padded_bins(b) * 4

    def test_hist_slot_expansion_accounts_slot_vector(self):
        _, hbm3 = hist_flops_bytes(10, 2, 7, channels=3)
        _, hbm6 = hist_flops_bytes(10, 2, 7, channels=6)
        # the [N] int32 slot vector rides only the multi-slot pass
        assert hbm6 - hbm3 == 10 * 4 + 3 * 2 * padded_bins(7) * 4

    def test_score_and_partition_match_brute_force(self):
        n = 11
        flops, hbm = score_update_flops_bytes(n)
        count = sum(2 for _ in range(n))   # gather + add per row
        assert flops == count
        assert hbm == n * 4 + 2 * n * 4
        # one slot (the strict grower): the slot's compare and seven
        # selects, six ops past the slots; its column, leaf_of_row in and out
        pf, pb = partition_flops_bytes(n, binned_itemsize=2)
        assert pf == (8 + 6) * n
        assert pb == n * 2 + 2 * n * 4
        # 16 slots a step: 16 columns read, and the rows' slot written too
        pf, pb = partition_flops_bytes(n, slots=16)
        assert pf == (8 * 16 + 6) * n
        assert pb == 16 * n + 3 * n * 4

    def test_train_hist_flops_per_iter_is_the_bench_formula(self):
        # 2 * 3 * n * F * Bp * (leaves - 1)
        assert train_hist_flops_per_iter(1000, 28, 63, 31) == \
            2.0 * 3 * 1000 * 28 * 64 * 30

    def test_ledger_per_iteration_and_share(self):
        led = FlopLedger.for_training(100, 4, 15, split_batch=2)
        sites = {s.site: s for s in led.sites()}
        assert set(sites) == {"hist", "hist_root", "split_scan",
                              "split_root", "partition", "score"}
        steps = 3
        f, b = led.per_iteration(steps)
        manual_f = sum(s.flops * (steps if s.cadence == "step" else 1)
                       for s in led.sites())
        assert f == manual_f and f > 0 and b > 0
        share = led.flop_share(steps)
        assert abs(sum(share.values()) - 1.0) < 0.01
        # the histogram contraction dominates by construction
        assert share["hist"] == max(share.values())


# -- trace-time registration agrees with the formulas ----------------------

class TestTracedSites:
    def test_call_sites_register_traced_shapes(self):
        # distinctive shapes force fresh traces even late in the suite
        x, y = _small_data(n=1237, f=9, seed=11)
        bst = _train({"num_leaves": 6, "max_bin": 37}, n_iter=1, x=x, y=y)
        m = bst._model
        ts = traced_sites()
        for site in ("hist", "split_scan", "partition"):
            assert site in ts, f"site {site!r} never registered"
        itemsize = int(m.binned_dev.dtype.itemsize)
        # the last-traced hist note is the smaller-child pass; under
        # the default hist_overlap its 1-slot mask is accounted as the
        # masked pass it is byte-identical to (num_slots == 1 adds no
        # slot-operand bytes — obs/flops.hist_flops_bytes convention)
        exp_f, exp_b = hist_flops_bytes(
            m.num_data, int(m.binned_dev.shape[1]), m.max_bin,
            channels=3, binned_itemsize=itemsize)
        assert ts["hist"].flops == exp_f
        assert ts["hist"].hbm_bytes == exp_b
        assert ts["partition"].flops == \
            partition_flops_bytes(m.num_data, itemsize)[0]
        assert ts["split_scan"].flops == \
            split_scan_flops_bytes(m.num_features, m.max_bin, 1)[0]
        # ...and they agree with the driver-side ledger formulas
        led = FlopLedger.for_training(
            m.num_data, m.num_features, m.max_bin, split_batch=1,
            binned_itemsize=itemsize)
        sites = {s.site: s for s in led.sites()}
        assert sites["hist_root"].flops == ts["hist"].flops
        assert sites["partition"].flops == ts["partition"].flops


# -- perf.* roofline keys ---------------------------------------------------

class TestPerfSnapshot:
    PEAKS = {"telemetry_peak_flops": 1e12, "telemetry_peak_hbm_gbs": 100.0}

    def test_perf_keys_with_explicit_peaks(self):
        bst = _train(dict(self.PEAKS, telemetry=True), n_iter=3)
        snap = bst.telemetry_snapshot()
        for ph in ("grow", "score", "total"):
            assert snap[f"perf.{ph}.flops"] > 0
            assert snap[f"perf.{ph}.hbm_bytes"] > 0
            assert snap[f"perf.{ph}.seconds"] > 0
            assert snap[f"perf.{ph}.flops_per_s"] > 0
            assert snap[f"perf.{ph}.mfu"] > 0
            assert snap[f"perf.{ph}.bound"] in ("compute", "memory")
        assert snap["perf.total.flops"] == \
            snap["perf.grow.flops"] + snap["perf.score.flops"]
        assert snap["perf.device.peak_flops_per_s"] == 1e12
        assert snap["perf.device.peak_hbm_bytes_per_s"] == 100e9
        # the flops.* counters backing the join are in the snapshot too
        assert any(k.startswith("flops.total{") for k in snap)

    def test_snapshot_is_a_deep_copy(self):
        bst = _train(dict(self.PEAKS, telemetry=True), n_iter=2)
        snap = bst.telemetry_snapshot()
        before = json.dumps(bst.telemetry_snapshot(), sort_keys=True)
        # mutate scalars, nested dicts and nested lists of the copy
        snap["train.iterations"]["value"] = 1e9
        snap["train.steps_per_tree"]["counts"][0] = 12345
        snap["perf.grow.flops"] = -1
        snap.clear()
        after = json.dumps(bst.telemetry_snapshot(), sort_keys=True)
        assert before == after

    def test_dp_equals_serial_static_perf(self):
        import jax
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device CPU mesh")
        x, y = _small_data(1600)
        serial = _train(dict(self.PEAKS, telemetry=True), n_iter=3,
                        x=x, y=y)
        dp = _train(dict(self.PEAKS, telemetry=True, tree_learner="data",
                         split_batch=1), n_iter=3, x=x, y=y)
        s_snap, d_snap = (serial.telemetry_snapshot(),
                          dp.telemetry_snapshot())
        # static accounting (logical global shapes x identical trees)
        # must agree byte-for-byte; achieved rates legitimately differ
        static = [k for k in s_snap
                  if k.startswith("flops.")
                  or k.endswith((".flops", ".hbm_bytes"))]
        assert static
        for k in static:
            assert s_snap[k] == d_snap[k], k

    def test_telemetry_off_has_no_perf_keys(self):
        bst = _train({}, n_iter=1)
        snap = bst.telemetry_snapshot()
        assert not any(k.startswith(("perf.", "flops.")) for k in snap)


# -- flight recorder --------------------------------------------------------

class TestFlightRecorder:
    def test_nan_grads_fault_dumps_last_k(self, tmp_path):
        from lightgbm_tpu.obs.trace import read_jsonl
        from lightgbm_tpu.utils import faultinject
        path = str(tmp_path / "bb.jsonl")
        faultinject.configure("nan_grads:3")
        try:
            bst = _train({"finite_check_freq": 1,
                          "finite_check_policy": "skip_iter",
                          "telemetry_blackbox": True,
                          "telemetry_blackbox_path": path,
                          "telemetry_blackbox_last_k": 8}, n_iter=4)
        finally:
            faultinject.clear()
        assert bst.current_iteration == 4    # skip_iter keeps training
        assert os.path.exists(path)
        events = read_jsonl(path)
        header, records = events[0], events[1:]
        assert header["blackbox"] is True
        assert header["reason"] == "finite_check"
        assert header["n_records"] == len(records)
        # the ring held the two clean iterations plus the trip event
        assert [r.get("iteration") for r in records] == [1, 2, 3]
        assert records[-1]["event"] == "finite_check_trip"
        assert all("dur_s" in r for r in records[:-1])
        bst._model._bbox.close()

    def test_disabled_is_zero_cost(self, tmp_path):
        bst = _train({"output_model": str(tmp_path / "m.txt")}, n_iter=1)
        assert bst._model._bbox is None      # no ring allocation
        assert not os.path.exists(str(tmp_path / "m.txt.blackbox.jsonl"))

    def test_ring_is_bounded_to_last_k(self, tmp_path):
        from lightgbm_tpu.obs.blackbox import FlightRecorder
        from lightgbm_tpu.obs.trace import read_jsonl
        rec = FlightRecorder(str(tmp_path / "r.jsonl"), last_k=3)
        for i in range(10):
            rec.record(iteration=i)
        rec.dump("test")
        events = read_jsonl(str(tmp_path / "r.jsonl"))
        assert [e["iteration"] for e in events[1:]] == [7, 8, 9]
        rec.close()

    def test_watchdog_fire_dumps_live_recorders(self, tmp_path):
        from lightgbm_tpu.obs.blackbox import FlightRecorder
        from lightgbm_tpu.obs.trace import read_jsonl
        from lightgbm_tpu.utils.resilience import Watchdog
        rec = FlightRecorder(str(tmp_path / "w.jsonl"), last_k=4)
        rec.record(iteration=1)
        try:
            with open(os.devnull, "w") as devnull:
                with Watchdog(0.1, label="wedge-sim", file=devnull):
                    time.sleep(0.5)          # outlive the timeout
        finally:
            rec.close()
        assert os.path.exists(str(tmp_path / "w.jsonl"))
        header = read_jsonl(str(tmp_path / "w.jsonl"))[0]
        assert header["reason"].startswith("watchdog")

    def test_serve_batch_failure_dumps(self, tmp_path):
        from lightgbm_tpu.serve.server import Server
        from lightgbm_tpu.utils import faultinject
        path = str(tmp_path / "serve_bb.jsonl")
        bst = _train({}, n_iter=2)
        srv = Server(params={"verbosity": 0, "serve_retries": 0,
                             "serve_breaker_failures": 0,
                             "telemetry_blackbox": True,
                             "telemetry_blackbox_path": path},
                     booster=bst)
        x, _ = _small_data(4)
        try:
            assert len(srv.predict(x)) == 4   # healthy batch recorded
            faultinject.configure("serve_batch:1-10")
            with pytest.raises(Exception):
                srv.predict(x)
        finally:
            faultinject.clear()
            srv.close()
        assert os.path.exists(path)
        from lightgbm_tpu.obs.trace import read_jsonl
        events = read_jsonl(path)
        assert events[0]["reason"] == "serve_batch_failure"
        assert any(r.get("event") == "batch_error" for r in events[1:])


# -- Prometheus exposition --------------------------------------------------

class TestPrometheus:
    def test_prometheus_text_rendering(self):
        from lightgbm_tpu.obs.metrics import (MetricsRegistry,
                                              prometheus_text)
        r = MetricsRegistry()
        r.counter("serve.rows").inc(42)
        r.gauge("serve.breaker_state", state="closed").set(0)
        r.histogram("serve.latency", buckets=(0.1, 1.0)).observe(0.5)
        snap = dict(r.snapshot())
        snap["perf.grow.mfu"] = 0.25
        snap["perf.grow.bound"] = "memory"
        snap["compile.count"] = 3
        snap["serve.engine"] = {"steps": 4, "num_trees": 7, "sig": "ab"}
        text = prometheus_text(snap)
        assert "# TYPE serve_rows counter" in text
        assert "serve_rows 42.0" in text
        assert 'serve_breaker_state{state="closed"} 0.0' in text
        assert "# TYPE serve_latency histogram" in text
        assert 'serve_latency_bucket{le="0.1"} 0' in text
        assert 'serve_latency_bucket{le="1.0"} 1' in text
        assert 'serve_latency_bucket{le="+Inf"} 1' in text
        assert "serve_latency_sum 0.5" in text
        assert "serve_latency_count 1" in text
        assert "perf_grow_mfu 0.25" in text
        assert 'perf_grow_bound{value="memory"} 1.0' in text
        assert "compile_count 3.0" in text
        assert "serve_engine_steps 4.0" in text      # flattened dict
        assert "sig" not in text                      # non-numeric leaf

    def test_http_metrics_prom_endpoint(self):
        from lightgbm_tpu.serve.server import Server, start_http
        bst = _train({}, n_iter=2)
        srv = Server(params={"verbosity": 0}, booster=bst)
        http = start_http(srv, port=0)
        try:
            x, _ = _small_data(8)
            srv.predict(x)
            url = f"http://127.0.0.1:{http.port}/metrics?format=prom"
            with urllib.request.urlopen(url, timeout=10) as resp:
                ctype = resp.headers.get("Content-Type", "")
                body = resp.read().decode()
            assert ctype.startswith("text/plain")
            assert "# TYPE serve_rows counter" in body
            assert "serve_rows 8.0" in body
            assert "perf_forest_flops_per_row" in body
            # the JSON default is untouched
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{http.port}/metrics",
                    timeout=10) as resp:
                snap = json.loads(resp.read())
            assert "perf.forest.flops_per_row" in snap
        finally:
            http.close()
            srv.close()
