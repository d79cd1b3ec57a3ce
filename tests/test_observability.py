"""Observability subsystem (lightgbm_tpu/obs/ — docs/Observability.md).

Covers the ISSUE 3 acceptance surface:

- sync lint green (tools/check_syncs.py; raw device_get /
  block_until_ready / .item() only at allowlisted sites);
- telemetry-off hot path is sync-free: counted ``jax.device_get`` calls
  per iteration match the seed's single batched fetch;
- JSONL traces round-trip through the Perfetto exporter;
- comm-bytes counters match the PR 1 per-shard hist-bytes math;
- metrics aggregation is deterministic and agrees dp == serial;
- satellites: verbosity -> log level mapping, timer atexit gating,
  profiler-window param validation, log_telemetry callback.
"""

import json
import os
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.obs import ObsSession, maybe_session
from lightgbm_tpu.obs.comm import CommLedger, wire_bytes
from lightgbm_tpu.obs.metrics import MetricsRegistry, aggregate_snapshots
from lightgbm_tpu.obs.trace import (Tracer, fence, jsonl_to_chrome,
                                    read_jsonl, timed_fenced)
from lightgbm_tpu.utils.log import Log

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))


@pytest.fixture(scope="module", autouse=True)
def _quiet_global_timer():
    """ObsSession flips the process-global timer on (the FunctionTimer
    feed); restore the off default so later test modules' scopes don't
    arm the exit summary."""
    yield
    from lightgbm_tpu.utils.timer import global_timer
    global_timer.enabled = False


def _small_data(n=1200, f=8, seed=3):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, f)
    y = (x[:, 0] - 0.5 * x[:, 1] > 0).astype(np.float32)
    return x, y


def _train(params, n_iter=3, x=None, y=None):
    if x is None:
        x, y = _small_data()
    base = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
            "verbosity": 0, "fused_chunk": 0, "max_bin": 31}
    base.update(params)
    ds = lgb.Dataset(x, label=y, params=base)
    ds.construct()
    bst = lgb.Booster(params=base, train_set=ds)
    for _ in range(n_iter):
        bst.update()
    return bst


# -- sync lint -------------------------------------------------------------

class TestSyncLint:
    def test_library_is_clean(self):
        from check_syncs import find_raw_syncs
        findings = find_raw_syncs()
        assert findings == [], "\n".join(findings)

    def test_lint_catches_raw_syncs_and_stale_entries(self, tmp_path):
        from check_syncs import find_raw_syncs
        root = tmp_path / "pkg"
        root.mkdir()
        (root / "bad.py").write_text(
            "import jax\n"
            "# a comment mentioning jax.device_get(x) must NOT trip\n"
            "def f(x):\n"
            '    """nor a docstring: block_until_ready."""\n'
            "    v = jax.device_get(x)\n"
            "    jax.block_until_ready(x)\n"
            "    return v.item()\n")
        allow = tmp_path / "allow.txt"
        allow.write_text("pkg/gone.py | jax.device_get(y)\n")
        findings = find_raw_syncs(str(root), str(allow))
        joined = "\n".join(findings)
        assert "bad.py:5" in joined and "bad.py:6" in joined \
            and "bad.py:7" in joined
        assert "comment" not in joined and "docstring" not in joined
        assert any("stale allowlist" in f for f in findings)


# -- telemetry-off: sync-free hot path ------------------------------------

class TestTelemetryOff:
    def test_default_has_no_session(self):
        bst = _train({}, n_iter=1)
        assert bst._model._obs is None
        # telemetry=false carries NO obs metrics — only the process-wide
        # compile accounting (utils/compile_cache.py), which is host-side
        # counters with zero device syncs
        snap = bst.telemetry_snapshot()
        assert all(k.startswith("compile.") for k in snap)
        assert {"compile.count", "compile.seconds", "compile.cache_hits",
                "compile.cache_misses", "compile.traces"} <= set(snap)
        assert bst.telemetry_finish() == {}

    def test_device_get_count_per_iteration_unchanged(self, monkeypatch):
        """The masked per-iteration path performs exactly ONE batched
        ``device_get`` per update (the small tree fetch — the
        'fetch' phase); telemetry=false must not add any."""
        import jax
        x, y = _small_data()
        base = {"objective": "binary", "num_leaves": 7,
                "min_data_in_leaf": 5, "verbosity": 0, "fused_chunk": 0,
                "max_bin": 31, "tpu_learner": "masked"}
        ds = lgb.Dataset(x, label=y, params=base)
        ds.construct()
        bst = lgb.Booster(params=base, train_set=ds)
        bst.update()                       # compile/warm outside the count

        calls = [0]
        real = jax.device_get

        def counting(*a, **kw):
            calls[0] += 1
            return real(*a, **kw)

        monkeypatch.setattr(jax, "device_get", counting)
        for _ in range(3):
            bst.update()
        assert calls[0] == 3, \
            f"expected 1 device_get per iteration, saw {calls[0]} over 3"

    def test_telemetry_on_only_adds_fences(self, monkeypatch):
        """With telemetry=true the extra syncs are exactly the three
        phase fences (grad/grow/score; fetch rides the existing
        device_get) — pinning the span structure."""
        import jax
        x, y = _small_data()
        base = {"objective": "binary", "num_leaves": 7,
                "min_data_in_leaf": 5, "verbosity": 0, "fused_chunk": 0,
                "max_bin": 31, "tpu_learner": "masked", "telemetry": True}
        ds = lgb.Dataset(x, label=y, params=base)
        ds.construct()
        bst = lgb.Booster(params=base, train_set=ds)
        bst.update()

        calls = [0]
        real = jax.device_get

        def counting(*a, **kw):
            calls[0] += 1
            return real(*a, **kw)

        monkeypatch.setattr(jax, "device_get", counting)
        bst.update()
        assert calls[0] == 4               # 1 fetch + 3 phase fences


# -- traces ----------------------------------------------------------------

class TestTrace:
    def test_jsonl_roundtrip_through_perfetto_exporter(self, tmp_path):
        sink = str(tmp_path / "t.jsonl")
        tr = Tracer(sink_path=sink, pid=7)
        with tr.span("outer", iteration=1):
            with tr.span("inner"):
                pass
        tr.instant("marker", note="x")
        tr.close()

        events = read_jsonl(sink)
        assert [e["name"] for e in events] == ["inner", "outer", "marker"]
        assert all(e["pid"] == 7 for e in events)
        outer = next(e for e in events if e["name"] == "outer")
        inner = next(e for e in events if e["name"] == "inner")
        # containment: nesting is recoverable from [ts, ts+dur)
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
        assert outer["args"] == {"iteration": 1}

        chrome = str(tmp_path / "t.trace.json")
        assert jsonl_to_chrome(sink, chrome) == 3
        loaded = json.load(open(chrome))
        assert loaded["traceEvents"] == events

    def test_jsonl_survives_torn_tail(self, tmp_path):
        sink = tmp_path / "torn.jsonl"
        sink.write_text('{"name": "a", "ph": "X", "ts": 0, "dur": 1}\n'
                        '{"name": "b", "ph"')
        assert [e["name"] for e in read_jsonl(str(sink))] == ["a"]

    def test_fence_returns_value_and_blocks(self):
        import jax.numpy as jnp
        x = jnp.arange(8.0)
        assert fence(x) is x
        assert fence(None) is None
        assert fence({"a": x, "b": 3}) is not None    # non-arrays pass

    def test_timed_fenced(self):
        import jax.numpy as jnp
        tr = Tracer()
        mn, avg = timed_fenced(lambda: jnp.arange(4.0) + 1, iters=3,
                               tracer=tr, name="probe")
        assert 0 < mn <= avg
        assert len(tr.durations("probe")) == 3

    def test_training_emits_phase_spans(self, tmp_path):
        sink = str(tmp_path / "train.jsonl")
        bst = _train({"telemetry": True, "telemetry_trace_file": sink},
                     n_iter=2)
        bst.telemetry_finish()
        names = {e["name"] for e in read_jsonl(sink)}
        assert {"grad", "grow", "fetch", "score"} <= names


# -- metrics ---------------------------------------------------------------

class TestMetrics:
    def test_registry_snapshot_deterministic(self):
        r = MetricsRegistry()
        r.counter("c", a=1).inc(2)
        r.gauge("g").set(5)
        r.histogram("h").observe(0.02)
        s1, s2 = r.snapshot(), r.snapshot()
        assert json.dumps(s1) == json.dumps(s2)
        assert s1["c{a=1}"] == {"type": "counter", "value": 2.0}
        assert s1["g"]["value"] == 5.0
        assert s1["h"]["count"] == 1

    def test_type_conflict_raises(self):
        r = MetricsRegistry()
        r.counter("x")
        with pytest.raises(TypeError):
            r.gauge("x")

    def test_aggregate_counters_histograms_gauges(self):
        r1, r2 = MetricsRegistry(), MetricsRegistry()
        for r, v in ((r1, 1.0), (r2, 3.0)):
            r.counter("n").inc(v)
            r.histogram("h").observe(v)
            r.gauge("same").set(7)
        r1.gauge("differs").set(1)
        r2.gauge("differs").set(2)
        agg = aggregate_snapshots([r1.snapshot(), r2.snapshot()])
        assert agg["n"]["value"] == 4.0
        assert agg["h"]["count"] == 2 and agg["h"]["sum"] == 4.0
        assert agg["h"]["min"] == 1.0 and agg["h"]["max"] == 3.0
        assert agg["same"]["value"] == 7.0
        assert agg["differs{shard=0}"]["value"] == 1.0
        assert agg["differs{shard=1}"]["value"] == 2.0
        # single-snapshot aggregation is identity (sorted)
        assert aggregate_snapshots([r1.snapshot()]) == r1.snapshot()

    def test_training_metrics_populated(self):
        bst = _train({"telemetry": True}, n_iter=3)
        snap = bst.telemetry_snapshot()
        assert snap["train.iterations"]["value"] == 3.0
        assert snap["train.steps_per_tree"]["count"] == 3
        for phase in ("grad", "grow", "fetch", "score"):
            key = f"train.phase_seconds{{phase={phase}}}"
            assert snap[key]["count"] == 3

    def test_fused_chunk_counts_iterations(self):
        x, y = _small_data(2400)
        base = {"objective": "binary", "num_leaves": 7,
                "min_data_in_leaf": 5, "verbosity": 0, "max_bin": 31,
                "telemetry": True, "tpu_learner": "masked",
                "fused_chunk": 4}
        ds = lgb.Dataset(x, label=y, params=base)
        ds.construct()
        bst = lgb.Booster(params=base, train_set=ds)
        assert bst.supports_fused()
        bst.update_chunk(4)
        snap = bst.telemetry_snapshot()
        assert snap["train.iterations"]["value"] == 4.0
        assert snap["train.fused_chunks"]["value"] == 1.0
        assert snap["train.steps_per_tree"]["count"] == 4


# -- comm accounting -------------------------------------------------------

class TestComm:
    def test_wire_model(self):
        assert wire_bytes("psum", 800, 8) == int(2 * 7 / 8 * 800)
        assert wire_bytes("psum_scatter", 800, 8) == 700
        assert wire_bytes("all_gather", 800, 8) == 700
        assert wire_bytes("psum", 800, 1) == 0

    def test_ledger_static_registration(self):
        import jax
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device CPU mesh")
        led = CommLedger(8)
        # registration happens at trace time, idempotently
        from jax.sharding import PartitionSpec as P
        from lightgbm_tpu.parallel import make_mesh
        import jax.numpy as jnp
        mesh = make_mesh((8,), ("data",))

        def f(x):
            return led.psum(x, "data", site="t.sum")

        g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("data"),),
                                  out_specs=P()))
        out = g(jnp.ones(16, jnp.float32))
        assert float(out[0]) == 8.0
        (site,) = led.sites()
        assert site.payload_bytes == 2 * 4       # local [2] f32 shard
        assert site.collective == "psum"
        assert site.wire_bytes == wire_bytes("psum", 8, 8)

    def test_dp_counters_match_owner_shard_hist_math(self):
        """comm.payload_bytes{site=dp.hist_reduce} per pass equals
        n_shards x OwnerShardPlan.hist_bytes(1, B) — the PR 1 per-shard
        histogram byte math (bench.py extras / mesh.owner_shard_plan),
        observed in-flight via the telemetry counters."""
        import jax
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device CPU mesh")
        bst = _train({"telemetry": True, "tree_learner": "data",
                      "split_batch": 1}, n_iter=2)
        m = bst._model
        ledger = m.grower.comm
        sites = {s.site: s for s in ledger.sites()}
        plan = m.grower.plan
        per_leaf = plan.hist_bytes(1, m.max_bin)
        n_sh = ledger.axis_size
        assert sites["dp.hist_reduce"].payload_bytes == n_sh * per_leaf
        assert sites["dp.hist_reduce"].wire_bytes == \
            wire_bytes("psum_scatter", n_sh * per_leaf, n_sh)
        # counter = wire bytes x total grower steps over both iterations
        snap = bst.telemetry_snapshot()
        steps = sum(m.step_counts)
        key = "comm.wire_bytes{collective=psum_scatter,site=dp.hist_reduce}"
        assert snap[key]["value"] == sites["dp.hist_reduce"].wire_bytes \
            * steps
        key = "comm.wire_bytes{collective=psum,site=dp.root_sum}"
        assert snap[key]["value"] == sites["dp.root_sum"].wire_bytes * 2
        assert ledger.bytes_per_iteration(1) == sum(
            s.wire_bytes for s in ledger.sites())

    def test_dp_equals_serial_and_aggregation_deterministic(self):
        """Trees (and therefore steps/iteration metrics) agree between
        tree_learner=data and serial; the serial run records zero comm;
        snapshots are byte-deterministic across repeated export."""
        import jax
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device CPU mesh")
        x, y = _small_data(1600)
        serial = _train({"telemetry": True, "tpu_learner": "masked"},
                        n_iter=3, x=x, y=y)
        dp = _train({"telemetry": True, "tree_learner": "data",
                     "split_batch": 1}, n_iter=3, x=x, y=y)
        s_snap, d_snap = serial.telemetry_snapshot(), dp.telemetry_snapshot()
        assert json.dumps(s_snap) == json.dumps(serial.telemetry_snapshot())
        assert s_snap["train.iterations"] == d_snap["train.iterations"]
        for fld in ("count", "counts", "sum", "min", "max"):
            assert s_snap["train.steps_per_tree"][fld] \
                == d_snap["train.steps_per_tree"][fld]
        assert not any(k.startswith("comm.") for k in s_snap)
        assert any(k.startswith("comm.wire_bytes") for k in d_snap)

    def test_bench_comm_extra_math(self):
        from lightgbm_tpu.obs.comm import dp_hist_bytes_per_iter
        from lightgbm_tpu.parallel.mesh import owner_shard_plan
        plan = owner_shard_plan(np.arange(28), 8)
        got = dp_hist_bytes_per_iter(8, plan.chunk, 64, n_steps=30)
        assert got == wire_bytes("psum_scatter",
                                 8 * plan.hist_bytes(1, 64), 8) * 30


# -- satellites ------------------------------------------------------------

class TestVerbosityMapping:
    @pytest.mark.parametrize("verbosity,level", [
        (-5, -1), (-1, -1), (0, 0), (1, 1), (2, 2), (7, 2)])
    def test_reference_semantics(self, verbosity, level):
        old = Log.level
        try:
            Config({"verbosity": verbosity})
            assert Log.level == level
        finally:
            Log.level = old

    def test_verbose_alias(self):
        old = Log.level
        try:
            Config({"verbose": -1})
            assert Log.level == -1
        finally:
            Log.level = old


class TestTimerGating:
    def test_atexit_not_armed_by_import_or_disabled_use(self):
        import atexit

        from lightgbm_tpu.utils.timer import Timer

        t = Timer()
        t.stop("x", t.start("x"))          # disabled: must not arm
        assert not t._atexit_armed
        t.enabled = True
        t.stop("x", t.start("x"))
        assert t._atexit_armed
        atexit.unregister(t.print_summary)  # keep the test run silent

    def test_print_summary_silent_without_stats(self, capsys):
        from lightgbm_tpu.utils.timer import Timer
        t = Timer()
        t.enabled = True
        t.print_summary()
        assert capsys.readouterr().out == ""


class TestSession:
    def test_maybe_session_off_by_default(self):
        assert maybe_session(Config({})) is None
        assert isinstance(maybe_session(Config({"telemetry": True})),
                          ObsSession)

    def test_profile_iters_validation(self):
        with pytest.raises(ValueError):
            Config({"telemetry_profile_iters": [1, 2, 3]})
        cfg = Config({"telemetry_profile_iters": [5]})
        assert cfg.telemetry_profile_iters == [5]

    def test_profile_capture_that_cannot_start_raises(self, tmp_path,
                                                      monkeypatch):
        """A capture the user asked for (telemetry_profile_iters) whose
        start_trace fails ends the run; it is not logged and skipped."""
        import jax.profiler as jp

        def boom(*a, **kw):
            raise RuntimeError("no profiler service")

        monkeypatch.setattr(jp, "start_trace", boom)
        x, y = _small_data()
        params = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
                  "min_data_in_leaf": 5, "verbosity": -1,
                  "telemetry": True, "telemetry_profile_iters": [1, 1],
                  "telemetry_trace_file": str(tmp_path / "t.jsonl")}
        with pytest.raises(RuntimeError, match="no profiler service"):
            lgb.train(params, lgb.Dataset(x, label=y, params=params),
                      num_boost_round=3)


class TestLogTelemetryCallback:
    def test_collects_and_logs(self):
        x, y = _small_data()
        collected = {}
        params = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
                  "min_data_in_leaf": 5, "verbosity": 0, "telemetry": True,
                  "fused_chunk": 0}
        ds = lgb.Dataset(x, label=y, params=params)
        lgb.train(params, ds, num_boost_round=4,
                  callbacks=[lgb.log_telemetry(period=2,
                                               collect=collected)])
        assert sorted(collected) == [2, 4]
        assert collected[4]["train.iterations"]["value"] == 4.0

    def test_cv_collects_per_fold(self):
        x, y = _small_data()
        collected = {}
        params = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
                  "min_data_in_leaf": 5, "verbosity": 0, "telemetry": True,
                  "fused_chunk": 0}
        lgb.cv(params, lgb.Dataset(x, label=y, params=params),
               num_boost_round=2, nfold=2, stratified=False,
               callbacks=[lgb.log_telemetry(period=2, collect=collected)])
        assert sorted(collected) == [2]
        assert isinstance(collected[2], list) and len(collected[2]) == 2
        for snap in collected[2]:
            assert snap["train.iterations"]["value"] == 2.0

    def test_noop_without_telemetry(self):
        x, y = _small_data()
        collected = {}
        params = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
                  "min_data_in_leaf": 5, "verbosity": 0, "fused_chunk": 0}
        ds = lgb.Dataset(x, label=y, params=params)
        lgb.train(params, ds, num_boost_round=2,
                  callbacks=[lgb.log_telemetry(period=1,
                                               collect=collected)])
        assert collected == {}
