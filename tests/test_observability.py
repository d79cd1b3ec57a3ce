"""Observability subsystem (lightgbm_tpu/obs/ — docs/Observability.md).

Covers the ISSUE 3 acceptance surface:

- sync lint green (tools/check_syncs.py; raw device_get /
  block_until_ready / .item() only at allowlisted sites);
- telemetry-off hot path is sync-free: counted ``jax.device_get`` calls
  per iteration match the seed's single batched fetch;
- JSONL traces round-trip through the Perfetto exporter;
- comm-bytes counters match the PR 1 per-shard hist-bytes math;
- metrics aggregation is deterministic and agrees dp == serial;
- satellites: verbosity -> log level mapping, profiler-window param
  validation, log_telemetry callback.

And ISSUE 26's: one span API on the profiler's clock — every phase of a
``lgb.cv`` job spanned with ``id`` / ``parent``, mirrored into a profiler
session, observed into the registry; device phases named by
``jax.named_scope`` without changing the lowered program.
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

    @pytest.mark.parametrize("with_valid", [False, True])
    def test_telemetry_on_only_adds_fences(self, monkeypatch, with_valid):
        """With telemetry=true the extra syncs of an iteration are
        exactly the fences of the phases that queue device work — grad,
        sample, grow, score and, with a valid set, valid_score; fetch
        rides the existing device_get, tree_host is host work — pinning
        the span structure, each fence by its span's name."""
        import jax
        from lightgbm_tpu.obs.trace import Span
        x, y = _small_data()
        base = {"objective": "binary", "num_leaves": 7,
                "min_data_in_leaf": 5, "verbosity": 0, "fused_chunk": 0,
                "max_bin": 31, "tpu_learner": "masked", "telemetry": True}
        ds = lgb.Dataset(x, label=y, params=base)
        ds.construct()
        bst = lgb.Booster(params=base, train_set=ds)
        if with_valid:
            bst.add_valid(lgb.Dataset(x[:300], label=y[:300],
                                      reference=ds), "valid")
        bst.update()

        calls, fenced = [0], []
        real, real_end = jax.device_get, Span.end

        def counting(*a, **kw):
            calls[0] += 1
            return real(*a, **kw)

        def naming(self, result=None):
            if result is not None:
                fenced.append(self.name)
            return real_end(self, result)

        monkeypatch.setattr(jax, "device_get", counting)
        monkeypatch.setattr(Span, "end", naming)
        bst.update()
        want = ["lgbtpu.grad", "lgbtpu.sample", "lgbtpu.grow",
                "lgbtpu.score"] + ["lgbtpu.valid_score"] * with_valid
        assert fenced == want
        assert calls[0] == 1 + len(want)   # 1 fetch + the phase fences


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
        assert {"lgbtpu." + p for p in (
            "booster.init", "booster.to_device", "grower.make", "iter",
            "init_score", "grad", "sample", "grow", "fetch", "tree_host",
            "score", "valid_score")} <= names


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
        for phase in ("grad", "sample", "grow", "fetch", "tree_host",
                      "score", "valid_score"):
            key = f"train.phase_seconds{{phase={phase}}}"
            assert snap[key]["count"] == 3
        # boost-from-average runs in the first iteration alone
        assert snap["train.phase_seconds{phase=init_score}"]["count"] == 1

    def test_scan_counts_iterations(self):
        x, y = _small_data(2400)
        base = {"objective": "binary", "num_leaves": 7,
                "min_data_in_leaf": 5, "verbosity": 0, "max_bin": 31,
                "telemetry": True, "tpu_learner": "masked",
                "fused_chunk": 4}
        ds = lgb.Dataset(x, label=y, params=base)
        ds.construct()
        bst = lgb.Booster(params=base, train_set=ds)
        assert bst._model.supports_fused()
        bst.update_superepoch(4, 0)
        snap = bst.telemetry_snapshot()
        assert snap["train.iterations"]["value"] == 4.0
        assert snap["train.superepochs"]["value"] == 1.0
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
        histogram byte math (mesh.owner_shard_plan),
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
        key = "comm.bytes{collective=psum_scatter,site=dp.hist_reduce}"
        assert snap[key]["value"] == sites["dp.hist_reduce"].wire_bytes \
            * steps
        key = "comm.bytes{collective=psum,site=dp.root_sum}"
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
        assert any(k.startswith("comm.bytes") for k in d_snap)

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


# -- ISSUE 26: one span API, on the profiler's clock ------------------------

CV_PARAMS = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
             "min_data_in_leaf": 5, "verbosity": 0, "telemetry": True,
             "fused_chunk": 0, "tpu_learner": "masked", "metric": "auc"}
SETUP_SPANS = {"cv.fold_setup", "dataset.subset", "booster.init",
               "booster.to_device", "grower.make"}
ITER_PHASES = ["init_score", "grad", "sample", "grow", "fetch", "tree_host",
               "score", "valid_score"]


def _cv(tmp_path=None, **extra):
    """A two-fold, two-round ``lgb.cv`` with telemetry on; the folds'
    boosters (their sessions hold the spans and the registry)."""
    x, y = _small_data()
    params = dict(CV_PARAMS, **extra)
    if tmp_path is not None:
        params["telemetry_trace_file"] = str(tmp_path / "cv.jsonl")
    out = lgb.cv(params, lgb.Dataset(x, label=y, params=params),
                 num_boost_round=2, nfold=2, stratified=False,
                 return_cvbooster=True)
    return out["cvbooster"].boosters


def _spans(bst):
    return [e for e in bst._model._obs.tracer.events if e["ph"] == "X"]


class TestSpanRecord:
    def test_id_parent_and_ctx(self):
        tr = Tracer(pid=0, ctx={"booster": 9})
        with tr.span("outer") as outer:
            with tr.span("inner") as inner:
                pass
            with tr.span("second") as second:
                pass
        assert outer.parent is None
        assert inner.parent == outer.id and second.parent == outer.id
        assert len({outer.id, inner.id, second.id}) == 3
        by_name = {e["name"]: e for e in tr.events}
        assert by_name["inner"]["parent"] == by_name["outer"]["id"]
        assert all(e["booster"] == 9 for e in tr.events)
        # an instant is no span: no id, no parent
        tr.instant("marker")
        assert "id" not in tr.events[-1]

    def test_self_time_is_duration_less_children(self):
        import time
        tr = Tracer(pid=0)
        with tr.span("layer"):
            time.sleep(0.002)
            with tr.span("below"):
                time.sleep(0.01)
        layer, below = (next(e for e in tr.events if e["name"] == n)
                        for n in ("layer", "below"))
        own = layer["dur"] - sum(e["dur"] for e in tr.events
                                 if e.get("parent") == layer["id"])
        assert 0 < own < below["dur"]

    def test_a_span_left_open_by_an_exception_is_dropped(self):
        tr = Tracer(pid=0)
        outer = tr.span("outer")
        tr.span("abandoned")            # never closed
        outer.end()
        assert tr.span("next").parent is None

    def test_spans_of_threads_do_not_adopt_each_other(self):
        import threading
        tr = Tracer(pid=0)
        got = []
        with tr.span("main"):
            t = threading.Thread(
                target=lambda: got.append(tr.span("other").parent))
            t.start()
            t.join()
        assert got == [None]

    def test_name_is_a_label_too(self):
        """``jax.traces{name=grower}``: the instrument's name is
        positional-only, so a label may be called ``name``."""
        r = MetricsRegistry()
        r.counter("jax.traces", name="grower").inc()
        assert r.snapshot()["jax.traces{name=grower}"]["value"] == 1.0


class TestJobSpans:
    def test_cv_job_yields_every_span_in_one_tree_a_booster(self, tmp_path):
        boosters = _cv(tmp_path)
        ids_seen = set()
        for fold, bst in enumerate(boosters):
            spans = _spans(bst)
            names = {e["name"][len("lgbtpu."):] for e in spans}
            assert all(e["name"].startswith("lgbtpu.") for e in spans)
            assert SETUP_SPANS | set(ITER_PHASES) | {"iter", "eval"} <= names
            by_id = {e["id"]: e for e in spans}
            assert len(by_id) == len(spans) and not ids_seen & set(by_id)
            ids_seen |= set(by_id)
            sid = bst._model._obs.id
            assert all(e["booster"] == sid for e in spans)
            # one tree under the booster: every parent is a span of the
            # same booster; at the top only the fold's set-up, the
            # iterations and the evaluations between them
            tops = {e["name"][len("lgbtpu."):] for e in spans
                    if e["parent"] is None}
            assert tops == {"cv.fold_setup", "iter", "eval"}
            assert all(e["parent"] in by_id for e in spans
                       if e["parent"] is not None)

            def parent_name(e):
                return by_id[e["parent"]]["name"][len("lgbtpu."):]
            for e in spans:
                name = e["name"][len("lgbtpu."):]
                if name in ITER_PHASES:
                    assert parent_name(e) == "iter"
                elif name in ("dataset.subset", "booster.init"):
                    assert parent_name(e) == "cv.fold_setup"
                elif name == "grower.make":
                    assert parent_name(e) == "booster.init"
                elif name == "booster.to_device":
                    assert parent_name(e) == {
                        "binned": "booster.init", "row_state": "booster.init",
                        "valid": "cv.fold_setup"}[e["args"]["what"]]
            setup = next(e for e in spans
                         if e["name"] == "lgbtpu.cv.fold_setup")
            assert setup["args"] == {"fold": fold}
            subsets = [e for e in spans
                       if e["name"] == "lgbtpu.dataset.subset"]
            assert sorted(e["args"]["rows"] for e in subsets) == [600, 600]
            # the gathered rows: uint8 bins and the float64 raw values
            assert all(e["args"]["bytes"] == 600 * 8 * (1 + 8)
                       for e in subsets)
            evals = [e for e in spans if e["name"] == "lgbtpu.eval"]
            assert [e["args"]["rows"] for e in evals] == [600, 600]
        # the shared sink holds both boosters' spans, told apart by id
        events = read_jsonl(str(tmp_path / "cv.jsonl"))
        assert {e["booster"] for e in events if e["ph"] == "X"} \
            == {b._model._obs.id for b in boosters}

    def test_registry_sees_what_the_spans_saw(self):
        # a grower no earlier test built: the memo's first answer is miss
        boosters = _cv(lambda_l2=0.0626)
        for fold, bst in enumerate(boosters):
            m, snap = bst._model, bst.telemetry_snapshot()
            spans = _spans(bst)
            placed = [m.binned_dev, m.score, m.objective.label] \
                + [a for _, vb, vs in m.valid_sets for a in (vb, vs)]
            assert snap["xfer.h2d_bytes"]["value"] \
                == sum(int(a.nbytes) for a in placed)
            assert sum(e["args"]["bytes"] for e in spans
                       if e["name"] == "lgbtpu.booster.to_device") \
                == snap["xfer.h2d_bytes"]["value"]
            # the memo of jitted growers: the second fold's booster runs
            # the program the first one traced
            result = "hit" if fold else "miss"
            assert snap[f"grower.memo{{result={result}}}"]["value"] == 1.0
            assert ("jax.traces{name=grower}" in snap) == (fold == 0)
            for stage, n in (("fold_setup", 1), ("subset", 2),
                             ("booster_init", 1), ("to_device", 3),
                             ("grower_make", 1)):
                h = snap[f"train.setup_seconds{{stage={stage}}}"]
                assert h["count"] == n
            assert snap["train.eval_seconds"]["count"] == 2
            # Dataset.construct's own stages, from the root of the
            # fold's reference chain
            root = bst.train_set.get_ref_chain()[-1]
            assert set(root.construct_seconds) == {"to_numpy", "fit_bins",
                                                   "bin_data"}
            for stage, seconds in root.construct_seconds.items():
                h = snap[f"data.construct_seconds{{stage={stage}}}"]
                assert h["count"] == 1 and h["sum"] == seconds

    def test_phases_partition_the_iteration(self):
        for bst in _cv():
            snap = bst.telemetry_snapshot()
            phases = sum(rec["sum"] for key, rec in snap.items()
                         if key.startswith("train.phase_seconds{"))
            whole = snap["train.iter_seconds"]["sum"]
            assert 0 < phases <= whole
            # what no phase owns is the loop's own few lines
            assert (whole - phases) / whole < 0.10
            for e in _spans(bst):
                if e["name"] == "lgbtpu.iter":
                    kids = sum(k["dur"] for k in _spans(bst)
                               if k["parent"] == e["id"])
                    assert kids <= e["dur"]

    def test_a_span_inside_a_phase_stays_out_of_the_registry(self):
        obs = ObsSession()
        obs.iter_begin(0)
        grow = obs.phase("grow", 0)
        inner = obs.phase("hist_pass", 0)        # nested: tracer only
        obs.end_phase(inner)
        obs.end_phase(grow)
        obs.iter_end(0)
        snap = obs.snapshot()
        assert "train.phase_seconds{phase=grow}" in snap
        assert "train.phase_seconds{phase=hist_pass}" not in snap
        names = [e["name"] for e in obs.tracer.events]
        assert names == ["lgbtpu.hist_pass", "lgbtpu.grow", "lgbtpu.iter"]

    def test_a_fold_setup_lands_in_the_boosters_own_session(self):
        """``cv.fold_setup`` opens before the Booster exists: the fold's
        session is built first and handed down, not replaced."""
        for bst in _cv():
            obs = bst._model._obs
            setup = [e for e in obs.tracer.events
                     if e["name"] == "lgbtpu.cv.fold_setup"]
            assert len(setup) == 1
            assert "train.setup_seconds{stage=fold_setup}" in obs.snapshot()

    def test_traces_go_to_the_booster_that_runs(self):
        """Five sessions are built before the first iteration runs; a
        trace is counted in the session of the booster whose iteration
        traced, not in the one built last."""
        a, b = ObsSession(), ObsSession()
        from lightgbm_tpu.utils.compile_cache import trace_event
        a.iter_begin(0)
        trace_event("probe_program")
        a.iter_end(0)
        assert a.snapshot()["jax.traces{name=probe_program}"]["value"] == 1
        assert "jax.traces{name=probe_program}" not in b.snapshot()


class TestProfilerClock:
    def test_host_plane_holds_the_partition_and_no_enclosing_span(self):
        """Under a profiler session (as benchmarks/run.py::Trace opens
        it) the program's spans are host events beside jax's; the span
        that encloses a whole iteration is not among them, or it would
        name every idle gap."""
        import jax
        from jax._src.lib import _profiler
        x, y = _small_data()
        ds = lgb.Dataset(x, label=y, params=CV_PARAMS).construct()
        jax.devices()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        session = _profiler.ProfilerSession(opts)
        try:
            lgb.cv(CV_PARAMS, ds, num_boost_round=2, nfold=2,
                   stratified=False)
        finally:
            data = session.stop_and_get_profile_data()
        host = [ev.name for plane in data.planes
                if plane.name.startswith("/host:")
                for line in plane.lines for ev in line.events
                if ev.name.startswith("lgbtpu.")]
        assert host.count("lgbtpu.cv.fold_setup") == 2
        assert host.count("lgbtpu.grow") == 4
        assert host.count("lgbtpu.eval") == 4
        assert "lgbtpu.iter" not in host
        assert set(host) == {"lgbtpu." + n for n in
                             SETUP_SPANS | set(ITER_PHASES) | {"eval"}}

    def test_no_annotation_without_telemetry(self, monkeypatch):
        import jax.profiler

        def boom(*a, **kw):
            raise AssertionError("telemetry=false made a TraceAnnotation")
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
        x, y = _small_data()
        params = dict(CV_PARAMS, telemetry=False)
        lgb.cv(params, lgb.Dataset(x, label=y, params=params),
               num_boost_round=1, nfold=2, stratified=False)


class TestDeviceScopes:
    """``jax.named_scope`` names the device's phases and changes nothing
    else: with the scopes patched to nothing the same program lowers."""

    @staticmethod
    def _grower_text(split_batch, debug):
        import jax
        import jax.numpy as jnp
        from lightgbm_tpu.grower import make_grower
        from lightgbm_tpu.ops.split import SplitParams
        n, f = 512, 6
        fn = make_grower(num_leaves=7, num_bins=32, split_batch=split_batch,
                         params=SplitParams(min_data_in_leaf=5), jit=False)
        args = (jnp.zeros((n, f), jnp.uint8), jnp.zeros((n, 3), jnp.float32),
                jnp.ones(f, bool), jnp.full(f, 32, jnp.int32),
                jnp.full(f, -1, jnp.int32))
        return jax.jit(fn).lower(*args).as_text(debug_info=debug)

    @staticmethod
    def _hist_text(debug):
        import jax
        import jax.numpy as jnp
        from lightgbm_tpu.ops.histogram import compute_histogram
        fn = jax.jit(lambda b, v, s: compute_histogram(
            b, v, num_bins=32, slot=s, num_slots=4))
        return fn.lower(jnp.zeros((512, 6), jnp.uint8),
                        jnp.zeros((512, 3), jnp.float32),
                        jnp.zeros(512, jnp.int32)).as_text(debug_info=debug)

    @pytest.mark.parametrize("split_batch", [1, 4])
    def test_grower_carries_every_scope(self, split_batch):
        text = self._grower_text(split_batch, True)
        for scope in ("lgbtpu.grow", "lgbtpu.partition", "lgbtpu.hist/",
                      "lgbtpu.hist.onehot", "lgbtpu.hist.contract",
                      "lgbtpu.hist.state", "lgbtpu.split"):
            assert scope in text, scope

    def test_histogram_pass_carries_its_scopes(self):
        text = self._hist_text(True)
        assert "lgbtpu.hist.onehot" in text
        assert "lgbtpu.hist.contract" in text

    def test_walk_score_and_eval_carry_scopes(self):
        import jax
        import jax.numpy as jnp
        from lightgbm_tpu.metrics import build_traced_eval
        from lightgbm_tpu.predict_device import add_tree_score
        z = jnp.zeros(3, jnp.int32)
        text = add_tree_score.lower(
            jnp.zeros(64), jnp.zeros((64, 4), jnp.uint8), z, z,
            jnp.zeros(3, bool), z, z, jnp.full(4, -1, jnp.int32),
            jnp.zeros(3, bool), jnp.zeros((3, 32), jnp.int32),
            jnp.zeros(4), jnp.float32(1.0), steps=2) \
            .as_text(debug_info=True)
        assert "lgbtpu.walk" in text and "lgbtpu.score" in text
        teval = build_traced_eval(((0, "valid", "auc", True),),
                                  Config({"objective": "binary"}))
        text = teval.lower((jnp.zeros(64),), ((jnp.zeros(64), jnp.ones(64)),)) \
            .as_text(debug_info=True)
        assert "lgbtpu.eval" in text

    def test_scopes_change_nothing_but_names(self, monkeypatch):
        """The lowered text without locations (where a scope's name
        lives) is the same with the scopes and with ``jax.named_scope``
        patched to nothing."""
        import contextlib

        import jax

        def texts(debug):
            return [self._grower_text(1, debug), self._grower_text(4, debug),
                    self._hist_text(debug)]
        with_scopes = texts(False)
        assert all("lgbtpu." not in t for t in with_scopes)

        @contextlib.contextmanager
        def no_scope(name):
            yield
        monkeypatch.setattr(jax, "named_scope", no_scope)
        jax.clear_caches()
        try:
            without, named = texts(False), texts(True)
        finally:
            jax.clear_caches()      # nothing traced here outlives the test
        # (a decorator applied at import keeps its scope; every ``with``
        # block and the growers' own decorators are patched away)
        assert all("lgbtpu.partition" not in t and "lgbtpu.grow" not in t
                   and "lgbtpu.hist.onehot" not in t for t in named)
        assert without == with_scopes
