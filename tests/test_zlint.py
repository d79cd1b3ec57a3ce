"""Static-analysis suite (tools/lint.py + tools/analyze/ —
docs/Static-Analysis.md).

- the UNIFIED tier-1 invocation: ``python tools/lint.py`` green over
  all four passes (races, purity, syncs, retraces) — this run replaces
  the separate sync/retrace invocations;
- a tamper negative control per pass (injected unguarded write,
  injected ``np.sum`` in a traced body, injected raw sync,
  budget-exceeding retrace), subprocess-driven like the retrace tests;
- lock-order cycle detection, stale-pin detection, mandatory-rationale
  enforcement, ``--update`` re-pin round-trip;
- in-process lintlib/guard-inference units;
- regression tests for the concrete races the lint surfaced and this
  PR fixed (registry in-flight counter, server version counter,
  continual freshness state);
- a marker-gated concurrency stress test hammering registry hot-swap +
  batcher drain from N threads to dynamically corroborate the
  statically-fixed races.
"""

import os
import shutil
import subprocess
import sys
import threading

import numpy as np
import pytest

import lightgbm_tpu as lgb

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT = os.path.join(REPO, "tools", "lint.py")
PKG = os.path.join(REPO, "lightgbm_tpu")

sys.path.insert(0, os.path.join(REPO, "tools"))


def _run_lint(*args, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, LINT, *args],
                          capture_output=True, text=True,
                          timeout=timeout, env=env, cwd=REPO)


def _copy_pkg(tmp_path) -> str:
    """Copy the package under a dir of the SAME name so the real
    allowlists (keyed ``lightgbm_tpu/...``) keep matching."""
    dst = str(tmp_path / "lightgbm_tpu")
    shutil.copytree(PKG, dst, ignore=shutil.ignore_patterns(
        "__pycache__"))
    return dst


def _train_tiny(seed=0, rounds=2, **over):
    rs = np.random.RandomState(seed)
    x = rs.randn(300, 6)
    y = (x[:, 0] - x[:, 1] + 0.2 * rs.randn(300) > 0).astype("float32")
    p = {"objective": "binary", "num_leaves": 7, "verbosity": 0,
         "min_data_in_leaf": 5, "max_bin": 15, "fused_chunk": 0}
    p.update(over)
    ds = lgb.Dataset(x, label=y, params=p)
    return lgb.train(p, ds, num_boost_round=rounds), x


# -- the tier-1 invocation --------------------------------------------------

class TestUnifiedDriver:
    def test_all_four_passes_green(self):
        """THE tier-1 lint run: one driver, one exit code, all four
        passes against the pinned allowlists/budget (the retrace
        matrix rides a warm compile cache, ~15 s)."""
        out = _run_lint(timeout=600)
        assert out.returncode == 0, out.stdout + out.stderr
        for name in ("races", "purity", "syncs", "retraces"):
            assert f"[{name}] clean" in out.stdout, out.stdout
        assert "all passes clean" in out.stdout

    def test_unknown_pass_rejected(self):
        out = _run_lint("--only", "nonsense")
        assert out.returncode == 2
        assert "unknown pass" in out.stderr


# -- race lint: tampers + mechanisms ----------------------------------------

class TestRaceLintTamper:
    def test_injected_unguarded_write_fails(self, tmp_path):
        """Negative control: a method writing a lock-guarded attribute
        without the lock must fail the driver."""
        root = _copy_pkg(tmp_path)
        p = os.path.join(root, "serve", "batcher.py")
        src = open(p).read()
        assert "def max_wait_ms_effective" in src
        src = src.replace(
            "    def max_wait_ms_effective(self) -> float:",
            "    def poke(self) -> None:\n"
            "        self._depth_rows += 1\n\n"
            "    def max_wait_ms_effective(self) -> float:")
        open(p, "w").write(src)
        out = _run_lint("--only", "races", "--package-root", root)
        assert out.returncode == 1, out.stdout + out.stderr
        assert "MicroBatcher.poke" in out.stderr
        assert "_depth_rows" in out.stderr
        assert "outside its guard" in out.stderr

    def test_lock_order_cycle_detected(self, tmp_path):
        """Static deadlock detection: two classes acquiring each
        other's locks through declared attr types form a cycle."""
        root = _copy_pkg(tmp_path)
        with open(os.path.join(root, "serve", "cycletamper.py"),
                  "w") as f:
            f.write('''\
"""Synthetic lock-order cycle."""
import threading


class Alpha:
    """A.

    Lock contract (tools/analyze/check_races.py):
        _lock guards: _a
        peer type: lightgbm_tpu/serve/cycletamper.py:Beta
    """

    def __init__(self, peer):
        self._lock = threading.Lock()
        self._a = 0
        self.peer = peer

    def tick(self):
        with self._lock:
            self._a += 1
            self.peer.tock()


class Beta:
    """B.

    Lock contract (tools/analyze/check_races.py):
        _lock guards: _b
        peer type: lightgbm_tpu/serve/cycletamper.py:Alpha
    """

    def __init__(self, peer):
        self._lock = threading.Lock()
        self._b = 0
        self.peer = peer

    def tock(self):
        with self._lock:
            self._b += 1

    def kick(self):
        with self._lock:
            self.peer.tick()
''')
        out = _run_lint("--only", "races", "--package-root", root)
        assert out.returncode == 1
        assert "lock-order cycle" in out.stderr
        assert "Alpha._lock" in out.stderr and "Beta._lock" \
            in out.stderr

    def test_stale_race_pin_rejected(self, tmp_path):
        allow = tmp_path / "races.txt"
        allow.write_text("lightgbm_tpu/serve/batcher.py | "
                         "MicroBatcher.ghost | _queue | no such site\n")
        out = _run_lint("--only", "races",
                        "--race-allowlist", str(allow))
        assert out.returncode == 1
        assert "stale race allowlist entry" in out.stderr

    def test_rationale_is_mandatory(self, tmp_path):
        allow = tmp_path / "races.txt"
        allow.write_text("lightgbm_tpu/serve/batcher.py | "
                         "MicroBatcher.submit | _queue |\n")
        out = _run_lint("--only", "races",
                        "--race-allowlist", str(allow))
        assert out.returncode == 1
        assert "malformed pin" in out.stderr


class TestRaceLintInference:
    """In-process units over synthetic packages: the inference
    mechanics the real-tree green run exercises only implicitly."""

    def _run_on(self, tmp_path, source: str, allow: str = ""):
        from analyze import check_races
        root = tmp_path / "lightgbm_tpu"
        root.mkdir()
        (root / "threaded.py").write_text(source)
        allowf = tmp_path / "allow.txt"
        allowf.write_text(allow)
        return check_races.run(str(root), str(allowf), modules=[])

    def test_locked_helper_contexts_propagate(self, tmp_path):
        """A private helper only ever called with the lock held is NOT
        flagged (the `_trip_locked` pattern), and the same helper
        reachable from a public method without the lock IS."""
        findings = self._run_on(tmp_path, '''\
import threading


class Good:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0

    def bump(self):
        with self._lock:
            self._bump_locked()

    def _bump_locked(self):
        self._n += 1


class Bad(Good):
    def __init__(self):
        self._lock = threading.Lock()
        self._m = 0

    def locked_write(self):
        with self._lock:
            self._m = 1

    def sneaky(self):
        self._helper()

    def _helper(self):
        self._m = 2
''')
        joined = "\n".join(findings)
        assert "Good" not in joined, joined
        assert "_helper" in joined and "_m" in joined, joined

    def test_condition_aliases_its_lock(self, tmp_path):
        """threading.Condition(self._lock) is the SAME mutex: holding
        the condition's with-block satisfies the lock's guard."""
        findings = self._run_on(tmp_path, '''\
import threading


class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._q = []

    def put(self, x):
        with self._cv:
            self._q.append(x)
            self._cv.notify()

    def take(self):
        with self._lock:
            return self._q.pop(0)
''')
        assert findings == [], "\n".join(findings)

    def test_docstring_contract_and_staleness(self, tmp_path):
        """A declared guard flags lock-free accesses inference alone
        would miss; a contract line naming a never-accessed attribute
        is stale and fails."""
        findings = self._run_on(tmp_path, '''\
import threading


class D:
    """Doc.

    Lock contract (tools/analyze/check_races.py):
        _lock guards: _flag, _ghost
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._flag = False

    def set(self):
        self._flag = True
''')
        joined = "\n".join(findings)
        assert "_flag" in joined and "outside its guard" in joined
        assert "stale lock contract" in joined and "_ghost" in joined

    def test_stale_type_line_flagged(self, tmp_path):
        """A `type:` contract line that resolves to no analyzed class
        silently drops deadlock-graph edges — it must be reported
        stale, like every other rotten pin."""
        findings = self._run_on(tmp_path, '''\
import threading


class T:
    """Doc.

    Lock contract (tools/analyze/check_races.py):
        _lock guards: _n
        peer type: lightgbm_tpu/gone.py:Ghost
    """

    def __init__(self, peer):
        self._lock = threading.Lock()
        self._n = 0
        self.peer = peer

    def tick(self):
        with self._lock:
            self._n += 1
            self.peer.tock()
''')
        joined = "\n".join(findings)
        assert "stale lock contract" in joined and "Ghost" in joined

    def test_multi_writer_without_lock_flagged(self, tmp_path):
        findings = self._run_on(tmp_path, '''\
import threading


class M:
    def __init__(self):
        self._lock = threading.Lock()   # owns a lock -> reported on
        self._count = 0

    def a(self):
        self._count += 1

    def b(self):
        self._count -= 1
''')
        joined = "\n".join(findings)
        assert "_count" in joined and "2 methods with no lock" \
            in joined


# -- purity lint ------------------------------------------------------------

class TestPurityLintTamper:
    def test_injected_np_sum_fails(self, tmp_path):
        """Negative control: np.* compute on a traced value inside the
        forest-walk body must fail the driver."""
        root = _copy_pkg(tmp_path)
        p = os.path.join(root, "predict_device.py")
        src = open(p).read()
        probe = ("    n = binned.shape[0]\n"
                 "    t = split_feature.shape[0]\n"
                 "    node = jnp.zeros((n, t), jnp.int32)")
        assert probe in src
        src = src.replace(probe,
                          "    n = binned.shape[0]\n"
                          "    t = split_feature.shape[0]\n"
                          "    import numpy as np\n"
                          "    _bad = np.sum(binned)\n"
                          "    node = jnp.zeros((n, t), jnp.int32)")
        open(p, "w").write(src)
        out = _run_lint("--only", "purity", "--package-root", root)
        assert out.returncode == 1, out.stdout + out.stderr
        assert "np.sum" in out.stderr
        assert "_forest_walk" in out.stderr

    def test_stale_purity_pin_rejected(self, tmp_path):
        allow = tmp_path / "purity.txt"
        allow.write_text("lightgbm_tpu/predict_device.py | ghost | "
                         "np.sum | gone\n")
        out = _run_lint("--only", "purity",
                        "--purity-allowlist", str(allow))
        assert out.returncode == 1
        assert "stale purity allowlist entry" in out.stderr

    def test_traced_reachability_covers_the_hot_paths(self):
        """The reachable-function inference must cover the grower, the
        super-epoch scan, the forest walk and the fused serve program — the
        bodies the issue names; an indexing regression that loses them
        would green-wash the whole pass."""
        from analyze import check_purity
        reach = set(check_purity.reachable_functions())
        for needle in (
                "lightgbm_tpu/grower.py:make_grower.grow_tree",
                "lightgbm_tpu/models/gbdt.py:"
                "GBDTModel._build_superepoch_body.sepoch_body.one_iter",
                "lightgbm_tpu/predict_device.py:_forest_walk",
                "lightgbm_tpu/predict_device.py:fused_forest_predict",
                "lightgbm_tpu/ops/histogram.py:compute_histogram",
        ):
            assert any(r.startswith(needle) for r in reach), \
                (needle, sorted(reach)[:40])


# -- sync lint through the driver -------------------------------------------

class TestSyncLintTamper:
    def test_injected_raw_sync_fails(self, tmp_path):
        root = _copy_pkg(tmp_path)
        p = os.path.join(root, "serve", "registry.py")
        src = open(p).read()
        src = src.replace(
            "import threading\nimport time",
            "import threading\nimport time\n\n\n"
            "def _bad_sync(x):\n"
            "    import jax\n"
            "    return jax.device_get(x)")
        open(p, "w").write(src)
        out = _run_lint("--only", "syncs", "--package-root", root)
        assert out.returncode == 1, out.stdout + out.stderr
        assert "device_get" in out.stderr


# -- retrace pass through the driver ----------------------------------------

class TestRetraceViaDriver:
    """The expensive pass: each test re-runs the canonical matrix in a
    subprocess (warm compile cache ~15 s), so the sensitivity checks
    are slow-marked like the existing test_zretrace tampers; the green
    run is already covered by TestUnifiedDriver."""

    @pytest.mark.slow
    def test_budget_breach_fails(self, tmp_path):
        import re
        budget = os.path.join(REPO, "tools", "retrace_budget.txt")
        tampered = tmp_path / "budget.txt"
        text = open(budget).read()
        text = re.sub(r"leaf_sweep.grower = \d+",
                      "leaf_sweep.grower = 0", text)
        tampered.write_text(text + "ghost.scenario = 9\n")
        out = _run_lint("--only", "retraces", "--budget",
                        str(tampered), timeout=600)
        assert out.returncode == 1
        assert "trace budget violated: leaf_sweep.grower" in out.stderr
        assert "stale budget entry" in out.stderr

    @pytest.mark.slow
    def test_update_repin_round_trip(self, tmp_path):
        """--update writes a budget the very next run is green
        against."""
        budget = tmp_path / "budget.txt"
        up = _run_lint("--only", "retraces", "--update",
                       "--budget", str(budget), timeout=600)
        assert up.returncode == 0, up.stdout + up.stderr
        assert budget.exists() and "leaf_sweep.grower" \
            in budget.read_text()
        green = _run_lint("--only", "retraces", "--budget",
                          str(budget), timeout=600)
        assert green.returncode == 0, green.stdout + green.stderr


# -- lintlib units ----------------------------------------------------------

class TestLintlib:
    def test_parse_pins_rationale_enforced(self, tmp_path):
        from analyze import lintlib
        f = tmp_path / "pins.txt"
        f.write_text("# comment\na.py | X.y | attr | because\n")
        [(key, why)] = lintlib.parse_pins(str(f), 3,
                                          require_rationale=True)
        assert key == ("a.py", "X.y", "attr") and why == "because"
        f.write_text("a.py | X.y | attr |\n")
        with pytest.raises(ValueError, match="malformed pin"):
            lintlib.parse_pins(str(f), 3, require_rationale=True)

    def test_stale_pins_and_kv_round_trip(self, tmp_path):
        from analyze import lintlib
        stale = lintlib.stale_pins({("a",), ("b",)}, {("a",)}, "zzz")
        assert stale == ["stale zzz entry (no matching finding): b"]
        p = str(tmp_path / "kv.txt")
        lintlib.write_kv_int({"x.y": 3, "a.b": 1}, p, ["# hdr"])
        assert lintlib.load_kv_int(p) == {"x.y": 3, "a.b": 1}

    def test_rel_to_root_is_copy_stable(self, tmp_path):
        """The path convention that makes tamper copies match the real
        allowlists: rel is computed against the PARENT of the scanned
        root, so <tmp>/lightgbm_tpu/serve/x.py pins identically to the
        real tree."""
        from analyze import lintlib
        root = tmp_path / "lightgbm_tpu"
        (root / "serve").mkdir(parents=True)
        f = root / "serve" / "x.py"
        f.write_text("pass\n")
        assert lintlib.rel_to_root(str(f), str(root)) == \
            os.path.join("lightgbm_tpu", "serve", "x.py")


# -- regression tests for the races this PR fixed ---------------------------

class TestRaceFixRegressions:
    def test_served_model_inflight_is_consistent_under_threads(self):
        """registry.py fix: the in-flight counter's reads take _iflock;
        N threads bracketing begin/end must land on exactly zero, and
        concurrent describe() must never crash or report < 0."""
        from lightgbm_tpu.serve.registry import ModelRegistry
        bst, _x = _train_tiny()
        reg = ModelRegistry(build_engine=False)
        v = reg.load(booster=bst)
        served = reg.get(v)
        errs = []

        def worker():
            try:
                for _ in range(300):
                    served.begin_request()
                    assert served.inflight >= 1
                    d = served.describe()
                    assert d["inflight"] >= 0
                    served.end_request()
            except BaseException as e:   # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=worker) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30)
        assert not errs, errs
        assert served.inflight == 0

    def test_server_version_counter_survives_concurrent_reloads(self):
        """server.py fix: _versions_loaded += 1 races from HTTP handler
        threads were lost updates; under the lock the count is exact."""
        from lightgbm_tpu.serve.server import Server
        bst, _x = _train_tiny()
        srv = Server({"verbosity": -1, "serve_max_wait_ms": 0.0},
                     booster=bst)
        try:
            per, n = 25, 6
            errs = []

            def reloader():
                try:
                    for _ in range(per):
                        srv.reload(booster=bst)
                except BaseException as e:   # noqa: BLE001
                    errs.append(e)

            ts = [threading.Thread(target=reloader) for _ in range(n)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(60)
            assert not errs, errs
            with srv._lock:
                got = srv._versions_loaded
            assert got == 1 + per * n
        finally:
            srv.close()

    def test_continual_freshness_readable_during_generation(
            self, tmp_path):
        """continual.py fix: the freshness surface (generation, chunk
        stamp, promote stamp) is lock-guarded, so an HTTP-style reader
        polling freshness_lag_s()/generation during a generation never
        sees a torn pair (a negative lag) and never crashes."""
        from lightgbm_tpu.pipeline.continual import ContinualTrainer
        rs = np.random.RandomState(1)
        x = rs.randn(400, 6)
        y = (x[:, 0] - x[:, 1] + 0.2 * rs.randn(400) > 0) \
            .astype("float64")
        out_model = str(tmp_path / "m.txt")
        params = {"objective": "binary", "num_leaves": 7,
                  "verbosity": -1, "min_data_in_leaf": 5,
                  "max_bin": 15, "output_model": out_model,
                  "continual_rounds": 2, "shadow_probe_batches": 2}
        ct = ContinualTrainer(params, x[:200], y[:200])
        stop = threading.Event()
        errs = []

        def reader():
            try:
                while not stop.is_set():
                    lag = ct.freshness_lag_s()
                    assert lag is None or lag >= 0, lag
                    assert ct.generation >= 0
                    # the /freshness surface: ONE-lock snapshot means
                    # the publish record can never be torn against the
                    # generation counter (standalone versions are
                    # genN with N == generation)
                    snap = ct.freshness_snapshot()
                    lp = snap["last_publish"]
                    if lp is not None:
                        assert lp["version"] == \
                            f"gen{snap['generation']}", snap
            except BaseException as e:   # noqa: BLE001
                errs.append(e)

        t = threading.Thread(target=reader)
        t.start()
        try:
            r1 = ct.run_generation(x[200:300], y[200:300])
            r2 = ct.run_generation(x[300:], y[300:])
        finally:
            stop.set()
            t.join(30)
        assert not errs, errs
        assert r1["status"] == "published", r1
        assert r2["status"] == "published", r2
        assert ct.generation == 2


# -- dynamic corroboration: hot-swap + drain storm --------------------------

@pytest.mark.stress
class TestConcurrencyStress:
    def test_hot_swap_drain_storm(self):
        """Hammer a live Server from N client threads while a reloader
        thread hot-swaps versions, then drain: every accepted request
        is answered (correct row count), refusals are the typed drain/
        closed errors only, and the drain leaves nothing queued — the
        dynamic counterpart of the statically-checked lock discipline
        in batcher/registry/server."""
        from lightgbm_tpu.serve.batcher import (BatcherClosed,
                                                BatcherDraining)
        from lightgbm_tpu.serve.server import Server
        bst_a, x = _train_tiny(seed=0)
        bst_b, _ = _train_tiny(seed=1, learning_rate=0.2)
        srv = Server({"verbosity": -1, "serve_max_batch": 64,
                      "serve_max_wait_ms": 0.5}, booster=bst_a)
        stop = threading.Event()
        errs: list = []
        answered = [0]
        refused = [0]

        def client(i):
            rs = np.random.RandomState(i)
            try:
                while not stop.is_set():
                    n = int(rs.randint(1, 9))
                    rows = x[rs.randint(0, len(x), n)]
                    try:
                        out = srv.predict(rows, timeout=30)
                    except (BatcherDraining, BatcherClosed):
                        refused[0] += 1
                        continue
                    assert len(np.atleast_1d(out)) == n
                    answered[0] += 1
            except BaseException as e:   # noqa: BLE001
                errs.append(e)

        def reloader():
            try:
                k = 0
                while not stop.is_set():
                    srv.reload(booster=[bst_a, bst_b][k % 2])
                    k += 1
            except BaseException as e:   # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(6)]
        threads.append(threading.Thread(target=reloader))
        for t in threads:
            t.start()
        import time
        time.sleep(1.5)
        # drain while the storm is still submitting: late submissions
        # must refuse with BatcherDraining, accepted work must finish
        report = srv.drain(timeout_s=20)
        stop.set()
        for t in threads:
            t.join(30)
        try:
            assert not errs, errs
            assert answered[0] > 0
            assert report["drained"] is True, report
            assert report["leftover_rows"] == 0, report
            health = srv.health()
            assert health["status"] == "draining"
        finally:
            srv.close()
