"""Chaos-injection soak harness for the serving subsystem.

Hammers a live ``serve.Server`` from concurrent clients while a
reloader thread hot-swaps between two model versions and a chaos thread
arms ``utils/faultinject`` windows (``serve_batch`` transient device
faults, ``serve_reload`` failed loads), then checks the INVARIANTS the
hardening layer promises (docs/Serving.md "Hardening"):

- **No request is ever lost or hung**: every accepted submission
  resolves — a prediction, or a typed rejection (``BacklogFull``,
  ``CircuitOpen``, ``DeadlineExceeded``, ``BatcherClosed``).  A
  ``result()`` timeout is a violation.
- **Parity under fire**: every successful prediction is byte-identical
  to ``Booster.predict`` of the model version that served it —
  micro-batch composition, concurrent reloads and injected faults may
  never corrupt a result.
- **Failed reloads are invisible**: an injected ``serve_reload`` fault
  leaves the current version serving.
- **The service recovers**: once chaos stops, predictions succeed again
  (the circuit breaker closes after its half-open probe).
- **Drain is clean**: after the soak, ``Server.drain`` answers every
  queued request, new work is refused, and the queue reads empty.

Run standalone (prints one JSON report, exit 1 on violations)::

    python tools/soak_serve.py duration_s=5 clients=8 chaos=1 http=0

Importable: ``run_soak(...)`` returns the report dict —
``tests/test_serve_hardening.py`` runs a short deterministic soak in
tier-1.
"""

from __future__ import annotations

import collections
import json
import os
import sys
import threading
import time
from typing import Dict, Optional

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

N_FEAT = 6


def build_models(seed: int = 0):
    """Two small distinguishable regression models to hot-swap between."""
    import lightgbm_tpu as lgb

    def one(s, rounds):
        rs = np.random.RandomState(s)
        x = rs.randn(400, N_FEAT)
        y = x[:, 0] + 0.5 * (s + 1) * x[:, 1]
        return lgb.train({"objective": "regression", "verbosity": -1,
                          "num_leaves": 8},
                         lgb.Dataset(x, label=y), num_boost_round=rounds)

    return one(seed, 8), one(seed + 1, 12)


def _request_pool(pool_size: int, max_rows: int, seed: int):
    rs = np.random.RandomState(seed + 7)
    return [rs.randn(int(n), N_FEAT)
            for n in rs.randint(1, max_rows + 1, pool_size)]


def run_soak(duration_s: float = 2.0, clients: int = 4,
             pool_size: int = 24, max_rows: int = 48, seed: int = 0,
             chaos: bool = True, reload_every_s: float = 0.25,
             deadline_ms: float = 2000.0, http: bool = False,
             device_binning: bool = False,
             chaos_spec: Optional[str] = None,
             params: Optional[Dict] = None) -> Dict:
    """One soak run; returns the report dict (see module docstring).

    ``device_binning=True`` serves through the fused device-resident
    path (``serve_device_binning``) and arms a ``serve_self_check``
    fault in the chaos window: a reload whose engine self-check fails
    must DEMOTE that version to the host walk — still answering every
    request with that version's own exact predictions
    (``serve.host_fallback_batches`` counts them) — never refuse
    traffic.  Successful responses must then byte-match EITHER the
    version's fused-path scores or its host-walk scores (both are
    sanctioned results of the mode; which one served depends on
    whether the chaos window demoted that load)."""
    from lightgbm_tpu.serve import (BacklogFull, BatcherClosed,
                                    BatcherDraining, CircuitOpen,
                                    DeadlineExceeded, Server)
    from lightgbm_tpu.serve.server import start_http
    from lightgbm_tpu.utils import faultinject

    b1, b2 = build_models(seed)
    pool = _request_pool(pool_size, max_rows, seed)
    # byte-parity oracles, computed OUTSIDE the soak: every ok response
    # must equal the serving version's own Booster.predict (host walk)
    # — or, under device_binning, its fused-path scores
    expected = {"m1": [[np.asarray(b1.predict(p))] for p in pool],
                "m2": [[np.asarray(b2.predict(p))] for p in pool]}
    if device_binning:
        from lightgbm_tpu.serve.engine import PredictorEngine
        for tag, bst in (("m1", b1), ("m2", b2)):
            ref = PredictorEngine.from_booster(bst, max_batch=64)
            for i, p in enumerate(pool):
                expected[tag][i].append(ref.fused_predict(p))
    srv_params = {"serve_max_batch": 64, "serve_max_wait_ms": 1.0,
                  "serve_queue_rows": 256, "serve_retries": 1,
                  "serve_breaker_failures": 3,
                  "serve_breaker_cooldown_ms": 200.0,
                  "serve_deadline_ms": deadline_ms, "verbosity": -1,
                  "serve_device_binning": device_binning}
    srv_params.update(params or {})
    srv = Server(srv_params, booster=b1)
    frontend = start_http(srv, port=0) if http else None
    base = f"http://127.0.0.1:{frontend.port}" if frontend else None

    stop = threading.Event()
    violations: list = []
    vlock = threading.Lock()

    def violate(msg: str) -> None:
        with vlock:
            violations.append(msg)

    version_tag = {"v1": "m1"}     # registry version -> model tag

    def tag_of(version) -> Optional[str]:
        return version_tag.get(version)

    # -- reloader: alternate hot swaps; injected failures must be no-ops
    reload_counts = collections.Counter()

    def reloader():
        k = 0
        while not stop.wait(reload_every_s):
            tag, bst = ("m1", b1) if k % 2 == 0 else ("m2", b2)
            version = f"{tag}@{k}"
            # mapping recorded BEFORE the load: activation is atomic
            # inside load, and a batch may resolve the new version the
            # instant it lands; a failed load leaves a harmless entry
            version_tag[version] = tag
            try:
                # through Server.reload, not registry.load directly:
                # the soak must exercise (and count into
                # serve.reload_failures) the surface operators use
                srv.reload(booster=bst, version=version)
                reload_counts["reload_ok"] += 1
            except Exception:     # noqa: BLE001 — injected serve_reload
                reload_counts["reload_failed"] += 1
            k += 1

    # -- chaos: windows of transient batch faults + failing reloads
    # (+ under device_binning: a failing engine self-check, which must
    # demote that reload to the host walk, not refuse traffic)
    spec = chaos_spec or ("serve_batch:1-6,serve_reload:1"
                          + (",serve_self_check:1" if device_binning
                             else ""))

    def chaos_thread():
        while not stop.wait(0.4):
            # the next 6 serve batches fail transiently (retries=1 ->
            # 2 attempts/batch -> 3 failed batches -> breaker opens at
            # threshold 3), and the next reload attempt fails too
            faultinject.configure(spec)
            stop.wait(0.15)
            faultinject.configure(None)

    # -- clients -----------------------------------------------------------
    def classify_and_count(counts, fut, i):
        try:
            out = fut.result(timeout=15.0)
        except DeadlineExceeded:
            counts["deadline_shed"] += 1
        except BatcherClosed:
            counts["closed"] += 1
        except TimeoutError:
            counts["hung"] += 1
            violate(f"request on pool[{i}] hung past 15s")
        except Exception as e:   # noqa: BLE001 — injected batch faults
            counts["error"] += 1
            if "injected fault" not in str(e):
                violate(f"unexpected request error: {e!r}")
        else:
            counts["ok"] += 1
            tag = tag_of(fut.info.get("model_version"))
            if tag is None:
                violate(f"response from unknown model version "
                        f"{fut.info.get('model_version')!r}")
            elif not any(np.array_equal(out, e)
                         for e in expected[tag][i]):
                violate(f"PARITY violation on pool[{i}] "
                        f"(version {fut.info.get('model_version')})")

    def client_inproc(tid, counts):
        rs = np.random.RandomState(seed * 100 + tid)
        while not stop.is_set():
            i = int(rs.randint(len(pool)))
            try:
                fut = srv.submit(pool[i])
            except BacklogFull:
                counts["backlog"] += 1
                stop.wait(0.002)
                continue
            except CircuitOpen:
                counts["circuit_open"] += 1
                stop.wait(0.01)
                continue
            except DeadlineExceeded:
                counts["deadline_rejected"] += 1
                continue
            except BatcherDraining:
                counts["draining"] += 1
                continue
            counts["submitted"] += 1
            classify_and_count(counts, fut, i)

    def client_http(tid, counts):
        import urllib.error
        import urllib.request
        rs = np.random.RandomState(seed * 100 + tid)
        while not stop.is_set():
            i = int(rs.randint(len(pool)))
            req = urllib.request.Request(
                base + "/predict",
                data=json.dumps({"rows": pool[i].tolist()}).encode(),
                headers={"Content-Type": "application/json"})
            try:
                resp = json.loads(urllib.request.urlopen(
                    req, timeout=15.0).read())
            except urllib.error.HTTPError as e:
                code = e.code
                e.read()
                counts[{429: "backlog", 503: "circuit_open",
                        504: "deadline_shed"}.get(code, "error")] += 1
                if code not in (429, 503, 504, 500):
                    violate(f"unexpected HTTP status {code}")
                stop.wait(0.01)
                continue
            except OSError:
                counts["hung"] += 1
                violate("HTTP request timed out (hung request)")
                continue
            counts["submitted"] += 1
            counts["ok"] += 1
            tag = tag_of(resp.get("model_version"))
            got = np.asarray(resp["predictions"])
            if tag is None:
                violate(f"response from unknown model version "
                        f"{resp.get('model_version')!r}")
            elif not any(np.array_equal(got, e)
                         for e in expected[tag][i]):
                violate(f"PARITY violation on pool[{i}] over HTTP "
                        f"(version {resp.get('model_version')})")

    client = client_http if http else client_inproc
    counts_per_thread = [collections.Counter() for _ in range(clients)]
    threads = [threading.Thread(target=client, args=(t, counts_per_thread[t]),
                                daemon=True, name=f"soak-client-{t}")
               for t in range(clients)]
    threads.append(threading.Thread(target=reloader, daemon=True,
                                    name="soak-reloader"))
    if chaos:
        threads.append(threading.Thread(target=chaos_thread, daemon=True,
                                        name="soak-chaos"))
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(timeout=30.0)
        if t.is_alive():
            violate(f"thread {t.name} failed to stop")
    faultinject.configure(None)

    # -- recovery: chaos is over, the breaker must close again -------------
    recovered = False
    t_end = time.perf_counter() + 10.0
    while time.perf_counter() < t_end:
        try:
            srv.predict(pool[0], timeout=10.0)
            recovered = True
            break
        except Exception:     # noqa: BLE001 — breaker cooldown et al.
            time.sleep(0.05)
    if not recovered:
        violate("service did not recover after chaos stopped")
    breaker_end = srv.breaker.describe() if srv.breaker else None
    if recovered and breaker_end and breaker_end["state"] != "closed":
        violate(f"breaker did not close after recovery: {breaker_end}")

    # -- graceful drain ----------------------------------------------------
    drain = srv.drain(10.0)
    if not drain["drained"]:
        violate(f"drain timed out with {drain['leftover_rows']} rows")
    if srv.batcher.depth_rows != 0:
        violate("queue not empty after drain")
    try:
        srv.submit(pool[0])
        violate("submit accepted during drain")
    except BatcherDraining:
        pass
    health = srv.health()
    if health["status"] != "draining":
        violate(f"health status {health['status']!r} during drain")

    counts = collections.Counter(reload_counts)
    for c in counts_per_thread:
        counts.update(c)
    snap = srv.metrics_snapshot()
    report = {
        "duration_s": round(time.perf_counter() - t0, 3),
        "mode": "http" if http else "inproc",
        "chaos": bool(chaos),
        "counts": dict(sorted(counts.items())),
        "recovered": recovered,
        "drain": drain,
        "breaker": breaker_end,
        "device_binning": bool(device_binning),
        "metrics": {k: snap[k] for k in
                    ("serve.requests", "serve.errors", "serve.rejected",
                     "serve.deadline_shed", "serve.deadline_rejected",
                     "serve.breaker_opens", "serve.breaker_rejected",
                     "serve.reload_failures", "serve.fused_batches",
                     "serve.host_fallback_batches") if k in snap},
        "violations": violations,
    }
    if frontend is not None:
        frontend.close()
    srv.close()
    return report


def run_continual_soak(duration_s: float = 4.0, clients: int = 3,
                       generations: int = 2, seed: int = 0,
                       gate_failure: bool = True, rows: int = 240,
                       chunk_rows: int = 120,
                       params: Optional[Dict] = None) -> Dict:
    """Continual-pipeline chaos soak (docs/Continual-Training.md): a
    live ``Server`` takes traffic from concurrent clients while a
    ``ContinualTrainer`` runs ``generations`` generations against its
    registry.  With ``gate_failure`` the FIRST continual generation's
    shadow probe is made to fail (injected ``shadow_probe`` fault) and
    must roll back.  Invariants checked:

    - the incumbent serves THROUGHOUT — every response carries a
      version that passed the gate; a rolled-back candidate's version
      never serves a single request;
    - no accepted request is lost or hung;
    - rollback is automatic and counted (``continual.rollbacks``), and
      the pipeline RECOVERS: the following generation publishes and its
      version takes traffic;
    - freshness is observable (``/freshness``-backed trainer state).
    """
    import shutil
    import tempfile

    import lightgbm_tpu  # noqa: F401 — path bootstrap before pipeline
    from lightgbm_tpu.pipeline.continual import ContinualTrainer
    from lightgbm_tpu.serve import (BacklogFull, BatcherClosed,
                                    BatcherDraining, CircuitOpen,
                                    DeadlineExceeded, Server)
    from lightgbm_tpu.utils import faultinject

    rs = np.random.RandomState(seed)

    def chunk(n):
        x = rs.randn(n, N_FEAT)
        return x, x[:, 0] + 0.5 * x[:, 1] + 0.05 * rs.randn(n)

    tmpdir = tempfile.mkdtemp(prefix="lgbtpu_continual_soak_")
    try:
        srv_params = {"objective": "regression", "num_leaves": 8,
                      "min_data_in_leaf": 5, "verbosity": -1,
                      "output_model": os.path.join(tmpdir, "m.txt"),
                      "continual_rounds": 3, "serve_max_batch": 64,
                      "serve_max_wait_ms": 1.0, "serve_queue_rows": 256}
        srv_params.update(params or {})
        srv = Server(srv_params)
        x0, y0 = chunk(rows)
        trainer = ContinualTrainer(srv_params, x0, y0, server=srv)
        base = trainer.run_generation()           # first incumbent
        violations: list = []
        vlock = threading.Lock()

        def violate(msg: str) -> None:
            with vlock:
                violations.append(msg)

        if base["status"] != "published":
            violate(f"base generation failed: {base}")
        promoted = {base.get("version")}
        refused: set = set()
        served_versions: set = set()
        stop = threading.Event()
        counts = collections.Counter()
        clock = threading.Lock()

        def client(tid):
            crs = np.random.RandomState(seed * 100 + tid)
            while not stop.is_set():
                rows_ = crs.randn(int(crs.randint(1, 24)), N_FEAT)
                try:
                    fut = srv.submit(rows_)
                except (BacklogFull, CircuitOpen, DeadlineExceeded,
                        BatcherDraining):
                    stop.wait(0.002)
                    continue
                try:
                    out = fut.result(timeout=15.0)
                except TimeoutError:
                    violate("request hung past 15s")
                    with clock:
                        counts["hung"] += 1
                    continue
                except Exception:   # noqa: BLE001 — incl. BatcherClosed
                    with clock:
                        counts["error"] += 1
                    continue
                with clock:
                    counts["ok"] += 1
                    served_versions.add(fut.info.get("model_version"))
                if not np.all(np.isfinite(np.asarray(out))):
                    violate("non-finite prediction served")

        threads = [threading.Thread(target=client, args=(t,), daemon=True,
                                    name=f"continual-soak-client-{t}")
                   for t in range(clients)]
        for t in threads:
            t.start()
        t0 = time.perf_counter()
        reports = [base]
        deadline = t0 + duration_s
        for g in range(generations):
            if gate_failure and g == 0:
                # one injected gate failure: the probe fires, the candidate
                # must quarantine and the incumbent keep serving
                faultinject.configure("shadow_probe:1-")
            rep = trainer.run_generation(*chunk(chunk_rows))
            faultinject.configure(None)
            reports.append(rep)
            if rep["status"] == "published":
                promoted.add(rep["version"])
            elif rep.get("version_refused"):
                refused.add(rep["version_refused"])
            if gate_failure and g == 0 and rep["status"] != "rolled_back":
                violate(f"injected gate failure did not roll back: {rep}")
            if (not gate_failure or g > 0) and rep["status"] != "published":
                violate(f"clean generation {g} failed: {rep}")
        # keep traffic flowing a moment on the final model
        while time.perf_counter() < deadline and not stop.is_set():
            time.sleep(0.01)
        stop.set()
        for t in threads:
            t.join(timeout=30.0)
            if t.is_alive():
                violate(f"thread {t.name} failed to stop")
        faultinject.configure(None)
        # gate invariants, judged on the COMPLETE ledger (checking inside
        # the client threads would race the activation): every version that
        # served passed the gate; a refused candidate never served
        ghost = served_versions - promoted
        if ghost:
            violate(f"responses from versions that never passed the gate: "
                    f"{sorted(v for v in ghost if v)}")
        hit = served_versions & refused
        if hit:
            violate(f"REFUSED candidate versions served requests: "
                    f"{sorted(hit)}")
        # the freshest published generation must be what serves now
        cur = srv.registry.current().version
        last_pub = [r for r in reports if r["status"] == "published"][-1]
        if cur != last_pub["version"]:
            violate(f"serving {cur!r}, expected freshest published "
                    f"{last_pub['version']!r}")
        fresh = srv.freshness()
        snap = srv.metrics_snapshot()
        drain = srv.drain(10.0)
        if not drain["drained"]:
            violate("drain timed out after continual soak")
        gen_hist = snap.get("continual.generation_seconds") or {}
        report = {
            "duration_s": round(time.perf_counter() - t0, 3),
            "mode": "continual",
            # headline numbers: chunk-arrival-to-serving lag of the
            # freshest generation, and mean wall time per generation
            "freshness_lag_s": fresh.get("freshness_lag_s"),
            "gen_s": round(gen_hist["sum"] / gen_hist["count"], 4)
            if gen_hist.get("count") else None,
            "generations": [
                {k: r.get(k) for k in ("generation", "status", "version",
                                       "iteration", "reason")}
                for r in reports],
            "counts": dict(sorted(counts.items())),
            "current_version": cur,
            "freshness": {k: fresh.get(k) for k in
                          ("model_version", "generation", "freshness_lag_s",
                           "generations_published",
                           "generations_rolled_back")},
            "metrics": {k: snap[k] for k in
                        ("continual.generations", "continual.published",
                         "continual.rollbacks", "continual.quarantined",
                         "serve.requests", "serve.errors") if k in snap},
            "violations": violations,
        }
        srv.close()
        return report
    finally:
        # the soak's working dir (snapshots, sidecars,
        # quarantine) is disposable: every bench/test
        # invocation must not leave debris in /tmp
        shutil.rmtree(tmpdir, ignore_errors=True)


def main(argv) -> int:
    if "--continual" in argv or \
            dict(a.split("=", 1) for a in argv if "=" in a) \
            .get("continual", "0") not in ("0", "false"):
        kv = dict(a.split("=", 1) for a in argv if "=" in a)
        report = run_continual_soak(
            duration_s=float(kv.get("duration_s", 4.0)),
            clients=int(kv.get("clients", 3)),
            generations=int(kv.get("generations", 2)),
            seed=int(kv.get("seed", 0)),
            gate_failure=kv.get("gate_failure", "1") not in ("0", "false"))
        print(json.dumps(report, indent=1, default=str))
        if report["violations"]:
            print(f"CONTINUAL SOAK FAILED: {len(report['violations'])} "
                  "violation(s)", file=sys.stderr)
            return 1
        print("continual soak clean: no invariant violations",
              file=sys.stderr)
        return 0
    kv = dict(a.split("=", 1) for a in argv if "=" in a)
    report = run_soak(
        duration_s=float(kv.get("duration_s", 3.0)),
        clients=int(kv.get("clients", 4)),
        pool_size=int(kv.get("pool_size", 24)),
        max_rows=int(kv.get("max_rows", 48)),
        seed=int(kv.get("seed", 0)),
        chaos=kv.get("chaos", "1") not in ("0", "false"),
        reload_every_s=float(kv.get("reload_every_s", 0.25)),
        deadline_ms=float(kv.get("deadline_ms", 2000.0)),
        http=kv.get("http", "0") not in ("0", "false"),
        device_binning=kv.get("device", "0") not in ("0", "false"))
    print(json.dumps(report, indent=1, default=str))
    if report["violations"]:
        print(f"SOAK FAILED: {len(report['violations'])} violation(s)",
              file=sys.stderr)
        return 1
    print("soak clean: no invariant violations", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
