"""Chaos-injection soak harness for the STREAMING INGEST pipeline
(the ``tools/soak_train.py`` analog for the data path).

Runs one streaming ingest + training job (``lightgbm_tpu/ingest.py``)
while ``utils/faultinject`` injects all three failure kinds the
pipeline promises to survive (docs/Fault-Tolerance.md "Out-of-core
ingest", docs/Ingest.md "Failure classes"):

- **Transient read errors** (``ingest_read``): must be retried with
  backoff and succeed — zero dropped rows, retry metrics present.
- **Corrupt chunks** (``ingest_checksum``): must be quarantined with a
  blackbox dump and an exact dropped-row accounting under
  ``ingest_bad_chunk=skip``; the degraded run still trains.
- **Reader hangs** (``ingest_hang``): the per-chunk deadline
  (``ingest_read_timeout_s``) must abandon the wedge and classify it —
  the soak only finishes inside its wall budget if no hang ever ran to
  its full sleep.

Plus **resume parity**: a second ingest over the same spool must resume
every committed chunk and train a model byte-identical to the chaos
run's (the chaos run's spool IS the checkpoint).

Run standalone (prints one JSON report, exit 1 on violations)::

    python tools/soak_ingest.py rows=4000 chunk_rows=250

Importable: ``run_soak_ingest(...)`` returns the report dict —
``tests/test_ingest_soak.py`` runs a short deterministic soak in
tier-1.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Dict, Optional

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

N_FEAT = 5


def _write_csv(path: str, n_rows: int, seed: int = 0) -> None:
    rs = np.random.RandomState(seed)
    x = np.round(rs.randn(n_rows, N_FEAT), 1)
    y = (x[:, 0] + 0.25 * rs.randn(n_rows) > 0).astype(np.float64)
    with open(path, "w", encoding="utf-8") as f:
        for i in range(n_rows):
            f.write(",".join([f"{y[i]:g}"]
                             + [f"{v:.1f}" for v in x[i]]) + "\n")


def run_soak_ingest(n_rows: int = 4000, chunk_rows: int = 250,
                    rounds: int = 6, seed: int = 0, chaos: bool = True,
                    chaos_spec: Optional[str] = None,
                    hang_s: float = 6.0,
                    read_timeout_s: float = 0.5,
                    budget_s: float = 120.0,
                    workdir: Optional[str] = None,
                    params: Optional[Dict] = None) -> Dict:
    """One ingest soak; returns the report dict (module docstring).
    ``chaos=False`` is the control arm: same config, no faults — must
    complete with zero retries, zero quarantines, zero drops."""
    import tempfile

    import lightgbm_tpu as lgb
    from lightgbm_tpu import ingest as ing
    from lightgbm_tpu.utils import faultinject

    workdir = workdir or tempfile.mkdtemp(prefix="lgbm_soak_ingest_")
    os.makedirs(workdir, exist_ok=True)
    src = os.path.join(workdir, "train.csv")
    _write_csv(src, n_rows, seed)
    spool = os.path.join(workdir, "spool")
    n_chunks = (n_rows + chunk_rows - 1) // chunk_rows

    p = {"objective": "binary", "num_leaves": 8, "max_bin": 31,
         "min_data_in_leaf": 5, "verbosity": -1,
         "ingest_chunk_rows": int(chunk_rows),
         "ingest_bad_chunk": "skip",
         "ingest_retries": 2, "ingest_retry_backoff_s": 0.05,
         "ingest_read_timeout_s": float(read_timeout_s),
         "telemetry_blackbox": True}
    p.update(params or {})

    # mid-run chaos: chunk 2 hits a transient read error (retried),
    # chunk 4 is corrupt (quarantined), chunk 6's reader wedges once
    # (deadline abandons it, retry succeeds)
    spec = chaos_spec or ("ingest_read:2,ingest_checksum:4,"
                          "ingest_hang:6" if chaos else None)
    prev_hang = os.environ.get(faultinject.HANG_ENV_VAR)
    os.environ[faultinject.HANG_ENV_VAR] = str(hang_s)
    ing.reset_metrics()
    violations = []
    t0 = time.monotonic()
    try:
        faultinject.configure(spec)
        ds = lgb.ingest_dataset(src, dict(p), spool_dir=spool)
        bst = lgb.train(dict(p), ds, num_boost_round=rounds)
    finally:
        faultinject.clear()
        if prev_hang is None:
            os.environ.pop(faultinject.HANG_ENV_VAR, None)
        else:
            os.environ[faultinject.HANG_ENV_VAR] = prev_hang
    wall_s = time.monotonic() - t0
    report = dict(ds.ingest_report)
    metrics = ing.metrics_snapshot()
    if bst.num_trees() < rounds:
        violations.append(
            f"degraded run under-trained: {bst.num_trees()} < {rounds}")

    # -- invariants --------------------------------------------------------
    if wall_s > budget_s:
        violations.append(
            f"soak exceeded its wall budget ({wall_s:.1f}s > "
            f"{budget_s}s): a hang was NOT bounded by the deadline")
    if chaos:
        # hang must classify via the deadline, not run its full sleep:
        # generous margin, but far below hang_s stacking onto the run
        if wall_s > hang_s:
            violations.append(
                f"wall {wall_s:.1f}s exceeds the injected hang "
                f"({hang_s}s): the read deadline never fired")
        if metrics.get("ingest.retries", {}).get("value", 0) < 2:
            violations.append(
                "expected >=2 retries (transient read error + abandoned "
                f"hang), metrics say {metrics.get('ingest.retries')}")
        if len(report["quarantined"]) != 1:
            violations.append(
                f"expected exactly 1 quarantined chunk, got "
                f"{len(report['quarantined'])}")
        if report["dropped_rows"] != chunk_rows:
            violations.append(
                f"dropped-row accounting wrong: {report['dropped_rows']}"
                f" != {chunk_rows} (one quarantined chunk)")
        if report["num_rows"] != n_rows - chunk_rows:
            violations.append(
                f"surviving rows {report['num_rows']} != "
                f"{n_rows - chunk_rows}")
        qdir = os.path.join(spool, "quarantine")
        if not (os.path.isdir(qdir) and os.listdir(qdir)):
            violations.append("quarantine directory missing/empty")
    else:
        if report["dropped_rows"] or report["quarantined"]:
            violations.append("control run dropped/quarantined chunks")
        if metrics.get("ingest.retries", {}).get("value", 0):
            violations.append("control run recorded retries")

    # -- resume parity: the chaos spool is the checkpoint ------------------
    # a quarantined chunk commits no manifest, so the resume run re-reads
    # it fault-free and HEALS — the resumed model must therefore match a
    # clean fresh-spool run over the full data, byte for byte
    ing.reset_metrics()
    ds2 = lgb.ingest_dataset(src, dict(p), spool_dir=spool)
    if ds2.ingest_report["resumed_chunks"] != \
            n_chunks - len(report["quarantined"]):
        violations.append(
            f"resume replayed chunks: {ds2.ingest_report['resumed_chunks']}"
            f" resumed of {n_chunks - len(report['quarantined'])} "
            "committed")
    if ds2.ingest_report["dropped_rows"] or \
            ds2.ingest_report["num_rows"] != n_rows:
        violations.append(
            "resume run did not heal the quarantined chunk: "
            f"{ds2.ingest_report['num_rows']} rows, "
            f"{ds2.ingest_report['dropped_rows']} dropped")
    bst2 = lgb.train(dict(p), ds2, num_boost_round=rounds)
    ds3 = lgb.ingest_dataset(src, dict(p),
                             spool_dir=os.path.join(workdir, "spool_clean"))
    bst3 = lgb.train(dict(p), ds3, num_boost_round=rounds)
    if bst2.model_to_string().split("parameters:")[0] != \
            bst3.model_to_string().split("parameters:")[0]:
        violations.append(
            "resume parity failed: resumed-spool model differs from a "
            "clean fresh-spool run")

    return {"violations": violations, "wall_s": round(wall_s, 2),
            "n_chunks": n_chunks, "report": report,
            "resumed_chunks": ds2.ingest_report["resumed_chunks"],
            "ingest_metrics": {k: v.get("value")
                               for k, v in metrics.items()
                               if v.get("type") != "histogram"},
            "workdir": workdir}


def main(argv) -> int:
    kv = dict(a.split("=", 1) for a in argv if "=" in a)
    # a host-side soak: runs on the CPU, never claims the chip
    import jax
    jax.config.update("jax_platforms", "cpu")
    rep = run_soak_ingest(
        n_rows=int(kv.get("rows", 4000)),
        chunk_rows=int(kv.get("chunk_rows", 250)),
        rounds=int(kv.get("rounds", 6)),
        chaos=kv.get("chaos", "1") not in ("0", "false"),
        hang_s=float(kv.get("hang_s", 6.0)),
        budget_s=float(kv.get("budget_s", 120.0)))
    print(json.dumps(rep, indent=1, sort_keys=True))
    return 1 if rep["violations"] else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
