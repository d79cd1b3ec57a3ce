"""Chaos-injection soak harness for ELASTIC TRAINING (the
``tools/soak_serve.py`` analog for the training side).

Runs one boosting job under the elastic recovery ladder
(``lightgbm_tpu/parallel/elastic.elastic_train``) while
``utils/faultinject`` windows wedge its collectives
(``collective_hang``), wedge its device claim (``claim_wedge``) and
kill a simulated peer (``host_loss``) mid-run, then checks the
invariants the elastic layer promises (docs/Fault-Tolerance.md
"Elastic training"):

- **Zero hangs**: every collective is bounded by
  ``elastic_collective_timeout_s`` — the injected wedges sleep far
  longer than the deadline, so the run only completes inside the
  wall-clock budget if the deadline actually fired and classified
  every one of them.
- **Shrink-to-survive**: the run completes WITH at least one mesh
  shrink (full mesh -> shrunk mesh -> serial as the chaos demands),
  resuming each rung from the newest COMPLETE snapshot — no lost
  iterations beyond the snapshot gap, counted via the final model's
  tree count.
- **Determinism**: the final model passes the metric-parity harness
  against an uninterrupted SERIAL run over the same data — bitwise
  tree text on the int32 quantized-histogram path (the default here),
  metric-epsilon on f32.
- **Observability**: ``elastic.*`` recovery metrics are present
  (failures by kind, shrinks, recoveries, mesh gauge), the
  per-failure JSONL event log exists next to the model, and the
  flight recorder (``telemetry_blackbox``) dumped on the classified
  failures.

The ``sdc=1`` mode swaps the liveness chaos for SILENT-data-corruption
chaos (lightgbm_tpu/integrity.py; docs/Fault-Tolerance.md layer 7):
seeded single-bit flips at the ``hist_sdc``/``score_sdc`` sites put one
TRANSIENT flip (re-check clean -> absorbed in place, no rewind) and one
STICKY flip (fires again on the re-check -> classified ``sdc``, suspect
device quarantined, ladder rewinds to the newest integrity-VERIFIED
snapshot) into a single run — which must still end byte-identical to an
uninjected reference.

Run standalone (prints one JSON report, exit 1 on violations)::

    python tools/soak_train.py rounds=16 mesh=4 chaos=1
    python tools/soak_train.py rounds=12 sdc=1

Importable: ``run_soak_train(...)`` returns the report dict —
``tests/test_zelastic.py`` (liveness) and ``tests/test_integrity.py``
(sdc) each run a short deterministic soak in tier-1.
"""

from __future__ import annotations

import glob
import json
import os
import sys
import time
from typing import Dict, Optional

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

N_FEAT = 6


def _data(n_rows: int, seed: int = 0):
    rs = np.random.RandomState(seed)
    x = rs.randn(n_rows, N_FEAT)
    y = (x[:, 0] - 0.7 * x[:, 1] + 0.25 * rs.randn(n_rows) > 0) \
        .astype("float32")
    return x, y


def run_soak_train(rounds: int = 12, n_rows: int = 400, mesh: int = 4,
                   seed: int = 0, chaos: bool = True,
                   chaos_spec: Optional[str] = None,
                   quant: bool = True, workdir: Optional[str] = None,
                   hang_s: float = 6.0,
                   collective_timeout_s: float = 1.0,
                   budget_s: float = 300.0, sdc: bool = False,
                   params: Optional[Dict] = None) -> Dict:
    """One elastic-training soak; returns the report dict (module
    docstring).  ``chaos=False`` is the control arm: same config, no
    faults — must complete with zero shrinks and the same final model.
    ``sdc=True`` runs the silent-data-corruption arm instead: serial
    masked learner under the elastic ladder, one transient + one sticky
    bit flip, ``integrity_policy=quarantine``.
    """
    import tempfile

    from lightgbm_tpu import Dataset, train as engine_train
    from lightgbm_tpu import integrity
    from lightgbm_tpu.metrics import _auc
    from lightgbm_tpu.parallel import elastic
    from lightgbm_tpu.utils import faultinject

    workdir = workdir or tempfile.mkdtemp(prefix="lgbm_soak_train_")
    os.makedirs(workdir, exist_ok=True)
    out_model = os.path.join(workdir, "soak_model.txt")
    x, y = _data(n_rows, seed)

    p = {"objective": "binary", "num_leaves": 8, "max_bin": 31,
         "min_data_in_leaf": 5, "verbosity": -1,
         "tree_learner": "data", "mesh_shape": [int(mesh)],
         "quant_train": bool(quant),
         "output_model": out_model,
         "snapshot_freq": 2, "snapshot_keep": 0,
         "elastic_enable": True,
         "elastic_collective_timeout_s": float(collective_timeout_s),
         "elastic_retries": 1,
         "elastic_recover_timeout_s": float(budget_s),
         "dist_init_timeout_s": float(collective_timeout_s),
         "dist_init_retries": 0,
         "telemetry_blackbox": True}
    if sdc:
        # SDC arm: serial masked learner (the integrity layer's shadow
        # grower is an independent trace there), every iteration
        # shadow-checked, sticky failures quarantined so the ladder —
        # not engine.train's own rewind loop — drives the recovery
        p.pop("tree_learner", None)
        p.pop("mesh_shape", None)
        p["tpu_learner"] = "masked"
        p["integrity_check_freq"] = 1
        p["integrity_policy"] = "quarantine"
    p.update(params or {})

    # uninterrupted SERIAL oracle over the same data — the parity
    # anchor the shrunk/ recovered run must reproduce
    ref_params = {k: v for k, v in p.items()
                  if not k.startswith(("elastic_", "dist_init",
                                       "telemetry", "snapshot",
                                       "mesh_shape", "output_model"))}
    ref_params["tree_learner"] = "serial"
    ref = engine_train(dict(ref_params), Dataset(x, label=y),
                       num_boost_round=rounds)

    violations = []
    if sdc:
        # one TRANSIENT (score gather, iteration 3: fires once, the
        # re-check hit does not -> absorbed) and one STICKY window
        # (histogram, 3 consecutive hits: fire + re-check fire ->
        # sticky -> ladder rewind, then the replay's fire re-checks
        # clean -> absorbed) in a single run
        s0 = max(4, int(rounds) - 5)
        spec = chaos_spec or (f"score_sdc:3,hist_sdc:{s0}-{s0 + 2}"
                              if chaos else None)
    else:
        spec = chaos_spec or ("collective_hang:4,claim_wedge:2,"
                              "host_loss:8" if chaos else None)
    prev_hang = os.environ.get(faultinject.HANG_ENV_VAR)
    os.environ[faultinject.HANG_ENV_VAR] = str(hang_s)
    elastic.reset_metrics()
    integrity.reset_metrics()
    t0 = time.monotonic()
    try:
        faultinject.configure(spec)
        bst = elastic.elastic_train(dict(p), x, y,
                                    num_boost_round=rounds)
    finally:
        faultinject.clear()
        if prev_hang is None:
            os.environ.pop(faultinject.HANG_ENV_VAR, None)
        else:
            os.environ[faultinject.HANG_ENV_VAR] = prev_hang
    wall_s = time.monotonic() - t0
    report = dict(bst.elastic_report)
    metrics = elastic.metrics_snapshot()

    # -- invariants --------------------------------------------------------
    if wall_s > budget_s:
        violations.append(
            f"run exceeded its wall budget ({wall_s:.1f}s > {budget_s}s):"
            " a collective was NOT bounded by the deadline")
    n_trees = len(bst.trees)
    if n_trees != rounds:
        violations.append(
            f"lost iterations: {n_trees} trees != {rounds} requested "
            "(recovery must lose nothing beyond the snapshot gap, which "
            "is retrained on resume)")
    trees_of = (lambda b:
                b.model_to_string().split("parameters:")[0]
                .split("feature_infos")[1])
    if quant:
        if trees_of(bst) != trees_of(ref):
            violations.append(
                "final model is not bitwise-identical to the "
                "uninterrupted serial run (int32 quantized path)")
    auc_ref = _auc(y, ref.predict(x, raw_score=True), None)
    auc_got = _auc(y, bst.predict(x, raw_score=True), None)
    if abs(float(auc_ref) - float(auc_got)) > 1e-6:
        violations.append(
            f"metric parity failed: soak auc {auc_got:.6f} vs "
            f"serial {auc_ref:.6f}")
    int_metrics = {k: v.get("value")
                   for k, v in integrity.metrics_snapshot().items()
                   if v.get("type") != "histogram"}
    if chaos:
        if report.get("shrinks", 0) < 1:
            violations.append("chaos run finished without a mesh shrink")
        if report.get("recoveries", 0) < 1:
            violations.append("no automatic recovery recorded")
        kinds = {f["kind"] for f in report.get("failures", ())}
        if not kinds:
            violations.append("no classified failures recorded")
        if sdc:
            if kinds != {"sdc"}:
                violations.append(
                    f"expected only classified 'sdc' failures, got {kinds}")
            if int_metrics.get("integrity.sticky", 0) != 1:
                violations.append(
                    "exactly one sticky SDC expected, got "
                    f"{int_metrics.get('integrity.sticky', 0)}")
            if int_metrics.get("integrity.transient_absorbed", 0) < 2:
                violations.append(
                    "transient SDCs (score @3 + post-rewind replay) were "
                    "not absorbed in place: "
                    f"{int_metrics.get('integrity.transient_absorbed', 0)}")
            if int_metrics.get("integrity.quarantined", 0) < 1:
                violations.append("sticky SDC did not quarantine a device")
            if not elastic.suspected_devices():
                violations.append("no suspect device recorded after the "
                                  "sticky SDC")
        if not any(k.startswith("elastic.failures")
                   for k in metrics):
            violations.append("elastic.failures metrics missing")
        if "elastic.shrinks" not in metrics:
            violations.append("elastic.shrinks metric missing")
        if not os.path.exists(out_model + ".elastic.jsonl"):
            violations.append("elastic failure event log missing")
        bb = glob.glob(os.path.join(workdir, "*.blackbox.jsonl*"))
        if not bb:
            violations.append("no flight-recorder (blackbox) dump found")
    else:
        if report.get("shrinks", 0) != 0:
            violations.append("control run shrank without chaos")

    return {"violations": violations, "wall_s": round(wall_s, 2),
            "rounds": rounds, "n_trees": n_trees,
            "report": report,
            "auc": round(float(auc_got), 6),
            "elastic_metrics": {k: v.get("value")
                                for k, v in metrics.items()
                                if v.get("type") != "histogram"},
            "integrity_metrics": int_metrics,
            "workdir": workdir}


def main(argv) -> int:
    kv = dict(a.split("=", 1) for a in argv if "=" in a)
    # a correctness soak: CPU + a virtual multi-device topology (same
    # pattern as tools/check_retraces.py), never the chip
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_"
                                     "device_count=8")
    import jax
    jax.config.update("jax_platforms", "cpu")
    rep = run_soak_train(
        rounds=int(kv.get("rounds", 12)),
        n_rows=int(kv.get("rows", 400)),
        mesh=int(kv.get("mesh", 4)),
        chaos=kv.get("chaos", "1") not in ("0", "false"),
        quant=kv.get("quant", "1") not in ("0", "false"),
        hang_s=float(kv.get("hang_s", 6.0)),
        budget_s=float(kv.get("budget_s", 300.0)),
        sdc=kv.get("sdc", "0") not in ("0", "false"))
    print(json.dumps(rep, indent=1, sort_keys=True))
    return 1 if rep["violations"] else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
