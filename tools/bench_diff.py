"""Perf regression gate: compare two BENCH json records on pinned
metrics with noise tolerances.

The third lint of the family (tools/check_syncs.py pins host syncs,
tools/check_retraces.py pins jit traces): nothing used to stop a PR
from silently regressing ``iters_per_s`` or ``serve_p99_ms`` — the
bench numbers were recorded, never compared.  This tool compares a NEW
bench record against an OLD one on exactly the metrics pinned in
``tools/perf_budget.txt``:

- each pin is ``<key> = <direction> <tolerance>`` — ``direction`` is
  ``higher`` (throughput-like: new must not fall more than
  ``tolerance`` fraction below old) or ``lower`` (latency-like: new
  must not rise more than ``tolerance`` above old).  The tolerance IS
  the noise allowance — pin it at the metric's observed run-to-run
  spread, not at zero;
- ``value`` resolves at the record's top level, every other key in
  its ``extra`` dict (the bench.py merge layout);
- a pinned key found in NEITHER record is reported STALE (the budget
  file cannot rot), and a key the old record had but the new one lost
  is a violation (a disappearing metric is a regression in coverage);
  a key only the new record has passes (new coverage needs a round of
  history before it can be pinned meaningfully);
- ``--update NEW`` re-pins the budget from a record: existing pins
  keep their direction/tolerance, newly appearing gateable metrics
  get direction-by-name defaults, pins the record no longer carries
  are dropped.

Input files may be either the raw final bench line
(``{"metric", "value", ..., "extra": {...}}``) or a wrapper around it
(``{"parsed": {...}}``).

Run: ``python tools/bench_diff.py NEW OLD`` — exit 1 on any
violation or stale pin; tier-1 exercises green/tamper/stale on a
synthetic pair (tests/test_perf_ledger.py, the test_zretrace lint
mold).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGET = os.path.join(REPO, "tools", "perf_budget.txt")

# direction-by-name defaults for --update: latency/compile/freshness
# metrics gate downward, everything else (rates, MFU) upward
_LOWER_BETTER = re.compile(
    r"(_ms|compile_s|_seconds|_lag_s|_gen_s|_hbm_bytes_per_iter"
    r"|_ms_per_pass|_ms_per_leaf(_k\d+|_wide)?"
    r"|_sync(s|_count)_per_iter"
    r"|_peak_rss_mb|_wire_bytes|_overhead_pct)$")
# extras worth gating by default: primary value, throughput points,
# serve latency/throughput (host-accumulation AND fused device paths),
# mfu, the continual pipeline's freshness numbers, and the histogram
# contraction's measured pass/per-leaf costs (ISSUE 15 — both the
# hist_* headline aliases and the per-width hist_quant_* sweep keys)
_GATEABLE = re.compile(
    r"(^value$|_iters_per_sec$|^serve(_device)?_rows_per_s$"
    r"|^serve(_device)?_p\d+_ms$|_mfu$|_compile_s$"
    r"|^hist_hbm_bytes_per_iter$"
    r"|^hist_ms_per_(pass|leaf_k\d+|leaf_wide)$"
    r"|^hist_quant_q(off|8|16)_k\d+_ms_per_(pass|leaf)$"
    # super-epoch sweep (ISSUE 16, tools/bench_fused.sweep): headline
    # throughput + the structural syncs-per-iter count (1/k), plus the
    # per-k sweep keys
    r"|^superepoch_(iters_per_s|sync_count_per_iter"
    r"|k\d+_(valid|novalid)_(iters_per_s|syncs_per_iter))$"
    # fleet sweep (ISSUE 19, tools/bench_fleet.run_bench): the N=8
    # vmapped aggregate + the speedup ratio vs sequential solos, plus
    # the per-width sweep keys
    r"|^fleet_(agg_iters_per_s|speedup_x8"
    r"|n\d+_(agg_iters_per_s|speedup)|solo\d+_agg_iters_per_s)$"
    r"|^continual_(freshness_lag_s|gen_s)$"
    # out-of-core ingest (ISSUE 17, lightgbm_tpu/ingest.py): streaming
    # throughput, the bounded-memory subprocess RSS, and the
    # sketch-allgather wire bytes
    r"|^ingest_(rows_per_s|peak_rss_mb)$"
    r"|^binning_wire_bytes$"
    # integrity layer (ISSUE 20, lightgbm_tpu/integrity.py): the
    # measured cost of integrity_check_freq=16 over an unchecked run —
    # the "pay only on check iterations" contract as a gated number
    r"|^integrity_overhead_pct$)")
_DEFAULT_TOL = {"higher": 0.20, "lower": 0.30}


def load_record(path: str) -> Dict:
    """A bench record from either the raw final-line shape or the
    round wrapper ({"parsed": {...}})."""
    with open(path) as f:
        obj = json.load(f)
    if isinstance(obj, dict) and "parsed" in obj \
            and isinstance(obj["parsed"], dict):
        obj = obj["parsed"]
    if not isinstance(obj, dict) or "metric" not in obj:
        raise ValueError(f"{path}: not a bench record "
                         "(no 'metric'/'parsed' key)")
    return obj


def resolve(rec: Dict, key: str) -> Optional[float]:
    """Pinned key -> numeric value: top-level for ``value`` /
    ``vs_baseline``, else ``extra[key]``; None when absent or
    non-numeric."""
    v = rec.get(key) if key in ("value", "vs_baseline") \
        else (rec.get("extra") or {}).get(key)
    return float(v) if isinstance(v, (int, float)) \
        and not isinstance(v, bool) else None


def load_budget(path: str = BUDGET) -> Dict[str, Tuple[str, float]]:
    """{key: (direction, tolerance)} from the pin file."""
    out: Dict[str, Tuple[str, float]] = {}
    try:
        with open(path) as f:
            for raw in f:
                raw = raw.split("#")[0].strip()
                if not raw or "=" not in raw:
                    continue
                k, _, v = raw.partition("=")
                parts = v.split()
                if len(parts) != 2 or parts[0] not in ("higher", "lower"):
                    raise ValueError(
                        f"bad budget line {raw!r} "
                        "(want: <key> = higher|lower <tolerance>)")
                out[k.strip()] = (parts[0], float(parts[1]))
    except OSError:
        pass
    return out


def write_budget(pins: Dict[str, Tuple[str, float]],
                 path: str = BUDGET) -> None:
    lines = [
        "# Perf budget (tools/bench_diff.py): metrics gated between",
        "# bench rounds.  <key> = higher|lower <tolerance>: 'higher'",
        "# metrics may not fall more than <tolerance> (fraction) below",
        "# the old record, 'lower' metrics may not rise more than",
        "# <tolerance> above it.  The tolerance is the metric's noise",
        "# allowance — re-pin with `python tools/bench_diff.py --update",
        "# NEW.json` and justify tolerance changes in review.",
        "",
    ]
    for k in sorted(pins):
        d, t = pins[k]
        lines.append(f"{k} = {d} {t:g}")
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")


def check(old: Dict, new: Dict,
          budget: Dict[str, Tuple[str, float]]) -> List[str]:
    """Violations + stale pins (empty list = gate green)."""
    findings: List[str] = []
    if not budget:
        return ["empty perf budget: nothing is pinned "
                "(tools/perf_budget.txt)"]
    eps = 1e-12
    for key in sorted(budget):
        direction, tol = budget[key]
        ov, nv = resolve(old, key), resolve(new, key)
        if ov is None and nv is None:
            findings.append(f"stale budget entry (metric in neither "
                            f"record): {key}")
            continue
        if ov is None:
            continue          # new coverage: gateable next round
        if nv is None:
            findings.append(f"metric disappeared: {key} "
                            f"(old={ov:g}, absent from the new record)")
            continue
        if direction == "higher":
            floor = ov * (1.0 - tol)
            if nv < floor - eps:
                findings.append(
                    f"regression: {key} = {nv:g} < {floor:g} "
                    f"(old {ov:g} - {tol:.0%} tolerance)")
        else:
            ceil = ov * (1.0 + tol)
            if nv > ceil + eps:
                findings.append(
                    f"regression: {key} = {nv:g} > {ceil:g} "
                    f"(old {ov:g} + {tol:.0%} tolerance)")
    return findings


def update(new: Dict, budget: Dict[str, Tuple[str, float]]
           ) -> Dict[str, Tuple[str, float]]:
    """Re-pin: keep tolerances of pins the record still carries, add
    defaults for newly gateable metrics, drop the rest."""
    keys = ["value"] + sorted(new.get("extra") or {})
    out: Dict[str, Tuple[str, float]] = {}
    for k in keys:
        if resolve(new, k) is None:
            continue
        if k in budget:
            out[k] = budget[k]
        elif _GATEABLE.search(k):
            d = "lower" if _LOWER_BETTER.search(k) else "higher"
            out[k] = (d, _DEFAULT_TOL[d])
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("new", help="new bench json (the candidate)")
    ap.add_argument("old", nargs="?", default=None,
                    help="old bench json (required unless --update)")
    ap.add_argument("--budget", default=BUDGET,
                    help="pin file (tests point this at a temp copy)")
    ap.add_argument("--update", action="store_true",
                    help="re-pin the budget from NEW instead of checking")
    args = ap.parse_args()

    new = load_record(args.new)
    if args.update:
        pins = update(new, load_budget(args.budget))
        write_budget(pins, args.budget)
        print(f"pinned {len(pins)} metric(s) to {args.budget}")
        return 0

    if args.old is None:
        ap.error("OLD is required: bench_diff compares two records")
    old = load_record(args.old)
    print(f"bench_diff: {os.path.basename(args.old)} -> "
          f"{os.path.basename(args.new)}")
    findings = check(old, new, load_budget(args.budget))
    if findings:
        print("perf gate: regressions / stale pins:", file=sys.stderr)
        for f in findings:
            print(f"  {f}", file=sys.stderr)
        print(f"\n{len(findings)} finding(s).  If the perf change is "
              "intentional (or the pin is stale), re-pin with `python "
              "tools/bench_diff.py --update <NEW.json>` and justify "
              "the diff in review", file=sys.stderr)
        return 1
    print("perf gate: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
