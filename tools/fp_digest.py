"""Are the feature-parallel cell's models the serial cell's?

The configuration ``epsilon-b255-fp4`` promises that the model under
``tree_learner=feature`` is the serial learner's, tree for tree.  The
benchmark's ``correct`` cannot hold it to that: the reference follows the
trees a run grew and judges them against their own rows, so a tree that is
self-consistent passes, and a tree grown with the exchange between the
workers left out is self-consistent (PERF.md section 7).  This tool compares
the two learners directly: one table and one set of folds from the seed, as
``benchmarks/run.py`` makes them for the two cells, one ``lgb.cv`` job of the
cell's rounds under each cell's parameters, and the sha-256 of every
booster's trees (the model string up to its parameter block, which names the
learner) and of the held-out curve.  Where they differ it names the first
fold, tree, field and node that do.

    python3 tools/fp_digest.py --seed <n>
    chiprun --chips 4 --timeout 1500 -- python3 tools/fp_digest.py --seed <n>

The last line of the output is the verdict as JSON; the exit code is 0 where
the digests are equal, 1 where they are not.  The one-chip cell's job runs on
the host's first chip.  ``--folds`` runs the first folds only (a shorter
call); the sizes are the cells' own unless ``compare`` is handed others, as
the test does on the CPU (tests/test_fp_digest.py).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
from typing import Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SHARDED, SERIAL = "epsilon-b255-fp4.cv5", "epsilon-b255.cv5"
# what the sharded configuration may add to the serial one's parameters
LEARNER_KEYS = {"tree_learner", "num_machines", "mesh_shape"}


def trees_of(model_string: str) -> str:
    """The model string up to its parameter block."""
    return model_string.split("\nparameters:")[0]


def sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def first_difference(ours: list, theirs: list) -> Optional[dict]:
    """Fold, tree, field and node where two jobs' trees first differ."""
    for fold, (a, b) in enumerate(zip(ours, theirs)):
        if a == b:
            continue
        # block 0 is the header (its ``tree_sizes`` follow the trees);
        # "Tree=<n>" opens each block after it
        blocks = zip(a.split("\nTree=")[1:], b.split("\nTree=")[1:])
        for tree, (ta, tb) in enumerate(blocks):
            for la, lb in zip(ta.splitlines(), tb.splitlines()):
                if la == lb:
                    continue
                va, vb = la.split("=")[-1].split(), lb.split("=")[-1].split()
                node = next((i for i, (x, y) in enumerate(zip(va, vb))
                             if x != y), min(len(va), len(vb)))
                return {"fold": fold, "tree": tree, "field": la.split("=")[0],
                        "node": node, "sharded": va[node:node + 1],
                        "serial": vb[node:node + 1]}
        # every tree both have is equal: one has more, or the headers differ
        return {"fold": fold, "field": "trees"}
    return None


def compare(seed: int, *, sharded: str = SHARDED, serial: str = SERIAL,
            folds: Optional[int] = None, sizes: Optional[dict] = None,
            extra_params: Optional[dict] = None) -> dict:
    """One job of each cell on one table; the digests and the verdict.
    ``sizes`` / ``extra_params`` are ``run.run_cell``'s, for a size the CPU
    holds."""
    from benchmarks import run
    import lightgbm_tpu as lgb
    cells = [run.load_cell(sharded), run.load_cell(serial)]
    for key in ("data", "precision"):
        if cells[0]["config"][key] != cells[1]["config"][key]:
            raise SystemExit(f"{sharded} and {serial} differ in {key!r}")
    if cells[0]["traffic"] != cells[1]["traffic"] \
            or cells[0]["rounds"] != cells[1]["rounds"]:
        raise SystemExit(f"{sharded} and {serial} differ in their traffic")
    p0, p1 = (c["config"]["params"] for c in cells)
    if {k: v for k, v in p0.items() if k not in LEARNER_KEYS} != p1:
        raise SystemExit(f"{sharded}'s parameters are not {serial}'s and a "
                         "learner")
    run.place_compile_cache(ROOT)
    traffic, rounds = cells[0]["traffic"], cells[0]["rounds"]
    x, y, _, _ = run.make_data(cells[0], seed, sizes or {}, False)
    parts = run.seeded_folds(len(y), traffic["nfold"], seed)[:folds]
    jobs = []
    ds = None
    for cell in cells:
        params = dict(cell["config"]["params"], **traffic["params"],
                      **(extra_params or {}))
        if ds is None:      # binning knows no learner: one Dataset for both
            ds = lgb.Dataset(x, label=y, params=params,
                             **traffic.get("dataset", {})).construct()
        boosters, curve = run.make_job(lgb, None, traffic, params, rounds,
                                       ds, None, parts)()
        jobs.append({"cell": cell["name"],
                     "trees": [trees_of(b.model_to_string())
                               for b in boosters],
                     "curve": [repr(float(v)) for v in curve]})
        del boosters
    out = {"seed": seed, "folds": len(parts), "rounds": rounds}
    for job in jobs:
        out[job["cell"]] = {"models": [sha(t)[:16] for t in job["trees"]],
                            "curve": sha(json.dumps(job["curve"]))[:16]}
    differs = first_difference(jobs[0]["trees"], jobs[1]["trees"])
    out["first_difference"] = differs
    out["curves_equal"] = jobs[0]["curve"] == jobs[1]["curve"]
    out["equal"] = differs is None and out["curves_equal"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--sharded", default=SHARDED)
    ap.add_argument("--serial", default=SERIAL)
    ap.add_argument("--folds", type=int, default=None,
                    help="run the first folds only (default: all)")
    args = ap.parse_args(argv)
    out = compare(args.seed, sharded=args.sharded, serial=args.serial,
                  folds=args.folds)
    print(json.dumps(out), flush=True)
    return 0 if out["equal"] else 1


if __name__ == "__main__":
    sys.exit(main())
