"""Retrace-budget lint: pin the number of jit traces for a canonical
config matrix so retrace regressions fail CI instead of silently
costing a minute or more of compile on device.

The sibling of tools/check_syncs.py for the OTHER silent perf tax:
XLA trace+compile before the first training iteration can rival the
steady-state work of a short run.
The shape-bucketing layer (utils/shapes.py: leaf-budget padding,
pinned split_batch widths, row-bucketed valid sets, pow2 serve
batches) bounds the trace family; this lint keeps that bound true
structurally:

- every library jit entry point records a ``jax.monitoring`` event
  (``/lgbtpu/trace/<name>``, utils/compile_cache.trace_event) at TRACE
  time — cache-state-independent, so the counts are deterministic for
  a fixed code + config matrix;
- the canonical matrix below (leaf-budget sweep, bagging/GOSS
  sampling, two valid-set sizes, super-epochs, serve batch mix) runs
  on CPU and the per-scenario counts must EXACTLY match
  ``tools/retrace_budget.txt``;
- entries in the budget file that the matrix no longer produces are
  reported as stale, so the file cannot rot;
- a deliberately unbucketed negative control (``trace_buckets=false``
  leaf sweep) must EXCEED the bucketed budget — proving the lint
  would catch a bucketing regression, not just rubber-stamp it.

Run via the unified driver (``python tools/lint.py``; tier-1) or
standalone (``python tools/check_retraces.py``; exit 1 on findings;
``--update`` rewrites the budget file).  Budget parsing and stale-entry
detection live in ``tools/analyze/lintlib.py``, shared with the
sync/race/purity lints.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Dict, List

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from analyze import lintlib                              # noqa: E402

REPO = lintlib.REPO
BUDGET = os.path.join(REPO, "tools", "retrace_budget.txt")
sys.path.insert(0, REPO)

_TRACE_PREFIX = "/lgbtpu/trace/"

# live monitoring-counted totals (event name -> count)
_counts: Dict[str, int] = {}


def _install_listener() -> None:
    from jax import monitoring

    def _on_event(event: str, **kw) -> None:
        if event.startswith(_TRACE_PREFIX):
            name = event[len(_TRACE_PREFIX):]
            _counts[name] = _counts.get(name, 0) + 1

    monitoring.register_event_listener(_on_event)


class _Scope:
    """Delta of the monitoring-counted traces over a scenario."""

    def __init__(self, scenario: str, into: Dict[str, int]):
        self.scenario = scenario
        self.into = into

    def __enter__(self):
        self.t0 = dict(_counts)
        return self

    def __exit__(self, *exc):
        for name, v in _counts.items():
            d = v - self.t0.get(name, 0)
            if d:
                self.into[f"{self.scenario}.{name}"] = \
                    self.into.get(f"{self.scenario}.{name}", 0) + d
        return False


def _data(n: int = 600, f: int = 12, seed: int = 0):
    import numpy as np
    rs = np.random.RandomState(seed)
    x = rs.randn(n, f)
    y = (x[:, 0] * 1.5 - x[:, 1] + 0.3 * rs.randn(n) > 0)
    return x, y.astype("float32")


def _base_params(**over):
    p = {"objective": "binary", "verbosity": 0, "min_data_in_leaf": 5,
         "max_bin": 31, "tpu_learner": "masked", "fused_chunk": 0,
         "num_leaves": 40}
    p.update(over)
    return p


def _train(lgb, x, y, rounds: int = 2, valid=None, **over):
    p = _base_params(**over)
    ds = lgb.Dataset(x, label=y, params=p)
    vs = None
    if valid:
        vs = [lgb.Dataset(vx, label=vy, params=p, reference=ds)
              for vx, vy in valid]
    return lgb.train(p, ds, num_boost_round=rounds, valid_sets=vs)


def run_matrix() -> Dict[str, int]:
    """Run the canonical scenarios; returns {scenario.counter: traces}."""
    import lightgbm_tpu as lgb
    measured: Dict[str, int] = {}
    x, y = _data()

    # 1. leaf-budget sweep: 31/40/63 bucket onto ONE L=64 grower trace
    #    (the headline of the bucketing layer)
    with _Scope("leaf_sweep", measured):
        for nl in (31, 40, 63):
            _train(lgb, x, y, num_leaves=nl)

    # 2. sampling variants re-use the sweep's trace: bagging and GOSS
    #    change VALUES (the in-bag weight column), never shapes, and
    #    the process-level grower memo must recognize the config
    with _Scope("sampling", measured):
        _train(lgb, x, y, bagging_fraction=0.7, bagging_freq=1)
        _train(lgb, x, y, data_sample_strategy="goss")

    # 2b. wide super-step (ISSUE 15): a num_leaves sweep at K=32 stays
    #    ONE grower trace — both budgets bucket onto L=64 and the
    #    lane-padded C=96->128 channel axis is a structural constant,
    #    so the wide trace family is exactly as closed as the shipped
    #    K<=16 one (33, not 31: at 31 leaves K=32 fits DOWN to 16 by
    #    utils/shapes.fit_split_batch, which is the other half of the
    #    width contract)
    with _Scope("hist_k32", measured):
        for nl in (33, 63):
            _train(lgb, x, y, num_leaves=nl, split_batch=32)

    # 3. two valid-set sizes row-bucket onto one shape (256 rows), so
    #    early stopping over mixed valid sets stops re-tracing.  The
    #    grower carries the valid sets' rows through its partition
    #    (gbdt._followers), so their shapes are part of ITS signature and
    #    no tree walk is traced at all: the two sizes in either order are
    #    one grower trace, where unbucketed they would be two
    with _Scope("valid_sizes", measured):
        sizes = [(x[:200], y[:200]), (x[200:430], y[200:430])]
        for valid in (sizes, sizes[::-1]):
            _train(lgb, x, y, rounds=3, num_leaves=15, valid=valid,
                   metric=["binary_logloss"])

    # 4. super-epoch scan (ISSUE 16): a num_leaves sweep at k=8 with a
    #    valid set + traced metric stays ONE scan trace — the leaf
    #    budget pads 31/63 onto L=64 and `_superepoch_key` carries only
    #    bucketed shapes, so the whole-run scan (k grows + k traced
    #    evals + the ES vote) compiles once per bucket, not per config.
    #    split_batch is pinned so the grower width doesn't fork the key.
    with _Scope("superepoch", measured):
        for nl in (31, 63):
            _train(lgb, x, y, rounds=8, num_leaves=nl, superepoch=8,
                   fused_chunk=8, split_batch=1,
                   valid=[(x[:200], y[:200])],
                   metric=["binary_logloss"])

    # 4b. fleet training (ISSUE 19): an N=8 member roster mixing
    #    num_leaves 31/63 and a learning-rate grid trains through ONE
    #    vmapped super-epoch scan trace — the leaf budget pads every
    #    member onto L=64, per-member lr/seeds ride as batched operands,
    #    and `fleet_superepoch_fn` keys the program on bucketed shapes
    #    only, so the whole fleet compiles once, not once per member
    with _Scope("fleet", measured):
        from lightgbm_tpu.fleet import fleet_train
        fp = _base_params(num_leaves=31, superepoch=8, fused_chunk=8,
                          split_batch=1, metric=["binary_logloss"],
                          fused_eval=True, padded_leaves=True,
                          deterministic=True, verbosity=-1)
        mem = [{"num_leaves": 31 if j % 2 == 0 else 63,
                "learning_rate": 0.05 + 0.02 * j} for j in range(8)]
        ds = lgb.Dataset(x, label=y, params=fp)
        va = lgb.Dataset(x[:200], label=y[:200], params=fp,
                         reference=ds)
        fleet_train(fp, ds, num_boost_round=8, valid_sets=[va],
                    members=mem)

    # 5. serve batch mix: pow2-bucketed engine bounds forest traces
    with _Scope("serve_buckets", measured):
        from lightgbm_tpu.serve.engine import PredictorEngine
        bst = _train(lgb, x, y)
        eng = PredictorEngine.from_booster(bst, max_batch=64)
        for n in (3, 5, 17, 30, 64, 100):
            eng.predict(x[:n])

    # 6. fused device-resident serve path (ISSUE 10): ONE jitted
    #    bin->traverse->accumulate->transform program per (model,
    #    row-bucket) — a mixed-size batch storm (self-check probe
    #    included, registry.load runs it) must stay within the pow2
    #    bucket bound ceil(log2(serve_max_batch)) + 1
    bf1 = _train(lgb, x, y, num_leaves=8, max_depth=4)
    bf2 = _train(lgb, x, y, num_leaves=8, max_depth=4,
                 learning_rate=0.2)
    from lightgbm_tpu.serve.registry import ModelRegistry
    reg = ModelRegistry(max_batch=64, device_binning=True)
    with _Scope("serve_fused", measured):
        v1 = reg.load(booster=bf1)
        e1 = reg.get(v1).engine
        assert e1 is not None and e1.fused_reason is None
        for n in (3, 5, 17, 30, 64, 100):
            e1.fused_predict(x[:n])

    # 7. co-hosted second version of the SAME model family: the pow2
    #    SoA padding (utils/shapes.py bucket_nodes/leaf_slots/steps)
    #    lands it on identical shapes, so EVERY serve trace — fused
    #    program, traversal, self-check probe — is already cached.
    #    check() enforces zero traces here; the budget file carries no
    #    serve_cohost pins by construction
    with _Scope("serve_cohost", measured):
        v2 = reg.load(booster=bf2)
        e2 = reg.get(v2).engine
        assert e2 is not None and e2.fused_reason is None
        for n in (3, 5, 17, 30, 64, 100):
            e2.fused_predict(x[:n])

    # 7b. fleet serving (ISSUE 19): a segment-routed request mix across
    #    the co-resident versions — per-segment assignments, an unknown
    #    key falling back to default, pow2 batch sizes — must serve
    #    with ZERO forest traces: routing only picks WHICH cached
    #    engine runs, and same-family versions share every serve trace
    #    (scenario 7).  check() enforces zero like serve_cohost; the
    #    budget file carries no fleet_serve pins by construction
    with _Scope("fleet_serve", measured):
        from lightgbm_tpu.fleet import SegmentRouter
        router = SegmentRouter()
        router.assign(router.default_segment, v1)
        router.assign("eu", v2)
        router.assign("us", v1)
        for seg in ("eu", "us", "unknown-key", None):
            ver, _fb = router.resolve(seg)
            eng = reg.get(ver).engine
            for n in (3, 17, 64, 100):
                eng.fused_predict(x[:n])

    # 8. distributed leaf sweep (ROADMAP item-1 remainder): the padded
    #    leaf budget + the process-level shard_map memo in the voting
    #    and feature-parallel builders collapse a num_leaves sweep onto
    #    ONE grower trace per learner (the serial leaf_sweep guarantee,
    #    extended).  Needs >= 2 devices (run_lint arranges the virtual
    #    CPU mesh before the backend initializes).
    import jax as _jax
    if len(_jax.devices()) >= 2:
        with _Scope("dist_leaf_sweep", measured):
            for nl in (31, 63):
                _train(lgb, x, y, tree_learner="voting", num_leaves=nl)
            for nl in (31, 63):
                _train(lgb, x, y, tree_learner="feature", num_leaves=nl)

    # 9. elastic recovery ladder (ISSUE 14): the shrink path rebuilds a
    #    Booster per rung — full mesh, shrunk mesh, serial.  The
    #    process-level dp-grower memo (parallel/data_parallel._SHARED)
    #    + the padded leaf budget must give ONE grower trace per
    #    TOPOLOGY for a 31/63 sweep (not one per Booster or per
    #    num_leaves), and the serial rung re-uses scenario 1's trace —
    #    so a recovery retries rungs for free and the whole ladder
    #    costs a bounded trace family.  Needs >= 4 devices.
    if len(_jax.devices()) >= 4:
        with _Scope("elastic_ladder", measured):
            for mesh_n in (4, 2):
                for nl in (31, 63):
                    _train(lgb, x, y, tree_learner="data",
                           mesh_shape=[mesh_n], num_leaves=nl)
            for nl in (31, 63):     # the serial rung: already traced
                _train(lgb, x, y, num_leaves=nl)

    # negative control: the SAME sweep unbucketed must blow the budget
    with _Scope("negative_unbucketed", measured):
        for nl in (31, 40, 63):
            _train(lgb, x, y, num_leaves=nl, trace_buckets=False)

    return measured


def load_budget(path: str = BUDGET) -> Dict[str, int]:
    return lintlib.load_kv_int(path)


def write_budget(measured: Dict[str, int], path: str = BUDGET) -> None:
    lintlib.write_kv_int(measured, path, [
        "# Retrace budget (tools/check_retraces.py): EXACT number of",
        "# library jit traces per canonical scenario, counted via",
        "# jax.monitoring /lgbtpu/trace/* events on CPU.  A failing",
        "# entry means a retrace regression (or an intentional trace-",
        "# family change: re-pin with `python tools/check_retraces.py",
        "# --update` and justify the diff in review).",
    ])


def check(measured: Dict[str, int],
          budget: Dict[str, int]) -> List[str]:
    findings: List[str] = []
    for multidev in ("dist_leaf_sweep.", "elastic_ladder."):
        if not any(k.startswith(multidev) for k in measured):
            # multi-device scenario skipped (a backend was live before
            # run_lint could arrange the virtual mesh): its pins are not
            # stale, just unmeasurable here
            budget = {k: v for k, v in budget.items()
                      if not k.startswith(multidev)}
    for k in sorted(measured):
        if k not in budget:
            findings.append(f"unpinned counter: {k} = {measured[k]} "
                            "(add it to tools/retrace_budget.txt)")
        elif measured[k] != budget[k]:
            findings.append(
                f"trace budget violated: {k} = {measured[k]}, "
                f"pinned {budget[k]}")
    findings.extend(lintlib.stale_pins(
        {(k,) for k in budget},
        {(k,) for k in budget if k in measured}, "budget"))
    # co-hosting invariant (ISSUE 10): the second model version of one
    # family must hit the first one's compile-cache entries — ANY trace
    # during its storm is a shape-sharing regression
    for k in sorted(measured):
        if k.startswith("serve_cohost."):
            findings.append(
                f"co-hosted model re-traced: {k} = {measured[k]} "
                "(second version of one model family must share every "
                "serve trace via the pow2 SoA padding)")
        elif k.startswith("fleet_serve."):
            findings.append(
                f"segment-routed serving re-traced: {k} = {measured[k]} "
                "(the fleet router only selects which cached engine "
                "serves — a segment mix must not compile anything)")
    # the negative control must PROVE the lint catches unbucketed
    # regressions: the same sweep without bucketing has to exceed the
    # bucketed grower budget
    neg = measured.get("negative_unbucketed.grower", 0)
    pos = measured.get("leaf_sweep.grower", 0)
    if neg <= pos:
        findings.append(
            f"negative control failed: unbucketed sweep traced the "
            f"grower {neg}x, not more than the bucketed sweep's {pos}x "
            "— the lint would not catch a bucketing regression")
    return findings


def run_lint(budget_path: str = BUDGET, update: bool = False,
             verbose: bool = True) -> List[str]:
    """Measure the canonical matrix and check (or, with ``update``,
    re-pin) the budget; the driver-facing entry point.  Runs on the
    CPU (trace counts do not depend on the backend) unless
    LGBTPU_RETRACE_DEVICE says otherwise."""
    # the dist_leaf_sweep scenario needs a multi-device mesh: arrange
    # the virtual 8-device CPU topology BEFORE the backend initializes
    # (a bare `python tools/lint.py` shell has 1 CPU device; under
    # pytest the conftest already set this).  Too late if a backend is
    # live — the scenario then degrades to a skip, never a false red.
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8")
    import jax
    if os.environ.get("LGBTPU_RETRACE_DEVICE", "cpu") == "cpu":
        jax.config.update("jax_platforms", "cpu")
    _install_listener()
    measured = run_matrix()
    if verbose:
        print("measured trace counters:")
        for k in sorted(measured):
            print(f"  {k} = {measured[k]}")
    if update:
        write_budget(measured, budget_path)
        print(f"pinned {len(measured)} counters to {budget_path}")
        return []
    return check(measured, load_budget(budget_path))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--update", action="store_true",
                    help="re-pin tools/retrace_budget.txt from this run")
    ap.add_argument("--budget", default=BUDGET,
                    help="budget file (tests point this at a temp copy)")
    args = ap.parse_args()
    findings = run_lint(args.budget, update=args.update)
    if findings:
        print("retrace lint: trace budget violations:", file=sys.stderr)
        for f in findings:
            print(f"  {f}", file=sys.stderr)
        print(f"\n{len(findings)} finding(s).  If the trace-family "
              "change is intentional, re-pin with `python "
              "tools/check_retraces.py --update`", file=sys.stderr)
        return 1
    print("retrace lint: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
