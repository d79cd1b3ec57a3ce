"""One run of one benchmark cell, in one process.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix, its limits and its per-layer
metrics are found by name from ``BENCHMARK.json`` and the data files under
``benchmarks/``; nothing here names one of them.  The run makes its data from
``--seed``, constructs the ``lgb.Dataset``, warms up with a job that runs the
window's programs (one fold of each size, the mix's warm-up rounds), then
drives whole jobs (one call of the mix's entry, ``lgb.train`` or ``lgb.cv``)
one after the other until the clock passes ``--seconds`` at a job's end; a
finished job's boosters are dropped before the next starts, so the peak is
one job's.  After the window it reads the device's peak memory, frees the
program's state and lets the plain reference (``benchmarks/reference.py``)
follow the first trees of every booster of the window's last job.  The last
line of standard output is the result.

Off the chip (platform not ``tpu``, a ``device_kind`` that ``peaks.json`` does
not know, fewer chips than the cell asks for) it exits non-zero and prints
no result.  Nor does a run that compiled a program inside its window (code
4), or a traced one that found more than ``UNSCOPED_MOST`` of the device's
busy time under none of the program's scopes (code 5).
"""

import time

T_PROCESS = time.perf_counter()

import argparse          # noqa: E402
import gc                # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import resource          # noqa: E402
import sys               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

EXIT_NO_CHIP = 3
EXIT_COMPILED_IN_WINDOW = 4
EXIT_UNSCOPED = 5
UNSCOPED_MOST = 0.05       # of the device's busy seconds, under no scope
ENTRIES = ("train", "cv")   # the public calls a traffic mix may drive
HIST_CHANNELS = 3          # gradient, hessian, count
HIST_PASSES = 3            # traced contraction passes, averaged
FOLLOWS_AT_ONCE = 3        # boosters the reference follows side by side


class NoChip(Exception):
    """The machine is not the chip the cell asks for."""


# -- the manifest and its data files ------------------------------------------

def load_json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def load_cell(name: str, root: str = ROOT) -> dict:
    """Everything the run needs to know about cell ``name``, by name."""
    manifest = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    config_entry = next(c for c in manifest["configs"]
                        if c["name"] == cell["config"])
    config = load_json(root, config_entry["file"])
    bench_dir = os.path.join(root, os.path.relpath(HERE, ROOT))
    traffic = load_json(bench_dir, "traffic", cell["traffic"] + ".json")
    own = load_json(bench_dir, "cells", name + ".json")
    limits, rounds = own["limits"], own["rounds_per_job"]
    if traffic["entry"] not in ENTRIES:
        raise SystemExit(f"traffic/{cell['traffic']}.json asks for the entry "
                         f"{traffic['entry']!r}; run.py drives {ENTRIES}")

    def reports(metric):
        return "workloads" not in metric or name in metric["workloads"]
    return {"name": name, "cell": cell, "config": config, "traffic": traffic,
            "limits": limits, "rounds": int(rounds), "bench_dir": bench_dir,
            "end_to_end": [m for m in manifest["end_to_end"] if reports(m)],
            "per_layer": [m for m in manifest["per_layer"] if reports(m)]}


def load_by_name(bench_dir: str, kind: str, name: str, attr: str):
    """``attr`` of ``<kind>/<name>.py``: a per-layer metric's ``read(ctx)``
    or a generator's ``make(n_rows, n_features, seed)``."""
    path = os.path.join(bench_dir, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, attr)


def load_reader(bench_dir: str, metric: str):
    return load_by_name(bench_dir, "layer_metrics", metric, "read")


# -- the device -----------------------------------------------------------------

def find_chips(chips: int, bench_dir: str):
    """The cell's devices and their row of the peaks table, or NoChip."""
    import jax
    devices = jax.devices()
    peaks = load_json(bench_dir, "peaks.json")
    if devices[0].platform != "tpu":
        raise NoChip(f"platform is {devices[0].platform!r}, not 'tpu': a "
                     "timing taken here is not a device number")
    if devices[0].device_kind not in peaks:
        raise NoChip(f"device_kind {devices[0].device_kind!r} is not in "
                     "benchmarks/peaks.json")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, jax sees "
                     f"{len(devices)}")
    return devices[:chips], peaks[devices[0].device_kind]


def place_compile_cache(root: str) -> None:
    """The compile cache: at a fixed path inside the checkout unless the
    environment places it, and every program kept, however fast it compiled,
    so that a job of the window reads what the warm-up wrote.  The program
    leaves a threshold alone that the environment pins."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(root, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", float(
        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]))


def peak_bytes(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def release_host_memory() -> None:
    """Freed host memory back to the machine before the reference runs:
    the profiler's session and the binning leave gigabytes on the C
    library's free lists, which count against the machine's 40 GiB like
    any other, and the reference's sorts at 8.4M-row folds need the room."""
    import ctypes
    gc.collect()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass                    # another C library: nothing to trim


class CompileCounter:
    """Backend compiles and persistent-cache reads, from ``jax.monitoring``.
    A backend-compile event fires on a cache hit too (with the read time), so
    fresh compiles are events minus hits."""

    def __init__(self):
        from jax import monitoring
        self.events = self.hits = self.misses = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if "backend_compile" in event:
            self.events += 1
            self.seconds += float(duration)

    def _event(self, event, **kw):
        if event.endswith("/cache_hits"):
            self.hits += 1
        elif event.endswith("/cache_misses"):
            self.misses += 1

    def snapshot(self) -> dict:
        return {"events": self.events, "hits": self.hits,
                "misses": self.misses, "seconds": self.seconds,
                "fresh": self.events - self.hits}


# -- the run --------------------------------------------------------------------

def make_data(cell: dict, seed: int, sizes: dict, with_valid: bool):
    """``x, y`` of the training rows and of the held-out rows (or ``None,
    None`` where the mix holds out rows of its own)."""
    d = dict(cell["config"]["data"], **sizes)
    n = d["train_rows"]
    make = load_by_name(cell["bench_dir"], "generators", d["generator"],
                        "make")
    x, y = make(n + (d["valid_rows"] if with_valid else 0), d["features"],
                seed)
    return (x[:n], y[:n], x[n:], y[n:]) if with_valid else (x, y, None, None)


def seeded_folds(n_rows: int, nfold: int, seed: int) -> list:
    """``[(train rows, held-out rows), ...]`` as ``lgb.cv`` takes them under
    ``folds=``: a shuffle from the seed cut into ``nfold`` parts."""
    import numpy as np
    perm = np.random.default_rng(seed).permutation(n_rows)
    folds = []
    for part in np.array_split(perm, nfold):
        held = np.zeros(n_rows, bool)
        held[part] = True
        folds.append((np.flatnonzero(~held), np.flatnonzero(held)))
    return folds


def one_fold_a_size(folds):
    """The folds a warm-up needs: the first of each pair of sizes (rows
    trained on, rows held out).  Folds of one size run the same programs;
    where they do not, the window compiles and the run ends, so this cannot
    pass wrongly."""
    if folds is None:
        return None
    first = {}
    for tr, te in folds:
        first.setdefault((len(tr), len(te)), (tr, te))
    return list(first.values())


def make_job(lgb, call, traffic: dict, params: dict, rounds: int, ds, dv,
             folds):
    """The mix's job: ``job()`` makes one call of the public entry and
    returns the boosters it trained and the held-out metric's curve."""
    metric = traffic["params"].get("metric")

    def train_job():
        evals = {}
        kw = {"valid_sets": [dv],
              "callbacks": [lgb.record_evaluation(evals)]} \
            if dv is not None else {}
        bst = (call or lgb.train)(params, ds, num_boost_round=rounds, **kw)
        return [bst], evals["valid_0"][metric] if dv is not None else None

    def cv_job():
        out = (call or lgb.cv)(params, ds, num_boost_round=rounds,
                               folds=folds, return_cvbooster=True)
        return list(out["cvbooster"].boosters), out[f"valid {metric}-mean"]

    def job():
        boosters, curve = {"train": train_job, "cv": cv_job}[
            traffic["entry"]]()
        for bst in boosters:
            if bst.num_trees() != rounds:
                raise RuntimeError(f"a job of {rounds} rounds returned "
                                   f"{bst.num_trees()} trees")
        return boosters, curve
    return job


class Trace:
    """One profiler session around ``fn()``: the run is timed and the trace
    kept in memory, to be reduced later (``reduced()``), so that reading it
    costs the measured window nothing.  The session is the one that
    ``jax.profiler.start_trace`` wraps; ``stop_trace`` would also write the
    trace out as JSON, which for a job's million operations takes longer
    than the job, so the session is stopped here and hands over the
    serialized ``XSpace``, which holds the operations' scopes too."""

    def __init__(self, fn):
        import jax
        from jax._src.lib import _profiler
        from benchmarks import xplane
        jax.devices()              # the backend before the session, as jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        session = _profiler.ProfilerSession(opts)
        try:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
                self.out = fn()
            self.host_s = time.perf_counter() - t0
        finally:
            self.data = session.stop()

    def reduced(self):
        """Busy and window seconds and the breakdown, or None where
        nothing ran on a device; the trace is dropped."""
        from benchmarks import xplane
        trace, self.data = xplane.columns(self.data), None
        print("traced operations %d" % sum(
            len(ops) for ops in trace["devices"].values()),
            file=sys.stderr, flush=True)
        return xplane.reduce(trace)


def unscoped_share(reduced):
    """The share of the device's busy seconds that no ``lgbtpu.`` scope
    names, or None where the trace saw no device."""
    from benchmarks import xplane
    scopes = (reduced or {}).get("device_scopes")
    if not scopes:
        return None
    return scopes.get(xplane.NO_SCOPE, 0.0) / sum(scopes.values())


def hold_to_scopes(reduced) -> None:
    """End a traced run whose scope metrics would lose time unseen: the
    per-layer metrics that read a scope's seconds shrink, with no other
    sign, when the program runs operations that carry none."""
    share = unscoped_share(reduced)
    if share is not None and share > UNSCOPED_MOST:
        print("%.1f%% of the device's busy seconds ran under no lgbtpu. "
              "scope (at most %.0f%%): %s" % (
                  100 * share, 100 * UNSCOPED_MOST,
                  json.dumps(reduced["device_scopes"])), file=sys.stderr)
        raise SystemExit(EXIT_UNSCOPED)


def merged_counters(snapshots) -> dict:
    """The boosters' telemetry snapshots as one: counters and histograms'
    ``count`` and ``sum`` added up."""
    out = {}
    for snap in snapshots:
        for key, rec in snap.items():
            if not isinstance(rec, dict):
                continue
            into = out.setdefault(key, {})
            for field in ("value", "count", "sum"):
                if field in rec:
                    into[field] = into.get(field, 0) + rec[field]
    return out


def iteration_work(members, n_features: int, bin_bytes: int, peak: dict,
                   traced_s, chips: int = 1) -> dict:
    """The least time the cell's ``chips`` chips could take between them
    for the algorithm's work of the job that grew the members' trees,
    beside the traced window's length (from the trace, not from the host's
    clock)."""
    from benchmarks import reference, work
    per_tree = [work.tree_work(m["n_rows"], n_features, bin_bytes,
                               work.split_child_counts(
                                   reference.flatten_tree(t)))
                for m in members for t in m["model"]["tree_info"]]
    least = work.least_seconds(
        {k: sum(w[k] for w in per_tree) for k in ("ops", "bytes")}, peak)
    return {"least_s": least["seconds"] / chips, "bound": least["bound"],
            "traced_s": traced_s,
            "rows_scanned": [w["rows_scanned"] for w in per_tree]}


def hist_pass(binned, slots: int, num_bins: int, peak: dict, seed: int):
    """The benchmark's own traced passes of the public contraction."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from lightgbm_tpu.ops.histogram import compute_histogram
    from benchmarks import work
    n, f = binned.shape
    rng = np.random.default_rng(seed)
    vals = jnp.asarray(np.concatenate(
        [rng.standard_normal((n, 2), dtype=np.float32),
         np.ones((n, 1), np.float32)], axis=1))
    bins_dev = jnp.asarray(binned)
    kw = {"num_bins": num_bins}
    if slots > 1:
        kw.update(slot=jnp.asarray(rng.integers(0, slots, n, dtype=np.int32)),
                  num_slots=slots)

    def passes():
        for _ in range(HIST_PASSES):
            jax.block_until_ready(compute_histogram(bins_dev, vals, **kw))
    jax.block_until_ready(compute_histogram(bins_dev, vals, **kw))
    reduced = Trace(passes).reduced()
    w = work.hist_pass_work(n, f, binned.dtype.itemsize, HIST_CHANNELS,
                            num_bins, slots)
    least = work.least_seconds(w, peak)
    return {"device_s": reduced["busy_s"] / HIST_PASSES if reduced else None,
            "least_s": least["seconds"], "bound": least["bound"]}


# -- what decides ``correct`` -----------------------------------------------------

def worst_of(followed, curves, recorded) -> dict:
    """Each compared number as the worst over the boosters and trees
    followed.  ``followed`` holds one list of per-tree records for each
    booster, ``curves`` the held-out metric after each tree for each booster
    (their mean over the boosters is what the entry records)."""
    import numpy as np
    flat = [r for out in followed for r in out]
    got = {"trees_followed": float(min(len(out) for out in followed)),
           "count_gap": max(r["count_gap"] for r in flat),
           "leaf_gap_median": max(float(np.median(r["leaf_gap"]))
                                  for r in flat),
           "leaf_gap_max": max(float(r["leaf_gap"].max()) for r in flat)}
    # a tree may hold no split whose gain is more than rounding
    # (reference.NOUGHT): it has leaves to compare and no gain
    gains = [float(np.median(r["gain_gap"])) for r in flat
             if len(r["gain_gap"])]
    if gains:
        got["gain_gap_median"] = max(gains)
    short = [r["split_shortfall"] for r in flat
             if len(r.get("split_shortfall", ()))]
    if short:
        got["split_shortfall"] = float(np.concatenate(short).max())
    if recorded is not None:
        mean = np.mean(np.asarray(curves, np.float64), axis=0)
        got["auc_gap"] = float(np.abs(
            mean - np.asarray(recorded[:len(mean)], np.float64)).max())
    return got


def judge(got: dict, limits: dict, cell_name: str) -> dict:
    """Each number beside its limit."""
    compared = {}
    for name, value in got.items():
        if name not in limits:
            raise SystemExit(f"cells/{cell_name}.json has no limit for "
                             f"{name}")
        lim = limits[name]
        ok = value >= lim["at_least"] if "at_least" in lim \
            else value <= lim["at_most"]
        compared[name] = {"value": value, "limit": lim.get(
            "at_least", lim.get("at_most")), "ok": bool(ok)}
    return compared


def split_check_of(cell: dict, trees, n_features: int, seed: int):
    """The split search's candidates for one booster: the features its
    followed trees split on down to one level under the checked depth, and
    a sample of the others drawn from the seed."""
    import numpy as np
    from benchmarks import reference as ref
    spec = cell["traffic"].get("split_check")
    if not spec:
        return None
    used = [t.split_feature[ref.node_depths(t) <= spec["depth"] + 1]
            for t in trees if len(t.left)]
    sample = np.random.default_rng(seed).choice(
        n_features, min(n_features, spec["sampled_features"]), replace=False)
    params = cell["config"]["params"]
    return {"depth": spec["depth"],
            "features": np.unique(np.concatenate(used + [sample])),
            "min_hess": params.get("min_sum_hessian_in_leaf", 1e-3),
            "min_data": params.get("min_data_in_leaf", 20)}


def routes_missing(trees) -> bool:
    """Whether any node of ``trees`` routes missing values by a direction of
    its own: the program met NaN in that column when it fitted its bins."""
    return any((tree.missing_type == "NaN").any() for tree in trees)


def fault_readings(trees, x, y, kw, recorded) -> dict:
    """What the comparison reads with a fault planted in the trees that the
    reference is given, at the run's own size, on the job's first booster:
    the numbers a limit's upper end is set from.  Not part of a benchmark
    run (``--control 1`` only)."""
    import copy
    import numpy as np
    from benchmarks import reference as ref

    no_valid = dict(kw, x_valid=None, y_valid=None, valid_rows=None)
    # a step that returns its state unchanged: the second tree is the
    # first one grown again (without the first tree's bias)
    again = copy.deepcopy(trees[0])
    rows = kw["rows"]
    again.leaf_value = again.leaf_value - ref.init_score(
        (y if rows is None else y[rows]).astype(np.float64))
    # one answer altered where it is produced: a leaf says the opposite
    altered = copy.deepcopy(trees)
    i = int(np.argmax(np.abs(altered[1].leaf_value)))
    altered[1].leaf_value[i] = -altered[1].leaf_value[i]
    n = len(y) if rows is None else len(rows)
    first_half = np.arange(n // 2) if rows is None else rows[:n // 2]
    stuck = ref.follow([trees[0], again] + trees[2:], x, y, **kw)
    out = {"state_unchanged": worst_of([stuck], None, None),
           "answer_altered": worst_of(
               [ref.follow(altered, x, y, **no_valid)], None, None),
           "half_batch": worst_of(
               [ref.follow(trees, x, y, **dict(no_valid, rows=first_half))],
               None, None)}
    if recorded is not None:
        # the held-out curve that the unchanged state would have reported
        # for this booster, against the one the sound follow gives it
        sound = ref.follow(trees, x, y, **kw)
        out["state_unchanged"]["auc_gap"] = max(
            abs(r["auc"] - s["auc"]) for r, s in zip(stuck, sound))
    if routes_missing(trees):
        # every node's default direction ignored: the missing rows all go
        # right, as under a reference that routes ``value <= threshold``
        # alone (or a partition that does)
        ignored = copy.deepcopy(trees)
        for tree in ignored:
            tree.default_left[:] = False
        out["direction_ignored"] = worst_of(
            [ref.follow(ignored, x, y, **no_valid)], None, None)
    return out


def compare(cell: dict, members, recorded, x, y, xv, yv, seed: int,
            control: bool):
    """The numbers that decide ``correct``, each beside its limit; with
    ``control`` also the lower-precision control's, judged the same way."""
    from concurrent.futures import ThreadPoolExecutor
    import numpy as np
    from benchmarks import reference as ref
    params = cell["config"]["params"]
    check_trees = cell["traffic"]["check_trees"]
    spec = cell["traffic"].get("split_check") or {}
    searched = set(np.random.default_rng(seed).choice(
        len(members), min(len(members), spec.get("members", 0)),
        replace=False).tolist())

    def follow_member(k: int):
        """Booster ``k``'s trees followed: the per-tree records, the
        trees with the arguments they were followed under, and (with
        ``control``) the control's records in the program's place."""
        m = members[k]
        trees = [ref.flatten_tree(t)
                 for t in m["model"]["tree_info"][:check_trees]]
        own_valid = m["valid_rows"] is not None
        kw = dict(learning_rate=params["learning_rate"],
                  lambda_l2=params.get("lambda_l2", 0.0), rows=m["rows"],
                  x_valid=None, y_valid=None, valid_rows=None)
        if recorded is not None:
            kw.update(x_valid=x if own_valid else xv,
                      y_valid=y if own_valid else yv,
                      valid_rows=m["valid_rows"])
        check = split_check_of(cell, trees, x.shape[1], seed) \
            if k in searched else None
        out = ref.follow(trees, x, y, split_check=check, **kw)
        low = None
        if control:
            # the control in the program's place: its values and gains
            # against the reference's, on the same trees and rows
            low = [dict(count_gap=0.0,
                        leaf_gap=ref.rel_gap(c["value"], r["value"]),
                        gain_gap=ref.rel_gap(c["gain"][r["gains_compared"]],
                                             r["gain"][r["gains_compared"]]),
                        auc=c.get("auc"))
                   for c, r in zip(ref.follow(trees, x, y,
                                              accumuland="bfloat16", **kw),
                                   out)]
        return out, (trees, kw), low

    # the boosters are followed side by side, those with a split search
    # first: each follow is NumPy on rows of its own, and one after the
    # other they would outlast the window at 8.4M-row folds; three at a
    # time, since each holds 0.8 GB there and the search 3 GB more
    order = sorted(range(len(members)), key=lambda k: k not in searched)
    with ThreadPoolExecutor(FOLLOWS_AT_ONCE) as pool:
        results = dict(zip(order, pool.map(follow_member, order)))
    results = [results[k] for k in range(len(members))]
    followed = [out for out, _, _ in results]
    curves = [[r.get("auc") for r in out] for out in followed]
    first = results[0][1]
    lows = [low for _, _, low in results]
    low_curves = [[c["auc"] for c in low] for low in lows] if control else []
    got = worst_of(followed, curves, recorded)
    compared = judge(got, cell["limits"], cell["name"])
    readings = {}
    if control:
        ref_mean = np.mean(np.asarray(curves, np.float64), axis=0) \
            if recorded is not None else None
        low_got = worst_of(lows, low_curves, ref_mean)
        low_compared = judge(low_got, cell["limits"], cell["name"])
        readings["control"] = {
            "correct": all(c["ok"] for c in low_compared.values()),
            "compared": {k: {"value": c["value"], "limit": c["limit"]}
                         for k, c in low_compared.items()}}
        readings["faults"] = fault_readings(first[0], x, y, first[1],
                                            recorded)
        # a search that takes the second-best feature at every node, and
        # one that never places a node's missing rows left (read where the
        # trees route missing values: without them it is the sound search):
        # the compared number is the worst node's, the least node's says
        # how close two features, or the two placements, can lie
        searches = {"second_best_feature": "runner_up"}
        if any(routes_missing(trees) for _, (trees, _), _ in results):
            searches["one_direction_search"] = "missing_right_only"
        for fault, key in searches.items():
            found = [r[key] for out in followed for r in out
                     if len(r.get(key, ()))]
            if found:
                found = np.concatenate(found)
                readings["faults"][fault] = {
                    "split_shortfall": float(found.max()),
                    "least_node": float(found.min())}
        readings["per_booster"] = [worst_of([out], None, None)
                                   for out in followed]
    return compared, readings


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             control: bool = False, devices=None, peak=None, sizes=None,
             extra_params=None, call=None, root: str = ROOT):
    """Run cell ``name`` once; returns the result line's dict.

    ``devices`` / ``peak`` given (a test, off the chip) skip the look for
    a chip; ``sizes`` overrides the configuration's row counts, ``extra_params``
    adds training parameters and ``call`` stands in for the mix's entry
    (``lgb.train`` or ``lgb.cv``): all three are for tests at a size a CPU
    can hold, the command line sets none.
    """
    cell = load_cell(name, root)
    place_compile_cache(root)
    if devices is None:
        devices, peak = find_chips(cell["cell"]["chips"], cell["bench_dir"])
    import numpy as np
    import jax
    import lightgbm_tpu as lgb
    compiles = CompileCounter()
    rounds, traffic = cell["rounds"], cell["traffic"]
    with_valid = bool(traffic["valid_set"])
    params = dict(cell["config"]["params"], **traffic["params"],
                  **{k: rounds for k in traffic.get("params_set_to_rounds",
                                                    ())},
                  **(extra_params or {}))
    if trace:
        params["telemetry"] = True     # the counters the readers read

    t0 = time.perf_counter()
    xt, yt, xv, yv = make_data(cell, seed, sizes or {}, with_valid)
    folds = seeded_folds(len(yt), traffic["nfold"], seed) \
        if traffic["entry"] == "cv" else None
    data_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ds_kw = traffic.get("dataset", {})
    ds = lgb.Dataset(xt, label=yt, params=params, **ds_kw).construct()
    dv = lgb.Dataset(xv, label=yv, reference=ds, **ds_kw).construct() \
        if with_valid else None
    bin_s = time.perf_counter() - t0
    job = make_job(lgb, call, traffic, params, rounds, ds, dv, folds)

    # the warm-up is a job on one fold of each size, of all the rounds or of
    # fewer where the mix says that later rounds run no program the first
    # did not (a compile inside the window ends the run, so a mix that says
    # so wrongly cannot pass)
    t0 = time.perf_counter()
    make_job(lgb, call, traffic, params,
             traffic.get("warmup_rounds", rounds), ds, dv,
             one_fold_a_size(folds))()
    gc.collect()               # the warm-up's boosters leave the device
    warm_s = time.perf_counter() - t0
    c_setup = compiles.snapshot()
    setup = {"data_s": data_s, "bin_s": bin_s, "warmup_s": warm_s,
             "compile_s": c_setup["seconds"],
             "cache_misses": c_setup["misses"],
             "cache_hits": c_setup["hits"]}
    print("setup " + json.dumps(setup), file=sys.stderr, flush=True)
    setup_s = time.perf_counter() - T_PROCESS

    # the window: whole jobs one after the other; a finished job's boosters
    # are dropped before the next starts (the collection is on the clock).
    # A traced run traces its first job, and what starting and stopping
    # the profiler costs is kept off the window's clock
    first = boosters = curve = None
    jobs, off_clock = 0, 0.0
    t_window = time.perf_counter()
    if trace:
        first = Trace(job)
        off_clock = time.perf_counter() - t_window - first.host_s
        boosters, curve = first.out
        first.out = None
        jobs = 1
        counters = merged_counters(b.telemetry_snapshot() for b in boosters)
    while not jobs or time.perf_counter() - t_window - off_clock < seconds:
        boosters = None
        gc.collect()
        boosters, curve = job()
        jobs += 1
    window_s = time.perf_counter() - t_window - off_clock
    c_window = compiles.snapshot()
    fresh = c_window["fresh"] - c_setup["fresh"]
    iterations = jobs * rounds * len(boosters)
    print("window " + json.dumps(
        {"jobs": jobs, "boosters": len(boosters), "iterations": iterations,
         "seconds": window_s, "s_per_iter": window_s / iterations,
         "cache_reads": c_window["hits"] - c_setup["hits"],
         "fresh_compiles": fresh,
         "host_peak_gib": resource.getrusage(
             resource.RUSAGE_SELF).ru_maxrss / 2 ** 20}),
          file=sys.stderr, flush=True)
    if fresh > 0 and not trace:
        # under the profiler jax compiles the small per-job metric program
        # past its cache; a traced run reports no end-to-end metric
        print(f"{fresh} program(s) compiled inside the measured window",
              file=sys.stderr)
        raise SystemExit(EXIT_COMPILED_IN_WINDOW)
    peak_b = peak_bytes(devices)
    members = [{"model": bst.dump_model(),
                "rows": folds[k][0] if folds else None,
                "valid_rows": folds[k][1] if folds else None,
                "n_rows": len(folds[k][0]) if folds else len(yt)}
               for k, bst in enumerate(boosters)]

    ctx, reduced = {}, None
    if trace:
        reduced = first.reduced()
        hold_to_scopes(reduced)
        ctx = {"trace": reduced, "counters": counters,
               "scan_rounds": rounds,
               # device self time by scope, ms a boosting iteration
               "scope_iter_ms": {
                   scope: 1e3 * t / (rounds * len(boosters)) for scope, t
                   in (reduced or {}).get("device_scopes", {}).items()},
               "unscoped_share": unscoped_share(reduced),
               "setup": setup, "peak_bytes": peak_b,
               "iter_work": iteration_work(
                   members, xt.shape[1], ds.binned.dtype.itemsize, peak,
                   reduced["window_s"] if reduced else None,
                   cell["cell"]["chips"])}
        # the matrix one booster contracts: a fold's rows, or all of them
        binned = np.asarray(ds.binned)
        if folds:
            binned = binned[folds[0][0]]
    del boosters, ds, dv, job
    gc.collect()
    if trace:
        # the program pads the bin axis to the next power of two
        ctx["hist_pass"] = hist_pass(
            binned, cell["config"]["hist_slots"],
            1 << int(params["max_bin"]).bit_length(), peak, seed)
        del binned
        print("trace " + json.dumps({k: ctx[k] for k in (
            "iter_work", "hist_pass", "scope_iter_ms")}),
              file=sys.stderr, flush=True)

    release_host_memory()
    t0 = time.perf_counter()
    compared, readings = compare(cell, members, curve, xt, yt, xv, yv, seed,
                                 control)
    check_s = time.perf_counter() - t0

    if trace:
        metrics = {}
        for m in cell["per_layer"]:
            value = load_reader(cell["bench_dir"], m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        values = {"train_iter_s": window_s / iterations, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak_b}
    result = {"correct": all(c["ok"] for c in compared.values()),
              "attempted": iterations, "failed": 0, "metrics": metrics,
              "device": device}
    if trace and reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["workload"] = name
    result["seed"] = seed
    result["check_s"] = check_s
    if readings:
        result["readings"] = readings
    result["compared"] = {k: {"value": c["value"], "limit": c["limit"]}
                          for k, c in compared.items()}
    for k, c in compared.items():
        print(f"compared {k}={c['value']!r} limit={c['limit']!r} "
              f"{'ok' if c['ok'] else 'NOT OK'}", file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the lower-precision control (the "
                         "driver never asks for it)")
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), control=bool(args.control))
    except NoChip as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
