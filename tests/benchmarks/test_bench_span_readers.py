"""The readers of the program's own spans and counters (ISSUE 26), fed a
hand-made ``ctx``: the counters as ``merged_counters`` makes them of five
boosters' snapshots, and a trace whose gaps carry the program's names and
jax's."""

import pytest

from bench_testlib import manifest

from benchmarks import run

CELL = manifest()["workloads"][0]["name"]
NEW = ("fold_setup_s", "upload_s", "h2d_gib", "grower_traces",
       "iter_unattributed", "idle_explained", "bin_fit_s", "bin_assign_s")
FOLD_BYTES = (320_000 + 131_072) * 2000 + 320_000 * 8 + 131_072 * 4


def hist(total, count=1):
    return {"type": "histogram", "count": count, "sum": total,
            "min": total / count, "max": total / count}


def snapshot(fold: int) -> dict:
    """What one fold's booster reports after a two-round job."""
    snap = {
        "train.iterations": {"type": "counter", "value": 2.0},
        "train.iter_seconds": hist(8.0, 2),
        "train.phase_seconds{phase=grad}": hist(0.1, 2),
        "train.phase_seconds{phase=grow}": hist(7.5, 2),
        "train.phase_seconds{phase=fetch}": hist(0.02, 2),
        "train.phase_seconds{phase=score}": hist(0.3, 2),
        "train.eval_seconds": hist(0.06, 2),
        "train.setup_seconds{stage=fold_setup}": hist(1.25),
        "train.setup_seconds{stage=subset}": hist(0.4, 2),
        "train.setup_seconds{stage=to_device}": hist(0.75, 3),
        "xfer.h2d_bytes": {"type": "counter", "value": float(FOLD_BYTES)},
        "grower.memo{result=hit}": {"type": "counter", "value": 1.0},
        "data.construct_seconds{stage=to_numpy}": hist(6.5),
        "data.construct_seconds{stage=fit_bins}": hist(12.0),
        "data.construct_seconds{stage=bin_data}": hist(80.0),
        "compile.count": 3,            # plain numbers are not counters
    }
    if fold == 0:
        snap["jax.traces{name=grower}"] = {"type": "counter", "value": 1.0}
    return snap


TRACE = {"busy_s": 36.9, "window_s": 43.7, "devices": 1, "device_ops": [],
         "idle_gaps": [["lgbtpu.cv.fold_setup", 1.25],
                       ["lgbtpu.cv.fold_setup", 1.25],
                       ["XlaLinearize", 0.5],
                       ["lgbtpu.eval", 0.75],
                       ["(no host event)", 0.25]]}


def ctx_of(folds=5, trace=TRACE):
    return {"counters": run.merged_counters(snapshot(k)
                                            for k in range(folds)),
            "trace": trace}


def reader(name):
    return run.load_reader(run.load_cell(CELL)["bench_dir"], name)


@pytest.mark.parametrize("name,value", [
    ("fold_setup_s", 5 * 1.25),
    ("upload_s", 5 * 0.75),
    ("h2d_gib", 5 * FOLD_BYTES / 2 ** 30),
    ("grower_traces", 1.0),
    ("iter_unattributed", 100.0 * (8.0 - 7.92) / 8.0),
    ("idle_explained", 100.0 * 3.25 / 4.0),
    ("bin_fit_s", 12.0),
    ("bin_assign_s", 80.0)])
def test_reader_reads_the_merged_counters(name, value):
    assert reader(name)(ctx_of()) == pytest.approx(value)


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_from_nothing(name):
    assert reader(name)({}) is None
    assert reader(name)({"counters": {}, "trace": None}) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_off_the_chip(name):
    """A run off the chip has counters and no device plane (``trace`` is
    None): a span's seconds taken there are not the chip's, and no reader
    of this family reports them."""
    assert reader(name)(ctx_of(trace=None)) is None


def test_no_grower_trace_reads_zero_not_nothing():
    """The memo held: iterations ran and no booster traced a grower."""
    ctx = ctx_of()
    del ctx["counters"]["jax.traces{name=grower}"]
    assert reader("grower_traces")(ctx) == 0.0


def test_gaps_named_by_jax_alone_explain_nothing():
    """The parent's trace: every gap carries one of jax's names."""
    gaps = [["XlaLinearize", 1.25], ["np.asarray_jax.Array_", 0.01]]
    assert reader("idle_explained")(ctx_of(trace=dict(
        TRACE, idle_gaps=gaps))) == 0.0
    assert reader("idle_explained")(ctx_of(trace=dict(
        TRACE, idle_gaps=[]))) is None


def test_the_span_readers_metrics_are_in_the_manifest():
    names = [m["name"] for m in manifest()["per_layer"]]
    assert set(NEW) <= set(names) and len(names) == len(set(names))


# -- the readers of the device's seconds by scope (ISSUE 28) ---------------------

SCOPED = {"lgbtpu.hist.contract": 1300.0, "lgbtpu.partition": 220.0,
          "lgbtpu.hist.state": 80.0, "lgbtpu.split": 150.0,
          "lgbtpu.walk": 190.0, "lgbtpu.grow": 5.0, "(no scope)": 8.0,
          "lgbtpu.hist.compact": 57.0}


@pytest.mark.parametrize("name,ms", [
    ("contract_iter_ms", 1300.0), ("partition_iter_ms", 220.0 + 80.0),
    ("split_iter_ms", 150.0), ("walk_iter_ms", 190.0),
    ("compact_iter_ms", 57.0)])
def test_scope_reader_reads_its_scopes(name, ms):
    assert reader(name)({"scope_iter_ms": SCOPED}) == pytest.approx(ms)
    # the scan's one-hot is the contraction's too
    if name == "contract_iter_ms":
        with_scan = dict(SCOPED, **{"lgbtpu.hist.onehot": 1050.0})
        assert reader(name)({"scope_iter_ms": with_scan}) \
            == pytest.approx(2350.0)


@pytest.mark.parametrize("name", ["contract_iter_ms", "partition_iter_ms",
                                  "split_iter_ms", "walk_iter_ms",
                                  "compact_iter_ms"])
def test_scope_reader_reads_nothing_without_its_scopes(name):
    """Off the chip or from a trace that came without its scopes (nothing
    folded), and in a job that never ran the scope (no held-out rows: no
    walk; a table whose rule compacts nothing): the metric is left out,
    never 0."""
    assert reader(name)({}) is None
    assert reader(name)({"scope_iter_ms": {}}) is None
    assert reader(name)({"scope_iter_ms": {"lgbtpu.grow": 5.0}}) is None


# -- what no scope names (REVIEW of PR 28) ---------------------------------------

def test_unscoped_share_is_what_the_scope_metrics_cannot_see():
    from benchmarks import run, xplane
    scopes = {"lgbtpu.walk": 1.9, "lgbtpu.partition": 7.9,
              xplane.NO_SCOPE: 0.2}
    share = run.unscoped_share({"device_scopes": scopes})
    assert share == pytest.approx(0.02)
    assert reader("unscoped_share")({"unscoped_share": share}) \
        == pytest.approx(2.0)
    assert run.unscoped_share({"device_scopes": {"lgbtpu.walk": 1.0}}) == 0.0
    # no device in the trace, or a trace that came without its scopes
    assert run.unscoped_share(None) is None
    assert run.unscoped_share({"device_scopes": {}}) is None
    assert reader("unscoped_share")({"unscoped_share": None}) is None


@pytest.mark.parametrize("no_scope,ends", [(0.2, False), (1.95, True)])
def test_a_traced_run_that_loses_a_scopes_seconds_ends_without_a_result(
        no_scope, ends, capsys):
    """Epsilon's walk served from a compile cache older than its scope read
    1.95 of 19.49 busy seconds under no scope (PR 28): such a run ends with
    a code of its own and says what it saw."""
    from benchmarks import run, xplane
    reduced = {"device_scopes": {"lgbtpu.hist.contract": 13.05,
                                 "lgbtpu.partition": 19.49 - 13.05 - no_scope,
                                 xplane.NO_SCOPE: no_scope}}
    if not ends:
        return run.hold_to_scopes(reduced)
    with pytest.raises(SystemExit) as e:
        run.hold_to_scopes(reduced)
    assert e.value.code == run.EXIT_UNSCOPED
    assert "10.0% of the device's busy seconds" in capsys.readouterr().err
