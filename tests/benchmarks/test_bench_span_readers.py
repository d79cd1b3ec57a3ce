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


def test_the_new_metrics_are_the_manifests_last_eight():
    names = [m["name"] for m in manifest()["per_layer"]]
    assert tuple(names[-8:]) == NEW
