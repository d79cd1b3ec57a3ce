"""The readers of the held-out evaluation's spans and of the bytes the chip
sends the host (``eval_iter_ms``, ``eval_fetch_iter_ms``, ``d2h_mib_iter``):
the cases of test_bench_span_readers.py, on its snapshot with the
evaluation's two parts and ``xfer.d2h_bytes{site=}`` added."""

import pytest

from bench_testlib import manifest

from benchmarks import run
from test_bench_span_readers import TRACE, hist, reader, snapshot

NEW = ("eval_iter_ms", "eval_fetch_iter_ms", "d2h_mib_iter")
HELD_OUT_BYTES = 4 * 2 ** 21    # a float32 score a row of a 2^21-row bucket
TREE_BYTES = 2 ** 14


def eval_snapshot(fold: int) -> dict:
    """One fold's booster after a two-round job: two evaluations of 0.03 s
    each (``train.eval_seconds``), each a fetch and a metric."""
    return dict(snapshot(fold), **{
        "train.eval_part_seconds{part=fetch}": hist(0.004, 2),
        "train.eval_part_seconds{part=metric}": hist(0.05, 2),
        "xfer.d2h_bytes{site=eval}": {"type": "counter",
                                      "value": 2.0 * HELD_OUT_BYTES},
        "xfer.d2h_bytes{site=fetch}": {"type": "counter",
                                       "value": 2.0 * TREE_BYTES}})


def ctx_of(trace=TRACE, drop=()):
    counters = run.merged_counters(eval_snapshot(k) for k in range(5))
    for key in [k for k in counters if drop and k.startswith(drop)]:
        del counters[key]
    return {"counters": counters, "trace": trace}


@pytest.mark.parametrize("name,value", [
    ("eval_iter_ms", 1e3 * 0.06 / 2),
    ("eval_fetch_iter_ms", 1e3 * 0.004 / 2),
    ("d2h_mib_iter", (HELD_OUT_BYTES + TREE_BYTES) / 2 ** 20)])
def test_reader_reads_the_merged_counters(name, value):
    assert reader(name)(ctx_of()) == pytest.approx(value)


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_from_nothing(name):
    assert reader(name)({}) is None
    assert reader(name)({"counters": {}, "trace": None}) is None
    assert reader(name)({"counters": {}, "trace": TRACE}) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_off_the_chip(name):
    assert reader(name)(ctx_of(trace=None)) is None


def test_a_program_without_the_split_reads_the_evaluation_alone():
    """The parent's program: no evaluation parts and no byte counter.
    ``eval_iter_ms`` reads, and the two new metrics are left out."""
    ctx = ctx_of(drop=("train.eval_part", "xfer.d2h"))
    assert reader("eval_iter_ms")(ctx) == pytest.approx(30.0)
    assert reader("eval_fetch_iter_ms")(ctx) is None
    assert reader("d2h_mib_iter")(ctx) is None


def test_the_eval_readers_metrics_are_in_the_manifest_for_every_cell():
    per_layer = {m["name"]: m for m in manifest()["per_layer"]}
    want = {"eval_iter_ms": ("ms", "program_span", "evaluation"),
            "eval_fetch_iter_ms": ("ms", "program_span", "evaluation"),
            "d2h_mib_iter": ("MiB", "program_counter", "device")}
    for name, (unit, source, layer) in want.items():
        assert per_layer[name] == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": "train_iter_s"}
