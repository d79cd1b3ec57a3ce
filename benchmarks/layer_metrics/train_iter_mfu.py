"""Least time the chip could take for the algorithm's work of the traced job's
iterations (benchmarks/work.py: rows scanned from the grown trees' own counts),
over the traced window's length as the trace itself gives it (first to last
event of the bench.window span and the device's operations).  A share of a
peak: never 0, never capped.  Unit, layer and source are BENCHMARK.json's."""


def read(ctx):
    w = ctx.get("iter_work")
    if not w or not w.get("traced_s"):
        return None
    return 100.0 * w["least_s"] / w["traced_s"]
