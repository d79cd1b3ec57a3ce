"""The benchmark's own traced call of the public ops.compute_histogram on the
cell's binned matrix at the cell's slot width: least time for N x F bin codes,
N x 3 float32 and the histogram (benchmarks/work.py) over the pass's device time."""


def read(ctx):
    h = ctx.get("hist_pass")
    if not h or not h.get("device_s"):
        return None
    return 100.0 * h["least_s"] / h["device_s"]
