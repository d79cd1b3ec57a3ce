"""Seconds Dataset.construct spent finding bin bounds (sampling, find_bin):
data.construct_seconds{stage=fit_bins}, which every booster of the job
reports from the one Dataset, so sum over count."""


def read(ctx):
    h = ctx.get("counters", {}).get("data.construct_seconds{stage=fit_bins}")
    if not ctx.get("trace") or not h or not h.get("count"):
        return None
    return h["sum"] / h["count"]
