"""Seconds Dataset.construct spent assigning bins (value_to_bin over the
columns): data.construct_seconds{stage=bin_data}, sum over count (every
booster of the job reports the one Dataset's)."""


def read(ctx):
    h = ctx.get("counters", {}).get("data.construct_seconds{stage=bin_data}")
    if not ctx.get("trace") or not h or not h.get("count"):
        return None
    return h["sum"] / h["count"]
