"""Seconds the host spent in the traced job's placements of binned matrices
and row state on the device (the host's part: a placement is not fenced, and
blocks only on the transfer before it): the sum of
train.setup_seconds{stage=to_device} over the job's boosters."""


def read(ctx):
    h = ctx.get("counters", {}).get("train.setup_seconds{stage=to_device}")
    if not ctx.get("trace") or not h or not h.get("count"):
        return None
    return h["sum"]
