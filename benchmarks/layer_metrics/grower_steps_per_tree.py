"""Mean of the telemetry histogram train.steps_per_tree over the traced job's trees."""


def read(ctx):
    h = ctx.get("counters", {}).get("train.steps_per_tree")
    if not h or not h.get("count"):
        return None
    return h["sum"] / h["count"]
