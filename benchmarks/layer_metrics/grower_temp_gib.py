"""GiB of temporaries in the grower's compiled executable, from XLA's own
memory analysis of it: the telemetry histogram grower.temp_bytes, one
observation a booster (the executable its iterations ran), so the mean over
the traced job's boosters.  The compiler's layouts are in it, padding and
all, which the logical bytes of the histogram state
(grower.hist_state_bytes) are not.  None from a program that has no such
counter."""


def read(ctx):
    c = ctx.get("counters", {}).get("grower.temp_bytes")
    if not ctx.get("trace") or not c or not c.get("count"):
        return None
    return c["sum"] / c["count"] / 2 ** 30
