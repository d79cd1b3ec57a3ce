"""Times the traced job traced a grower program anew: the telemetry counter
jax.traces{name=grower} over the job's boosters.  0 where iterations ran and
none did: the process-wide memo of jitted growers held."""


def read(ctx):
    c = ctx.get("counters", {})
    if not ctx.get("trace") or not c.get("train.iterations", {}).get("value"):
        return None
    return float(c.get("jax.traces{name=grower}", {}).get("value", 0.0))
