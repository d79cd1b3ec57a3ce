"""KiB that one chip sends over the interconnect a boosting iteration of the
traced job, by the program's own static account of its collectives: the
telemetry counters comm.bytes{collective=,site=} (obs/comm.py's ring model of
each site's traced payload, times the steps the grower ran), added up over the
sites and the boosters, over train.iterations.  Nothing from a program without
the counter (the parent) or from a learner that crosses no chip."""

PREFIX = "comm.bytes{"


def read(ctx):
    c = ctx.get("counters", {})
    done = c.get("train.iterations", {}).get("value", 0)
    sent = [rec["value"] for key, rec in c.items()
            if key.startswith(PREFIX) and "value" in rec]
    if not done or not sent:
        return None
    return sum(sent) / done / 1024.0
