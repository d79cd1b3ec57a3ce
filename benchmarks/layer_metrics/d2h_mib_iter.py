"""MiB the traced job's boosters fetched from the device to the host a
boosting iteration: the telemetry counters xfer.d2h_bytes{site=} (the
nbytes of every fetched host array: the tree's fields, the held-out
scores, ...) added up over the sites and the boosters, over
train.iterations.  Nothing from a program without the counter or from a
run whose trace saw no device."""

PREFIX = "xfer.d2h_bytes{"


def read(ctx):
    c = ctx.get("counters", {})
    done = c.get("train.iterations", {}).get("value", 0)
    fetched = [rec["value"] for key, rec in c.items()
               if key.startswith(PREFIX) and "value" in rec]
    if not ctx.get("trace") or not done or not fetched:
        return None
    return sum(fetched) / done / 2 ** 20
