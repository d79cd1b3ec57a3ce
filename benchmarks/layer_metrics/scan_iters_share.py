"""Share of the traced job's iterations that ran inside a scan-fused program
(a super-epoch), from the telemetry counters: scan programs run times the
rounds each covers, over train.iterations.  100 means the
defaults engaged a scan loop for every iteration, 0 that the per-iteration
loop ran them all (lgb.cv).  Not capped: a reading over 100 is a miscount."""


def _value(counters, key):
    return int(counters.get(key, {}).get("value", 0))


def read(ctx):
    c = ctx.get("counters", {})
    done = _value(c, "train.iterations")
    if not done:
        return None
    return 100.0 * ctx.get("scan_rounds", 0) \
        * _value(c, "train.superepochs") / done
