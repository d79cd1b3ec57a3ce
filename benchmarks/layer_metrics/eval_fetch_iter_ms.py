"""Milliseconds of the traced job's held-out evaluations that the score's
transfer to the host took a boosting iteration: the sum of the telemetry
histogram train.eval_part_seconds{part=fetch} (the ``eval.fetch`` span)
over the job's boosters, over train.iterations.  eval_iter_ms less this is
the host metric.  Nothing from a program without the span or from a run
whose trace saw no device."""


def read(ctx):
    c = ctx.get("counters", {})
    h = c.get("train.eval_part_seconds{part=fetch}")
    done = c.get("train.iterations", {}).get("value", 0)
    if not ctx.get("trace") or not h or not h.get("count") or not done:
        return None
    return 1e3 * h["sum"] / done
