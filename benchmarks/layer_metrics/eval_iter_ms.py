"""Milliseconds the traced job's held-out evaluations took a boosting
iteration: the sum of the telemetry histogram train.eval_seconds (the
``eval`` span: the score's fetch and the host metric) over the job's
boosters, over train.iterations.  Like every reader of the program's spans
it reads nothing from a run whose trace saw no device."""


def read(ctx):
    c = ctx.get("counters", {})
    h = c.get("train.eval_seconds")
    done = c.get("train.iterations", {}).get("value", 0)
    if not ctx.get("trace") or not h or not h.get("count") or not done:
        return None
    return 1e3 * h["sum"] / done
