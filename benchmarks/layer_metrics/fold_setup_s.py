"""Seconds lgb.cv spent setting the traced job's folds up before its first
iteration (two host gathers, a Booster, two uploads a fold): the sum of the
telemetry histogram train.setup_seconds{stage=fold_setup} over the job's
boosters.  Like every reader of the program's spans it reads nothing from a
run whose trace saw no device: a time taken off the chip is no chip number."""


def read(ctx):
    h = ctx.get("counters", {}).get("train.setup_seconds{stage=fold_setup}")
    if not ctx.get("trace") or not h or not h.get("count"):
        return None
    return h["sum"]
