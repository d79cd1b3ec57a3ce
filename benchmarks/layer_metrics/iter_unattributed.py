"""Share of the traced job's iteration seconds that no phase span owns:
train.iter_seconds less every train.phase_seconds{phase=}, over
train.iter_seconds.  The phases partition the iteration, so this is the
loop's own lines between them."""


def read(ctx):
    c = ctx.get("counters", {})
    whole = c.get("train.iter_seconds", {}).get("sum")
    if not ctx.get("trace") or not whole:
        return None
    phases = sum(rec.get("sum", 0.0) for key, rec in c.items()
                 if key.startswith("train.phase_seconds{"))
    return 100.0 * (whole - phases) / whole
