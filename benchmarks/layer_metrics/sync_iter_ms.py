"""Device self time a boosting iteration of the traced job, in ms, of what a
sharded grower's step does across chips: the exchange of the workers' best
split candidates and the choice among them (ops/split.py ``gather_best``),
the part of a step that waits on the other chips.
The operations whose innermost scope is one of SCOPES, as xplane.py folds them
(mean over the device planes; one without a scope takes the scope it runs
inside), over the job's iterations (run.py's ``scope_iter_ms``).  Nothing where
the trace saw no device or none of the scopes: a program without the scope
(the parent), or a learner that crosses no chip."""

SCOPES = ("lgbtpu.sync",)


def read(ctx):
    found = [ms for scope, ms in ctx.get("scope_iter_ms", {}).items()
             if scope in SCOPES]
    return sum(found) if found else None
