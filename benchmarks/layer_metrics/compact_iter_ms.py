"""Device self time a boosting iteration of the traced job, in ms, of making
a step's row bucket: the sort of the target rows' numbers and the gathers of
their bins, accumulands and slots, which hand the contraction the rows a step
needs and not all of them (beside contract_iter_ms: the two together are what
a step's histograms cost).
The operations whose innermost scope is SCOPE, as xplane.py folds them (one
without a scope takes the scope it runs inside), over the job's iterations
(run.py's ``scope_iter_ms``).  Nothing where the trace saw no device or the
program compacts nothing (no operation under the scope)."""

SCOPE = "lgbtpu.hist.compact"


def read(ctx):
    return ctx.get("scope_iter_ms", {}).get(SCOPE)
