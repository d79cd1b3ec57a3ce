"""Share of the seconds in the trace's longest idle gaps that a span of the
program names (a host event whose name starts with lgbtpu.), the rest being
named by jax's own events or by none."""

PREFIX = "lgbtpu."


def read(ctx):
    gaps = (ctx.get("trace") or {}).get("idle_gaps")
    total = sum(seconds for _, seconds in gaps or ())
    if not total:
        return None
    return 100.0 * sum(seconds for name, seconds in gaps
                       if name.startswith(PREFIX)) / total
