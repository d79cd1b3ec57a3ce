"""Device time of that one full-N contraction pass, from the xplane."""


def read(ctx):
    h = ctx.get("hist_pass")
    if not h or not h.get("device_s"):
        return None
    return 1e3 * h["device_s"]
