"""Host clock around Dataset construction (host binning and the transfer)."""


def read(ctx):
    return ctx.get("setup", {}).get("bin_s") or None
