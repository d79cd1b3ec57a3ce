"""GiB the traced job's boosters placed on the device (binned matrices,
scores, labels): the telemetry counter xfer.h2d_bytes, added up."""


def read(ctx):
    c = ctx.get("counters", {}).get("xfer.h2d_bytes")
    if not ctx.get("trace") or not c:
        return None
    return c["value"] / 2 ** 30
