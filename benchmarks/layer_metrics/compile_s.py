"""Seconds jax spent in backend compiles or cache reads during set-up."""


def read(ctx):
    return ctx.get("setup", {}).get("compile_s") or None
