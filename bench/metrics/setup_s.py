"""Set-up seconds: process start to the window's start (JAX start-up,
instance build, and the warm-up solve with its compiles or cache loads)."""


def read(ctx):
    return ctx.setup_s
