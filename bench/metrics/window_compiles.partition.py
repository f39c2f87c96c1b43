"""Compile requests that reached XLA inside the window, per solve: programs
JAX's in-memory caches did not hold, persistent-cache loads included."""


def read(ctx):
    if ctx.kind != "partition" or not ctx.solves:
        return None
    return ctx.compiles / ctx.solves
