"""Seconds per replicated BSP schedule: the whole window's wall time over
the solves completed in it (each solve ends on its result on the host)."""


def read(ctx):
    if ctx.kind != "schedule" or not ctx.solves:
        return None
    return ctx.window_s / ctx.solves
