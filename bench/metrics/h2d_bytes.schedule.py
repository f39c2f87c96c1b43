"""Host-to-device bytes the device schedule window pricers uploaded per
solve in the window (``front_pass.SCHEDULE_TOTALS["h2d_bytes"]``): the
per-superstep rows of each refresh and each pricer's arguments."""


def read(ctx):
    if (ctx.kind != "schedule" or not ctx.solves
            or "h2d_bytes" not in ctx.counters):
        return None
    return ctx.counters["h2d_bytes"] / ctx.solves
