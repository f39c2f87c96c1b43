"""Host syncs of the device schedule window pricers per solve in the window
(``front_pass.SCHEDULE_TOTALS["syncs"]``)."""


def read(ctx):
    if ctx.kind != "schedule" or not ctx.solves or "syncs" not in ctx.counters:
        return None
    return ctx.counters["syncs"] / ctx.solves
