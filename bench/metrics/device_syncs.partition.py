"""Blocking device-to-host reads of the device-resident partition passes
per solve in the window (``front_pass.PARTITION_TOTALS`` syncs)."""


def read(ctx):
    if ctx.kind != "partition" or not ctx.solves or "syncs" not in ctx.counters:
        return None
    return ctx.counters["syncs"] / ctx.solves
