"""Comm windows read per device schedule window sync in the window: the
rows priced (``front_pass.SCHEDULE_TOTALS["windows"]``) less those dropped
unused because the schedule changed first (``["discarded"]``), over the
syncs (``["syncs"]``).  A program without the batched comm pricer counts
no windows, and the metric is left out."""


def read(ctx):
    c = ctx.counters
    if (ctx.kind != "schedule" or not c.get("syncs")
            or "windows" not in c or "discarded" not in c):
        return None
    return (c["windows"] - c["discarded"]) / c["syncs"]
