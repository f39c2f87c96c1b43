"""Coarsening of the schedule V-cycle per solve in the window, in seconds:
the inclusive time of the ``schedule.coarsen`` spans (``build_levels``)."""

NAME = "schedule.coarsen"


def read(ctx):
    spans = (ctx.trace or {}).get("spans") or {}
    if ctx.kind != "schedule" or NAME not in spans or not ctx.solves:
        return None
    return spans[NAME]["seconds"] / ctx.solves
