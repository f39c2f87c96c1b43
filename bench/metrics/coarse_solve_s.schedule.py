"""Coarse solve of the schedule V-cycle per solve in the window, in
seconds: the inclusive time of the ``schedule.initial`` spans (the solve of
the coarsest level, or the flat heuristic where no coarse level exists)."""

NAME = "schedule.initial"


def read(ctx):
    spans = (ctx.trace or {}).get("spans") or {}
    if ctx.kind != "schedule" or NAME not in spans or not ctx.solves:
        return None
    return spans[NAME]["seconds"] / ctx.solves
