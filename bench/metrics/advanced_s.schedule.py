"""Advanced-heuristic rounds of the schedule V-cycle per solve in the
window, in seconds: the inclusive time of the ``schedule.advanced`` spans
(the winner-commit SM/BR/SR/split rounds, one span per refined level)."""

NAME = "schedule.advanced"


def read(ctx):
    spans = (ctx.trace or {}).get("spans") or {}
    if ctx.kind != "schedule" or NAME not in spans or not ctx.solves:
        return None
    return spans[NAME]["seconds"] / ctx.solves
