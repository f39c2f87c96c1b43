"""Superstep-merge passes of the advanced heuristic per solve in the
window, in seconds: the inclusive time of the ``schedule.sm`` spans, one
per ``superstep_merge_pass`` call.  A program without the span is left
out."""

NAME = "schedule.sm"


def read(ctx):
    spans = (ctx.trace or {}).get("spans") or {}
    if ctx.kind != "schedule" or NAME not in spans or not ctx.solves:
        return None
    return spans[NAME]["seconds"] / ctx.solves
