"""Share of the superstep-merge candidates that a round of the pass took
from its price cache instead of replaying them, in the window:
``front_pass.SCHEDULE_TOTALS["sm_reused"]`` over ``["sm_priced"]`` plus
``["sm_reused"]``.  A program without the two counters, or a window that
priced no candidate, is left out."""


def read(ctx):
    c = ctx.counters
    if ctx.kind != "schedule" or "sm_priced" not in c or "sm_reused" not in c:
        return None
    total = c["sm_priced"] + c["sm_reused"]
    return c["sm_reused"] / total if total else None
