"""Host work of the schedule V-cycle per solve in the window, in seconds:
the self time of the ``schedule.initial`` (the flat heuristic where no
coarse level exists) and ``schedule.level`` spans, each less what its
direct children on its host line cover (window syncs, JAX dispatch,
nested spans)."""

NAMES = ("schedule.initial", "schedule.level")


def read(ctx):
    spans = (ctx.trace or {}).get("spans")
    if ctx.kind != "schedule" or not spans or not ctx.solves:
        return None
    found = [spans[name]["self_s"] for name in NAMES if name in spans]
    return sum(found) / ctx.solves if found else None
