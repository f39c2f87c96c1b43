"""Host refinement of the partition V-cycle per solve in the window, in
seconds: the self time of the ``partition.initial``, ``partition.level``
and ``partition.alternate`` spans, each less what its direct children on
its host line cover (device passes, JAX dispatch, nested spans)."""

NAMES = ("partition.initial", "partition.level", "partition.alternate")


def read(ctx):
    spans = (ctx.trace or {}).get("spans")
    if ctx.kind != "partition" or not spans or not ctx.solves:
        return None
    found = [spans[name]["self_s"] for name in NAMES if name in spans]
    return sum(found) / ctx.solves if found else None
