"""Share of the traced window in which no operation ran on the chip."""


def read(ctx):
    if ctx.kind != "schedule" or not ctx.trace or ctx.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
