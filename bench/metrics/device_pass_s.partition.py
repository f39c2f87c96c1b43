"""Device-resident partition passes per solve in the window, in seconds:
the inclusive time of the ``device.pass`` spans (``fm_pass`` and
``rep_pass``), their finds, waits and host work included."""

NAME = "device.pass"


def read(ctx):
    spans = (ctx.trace or {}).get("spans") or {}
    if ctx.kind != "partition" or NAME not in spans or not ctx.solves:
        return None
    return spans[NAME]["seconds"] / ctx.solves
