"""Device schedule window syncs per solve in the window, in seconds: the
inclusive time of the ``windows.price`` spans (one window priced on the
device: refresh, upload, dispatch and the blocking read)."""

NAME = "windows.price"


def read(ctx):
    spans = (ctx.trace or {}).get("spans") or {}
    if ctx.kind != "schedule" or NAME not in spans or not ctx.solves:
        return None
    return spans[NAME]["seconds"] / ctx.solves
