"""Mean device time of one execution of the device pass's find program
(the ``jit_find`` module in the trace's ``XLA Modules`` line), in ms."""

PROGRAM = "jit_find"


def read(ctx):
    mod = (ctx.trace or {}).get("modules", {}).get(PROGRAM)
    if not mod or not mod["count"]:
        return None
    return 1e3 * mod["seconds"] / mod["count"]
