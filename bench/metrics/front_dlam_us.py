"""Mean device time of one call of the Pallas ``front_dlam`` kernel, in
microseconds: the custom calls inside the device pass's find program (the
only Pallas kernel that program holds)."""

PROGRAM = "jit_find"


def read(ctx):
    calls = (ctx.trace or {}).get("custom_calls", {}).get(PROGRAM)
    if not calls:
        return None
    return 1e-3 * sum(d for _, d, _ in calls) / len(calls)
