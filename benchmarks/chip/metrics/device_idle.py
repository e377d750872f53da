"""Share of the traced sub-window in which no operation ran on the device."""

UNIT, LAYER, MOVES = "%", "device", "tok_s"


def read(ctx):
    if not ctx.trace or not ctx.trace["window_s"]:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
