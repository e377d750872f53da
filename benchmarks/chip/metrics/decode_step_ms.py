"""Device time per execution of the decode program, in the traced
sub-window."""

UNIT, LAYER, MOVES = "ms", "steps", "itl_p50_ms"


def read(ctx):
    prog = ctx.trace["programs"].get("decode") if ctx.trace else None
    return prog["s"] / prog["n"] * 1e3 if prog and prog["n"] else None
