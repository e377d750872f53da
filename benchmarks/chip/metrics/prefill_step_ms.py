"""Device time per execution of the admission prefill program, in the
traced sub-window."""

UNIT, LAYER, MOVES = "ms", "steps", "ttft_p90_s"


def read(ctx):
    prog = ctx.trace["programs"].get("prefill") if ctx.trace else None
    return prog["s"] / prog["n"] * 1e3 if prog and prog["n"] else None
