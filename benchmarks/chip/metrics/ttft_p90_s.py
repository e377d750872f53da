"""90th percentile of closed-loop time to first token, over every request
whose first token falls in the window (see `Window.closed_loop_ttft`)."""

from chipbench.window import percentile

UNIT, LAYER, MOVES = "s", None, None


def read(ctx):
    return percentile(ctx.window.closed_loop_ttft(), 90)
