"""99th percentile of the gaps between consecutive tokens of a request,
over every request and every gap inside the window: the stall a live
answer feels when another request is admitted (a window holds some 1,500
gaps, so some 15 lie beyond it)."""

from chipbench.window import percentile

UNIT, LAYER, MOVES = "ms", None, None


def read(ctx):
    p = percentile(ctx.window.itl_gaps(), 99)
    return None if p is None else p * 1e3
