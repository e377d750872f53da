"""Median of the gaps between consecutive tokens of a request, over every
request and every gap inside the window: the pace at which an answer
streams, one decode round."""

from chipbench.window import percentile

UNIT, LAYER, MOVES = "ms", None, None


def read(ctx):
    p = percentile(ctx.window.itl_gaps(), 50)
    return None if p is None else p * 1e3
