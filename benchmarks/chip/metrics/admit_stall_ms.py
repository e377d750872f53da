"""What one admission costs on the host's clock: the median length of a
`serve.admit` span, from building the group's prompt block to its first
tokens on the host, over the admissions of the window.  Every live answer
waits that long between two of its tokens."""

import statistics

from chipbench.spans import ending_inside, seconds

UNIT, LAYER, MOVES = "ms", "scheduler", "itl_p99_ms"


def read(ctx):
    admits = [seconds(r) for _, r in ending_inside(ctx, "serve.admit")]
    return statistics.median(admits) * 1e3 if admits else None
