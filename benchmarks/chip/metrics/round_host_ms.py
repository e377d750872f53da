"""Host time of a decode round: the median, over the rounds of the window,
of a `serve.round` span less its `serve.round.wait` (the read-back of the
step's tokens, where the host waits for the device).  The rounds in which
the profiler starts or stops are a few among hundreds, outside the median."""

import statistics

from chipbench.spans import rounds, seconds

UNIT, LAYER, MOVES = "ms", "scheduler", "itl_p50_ms"


def read(ctx):
    host = [seconds(r) - seconds(w) for r, w in rounds(ctx)]
    return statistics.median(host) * 1e3 if host else None
