"""Admission work that lands in the next decode round: the median token
read-back (`serve.round.wait`) of the first round after an admission less
that of the other rounds, over the window.  The page-table writes, copies
and graft an admission dispatches run on the device before that round's
step.  Nothing is read with fewer than 3 rounds of either kind."""

import statistics

from chipbench.spans import rounds, seconds

UNIT, LAYER, MOVES = "ms", "scheduler", "itl_p99_ms"

MIN_ROUNDS = 3


def read(ctx):
    after, other = [], []
    for r, w in rounds(ctx):
        (after if r["attrs"]["after_admit"] else other).append(seconds(w))
    if len(after) < MIN_ROUNDS or len(other) < MIN_ROUNDS:
        return None
    return (statistics.median(after) - statistics.median(other)) * 1e3
