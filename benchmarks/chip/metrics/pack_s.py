"""Seconds the timed call of the scheduler spends packing the weights to
int8 in its set-up: its `serve.pack` span, which ends before the window's
t0 and so is read by name, not by the window."""

from chipbench.spans import seconds

UNIT, LAYER, MOVES = "s", "weights", "setup_s"


def read(ctx):
    packs = [r for r in ctx.stats.get("spans") or ()
             if r["name"] == "serve.pack"]
    return seconds(packs[-1]) if packs else None
