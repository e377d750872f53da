"""Mean share of slots live in a decode round (the scheduler's counter)."""

UNIT, LAYER, MOVES = "%", "scheduler", "tok_s"


def read(ctx):
    return 100.0 * ctx.stats["occupancy"]
