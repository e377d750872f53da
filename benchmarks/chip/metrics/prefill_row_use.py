"""Requests admitted per row of the admission prefill grids launched in the
window: the grid is `slots` rows whatever the number admitted."""

UNIT, LAYER, MOVES = "%", "scheduler", "ttft_p90_s"


def read(ctx):
    use = ctx.window.prefill_row_use()
    return None if use is None else 100.0 * use
