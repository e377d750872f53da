"""Seconds from process start to the scheduler's t0: JAX start-up, weights,
quantization, compile-cache loads and warm-up."""

UNIT, LAYER, MOVES = "s", None, None


def read(ctx):
    return ctx.setup_s
