"""Output tokens emitted in the window, over the window's length."""

UNIT, LAYER, MOVES = "tokens/s", None, None


def read(ctx):
    return ctx.window.tokens() / ctx.window.seconds
