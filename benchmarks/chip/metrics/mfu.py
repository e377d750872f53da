"""The window's useful model operations over the window at the bf16 peak:
every admitted prompt and every decoded token, attention included, padding
rows left out, logits only where the program forms them."""

UNIT, LAYER, MOVES = "%", "model", "tok_s"


def read(ctx):
    w = ctx.window
    return 100.0 * w.useful_flops(ctx.spec) / (w.seconds * ctx.peak["bf16_flops"])
