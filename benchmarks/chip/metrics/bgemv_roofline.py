"""Share of its roofline that `bgemv` reaches in a decode step: the least
time for the step's projections (their weights streamed once at the HBM
peak, or their operations at the bf16 peak, whichever is longer) over the
kernel's device time per decode step."""

from chipbench import counts

UNIT, LAYER, MOVES = "%", "kernels", "tok_s"


def read(ctx):
    if not ctx.trace:
        return None
    prog = ctx.trace["programs"].get("decode")
    t = ctx.trace["kernels"].get("decode/bgemv")
    if not prog or not prog["n"] or not t:
        return None
    least = counts.least_time(*counts.bgemv_step(ctx.spec), ctx.peak)
    return 100.0 * least / (t / prog["n"])
