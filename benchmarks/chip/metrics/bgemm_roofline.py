"""Share of its roofline that `bgemm` reaches in an admission prefill: the
least time for the projections of the whole (slots, prompt) grid, padding
rows included, over the kernel's device time per prefill."""

from chipbench import counts

UNIT, LAYER, MOVES = "%", "kernels", "ttft_p90_s"


def read(ctx):
    if not ctx.trace:
        return None
    prog = ctx.trace["programs"].get("prefill")
    t = ctx.trace["kernels"].get("prefill/bgemm")
    if not prog or not prog["n"] or not t:
        return None
    flops, nbytes = counts.bgemm_prefill(ctx.spec, ctx.mix.prompt_len)
    return 100.0 * counts.least_time(flops, nbytes, ctx.peak) / (t / prog["n"])
