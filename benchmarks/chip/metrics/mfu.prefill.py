"""The admission prefill's useful operations (the real prompts it admits,
on average over the window's launches) over its device time at the bf16
peak: the whole prefill step's share of the chip, beside `bgemm_roofline`."""

from chipbench import counts

UNIT, LAYER, MOVES = "%", "model", "ttft_p90_s"


def read(ctx):
    prog = ctx.trace["programs"].get("prefill") if ctx.trace else None
    launches = ctx.window.prefill_launches()
    if not prog or not prog["n"] or not launches:
        return None
    useful = (len(ctx.window.admitted()) / launches
              * counts.prompt_flops(ctx.spec, ctx.mix.prompt_len))
    return 100.0 * useful / (prog["s"] / prog["n"] * ctx.peak["bf16_flops"])
