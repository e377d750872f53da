"""`bgemm_roofline` in the cells whose weights are served in int8: the
least time of an admission's projections over the whole (slots, prompt)
grid, over the kernel's device time per prefill (the accepted reader,
loaded by path)."""

from pathlib import Path

from chipbench import harness

UNIT, LAYER, MOVES = "%", "kernels", "ttft_p90_s"

read = harness.load_reader({"name": "bgemm_roofline", "unit": UNIT,
                            "layer": LAYER, "moves": MOVES},
                           Path(__file__).parents[1]).read
