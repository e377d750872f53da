"""`flash_roofline` in the cells whose KV cache is int8: a mean round's keys,
values and their scales at logical size, once at the HBM peak, over paged
flash attention's device time per decode step (the accepted reader, loaded
by path)."""

from pathlib import Path

from chipbench import harness

UNIT, LAYER, MOVES = "%", "kernels", "itl_p50_ms"

read = harness.load_reader({"name": "flash_roofline", "unit": UNIT,
                            "layer": LAYER, "moves": MOVES},
                           Path(__file__).parents[1]).read
