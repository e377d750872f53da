"""`bgemv_roofline` in the cells whose weights are served in int8: the
packed weights and their scales streamed once at the HBM peak, over the
kernel's device time per decode step (the accepted reader, loaded by
path; `counts.proj_weight_bytes` counts the int8 bytes)."""

from pathlib import Path

from chipbench import harness

UNIT, LAYER, MOVES = "%", "kernels", "tok_s"

read = harness.load_reader({"name": "bgemv_roofline", "unit": UNIT,
                            "layer": LAYER, "moves": MOVES},
                           Path(__file__).parents[1]).read
