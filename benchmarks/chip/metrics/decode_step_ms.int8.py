"""`decode_step_ms` in the cells whose weights are served in int8: device
time per execution of the decode program (the accepted reader, loaded by
path)."""

from pathlib import Path

from chipbench import harness

UNIT, LAYER, MOVES = "ms", "steps", "itl_p50_ms"

read = harness.load_reader({"name": "decode_step_ms", "unit": UNIT,
                            "layer": LAYER, "moves": MOVES},
                           Path(__file__).parents[1]).read
