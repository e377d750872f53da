"""`mfu` in the cells whose weights are served in int8: the window's useful
model operations over the window at the bf16 peak (the accepted reader,
loaded by path)."""

from pathlib import Path

from chipbench import harness

UNIT, LAYER, MOVES = "%", "model", "tok_s"

read = harness.load_reader({"name": "mfu", "unit": UNIT, "layer": LAYER,
                            "moves": MOVES}, Path(__file__).parents[1]).read
