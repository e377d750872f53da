#!/usr/bin/env python3
"""Readings for the output check's limit, on the chip, in one process.

    python3 benchmarks/chip/calibrate.py --workload stablelm-1.6b.conversation \
        --seeds 11,12,13 --seconds 45 [--control-seeds 3] [--out FILE]

For each seed it serves the cell's traffic for `--seconds` at the cell's own
load, then reads the widest reference-logit gap of the served tokens (the
number `correct` compares) and, on the first `--control-seeds` seeds, the
control's: the same reference computed in the next lower integer precision
(`harness.CONTROL_BITS`), in the program's place.  One JSON line per seed on
standard output (and appended to `--out`); the limit in the configuration
file is set between the largest program reading and the smallest control
reading.  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]
os.environ["REPRO_AUTOTUNE"] = "0"
os.environ["REPRO_AUTOTUNE_CACHE"] = "off"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args()

    from chipbench import harness
    from chipbench.window import Window

    su = harness.set_up(args.workload)
    rows = []
    for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.time()
        prompts, stats, _, n = harness.serve_window(su, seed, args.seconds)
        win = Window.of(stats, args.seconds, su.spec.slots, su.mix.prompt_len)
        gc.collect()
        got = harness.compare(su.spec, seed, prompts, stats,
                              control=k < args.control_seeds)
        row = {"workload": args.workload, "seed": seed, **got,
               "control_bits": (harness.CONTROL_BITS[su.spec.weights]
                                if k < args.control_seeds else None),
               "window_tokens": win.tokens(),
               "admitted": len(win.admitted()), "queued": n,
               "seconds_taken": time.time() - t}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    prog = [r["program"] for r in rows]
    ctrl = [r["control"] for r in rows if r["control"] is not None]
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "program_max": max(prog), "program_all": prog,
                      "control_min": min(ctrl) if ctrl else None,
                      "control_all": ctrl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
