#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result as the last line.

    python3 benchmarks/chip/run_cell.py --workload stablelm-1.6b.conversation \
        --seed 1234 --seconds 45 --trace 0

Run from the root of a checkout, on a machine that holds the chips the cell
asks for.  `--trace 0` reports the cell's end-to-end metrics, `--trace 1`
its per-layer metrics from a profiler trace of a sub-window.  The numbers
the output check compared are printed with their limits as the last lines
of standard error and, under `compared`, at the end of the result.  Without
an accelerator, or with fewer chips than the cell asks for, it exits with
status 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]
# the kernels' block plans come from the analytic ranking alone: no
# measurement at first touch, no plan read from a user's disk cache
os.environ["REPRO_AUTOTUNE"] = "0"
os.environ["REPRO_AUTOTUNE_CACHE"] = "off"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    from chipbench import harness

    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"[chipbench] {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    gap, n = (result["compared"][k] for k in ("widest_logit_gap",
                                               "requests_checked"))
    print(f"widest_logit_gap: {gap['value']!r} (limit: at most "
          f"{gap['limit']!r})\nrequests_checked: {n['value']} (limit: at "
          f"least {n['limit']})", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
