#!/usr/bin/env python3
"""The control of a cell's comparison, as the configuration's check
(``"check"``: ``bench/checks/<name>.py``) defines it: for a DGO
configuration the plain reference computed in bfloat16, the precision
below the float32 the configurations state, put in the program's place.
Its readings set the upper end of each limit, and it has to come out as
not correct.

    python3 bench/control.py --workload sfu-suite.open --seeds 1,2,3

One JSON line per seed: the numbers, their limits, and whether the
control was (wrongly) found correct. The DGO check's control is numpy
only: no program, no chip.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402


def control_numbers(cell, seed: int, seconds: float):
    """(numbers, limits) of the cell's control on one seed."""
    return harness.check_module(cell.config["check"], cell.root) \
        .control_numbers(cell, seed, seconds)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="the window (default: run_seconds)")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    seconds = args.seconds or float(harness.load_benchmark()["run_seconds"])
    for seed in (int(s) for s in args.seeds.split(",")):
        got, limits = control_numbers(cell, seed, seconds)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "numbers": got, "limits": limits,
                          "correct": all(got[k] <= limits[k]
                                         for k in got)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
