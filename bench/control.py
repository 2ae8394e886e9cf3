#!/usr/bin/env python3
"""The control of a cell's comparison: the plain reference computed in
bfloat16, the precision below the float32 the configurations state, put
in the program's place. Its readings set the upper end of each limit,
and it has to come out as not correct.

    python3 bench/control.py --workload sfu-suite.open --seeds 1,2,3

For each seed the cell's own traffic is drawn (an open loop's window at
the mix's rate; a closed loop's first ``check_sample + 1`` starts), the
check's sample is taken from it, and each sampled request is answered by
the bfloat16 reference and compared, as a run compares the program, with
the float32 reference. One JSON line per seed: the numbers, their limits, and
whether the control was (wrongly) found correct. Numpy only: no program,
no chip.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import harness  # noqa: E402
import traffic as traffic_gen  # noqa: E402
from drive import Answer  # noqa: E402


def starts(cell, seed: int, seconds: float) -> list:
    """The requests of the cell's traffic, unanswered."""
    cfg, mix = cell.config, cell.traffic
    if mix["loop"] == "closed":
        [entry] = cfg["problems"]
        return [Answer(0, traffic_gen.closed_loop_start(entry, seed, i))
                for i in range(int(cfg["check_sample"]) + 1)]
    return [Answer(a.problem, a.x0) for a in
            traffic_gen.open_loop(mix, cfg["problems"], seed, seconds)]


def control_numbers(cell, seed: int, seconds: float):
    """(numbers, limits) of the bfloat16 control on one seed."""
    cfg = cell.config
    answers = starts(cell, seed, seconds)
    for a in answers:              # every request counts as answered
        a.best_f, a.iterations = 0.0, 0
    idx = check.sample(answers, cfg["problems"], seed,
                       int(cfg["check_sample"]))
    low = check.reference_answers(cfg, answers, idx, dtype="bfloat16")
    for i, ref in low.items():
        answers[i].best_x = ref.best_x
        answers[i].best_f = float(ref.best_f)
        answers[i].iterations = ref.iterations
    got = check.numbers(cfg, answers,
                        check.reference_answers(cfg, answers, idx))
    return got, {k: float(v) for k, v in cfg["limits"].items()}


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
