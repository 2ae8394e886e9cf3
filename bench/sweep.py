#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate at which
the backlog does not grow over the window.

    python3 bench/sweep.py --workload sfu-suite.open --seed 1 \\
        --seconds 10 --rates 50,100,150,200

One process warms the cell's programs once, then offers each rate in
turn for ``--seconds`` and prints one JSON line per rate: the offered and
achieved rates (answers by the close over the window), the backlog
(requests due but not answered) at the close and its mean over each half
of the window, and the median and 95th-percentile latency. The chosen
cell rate is written into the traffic file by hand, with the sweep's
points in PERF.md; the benchmark's own runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import drive  # noqa: E402
import e2e  # noqa: E402
import harness  # noqa: E402
import traffic as traffic_gen  # noqa: E402


def backlog(due, seen, t) -> int:
    return int((due <= t).sum() - (seen <= t).sum())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    import jax
    import numpy as np

    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    compiles = drive.CompileCounter()
    cfg, mix = cell.config, cell.traffic
    mesh = drive.make_mesh(cell.chips)
    problems = [drive.make_problem(e) for e in cfg["problems"]]
    sched = drive.build_scheduler(cfg, mesh)
    try:
        drive.warm_scheduler(sched, problems, cfg["problems"], cfg)
        for rate in (float(r) for r in args.rates.split(",")):
            arrivals = traffic_gen.open_loop(mix, cfg["problems"],
                                             args.seed, args.seconds, rate)
            handles, due, seen, _, window, n_comp, _ = drive.open_loop(
                sched, problems, arrivals, args.seconds,
                int(cfg["max_iters"]), compiles)
            t0, close = due[0], due[0] + args.seconds
            answered = np.array([h.done() and h.error is None
                                 for h in handles]) & ~np.isnan(seen)
            lat = e2e.latencies(due, seen, answered, close, drive.GRACE_S)
            s = np.where(np.isnan(seen), np.inf, seen)
            grid = np.linspace(t0, close, 41)
            series = [backlog(due, s, t) for t in grid]
            slope = np.polyfit(grid[20:] - t0, series[20:], 1)[0]
            print(json.dumps({
                "offered_per_s": rate,
                "achieved_per_s": float((answered & (s <= close)).sum()
                                        / args.seconds),
                "backlog_at_close": backlog(due, s, close),
                "backlog_mean_first_half": float(np.mean(series[:21])),
                "backlog_mean_second_half": float(np.mean(series[20:])),
                "backlog_slope_second_half_per_s": float(slope),
                "latency_p50_ms": 1e3 * e2e.nearest_rank(lat, 50),
                "latency_p95_ms": 1e3 * e2e.nearest_rank(lat, 95),
                "failed": int((~answered).sum()),
                "waves": window["waves"],
                "bucket_fill": (window["slots"] - window["padded_slots"])
                / max(1, window["slots"]),
                "programs_built": n_comp,
                "wall_s": time.perf_counter() - t0}), flush=True)
    finally:
        sched.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
