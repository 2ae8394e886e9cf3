"""Bench-regression gate: compare freshly measured BENCH_*.json against the
committed baselines and fail CI on a real slowdown.

Only RATIO metrics are gated (speedups and normalized overheads): ratios of
two timings taken on the same box in the same run largely cancel machine
speed, so they are comparable between a CI runner and the box that blessed
the baseline.  Wall-clock rows (``*_wall_s``, ``*_s_per_iter``, ...) and
modeled curves (``modeled_pe*``) are reported but exempt — absolute seconds
on shared runners are noise, and the model is not a measurement.

Policy (recorded in ROADMAP.md):

* a gated higher-is-better metric fails when ``fresh < baseline / tol``
  (default ``tol`` 1.5: a >1.5x slowdown of the ratio);
* a gated lower-is-better metric fails when ``fresh > baseline * tol``;
* a gated metric missing from the fresh run fails (silently dropping a
  measurement is itself a regression); one missing from the baseline is
  skipped with a note (it is new — bless it by committing the fresh file);
* REQUIRED metrics (``REQUIRED`` below, e.g. the serving p99 latency)
  must be PRESENT in the fresh run but are never value-gated — they are
  absolute seconds that do not transfer across machines, yet the
  artifact dropping them would regress every consumer silently;
* to bless a new baseline, re-run the bench and commit the JSON it emits
  (CI regenerates into ``bench-out/`` and never touches the baseline).

Usage::

    python benchmarks/check_regression.py \
        --baseline BENCH_distributed.json --fresh bench-out/BENCH_distributed.json
"""
from __future__ import annotations

import argparse
import json
import math
import sys

# gated metrics per bench family: name -> "higher" | "lower" (better)
GATED = {
    "speedup": {
        "bench_speedup.simd_speedup": "higher",
        "bench_speedup.fused_engine_speedup_n3": "higher",
        "bench_speedup.fused_engine_speedup_n5": "higher",
        "bench_speedup.fused_engine_speedup_n9": "higher",
    },
    # speedup_device_vs_sequential is reported but NOT gated: its
    # denominator is the numpy sequential loop, so the ratio compares
    # different substrates and does not cancel machine speed (measured
    # 17.8 vs 53.3 across environments with identical code) — same
    # rationale as the wall-clock exemption
    "distributed": {
        "bench_distributed.speedup_folded_vs_chained": "higher",
        "bench_distributed.batched_over_single": "lower",
    },
    "subspace": {
        "bench_subspace.wave_over_sequential": "higher",
    },
    "serving": {
        "bench_serving.bucketed_over_per_request": "higher",
        "bench_serving.degraded_over_bucketed": "higher",
        # pipelined vs synchronous drain: ~1.0 on single-core runners
        # (host assembly and device compute share the core), >1 with
        # real parallel hardware — gated so the pipeline can't silently
        # regress below its committed baseline either way
        "bench_serving.pipelined_over_synchronous": "higher",
    },
    "scaling": {
        # mesh-size invariance is a hard correctness property: the
        # reference trajectory must stay bitwise identical at every
        # swept virtual-device count (1.0 = match; any drop fails)
        "bench_scaling.trajectory_bitwise_match": "higher",
        # the folded-vs-chained dispatch ratio at the reference 8-device
        # mesh — the one point every sweep profile contains; the per-size
        # speedup_vs_pe* and parallel_fraction rows are trend-reported
        # only (virtual devices time-slice the same cores, so cross-size
        # wall ratios do not transfer)
        "bench_scaling.pe8_speedup_folded_vs_chained": "higher",
    },
}

# REQUIRED metrics per bench family: presence-asserted in the fresh run
# but NOT value-gated — they are absolute measurements (seconds) that do
# not transfer across machines, yet silently dropping them from the
# artifact is itself a regression (dashboards and the ROADMAP tail-latency
# criterion consume them)
REQUIRED = {
    "serving": ["bench_serving.p99_latency_s"],
    "scaling": ["bench_scaling.pe8_folded_wall_s",
                "bench_scaling.pe8_wave_runs_per_s"],
}


def load(path: str) -> dict:
    with open(path) as fh:
        payload = json.load(fh)
    if "bench" not in payload or "metrics" not in payload:
        raise SystemExit(f"{path}: not a BENCH_*.json artifact "
                         f"(missing 'bench'/'metrics')")
    return payload


def check(baseline: dict, fresh: dict, tolerance: float) -> list[str]:
    """Return a list of failure messages (empty == gate passes)."""
    family = fresh["bench"]
    if baseline["bench"] != family:
        return [f"bench family mismatch: baseline={baseline['bench']!r} "
                f"fresh={family!r}"]
    failures = []
    for name in REQUIRED.get(family, ()):
        row = fresh["metrics"].get(name)
        # a present-but-NaN/inf value is as useless to every consumer as
        # a missing one: treat non-finite as absent
        if (row is None or row.get("value") is None
                or not math.isfinite(float(row["value"]))):
            failures.append(f"{name}: REQUIRED metric absent from fresh "
                            f"run (presence-asserted, not value-gated)")
        else:
            print(f"  ok   {name} [required, ungated]: "
                  f"{float(row['value']):.6f}")
    gated = GATED.get(family)
    if gated is None:
        if failures:
            return failures
        print(f"  (no gated metrics for bench family {family!r}; pass)")
        return []
    for name, direction in sorted(gated.items()):
        base_row = baseline["metrics"].get(name)
        fresh_row = fresh["metrics"].get(name)
        if base_row is None:
            print(f"  SKIP {name}: not in baseline (new metric — bless it "
                  f"by committing the fresh JSON)")
            continue
        if fresh_row is None:
            failures.append(f"{name}: gated metric missing from fresh run")
            continue
        base, new = float(base_row["value"]), float(fresh_row["value"])
        if direction == "higher":
            ok = new >= base / tolerance
            verdict = (f"{new:.3f} vs baseline {base:.3f} "
                       f"(floor {base / tolerance:.3f})")
        else:
            ok = new <= base * tolerance
            verdict = (f"{new:.3f} vs baseline {base:.3f} "
                       f"(ceiling {base * tolerance:.3f})")
        status = "ok  " if ok else "FAIL"
        print(f"  {status} {name} [{direction} better]: {verdict}")
        if not ok:
            failures.append(f"{name}: {verdict}")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", required=True,
                    help="committed BENCH_*.json to compare against")
    ap.add_argument("--fresh", required=True,
                    help="freshly produced BENCH_*.json from this run")
    ap.add_argument("--tolerance", type=float, default=1.5,
                    help="allowed ratio-metric degradation factor "
                         "(default 1.5 = fail on >1.5x slowdown)")
    args = ap.parse_args(argv)
    if args.tolerance <= 1.0:
        ap.error("--tolerance must be > 1.0")

    baseline, fresh = load(args.baseline), load(args.fresh)
    print(f"regression gate: {args.fresh} vs {args.baseline} "
          f"(tolerance {args.tolerance}x)")
    failures = check(baseline, fresh, args.tolerance)
    if failures:
        print(f"\nREGRESSION GATE FAILED ({len(failures)}):")
        for msg in failures:
            print(f"  - {msg}")
        print("If the slowdown is expected and understood, bless a new "
              "baseline by committing the fresh JSON (see ROADMAP.md).")
        return 1
    print("regression gate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
