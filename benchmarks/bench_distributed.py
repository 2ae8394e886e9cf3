"""Distributed-engine benchmark: the on-device while_loop vs batched
multi-start, on the paper's n=9 problem (bits=7 -> N=63, 125 children —
the config that filled MP-1's 128 PEs).

``device_loop`` is ``Distributed()``: the entire loop is one
``lax.while_loop`` inside ``shard_map``; one dispatch per optimization.
Plus ``Batched`` with R=8 restarts (one compiled loop for the whole batch)
against R * single-run wall-clock, the ``Sequential`` strategy as the
absolute baseline, and a chained-vs-folded resolution-schedule comparison:
``Distributed(max_bits=...)`` folds the paper's step-5 escalation into ONE
compiled dispatch, measured against the pre-PR form (one fixed-resolution
engine dispatched per resolution, parent re-encoded on the host between
them) so the dispatch-overhead claim is a column, not an assertion. Emits
``BENCH_distributed.json``:

  PYTHONPATH=src python benchmarks/bench_distributed.py [--fast]

Run standalone it forces a ``DGO_HOST_DEVICES`` (default 8) virtual-device
CPU mesh; under an explicit ``XLA_FLAGS`` device count — e.g. wrapped by
``python -m repro.launch.launcher --devices N -- ...`` — it uses whatever
devices exist.
"""
from __future__ import annotations

import os

if __name__ == "__main__" and "xla_force_host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count="
        + os.environ.get("DGO_HOST_DEVICES", "8")).strip()

import time

import jax
import jax.numpy as jnp
import numpy as np

N_VARS = 9          # the paper's large problem
BITS = 7            # 63-bit string -> 125 children (fills 128 PEs)
MAX_ITERS = 64
N_RESTARTS = 8
SCHED_MAX_BITS = 11  # folded-vs-chained schedule: (7, 9, 11)


def _median_time(fn, reps: int) -> float:
    fn()                                  # compile / warm caches
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def run(fast: bool = True):
    from repro.compat import AxisType, make_mesh
    from repro.core import cache
    from repro.core.encoding import decode
    from repro.core.solver import (
        Batched,
        Distributed,
        Fused,
        Problem,
        Sequential,
        solve,
    )

    reps = 5 if fast else 20
    n_dev = jax.device_count()
    mesh = make_mesh((n_dev,), ("data",), axis_types=(AxisType.Auto,))
    cache.clear()   # cold start so the emitted cache stats cover this run
    #               (before Problem.get: the objective-defaults and
    #                population-table memo rows should see the build too)
    problem = Problem.get("quadratic", n=N_VARS)
    enc = problem.encoding.with_bits(BITS)
    problem = problem.replace(encoding=enc)
    obj_fn = problem.fn
    x0 = jnp.full((N_VARS,), 5.0)

    # --- absolute baseline: numpy one-child-at-a-time -----------------------
    t0 = time.perf_counter()
    seq = solve(problem, Sequential(max_bits=BITS), x0=np.asarray(x0),
                max_iters=MAX_ITERS)
    t_seq = time.perf_counter() - t0

    # --- device_loop: the on-device while_loop engine -----------------------
    def device_loop():
        return solve(problem, Distributed(mesh=mesh), x0=x0,
                     max_iters=MAX_ITERS)

    t_dev = _median_time(device_loop, reps)
    r_dev = device_loop()
    iters = r_dev.iterations

    # --- batched multi-start (R restarts, one compiled loop) ----------------
    x0s = x0[None] + jnp.linspace(-1.0, 1.0, N_RESTARTS)[:, None]

    def batched():
        return solve(problem, Batched(mesh=mesh), x0=x0s,
                     max_iters=MAX_ITERS)

    t_batched = _median_time(batched, reps)
    res = batched().extras
    assert bool(jnp.all(res["values"] <= res["trace"][:, 0] + 1e-6))

    ips_dev = iters / t_dev
    # sustained throughput: total population steps the on-device engine
    # executes per second across concurrent restarts — the population-of-
    # runs metric the distributed-GA literature calls for (PAPERS "A
    # Fresh Approach to Evaluate Performance in Distributed Parallel
    # Genetic Algorithms")
    total_batched_iters = int(jnp.sum(res["restart_iterations"]))
    ips_dev_sustained = total_batched_iters / t_batched

    # --- resolution schedule: folded (one dispatch) vs chained (pre-PR) ----
    schedule = tuple(range(BITS, SCHED_MAX_BITS + 1, 2))

    def folded_schedule():
        return solve(problem, Distributed(mesh=mesh,
                                          max_bits=SCHED_MAX_BITS),
                     x0=x0, max_iters=MAX_ITERS)

    def chained_schedule():
        """The removed facade-level chaining loop: one engine dispatch per
        resolution, parent re-encoded on the host between them."""
        x = x0
        best = np.inf
        for b in schedule:
            enc_b = enc.with_bits(b)
            r = solve(problem.replace(encoding=enc_b),
                      Distributed(mesh=mesh), x0=x, max_iters=MAX_ITERS)
            best = min(best, float(r.best_f))
            x = decode(r.extras["bits"], enc_b)
        return best

    t_folded = _median_time(folded_schedule, reps)
    t_chained = _median_time(chained_schedule, reps)
    r_folded = folded_schedule()
    v_chained = chained_schedule()
    assert r_folded.extras["schedule"] == schedule
    assert np.isclose(float(r_folded.best_f), v_chained, atol=1e-6), \
        (float(r_folded.best_f), v_chained)

    # --- fused engine width: single compilation vs coarse/fine buckets ------
    # a (3..11)-bit schedule so a coarse bucket exists (resolutions at
    # <= half the final width run at their own buffer width); same
    # trajectory either way — asserted bitwise
    prob_wide = problem.replace(encoding=enc.with_bits(3))
    x0_f = jnp.asarray(x0, jnp.float32)

    def fused_single():
        return solve(prob_wide, Fused(max_bits=SCHED_MAX_BITS), x0=x0_f,
                     max_iters=MAX_ITERS)

    def fused_bucketed():
        return solve(prob_wide,
                     Fused(max_bits=SCHED_MAX_BITS, bucketed=True),
                     x0=x0_f, max_iters=MAX_ITERS)

    t_fused = _median_time(fused_single, reps)
    t_fused_b = _median_time(fused_bucketed, reps)
    r_fused, r_fused_b = fused_single(), fused_bucketed()
    assert float(r_fused.best_f) == float(r_fused_b.best_f), \
        (float(r_fused.best_f), float(r_fused_b.best_f))
    assert np.array_equal(r_fused.trace, r_fused_b.trace)

    cstats = cache.totals(suffix=".engine")   # engine compilations only
    #         (memo tables like solver.problem are excluded, so these
    #          rows keep meaning "compiled engines" as the notes say)
    rows = [
        ("bench_distributed.sequential_wall_s", t_seq,
         "Sequential strategy end-to-end (numpy baseline)"),
        ("bench_distributed.iterations", iters,
         "population steps to convergence"),
        ("bench_distributed.device_loop_wall_s", t_dev,
         "Distributed(): one lax.while_loop dispatch per optimization"),
        ("bench_distributed.device_loop_iters_per_s", ips_dev,
         "iteration throughput of the on-device engine"),
        ("bench_distributed.device_sustained_iters_per_s", ips_dev_sustained,
         f"AGGREGATE population steps/s across {N_RESTARTS} concurrent "
         "restarts in ONE on-device while_loop"),
        ("bench_distributed.speedup_device_vs_sequential", t_seq / t_dev,
         "wall-clock vs the sequential baseline"),
        ("bench_distributed.batched_r8_wall_s", t_batched,
         f"Batched strategy, R={N_RESTARTS} restarts, one dispatch"),
        ("bench_distributed.batched_over_single", t_batched / t_dev,
         "batched wall / single-run wall (< 2x target: R runs for the "
         "dispatch+sync cost of ~one)"),
        ("bench_distributed.batched_runs_per_s", N_RESTARTS / t_batched,
         "completed optimizations per second in the batched path"),
        ("bench_distributed.schedule_chained_wall_s", t_chained,
         f"pre-PR resolution chaining: {len(schedule)} engine dispatches "
         f"(one per resolution), host re-encode between them"),
        ("bench_distributed.schedule_folded_wall_s", t_folded,
         "folded on-device schedule: the SAME escalation in ONE compiled "
         "dispatch (stacked tables + resolution counter in the while_loop)"),
        ("bench_distributed.speedup_folded_vs_chained",
         t_chained / t_folded,
         "dispatch-overhead saving of folding the schedule on device "
         "(same trajectory — asserted — so the ratio is pure dispatch/"
         "re-encode overhead)"),
        ("bench_distributed.fused_single_wall_s", t_fused,
         "fused engine, 3..11-bit schedule, ONE compilation at max width"),
        ("bench_distributed.fused_bucketed_wall_s", t_fused_b,
         "same schedule in TWO width buckets (coarse resolutions at "
         "their own buffer width; trajectory bitwise-asserted)"),
        ("bench_distributed.fused_bucketed_over_single",
         t_fused / t_fused_b,
         "UNGATED: >1 means the width buckets pay for their extra "
         "dispatch; tiny shapes on a time-sliced container understate "
         "the coarse-phase saving"),
        # compilation-cache health (core/cache.py): engines_built should
        # stay flat across PRs for this fixed workload — a jump means a
        # cache key started churning (recompile regression); hits growing
        # with reps is the steady-state serving property
        ("bench_distributed.cache_engines_built", cstats["built"],
         "distinct engine compilations paid for during this bench"),
        ("bench_distributed.cache_hits", cstats["hits"],
         "compiled-engine reuses across reps"),
        ("bench_distributed.cache_misses", cstats["misses"],
         "cache misses (hashable keys compiled + stored)"),
        ("bench_distributed.cache_uncached", cstats["uncached"],
         "unhashable-key builds (should be 0 for registry objectives)"),
    ]
    # memo-table health: the host-side table/introspection memos that used
    # to hide behind lru_cache (migrated in the dgolint PR) — misses flat
    # across PRs for this fixed workload, hits >> misses once warm
    all_stats = cache.stats()
    for short, cname in (("population_tables", "population.tables"),
                         ("objective_defaults",
                          "objectives.factory_defaults")):
        st = all_stats.get(cname, {})
        rows.append((f"bench_distributed.cache_{short}_misses",
                     st.get("misses", 0),
                     f"distinct {cname} memo entries built this run"))
        rows.append((f"bench_distributed.cache_{short}_hits",
                     st.get("hits", 0),
                     f"{cname} memo reuses this run"))
    return rows


if __name__ == "__main__":
    import argparse

    try:
        from benchmarks.bench_speedup import write_json
    except ImportError:       # invoked as a script, not a module
        from bench_speedup import write_json

    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--json", default="BENCH_distributed.json",
                    help="path for the machine-readable artifact "
                         "('' disables)")
    args = ap.parse_args()
    rows = run(fast=args.fast)
    for name, val, note in rows:
        print(f"{name},{val},{note}")
    if args.json:
        write_json(rows, args.json, bench="distributed")
