"""Batched serving driver: prefill + decode loop over synthetic requests.

  PYTHONPATH=src python -m repro.launch.serve --arch zamba2-1.2b --reduced \\
      --batch 4 --prompt-len 32 --gen-len 16

Continuous-batching-lite: requests arrive in waves; each wave is prefilled
as a batch and decoded token-by-token (greedy); throughput reported as
decode tokens/s. The production-mesh serving path (TP-sharded params,
batch-sharded cache, sequence-parallel long-context) is what dryrun.py
lowers for the decode_32k / long_500k cells.

DGO optimization-serving path — a thin CLI over ``repro.serving``
(RequestQueue + signature-bucketed Scheduler + ``solve_many``):

  # open-loop arrival simulation: Poisson arrivals at --rps for --duration
  # seconds, a mixed workload of problems, p50/p95 latency + runs/s out
  PYTHONPATH=src python -m repro.launch.serve --dgo \\
      --problems rastrigin:2,shekel,ackley:5 --rps 20 --duration 5

  # closed-loop waves (the legacy shape): submit restarts*waves requests,
  # drain the queue
  PYTHONPATH=src python -m repro.launch.serve --dgo --problem rastrigin \\
      --n-vars 2 --restarts 8 --waves 2

``--problems`` takes ``name[:n_vars]`` specs, comma-separated; every name
comes from the objective registry (``repro.core.objectives.names()``) and
is validated HERE, at the CLI boundary — an unknown name, a bad variable
count, or ``n`` passed to a fixed-dimensional objective exits with the
valid names/range instead of erroring deep inside a solve.  The scheduler
buckets queued requests by engine signature, pads each bucket to
``--restarts`` slots with inactive lanes, and dispatches it as ONE
compiled on-device while_loop; per-request results are bitwise what
individual solves would return.

Serving is PIPELINED by default (``serving.PipelinedScheduler``): a
dispatch worker finalizes the in-flight wave while the serving thread
assembles and submits the next one, and open-loop arrivals run on their
own thread so submission timing is never perturbed by dispatch.
``--no-pipeline`` restores the synchronous scheduler;
``--max-in-flight`` sets the pipeline depth (2 = double-buffering).
See docs/architecture.md for the thread model and
docs/serving-ops.md for the operator runbook.

Model-zoo tuning is served through the same loop: ``subspace-lm:<arch>``
names (e.g. ``--problems subspace-lm:xlstm-125m,rastrigin:2``) are
subspace-DGO tuning problems over ``configs.reduced`` zoo models — an
expensive batched objective whose requests bucket by their semantic
(arch, d, bits, ...) signature.  ``--ckpt-dir`` persists each tuning
problem's winner parameters through the atomic checkpoint store.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import REGISTRY, get_arch, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_model, lm_decode, lm_prefill


# upper bound on --n-vars accepted at the CLI: the population is
# 2*n_vars*bits-1 children per step — beyond this the wave would not fit
# a sane demo budget (the library itself has no hard cap)
MAX_CLI_N_VARS = 1024


def _parse_problem_specs(args) -> list:
    """Resolve ``--problems name[:n],...`` (or legacy ``--problem`` +
    ``--n-vars``) into Problem instances, validating at the CLI boundary.

    ``Problem.get`` memoizes per spec, so every request of a spec (and
    duplicate specs) shares ONE Problem instance — engine signatures key
    on the objective callable, so rebuilding per request would defeat
    both bucketing and the compile cache.
    """
    from repro.core.solver import Problem

    specs: list[tuple[str, int | None]] = []
    if args.problems:
        for item in args.problems.split(","):
            item = item.strip()
            if not item:
                continue
            # the trailing :n is optional AND registry names may contain
            # ":" themselves (subspace-lm:xlstm-125m), so split from the
            # right and only treat an integer tail as a variable count
            name, sep, n_str = item.rpartition(":")
            if sep and n_str.lstrip("-").isdigit():
                specs.append((name, int(n_str)))
            else:
                specs.append((item, None))
    else:
        specs.append((args.problem, args.n_vars))

    if not specs:
        raise SystemExit("--problems: no problem specs given "
                         "(want comma-separated name[:n_vars])")
    problems = []
    for name, n in specs:
        if n is not None and not 1 <= n <= MAX_CLI_N_VARS:
            raise SystemExit(
                f"--problems: n_vars for {name!r} must be in "
                f"[1, {MAX_CLI_N_VARS}], got {n}")
        try:
            problems.append(Problem.get(name, n=n))
        except ValueError as e:
            raise SystemExit(f"--problems: {e}")
    return problems


def _make_fault_plan(args):
    """The CLI's chaos knobs -> a seeded ``runtime.failure.FaultPlan``
    (None when no injection was asked for) — degraded-mode serving runs
    the same fault model as the chaos tests and the bench."""
    if not (args.fault_rate or args.fault_latency_rate):
        return None
    from repro.runtime.failure import FaultPlan

    return FaultPlan(seed=args.fault_seed,
                     dispatch_error_rate=args.fault_rate,
                     latency_rate=args.fault_latency_rate)


def _build_scheduler(args, problems):
    from repro.serving import PipelinedScheduler, RequestQueue, Scheduler

    queue = RequestQueue(capacity=args.capacity, admission=args.admission)
    # mesh=None -> the library's shared default (all local devices on
    # ("data",)) — one source of truth for the serving geometry
    kwargs = dict(wave_size=args.restarts,
                  max_bits=args.max_bits,
                  max_retries=args.max_retries,
                  retry_backoff_s=args.retry_backoff_s,
                  faults=_make_fault_plan(args))
    if args.no_pipeline:
        sched = Scheduler(queue, **kwargs)
    else:
        sched = PipelinedScheduler(queue, max_in_flight=args.max_in_flight,
                                   **kwargs)
    sched.warmup(problems, max_iters=args.max_iters)
    return sched


def _persist_winners(ckpt_dir: str, handles, submitted: int) -> list[str]:
    """Persist the best materializable result per problem: the winning z
    of each ``subspace-lm:*`` tuning problem is mapped back to concrete
    model parameters (``Problem.materialize`` ->
    ``core.subspace.materialize_winner``) and written through the atomic
    keep-k checkpoint store.  Returns the checkpoint paths written."""
    from pathlib import Path

    from repro.checkpoint.store import save_checkpoint

    winners: dict[str, tuple[float, object, object]] = {}
    for h in handles:
        if not (h.done() and h.error is None):
            continue
        prob = h.request.problem
        if getattr(prob, "materialize", None) is None:
            continue
        res = h.result()
        f = float(res.best_f)
        if prob.name not in winners or f < winners[prob.name][0]:
            winners[prob.name] = (f, prob, res)
    paths = []
    for name, (_, prob, res) in sorted(winners.items()):
        params = prob.materialize(res.best_x)
        sub = name.replace(":", "__").replace("/", "__")
        path = save_checkpoint(Path(ckpt_dir) / sub, step=submitted,
                               tree=params)
        paths.append(str(path))
    return paths


def _report(sched, problems, best: float, wall_s: float,
            checkpoints: list[str] | None = None) -> dict:
    from repro.core import cache

    m = sched.metrics()

    def _ms(key):
        return round(m[key], 1) if m[key] is not None else None

    # engine caches only: memo tables (solver.problem) would otherwise
    # inflate "engines built"/"hits" by one per request spec/submission
    eng = cache.totals(suffix=".engine")
    out = {
        "problems": [p.name for p in problems],
        "completed": m["completed"],
        "failed": m["failed"],
        "requeued": m["requeued"],
        # lifecycle counters: deadline expiries + admission-control drops
        # (rejected raises at submit, shed evicts queued victims)
        "expired": m["expired"],
        "rejected": m["rejected"],
        "shed": m["shed"],
        "runs_per_s": (round(m["completed"] / wall_s, 1)
                       if wall_s > 0 else None),
        "latency_p50_ms": _ms("latency_p50_ms"),
        "latency_p95_ms": _ms("latency_p95_ms"),
        "latency_p99_ms": _ms("latency_p99_ms"),
        "waves": m["waves"],
        "bucket_fill": (round(m["fill_fraction"], 3)
                        if m["fill_fraction"] is not None else None),
        "cache_engines_built": eng["built"],
        "cache_hits": eng["hits"],
        "cache_evictions": m["cache_evictions"],
        "best_value": None if best == float("inf") else best,
        "checkpoints": checkpoints or [],
    }
    if "fault_injections" in m:
        out["fault_injections"] = m["fault_injections"]
    print(json.dumps(out))
    return out


def _run_serving_loop(args, problems, rps: float | None,
                      n_requests: int | None = None):
    """One serving run: open loop at ``rps`` (Poisson arrivals for
    ``--duration`` seconds) or, with ``rps=None``, closed loop
    (``n_requests`` requests up front, ``restarts * waves`` by default;
    request ``i`` is ``problems[i % len(problems)]`` with seed
    ``--seed + i``).  Returns ``(sched, handles, wall_s, submitted)``."""
    import numpy as np

    from repro.core.solver import SolveRequest
    from repro.serving import QueueFull

    sched = _build_scheduler(args, problems)
    rng = np.random.default_rng(args.seed)
    submitted = 0
    handles = []

    def submit_next(arrived_at: float | None = None):
        nonlocal submitted
        prob = problems[submitted % len(problems)]
        req = SolveRequest(prob, seed=args.seed + submitted,
                           max_iters=args.max_iters,
                           deadline_s=args.deadline_s)
        submitted += 1
        try:
            h = sched.submit(req)
        except QueueFull:
            # admission control refused the arrival — the queue counted
            # it (rejected/shed); an open-loop client just moves on
            return
        if arrived_at is not None:
            # open-loop discipline: latency counts from the simulated
            # ARRIVAL, not from when the loop got around to submitting —
            # arrivals during a blocking dispatch must still pay their
            # queueing delay (no coordinated omission)
            h.submitted_at = arrived_at
            if h.deadline_at is not None:
                h.deadline_at = arrived_at + args.deadline_s
        handles.append(h)

    t_start = time.perf_counter()
    try:
        if rps is not None:
            t_end = t_start + args.duration
            stop = threading.Event()

            def arrivals():
                # the arrival clock lives on its OWN thread so submission
                # timing is never perturbed by dispatch: a wave blocking
                # the serving thread cannot delay (or batch up) arrivals
                next_arrival = t_start
                while next_arrival < t_end and not stop.is_set():
                    now = time.perf_counter()
                    if next_arrival > now:
                        time.sleep(min(next_arrival - now, 0.01))
                        continue
                    submit_next(arrived_at=next_arrival)
                    next_arrival += rng.exponential(1.0 / rps)

            arr = threading.Thread(target=arrivals, name="dgo-arrivals",
                                   daemon=True)
            arr.start()
            try:
                # serve while arrivals flow: step() is one non-blocking
                # pump on the pipelined scheduler (one blocking wave on
                # --no-pipeline); idle ticks yield to the arrival thread
                while arr.is_alive() or len(sched.queue):
                    if not sched.step():
                        time.sleep(0.001)
            finally:
                stop.set()
                arr.join()
            sched.drain()
        else:
            if n_requests is None:
                n_requests = args.restarts * args.waves
            for _ in range(n_requests):
                submit_next()
            sched.drain()
        wall_s = time.perf_counter() - t_start
    finally:
        sched.close()
    return sched, handles, wall_s, submitted


def _warn_unwritable_tile_cache() -> None:
    """Surface (once, at startup) a ``REPRO_POPSTEP_TILE_CACHE`` pointing
    at an unwritable location.  The popstep autotuner tolerates the
    failed write silently — correct for the hot path — but an operator
    who set the env var expects persistence, and without this warning
    the only symptom is a re-tune on every process start."""
    target = os.environ.get("REPRO_POPSTEP_TILE_CACHE")
    if not target:
        return
    probe = Path(target)
    # writability of the file == writability of the nearest existing
    # ancestor (the autotuner creates missing parent dirs); an ancestor
    # that exists but is a regular file blocks creation outright
    anc = probe if probe.exists() else probe.parent
    while not anc.exists() and anc != anc.parent:
        anc = anc.parent
    if anc == probe:
        writable = os.access(probe, os.W_OK)
    elif anc.is_dir():
        writable = os.access(anc, os.W_OK | os.X_OK)
    else:
        writable = False
    if not writable:
        print(f"warning: REPRO_POPSTEP_TILE_CACHE={target!r} is not "
              f"writable ({anc} denies write access); tile autotune "
              f"results will stay in-process only and every restart "
              f"re-tunes. Fix the path/permissions, or unset the "
              f"variable to accept the in-process cache (suppression "
              f"policy: README 'Static analysis' / tools/dgolint).",
              file=sys.stderr)


def serve_dgo(args) -> None:
    """Serve DGO requests through the serving subsystem.

    Open loop (``--rps``/``--duration``): requests arrive on a Poisson
    clock independent of service progress (arrival times never wait on
    dispatches — the open-loop discipline the distributed-GA serving
    literature measures under); the scheduler serves signature buckets
    whenever work is queued.  Closed loop (``--waves``): submit
    ``restarts * waves`` requests up front and drain.  ``--sweep-rps``
    runs the open loop once per arrival rate (saturation sweep): as the
    offered load crosses the service capacity, queueing delay — and
    with ``--deadline-s``/``--capacity``, expiries and admission drops —
    shows up in the per-point p99 before throughput degrades.
    """
    if args.rps is not None and args.rps <= 0:
        raise SystemExit(f"--rps must be > 0, got {args.rps}")
    if (args.rps is not None or args.sweep_rps) and args.duration <= 0:
        raise SystemExit(f"--duration must be > 0, got {args.duration}")
    _warn_unwritable_tile_cache()
    problems = _parse_problem_specs(args)

    if args.sweep_rps:
        try:
            points = [float(s) for s in args.sweep_rps.split(",") if s]
        except ValueError:
            raise SystemExit(f"--sweep-rps: want comma-separated rates, "
                             f"got {args.sweep_rps!r}")
        if not points or any(p <= 0 for p in points):
            raise SystemExit(f"--sweep-rps: rates must be > 0, "
                             f"got {args.sweep_rps!r}")
        sweep = []
        for rps in points:
            sched, handles, wall_s, submitted = _run_serving_loop(
                args, problems, rps)
            best = min((float(h.result().best_f) for h in handles
                        if h.done() and h.error is None),
                       default=float("inf"))
            row = _report(sched, problems, best, wall_s)
            row["rps"] = rps
            row["offered_rps"] = rps
            row["achieved_rps"] = row["runs_per_s"]
            # a point saturates when the queue backlogs faster than the
            # service drains it: the run then needs a drain tail well
            # past the arrival window to finish what arrived (a short
            # tail — the in-flight waves — is normal at any load)
            row["drain_tail_s"] = round(max(wall_s - args.duration, 0.0), 3)
            row["saturated"] = wall_s > 1.15 * args.duration
            row["submitted"] = submitted
            sweep.append(row)
        _exit_on_failures(args, sum(r["failed"] for r in sweep))
        unsat = [r["offered_rps"] for r in sweep if not r["saturated"]]
        achieved = [r["achieved_rps"] for r in sweep
                    if r["achieved_rps"] is not None]
        print(json.dumps({
            "sweep_rps": points,
            # the saturation knee: the highest offered rate the service
            # still kept up with, and the throughput ceiling it pinned
            # at beyond that (the Amdahl-style serial-fraction readout —
            # see docs/serving-ops.md for reading these)
            "knee_rps": max(unsat) if unsat else None,
            "capacity_rps": max(achieved) if achieved else None,
            "sweep": sweep,
        }))
        return

    sched, handles, wall_s, submitted = _run_serving_loop(
        args, problems, args.rps)
    best = min((float(h.result().best_f) for h in handles
                if h.done() and h.error is None), default=float("inf"))
    checkpoints = (_persist_winners(args.ckpt_dir, handles, submitted)
                   if args.ckpt_dir else None)
    out = _report(sched, problems, best, wall_s, checkpoints)
    _exit_on_failures(args, out["failed"])


def _exit_on_failures(args, failed: int) -> None:
    """A failed request is an error of the run unless faults were
    injected on purpose (``--fault-rate`` / ``--fault-latency-rate``)."""
    if failed and _make_fault_plan(args) is None:
        raise SystemExit(f"serve: {failed} request(s) failed with no "
                         f"fault injection asked for")


def build_parser() -> argparse.ArgumentParser:
    """The serve CLI's argument parser (LM decode and ``--dgo``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(REGISTRY))
    ap.add_argument("--dgo", action="store_true",
                    help="serve DGO optimization requests (via the "
                         "repro.serving scheduler) instead of LM decode")
    ap.add_argument("--problem", default="rastrigin",
                    help="objective registry name (see "
                         "repro.core.objectives.names()); unknown names "
                         "exit with the valid list")
    ap.add_argument("--n-vars", type=int, default=None,
                    help="variable count for dimensioned objectives "
                         "(quadratic/rastrigin/ackley/griewank); omit for "
                         "fixed-dimensional ones (shekel, xor, ...)")
    ap.add_argument("--problems", default=None,
                    help="mixed workload as comma-separated name[:n_vars] "
                         "specs, e.g. rastrigin:2,shekel,ackley:5 "
                         "(overrides --problem/--n-vars)")
    ap.add_argument("--rps", type=float, default=None,
                    help="open-loop mode: mean Poisson arrival rate "
                         "(requests/s); requires --duration")
    ap.add_argument("--duration", type=float, default=5.0,
                    help="open-loop mode: seconds of simulated arrivals")
    ap.add_argument("--sweep-rps", default=None,
                    help="saturation sweep: comma-separated arrival rates "
                         "(e.g. 10,20,40,80), one open-loop run of "
                         "--duration seconds each; emits per-point "
                         "p50/p95/p99 + lifecycle counters and a final "
                         "summary JSON line with the saturation knee "
                         "(knee_rps / capacity_rps)")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="serve with the synchronous Scheduler instead of "
                         "the default PipelinedScheduler (one wave in "
                         "flight, host blocks on every dispatch)")
    ap.add_argument("--max-in-flight", type=int, default=2,
                    help="pipelined scheduler: waves in flight before "
                         "submission backpressures (2 = double-buffering)")
    ap.add_argument("--capacity", type=int, default=None,
                    help="bound the request queue (admission control "
                         "kicks in at this backlog; None = unbounded)")
    ap.add_argument("--admission", default="reject",
                    choices=["reject", "shed-lowest-priority", "block"],
                    help="what a full queue does to an arrival")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request TTL: expired requests fail fast "
                         "(DeadlineExceeded) and never occupy a wave slot")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="charged dispatch retries per request before its "
                         "handle fails (DispatchFailed)")
    ap.add_argument("--retry-backoff-s", type=float, default=0.05,
                    help="base exponential backoff per failing signature "
                         "bucket (0 disables)")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="chaos: Bernoulli dispatch-failure rate via a "
                         "seeded runtime.failure.FaultPlan (degraded-mode "
                         "serving)")
    ap.add_argument("--fault-latency-rate", type=float, default=0.0,
                    help="chaos: Bernoulli dispatch latency-spike rate")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the fault plan (decisions are pure "
                         "functions of (seed, kind, index))")
    ap.add_argument("--restarts", type=int, default=8,
                    help="scheduler wave width (requests per dispatch; "
                         "buckets are padded to it with inactive slots)")
    ap.add_argument("--max-iters", type=int, default=64)
    ap.add_argument("--max-bits", type=int, default=None,
                    help="fold a resolution schedule up to this many bits "
                         "into every dispatch (None = fixed resolution)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="persist each tuning problem's winner parameters "
                         "(subspace-lm:* problems) under this directory "
                         "via the checkpoint store")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--waves", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main():
    ap = build_parser()
    args = ap.parse_args()
    enable_compile_cache()

    if args.dgo:
        serve_dgo(args)
        return
    if args.arch is None:
        ap.error("--arch is required unless --dgo is given")

    arch = get_arch(args.arch)
    if args.reduced:
        arch = reduced(arch)
    dtype = jnp.float32
    params = init_model(arch, jax.random.PRNGKey(args.seed), dtype)
    cache_len = args.prompt_len + args.gen_len

    @jax.jit
    def prefill(params, batch):
        return lm_prefill(params, arch, batch, cache_len=cache_len,
                          dtype=dtype)

    @jax.jit
    def decode(params, tok, cache):
        return lm_decode(params, arch, tok, cache, dtype=dtype)

    key = jax.random.PRNGKey(args.seed + 1)
    total_tokens = 0
    t_decode = 0.0
    for wave in range(args.waves):
        key, kw = jax.random.split(key)
        batch = {"tokens": jax.random.randint(
            kw, (args.batch, args.prompt_len), 0, arch.vocab_size)}
        if arch.vision_tokens:
            batch["images"] = 0.02 * jax.random.normal(
                kw, (args.batch, arch.vision_tokens, arch.d_frontend), dtype)
        if arch.enc_dec:
            batch["frames"] = 0.02 * jax.random.normal(
                kw, (args.batch, arch.n_frames, arch.d_model), dtype)
        logits, cache = prefill(params, batch)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        outs = [tok]
        jax.block_until_ready(tok)
        t0 = time.time()
        for _ in range(args.gen_len - 1):
            logits, cache = decode(params, tok, cache)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            outs.append(tok)
        jax.block_until_ready(tok)
        t_decode += time.time() - t0
        total_tokens += args.batch * (args.gen_len - 1)
        seqs = jnp.stack(outs, axis=1)
        assert bool(jnp.all(jnp.isfinite(logits))), "non-finite logits"
        print(f"[serve] wave {wave}: generated {seqs.shape} tokens")

    print(json.dumps({
        "decode_tokens_per_s": round(total_tokens / max(t_decode, 1e-9), 1),
        "total_tokens": total_tokens,
    }))


if __name__ == "__main__":
    main()
