#!/usr/bin/env python3
"""Run the DGO serving path, and its Pallas step, on TPU chips.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the cross-chip mesh path only

One chip: the path ``serve --dgo`` runs (``RequestQueue`` ->
``PipelinedScheduler`` -> ``solve_many`` -> the batched folded-schedule
engine on the default ``("data",)`` mesh), driven through the serve CLI's
own helpers at BBOB-sized problems; then the checks

* every handle completes (none failed, none expired, every result finite);
* every served result is bitwise what the request's own
  ``solve_many([req], pad_to=16)`` returns (the serving contract);
* ``quadratic:9`` reaches the value of the numpy ``Sequential`` reference;
* ``Distributed()`` runs the compiled Pallas ``popstep`` kernel (a
  ``tpu_custom_call`` in its program) and agrees with the XLA inner.

Four chips: ``Distributed(max_bits=16)`` of ``quadratic:9`` and
``rastrigin:40`` and one 16-request ``solve_many`` wave, on the 4-chip
``("data",)`` mesh and on a mesh over the first chip alone; results must
be bitwise equal, and the 4-chip program must gather across all four.

Every check is a hard failure. The script exits non-zero, printing no
result, when JAX finds no TPU. Its last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ANALYTIC = "quadratic:9,rastrigin:40,ackley:20,griewank:10,shekel"
SUBSPACE = "subspace-lm:xlstm-125m"
WAVE = 16              # slots per dispatch (the engine's restart width)
SEEDS_PER_SPEC = 4
MAX_ITERS = 256        # per resolution
MAX_BITS = 16          # schedule enc.bits, +2, ..., 16
# the LM-loss request is the device-heavy one: a 16-slot wave evaluates
# 16 x 767 perturbed models per iteration, so its budget is a few
# iterations per resolution, as a tuning service would give it
SUBSPACE_ITERS = 4

# quadratic:9 against the Sequential reference: both decode the same
# float32 lattice, but the reference evaluates one child per dispatch and
# the engine a batch, and XLA may order the 9-term sum differently for the
# two shapes. Values then differ by float32 ULPs, and a near-tie decided
# by one ULP can move the trajectory by an iteration without changing
# where it stalls (on the CPU the two agree to 2e-6 relative).
SEQUENTIAL_RTOL = 1e-4
# popstep (Mosaic) against the fused XLA inner: same children, same
# decode; the objective is evaluated by two compilers, whose float32
# reductions and reciprocal (shekel's 1/(d+c)) may differ in the last ULP
POPSTEP_RTOL = 1e-5


class SmokeFailure(AssertionError):
    """A check of the smoke failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileCounter:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events (the process's whole lifetime; phases take differences)."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple:
        return self.compiles, self.compile_s, self.cache_hits


@contextlib.contextmanager
def phase(name: str, counter: CompileCounter):
    """Print the phase's status, wall and compile seconds, engine-cache
    totals and device memory; a failure propagates (no carrying on)."""
    import jax

    from repro.core import cache

    c0 = counter.snapshot()
    t0 = time.perf_counter()
    status = "FAIL"
    try:
        yield
        status = "PASS"
    finally:
        c1 = counter.snapshot()
        mem = [d.memory_stats() or {} for d in jax.local_devices()]
        print(json.dumps({
            "phase": name, "status": status,
            "wall_s": time.perf_counter() - t0,
            "compiles": c1[0] - c0[0], "compile_s": c1[1] - c0[1],
            "persistent_cache_hits": c1[2] - c0[2],
            "engines": cache.totals(suffix=".engine"),
            "bytes_in_use": [m.get("bytes_in_use") for m in mem],
            "peak_bytes_in_use": [m.get("peak_bytes_in_use") for m in mem],
        }), flush=True)


def same_bits(a, b) -> bool:
    """Bitwise equality of two arrays (NaN-safe, dtype-exact)."""
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and \
        a.tobytes() == b.tobytes()


def same_result(a, b) -> bool:
    return (same_bits(a.best_x, b.best_x) and same_bits(a.best_f, b.best_f)
            and a.iterations == b.iterations and same_bits(a.trace, b.trace))


def request_x0(prob, seed: int):
    """The start point a request with this seed is served from."""
    import jax

    return prob.random_x0(jax.random.PRNGKey(seed), batch=1)[0]


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def serve_closed_loop(specs: str, n_per_spec: int, max_iters: int) -> list:
    """Serve ``n_per_spec`` seeds of every spec through the CLI's helpers;
    returns the handles after checking the report."""
    from repro.launch import serve

    args = serve.build_parser().parse_args([
        "--dgo", "--problems", specs, "--restarts", str(WAVE),
        "--max-iters", str(max_iters), "--max-bits", str(MAX_BITS),
        "--max-retries", "0"])
    problems = serve._parse_problem_specs(args)
    sched, handles, wall_s, submitted = serve._run_serving_loop(
        args, problems, None, n_requests=n_per_spec * len(problems))
    best = min((float(h.result().best_f) for h in handles
                if h.done() and h.error is None), default=float("inf"))
    report = serve._report(sched, problems, best, wall_s)
    for h in handles:
        if h.error is not None:
            print(f"request {h.seq} failed: {h.error!r} "
                  f"(cause: {h.error.__cause__!r})", file=sys.stderr)
    check(report["failed"] == 0, f"{report['failed']} request(s) failed")
    check(report["expired"] == 0, f"{report['expired']} request(s) expired")
    check(report["completed"] == submitted == len(handles),
          f"completed {report['completed']} of {submitted}")
    check(all(h.result().extras["finite"] for h in handles),
          "a served result is not finite")
    return handles


def check_wave_parity(handles) -> None:
    """Each served result == its own per-request wave, bitwise."""
    from repro.core.solver import solve_many

    for h in handles:
        [ref] = solve_many([h.request], max_bits=MAX_BITS, pad_to=WAVE)
        check(same_result(h.result(), ref),
              f"request {h.seq} ({h.request.problem.name}, seed "
              f"{h.request.seed}) differs from its own solve_many")
    print(f"wave parity: {len(handles)} served results bitwise equal to "
          f"their own solve_many([req], pad_to={WAVE})", flush=True)


def check_sequential(handles) -> None:
    """quadratic:9 against the numpy Sequential reference."""
    import numpy as np

    from repro.core.solver import Sequential, solve

    q9 = [h for h in handles if h.request.problem.name == "quadratic9d"]
    check(len(q9) == SEEDS_PER_SPEC, f"{len(q9)} quadratic:9 requests")
    for h in q9:
        served = h.result()
        x0 = np.asarray(request_x0(h.request.problem, h.request.seed))
        ref = solve(h.request.problem, Sequential(max_bits=MAX_BITS),
                    x0=x0, max_iters=MAX_ITERS)
        f, f_ref = float(served.best_f), float(ref.best_f)
        print(f"quadratic:9 seed {h.request.seed}: served {f!r} in "
              f"{served.iterations} iterations, Sequential {f_ref!r} in "
              f"{ref.iterations}", flush=True)
        check(abs(f - f_ref) <= SEQUENTIAL_RTOL * max(1.0, abs(f_ref)),
              f"quadratic:9 seed {h.request.seed}: served {f!r} vs "
              f"Sequential {f_ref!r} (rtol {SEQUENTIAL_RTOL})")


def check_popstep() -> None:
    """Distributed() on the TPU runs the compiled popstep kernel and
    agrees with the fused XLA inner."""
    import jax.numpy as jnp

    from repro.core import distributed
    from repro.core.solver import Distributed, Problem, resolve_mesh, solve

    check(distributed._resolve_inner(None) == "popstep",
          "the TPU's default inner is not popstep")
    mesh = resolve_mesh(None)
    for spec, n in (("quadratic", 9), ("shekel", None)):
        prob = Problem.get(spec, n=n)
        x0 = request_x0(prob, 0)
        kern = solve(prob, Distributed(), x0=x0, max_iters=MAX_ITERS)
        fused = solve(prob, Distributed(inner="fused"), x0=x0,
                      max_iters=MAX_ITERS)
        # the program solve() ran: same engine-cache key, so a cache hit
        engine = distributed._engine_for(
            prob.jax_fn, prob.encoding, mesh, ("data",), MAX_ITERS, 256,
            None, None, None)
        text = engine.lower(jnp.asarray(x0, jnp.float32),
                            jnp.ones((mesh.size,), bool)).compile().as_text()
        check("tpu_custom_call" in text,
              f"{prob.name}: no tpu_custom_call in the Distributed() program")
        f, f_ref = float(kern.best_f), float(fused.best_f)
        bitwise = same_result(kern, fused)
        print(f"{prob.name}: popstep {f!r} in {kern.iterations} iterations, "
              f"fused {f_ref!r} in {fused.iterations}; bitwise {bitwise}",
              flush=True)
        check(bitwise or (kern.iterations == fused.iterations and abs(
            f - f_ref) <= POPSTEP_RTOL * max(1.0, abs(f_ref))),
            f"{prob.name}: popstep and fused inners disagree")


def one_chip(counter: CompileCounter) -> None:
    with phase("serve-analytic", counter):
        handles = serve_closed_loop(ANALYTIC, SEEDS_PER_SPEC, MAX_ITERS)
    with phase("serve-subspace-lm", counter):
        handles += serve_closed_loop(SUBSPACE, 1, SUBSPACE_ITERS)
    with phase("wave-parity", counter):
        check_wave_parity(handles)
    with phase("sequential-reference", counter):
        check_sequential(handles)
    with phase("popstep-kernel", counter):
        check_popstep()


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def mesh_results(mesh) -> tuple[list, list]:
    """Single solves and one 16-request wave on ``mesh``; returns the
    results and the single solves' engine outputs' device counts."""
    from repro.core.solver import (
        Distributed, Problem, SolveRequest, solve, solve_many,
    )

    out, n_devices = [], []
    for spec, n in (("quadratic", 9), ("rastrigin", 40)):
        prob = Problem.get(spec, n=n)
        res = solve(prob, Distributed(mesh=mesh, max_bits=MAX_BITS),
                    x0=request_x0(prob, 0), max_iters=MAX_ITERS)
        out.append(res)
        n_devices.append(len(res.best_f.sharding.device_set))
    r40 = Problem.get("rastrigin", n=40)
    out += solve_many([SolveRequest(r40, seed=s, max_iters=MAX_ITERS)
                       for s in range(WAVE)],
                      mesh=mesh, max_bits=MAX_BITS, pad_to=WAVE)
    return out, n_devices


def four_chips(counter: CompileCounter) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.compat import mesh_from_devices
    from repro.core import distributed
    from repro.core.solver import Problem, resolve_mesh

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, want 4")
    mesh4 = resolve_mesh(None)
    mesh1 = mesh_from_devices(np.array(jax.devices()[:1]), ("data",))
    with phase("mesh-4", counter):
        res4, ndev4 = mesh_results(mesh4)
        check(ndev4 == [4, 4], f"4-chip results live on {ndev4} devices")
        # the folded engine the rastrigin:40 solve ran (a cache hit)
        r40 = Problem.get("rastrigin", n=40)
        engine = distributed._engine_for(
            r40.jax_fn, r40.encoding, mesh4, ("data",), MAX_ITERS, 256,
            None, None, None, res_bits=res4[1].extras["schedule"])
        lowered = engine.lower(jnp.zeros((40,), jnp.float32),
                               jnp.ones((4,), bool))
        collectives = [ln.strip()[:160] for ln in
                       lowered.compile().as_text().splitlines()
                       if " all-gather(" in ln or " all-reduce(" in ln]
        check("all_gather" in lowered.as_text() and collectives,
              "no cross-chip gather in the 4-chip rastrigin:40 program")
        plan = distributed._shard_plan(2 * 40 * MAX_BITS - 1, mesh4,
                                       ("data",), 256)
        print(f"rastrigin:40 on 4 chips: {plan.n_shards} shards of "
              f"{plan.chunk} children each (population {plan.pop}); "
              f"collectives: {collectives}", flush=True)
    with phase("mesh-1", counter):
        res1, ndev1 = mesh_results(mesh1)
        check(ndev1 == [1, 1], f"1-chip results live on {ndev1} devices")
    with phase("mesh-invariance", counter):
        names = ["quadratic:9", "rastrigin:40"] + [
            f"wave slot {s}" for s in range(WAVE)]
        for name, a, b in zip(names, res4, res1):
            check(same_result(a, b), f"{name}: 4-chip result differs from "
                                     f"the 1-chip mesh")
        print(f"mesh invariance: {len(names)} results bitwise equal on 4 "
              f"chips and on 1 (best_f {[float(r.best_f) for r in res4]})",
              flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the serving path and its references; "
                         "4: the cross-chip mesh path against one chip")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is {dev.platform!r})",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {dev.device_kind} x {len(jax.devices())}; compile "
          f"cache: {enable_compile_cache()}", flush=True)
    counter = CompileCounter()
    if args.chips == 4:
        four_chips(counter)
    else:
        one_chip(counter)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
