"""Drives the program through its public entries and records what happened.

``serve`` drives ``RequestQueue`` -> ``PipelinedScheduler`` (``submit``,
``step``) -> ``submit_wave`` -> the batched folded engine, under the
traffic mix's open loop.

``solve`` drives ``solve(problem, Distributed(...))`` -> the folded
single-solve engine over the cell's chips, in a closed loop with one
solve in flight.

Set-up (imports, the chip, warm-up of every program the window uses) ends
where the window starts. Latencies are timed by the benchmark's own
clock: from each request's due time to the moment the benchmark sees its
result on the host. Nothing is read from the program's own timers.
"""
from __future__ import annotations

import gc
import queue
import threading
import time
from dataclasses import dataclass, field

import numpy as np

import e2e
import traffic as traffic_gen
from devtrace import Capture, span

GRACE_S = 60.0        # how long past the window's close a result may come
POLL_S = 0.001        # the serving loop's and the collector's idle poll


@dataclass
class Answer:
    """One request due in the window, as the program answered it."""
    problem: int
    x0: np.ndarray
    best_x: np.ndarray | None = None
    best_f: float | None = None
    iterations: int | None = None


@dataclass
class Run:
    window_s: float
    setup_s: float
    attempted: int
    failed: int                       # errors and answers that never came
    completed_in_window: int
    latencies_s: np.ndarray           # inf: failed or never answered
    answers: list                     # [Answer], every request due
    counters: dict = field(default_factory=dict)
    compiles_in_window: int = 0
    trace: object = None              # trace.Summary
    memory_peak_bytes: int = 0
    notes: list = field(default_factory=list)
    traced: list = field(default_factory=list)  # answers run wholly traced


class CompileCounter:
    """Programs built (backend compiles, and loads from the persistent
    cache) from JAX's monitoring events, over the process's life."""

    def __init__(self):
        import jax

        self.count = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def make_mesh(chips: int):
    """None (the program's default mesh over every device) when the cell
    holds the whole machine, else a ("data",) mesh over its chips."""
    import jax
    from jax.sharding import Mesh

    if jax.device_count() == chips:
        return None
    return Mesh(np.array(jax.devices()[:chips]), ("data",))


def make_problem(entry: dict):
    """The program's Problem for a configuration's problem entry: the
    registry's objective on the box the configuration states (the
    registry's own box may differ). Its size and starting resolution are
    checked against the configuration."""
    import dataclasses

    from repro.core.solver import Problem

    reg = entry["registry"]
    prob = Problem.get(reg["name"], n=reg.get("n"), **reg.get("kwargs", {}))
    enc = prob.encoding
    if (enc.n_vars, enc.bits) != (entry["n"], entry["bits"]):
        raise ValueError(f"{entry['name']}: the program's encoding {enc} "
                         f"differs from the configuration's n and bits")
    box = (float(entry["lo"]), float(entry["hi"]))
    if (enc.lo, enc.hi) != box:
        prob = prob.replace(encoding=dataclasses.replace(
            enc, lo=box[0], hi=box[1]))
    return prob


def settle() -> None:
    """End of set-up: collect, then move every object set-up left (the
    traced and lowered programs among them) out of the garbage
    collector's reach, as a long-running server does after start-up, so
    that a full collection in the window scans only what the window
    made."""
    gc.collect()
    gc.freeze()


def memory_peak(mesh) -> int:
    import jax

    devs = jax.devices() if mesh is None else list(mesh.devices.flat)
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


# ---------------------------------------------------------------------------
# serve: the open loop through the pipelined scheduler
# ---------------------------------------------------------------------------

def build_scheduler(config: dict, mesh):
    from repro.serving import PipelinedScheduler, RequestQueue

    return PipelinedScheduler(RequestQueue(),
                              wave_size=int(config["wave_size"]), mesh=mesh,
                              max_bits=int(config["max_bits"]),
                              bits_step=int(config["bits_step"]))


def warm_scheduler(sched, problems, entries, config) -> None:
    """One full wave per signature: every program, and every slot's
    result assembly, that a wave of the window can use."""
    from repro.core.solver import SolveRequest

    width, cap = int(config["wave_size"]), int(config["max_iters"])
    with span("warmup"):
        for prob, entry in zip(problems, entries):
            for x0 in traffic_gen.warmup_starts(entry, width):
                sched.submit(SolveRequest(prob, x0=x0, max_iters=cap))
        sched.drain()
    settle()


def counters(sched) -> dict:
    m = sched.metrics_
    return {"slots": m.slots, "padded_slots": m.padded_slots,
            "waves": m.waves, "completed": m.completed}


def open_loop(sched, problems, arrivals, seconds: float, max_iters: int,
              compiles: CompileCounter, trace_s: float | None = None):
    """Offer ``arrivals`` to ``sched`` on their due times, serve until
    every answer is in (or ``GRACE_S`` past the close). With ``trace_s``
    the last ``trace_s`` seconds of the window are traced (stopping the
    profiler takes seconds, which then fall after the close). Returns
    ``(handles, due, seen, submitted, window counters, programs built in
    the window, trace summary)``."""
    from repro.core.solver import SolveRequest

    k = len(arrivals)
    handles = [None] * k
    submitted = np.full(k, np.nan)
    seen = np.full(k, np.nan)
    new = queue.SimpleQueue()
    stop = threading.Event()
    capture = None
    t0 = time.perf_counter()
    due = t0 + np.array([a.due_s for a in arrivals])

    def arrive():
        for i, a in enumerate(arrivals):
            wait = due[i] - time.perf_counter()
            if stop.wait(wait) if wait > 0 else stop.is_set():
                return
            with span("submit"):
                handles[i] = sched.submit(SolveRequest(
                    problems[a.problem], x0=a.x0, max_iters=max_iters))
            submitted[i] = time.perf_counter()
            new.put(i)

    def collect():
        live: list[int] = []
        while True:
            last = stop.is_set()
            while not new.empty():
                live.append(new.get())
            still = []
            for i in live:
                if handles[i].done():
                    seen[i] = time.perf_counter()
                else:
                    still.append(i)
            live = still
            if last:
                return
            time.sleep(POLL_S)

    c_start, p_start = counters(sched), compiles.count
    threads = [threading.Thread(target=arrive, name="bench-arrivals"),
               threading.Thread(target=collect, name="bench-collector")]
    for t in threads:
        t.start()
    close = t0 + seconds
    c_close = p_close = None
    try:
        while True:
            now = time.perf_counter()
            if trace_s and capture is None and now >= close - trace_s:
                capture = Capture().__enter__()
            if c_close is None and now >= close:
                c_close, p_close = counters(sched), compiles.count
                if capture is not None:
                    capture.stop()
            if c_close is not None and (
                    now >= close + GRACE_S
                    or (not threads[0].is_alive()
                        and not np.isnan(seen).any())):
                break
            with span("step" if c_close is None else "drain"):
                worked = sched.step()
            if not worked:
                with span("wait"):
                    time.sleep(POLL_S)
    finally:
        stop.set()
        for t in threads:
            t.join()
        if capture is not None:
            capture.stop()
    window = {k2: c_close[k2] - c_start[k2] for k2 in c_start}
    return (handles, due, seen, submitted, window, p_close - p_start,
            capture.summary() if capture is not None else None)


def serve(cell, seed: int, seconds: float, trace_on: bool,
          compiles: CompileCounter, t_start: float) -> Run:
    cfg, mix = cell.config, cell.traffic
    if mix["loop"] != "open":
        raise ValueError(f"the serve entry drives open loops, not "
                         f"{mix['loop']!r}")
    mesh = make_mesh(cell.chips)
    entries = cfg["problems"]
    problems = [make_problem(e) for e in entries]
    sched = build_scheduler(cfg, mesh)
    try:
        warm_scheduler(sched, problems, entries, cfg)
        arrivals = traffic_gen.open_loop(mix, entries, seed, seconds)
        setup_s = time.perf_counter() - t_start
        handles, due, seen, submitted, window, n_comp, summary = open_loop(
            sched, problems, arrivals, seconds, int(cfg["max_iters"]),
            compiles, float(mix["trace_window_s"]) if trace_on else None)
    finally:
        sched.close()
    close = due[0] + seconds
    answered = np.array([h is not None and h.done() and h.error is None
                         for h in handles]) & ~np.isnan(seen)
    answers = [Answer(a.problem, a.x0) for a in arrivals]
    for i in np.flatnonzero(answered):
        res = handles[i].result(timeout=0)
        answers[i].best_x = np.asarray(res.best_x, np.float32)
        answers[i].best_f = float(res.best_f)
        answers[i].iterations = int(res.iterations)
    peak = memory_peak(mesh)
    lateness = submitted - due
    in_window = int((answered & (seen <= close)).sum())
    notes = [f"generator lateness ms: p50 "
             f"{float(np.nanpercentile(lateness, 50)) * 1e3!r} p99 "
             f"{float(np.nanpercentile(lateness, 99)) * 1e3!r} max "
             f"{float(np.nanmax(lateness)) * 1e3!r}",
             f"requests due {len(handles)}, answered "
             f"{int(answered.sum())}, by the close {in_window}; waves "
             f"{window['waves']}, programs built in the window {n_comp}"]
    return Run(window_s=seconds, setup_s=setup_s, attempted=len(handles),
               failed=int((~answered).sum()),
               completed_in_window=in_window,
               latencies_s=e2e.latencies(due, seen, answered, close,
                                         GRACE_S),
               answers=answers, counters=window, compiles_in_window=n_comp,
               trace=summary, memory_peak_bytes=peak, notes=notes)


# ---------------------------------------------------------------------------
# solve: one global solve at a time, in a closed loop
# ---------------------------------------------------------------------------

def solve_entry(cfg: dict, mesh):
    """``(problem entry, x0 -> SolveResult)``: the cell's one problem and
    a call of the program's ``solve`` on it."""
    from repro.core.solver import Distributed, solve as program_solve

    [entry] = cfg["problems"]
    problem = make_problem(entry)
    strategy = Distributed(mesh=mesh, max_bits=int(cfg["max_bits"]),
                           bits_step=int(cfg["bits_step"]))
    cap = int(cfg["max_iters"])

    def one(x0):
        return program_solve(problem, strategy, x0=x0, max_iters=cap)
    return entry, one


def answer(a: Answer, res) -> None:
    """Fill ``a`` from a SolveResult: host values, which wait on the
    device."""
    a.best_x = np.asarray(res.best_x, np.float32)
    a.best_f = float(res.best_f)
    a.iterations = int(res.iterations)


def warm_solve(one, entry: dict) -> None:
    """One solve; then, at every resolution of the schedule, what the
    program does after the engine: it slices the best parent's bits out
    of the engine's widest buffer at the resolution they were found at
    and decodes them there, in programs of that resolution's own. One
    solve ends on one resolution; the window's may end on any."""
    import jax
    import jax.numpy as jnp

    from repro.core.encoding import decode

    with span("warmup"):
        [x0] = traffic_gen.warmup_starts(entry, 1)
        res = one(x0)
        answer(Answer(0, x0), res)
        bits, enc = res.extras["bits"], make_problem(entry).encoding
        schedule = res.extras["schedule"]
        widest = jax.device_put(
            jnp.zeros(enc.n_vars * max(schedule), bits.dtype),
            bits.sharding)
        for b in schedule:
            enc_b = enc.with_bits(b)
            np.asarray(decode(widest[: enc_b.n_bits], enc_b))
    settle()


def closed_loop(one, entry: dict, seed: int, seconds: float,
                compiles: CompileCounter, trace_s: float | None = None):
    """Solve after solve, each due when the previous answer reached the
    host, until the window closes; the solve in flight then runs to its
    end. With ``trace_s`` the last ``trace_s`` seconds are traced, and the
    solve in flight at the close with them. Returns ``(answers, start,
    end, ok, indices of the answered solves traced whole, programs built
    in the window, trace summary, first error)``."""
    answers, start, end, ok = [], [], [], []
    error = capture = None
    t0 = time.perf_counter()
    close = t0 + seconds
    trace_from = close - trace_s if trace_s else np.inf
    p_start = compiles.count
    try:
        while time.perf_counter() < close:
            if capture is None and time.perf_counter() >= trace_from:
                capture = Capture().__enter__()
                trace_from = time.perf_counter()
            a = Answer(0, traffic_gen.closed_loop_start(entry, seed,
                                                        len(answers)))
            answers.append(a)
            start.append(time.perf_counter())
            try:
                with span("solve"):
                    answer(a, one(a.x0))
                ok.append(True)
            except Exception as e:     # counted as failed; the run goes on
                ok.append(False)
                error = error or f"{type(e).__name__}: {e}"
            end.append(time.perf_counter())
        n_comp = compiles.count - p_start
    finally:
        if capture is not None:
            capture.stop()
    start, end, ok = np.array(start), np.array(end), np.array(ok, bool)
    traced = [int(i) for i in np.flatnonzero(ok & (start >= trace_from))]
    return (answers, start, end, ok, traced, n_comp,
            capture.summary() if capture is not None else None, error)


def solve(cell, seed: int, seconds: float, trace_on: bool,
          compiles: CompileCounter, t_start: float) -> Run:
    cfg, mix = cell.config, cell.traffic
    if mix["loop"] != "closed" or int(mix["in_flight"]) != 1:
        raise ValueError(f"the solve entry drives a closed loop with one "
                         f"solve in flight, not {mix!r}")
    mesh = make_mesh(cell.chips)
    entry, one = solve_entry(cfg, mesh)
    warm_solve(one, entry)
    setup_s = time.perf_counter() - t_start
    answers, start, end, ok, traced, n_comp, summary, error = closed_loop(
        one, entry, seed, seconds, compiles,
        float(mix["trace_window_s"]) if trace_on else None)
    close = start[0] + seconds
    in_window = int((ok & (end <= close)).sum())
    notes = [f"solves due {len(answers)}, answered {int(ok.sum())}, by "
             f"the close {in_window}; programs built in the window "
             f"{n_comp}"]
    if summary is not None:
        notes.append(f"solves traced whole {len(traced)}")
    if error is not None:
        notes.append(f"first error: {error}")
    return Run(window_s=seconds, setup_s=setup_s, attempted=len(answers),
               failed=int((~ok).sum()), completed_in_window=in_window,
               latencies_s=e2e.latencies(start, end, ok, close, GRACE_S),
               answers=answers, compiles_in_window=n_comp, trace=summary,
               memory_peak_bytes=memory_peak(mesh), notes=notes,
               traced=traced)
