"""The pipelined scheduler: parity, backpressure, and the drain edges.

The pipeline's contract (ISSUE 9 acceptance): completions are BITWISE
identical to the synchronous scheduler's — the two threads only reorder
WHEN the host blocks, never what the device computes — and every PR 7
fault-tolerance invariant (deadline-at-pop, backoff, quarantine
bisection, deterministic FaultPlan injection) survives the handoff to
the dispatch worker.  ``pytest.mark.timeout`` is the hang watchdog under
the CI pytest-timeout plugin (inert without it, see conftest).
"""
import threading
import time

import numpy as np
import pytest

from repro.core.solver import Problem, SolveRequest, solve_many
from repro.runtime.failure import FaultPlan, PoisonError
from repro.serving import (
    DeadlineExceeded, DispatchFailed, PipelinedScheduler, RequestQueue,
    Scheduler,
)

pytestmark = pytest.mark.timeout(300)

MAX_ITERS = 8

# both-scheduler parametrization: the drain-edge invariants are the BASE
# scheduler's contract, and the pipelined subclass must preserve them
BOTH = pytest.mark.parametrize(
    "make_sched", [Scheduler, PipelinedScheduler],
    ids=["synchronous", "pipelined"])


@pytest.fixture(scope="module")
def problems():
    return {
        "rastrigin": Problem.get("rastrigin", n=2),
        "quadratic": Problem.get("quadratic", n=3),
    }


def _assert_bitwise(res, ref, ctx=None):
    assert float(res.best_f) == float(ref.best_f), ctx
    assert np.array_equal(np.asarray(res.best_x),
                          np.asarray(ref.best_x)), ctx
    assert res.iterations == ref.iterations, ctx
    assert np.array_equal(np.asarray(res.trace),
                          np.asarray(ref.trace)), ctx


# ---------------------------------------------------------------------------
# parity: the pipeline must not perturb a single bit
# ---------------------------------------------------------------------------

@pytest.mark.timeout(240)
def test_pipelined_matches_synchronous_bitwise(problems):
    """ACCEPTANCE: the same mixed-signature workload through the
    synchronous and the pipelined scheduler completes bitwise identical
    (and identical to per-request ``solve_many``)."""
    reqs = [SolveRequest(problems["rastrigin" if i % 3 else "quadratic"],
                         seed=300 + i, max_iters=MAX_ITERS)
            for i in range(10)]
    sync = Scheduler(wave_size=4)
    sync_handles = [sync.submit(r) for r in reqs]
    assert sync.drain() == len(reqs)
    with PipelinedScheduler(wave_size=4, max_in_flight=2) as piped:
        piped_handles = [piped.submit(r) for r in reqs]
        assert piped.drain() == len(reqs)
        m = piped.metrics()
    for req, hs, hp in zip(reqs, sync_handles, piped_handles):
        assert hp.error is None, hp
        (ref,) = solve_many([req])
        _assert_bitwise(hp.result(), hs.result(), hp)
        _assert_bitwise(hp.result(), ref, hp)
    # the pipelined snapshot carries the depth rows (the synchronous
    # scheduler pins them at depth 1 / overlap 0.0)
    assert m["max_in_flight_depth"] >= 1
    assert 0.0 <= m["overlap_fraction"] <= 1.0
    sync_m = sync.metrics()
    assert sync_m["max_in_flight_depth"] == 1
    assert sync_m["overlap_fraction"] == 0.0


# ---------------------------------------------------------------------------
# backpressure: pump never exceeds max_in_flight
# ---------------------------------------------------------------------------

class _GatedPending:
    """A PendingWave stand-in whose finalize blocks on an Event, so the
    test controls exactly when the worker can retire a wave."""

    fetch = host = (0.0, 0.0)       # PendingWave's phase times, untimed

    def __init__(self, reqs, pad_to, gate):
        self.reqs = reqs
        self.pad_to = pad_to
        self.gate = gate

    def finalize(self):
        assert self.gate.wait(timeout=60), "test gate never opened"
        return solve_many(self.reqs, pad_to=self.pad_to)


@pytest.mark.timeout(240)
def test_pump_backpressure_caps_in_flight_depth(problems, monkeypatch):
    from repro.serving import pipeline

    gate = threading.Event()
    monkeypatch.setattr(
        pipeline, "submit_wave",
        lambda reqs, pad_to=None, **kw: _GatedPending(reqs, pad_to, gate))
    sched = PipelinedScheduler(wave_size=1, max_in_flight=2)
    try:
        reqs = [SolveRequest(problems["rastrigin"], seed=400 + i,
                             max_iters=MAX_ITERS) for i in range(4)]
        handles = [sched.submit(r) for r in reqs]
        assert sched.pump() and sched.pump()       # two waves submitted
        assert sched.in_flight == 2
        assert not sched.pump(), "pump must refuse past max_in_flight"
        assert sched.in_flight == 2 and len(sched.queue) == 2
        assert not any(h.done() for h in handles), \
            "nothing finalizes while the gate is shut"
        gate.set()
        assert sched.drain() == 4
    finally:
        gate.set()
        sched.close()
    for req, h in zip(reqs, handles):
        (ref,) = solve_many([req])
        _assert_bitwise(h.result(), ref, h)
    m = sched.metrics()
    assert m["max_in_flight_depth"] == 2
    assert m["overlap_fraction"] > 0.0


def test_max_in_flight_validated():
    with pytest.raises(ValueError, match="max_in_flight"):
        PipelinedScheduler(max_in_flight=0)


# ---------------------------------------------------------------------------
# drain edge: backoff release vs deadline expiry in the same tick
# ---------------------------------------------------------------------------

@pytest.mark.timeout(120)
@BOTH
def test_backoff_release_races_deadline_expiry(problems, make_sched):
    """A bucket fails and backs off; one member's deadline lapses DURING
    the backoff sleep.  At release, the same drain tick sees both edges —
    the expiry must win: the retried wave carries only the live request,
    the expired one fails at pop without ever occupying a slot."""
    plan = FaultPlan(seed=0, error_dispatches={1})
    sched = make_sched(wave_size=2, faults=plan, max_retries=2,
                       retry_backoff_s=0.08, backoff_cap_s=0.08,
                       backoff_jitter=0.0)
    try:
        doomed = sched.submit(SolveRequest(
            problems["rastrigin"], seed=1, max_iters=MAX_ITERS,
            deadline_s=0.02))
        live_req = SolveRequest(problems["rastrigin"], seed=2,
                                max_iters=MAX_ITERS)
        live = sched.submit(live_req)
        sched.drain()
    finally:
        sched.close()
    assert plan.injected_errors == 1
    assert isinstance(doomed.error, DeadlineExceeded)
    assert live.error is None
    (ref,) = solve_many([live_req])
    _assert_bitwise(live.result(), ref, live)
    m = sched.metrics()
    assert m["expired"] == 1 and m["failed_waves"] == 1
    assert m["backoff_s"] > 0, "drain slept out the backoff, no hot spin"
    # the proof: one successful wave with exactly ONE active slot — the
    # expired request was failed at pop, not retried alongside the
    # survivor when the backoff released
    assert m["waves"] == 1
    assert m["slots"] - m["padded_slots"] == 1


# ---------------------------------------------------------------------------
# capacity accounting: in-flight waves + bisection requeues
# ---------------------------------------------------------------------------

class _AuditedQueue(RequestQueue):
    """Tracks the peak of (queued + in-flight) requests across every
    requeue — the accounting a bounded queue must never blow through."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.sched = None
        self.peak = 0

    def requeue(self, handle):
        super().requeue(handle)
        inflight = 0
        if self.sched is not None:
            with self.sched._flight:
                inflight = sum(len(f.bucket)
                               for f in self.sched._inflight)
        with self._lock:
            self.peak = max(self.peak, len(self._heap) + inflight)


@pytest.mark.timeout(240)
def test_inflight_wave_plus_bisection_respects_capacity(problems):
    """REGRESSION: a full wave in flight on the worker while quarantine
    bisection requeues probe remainders must never push queued +
    in-flight past the queue's capacity — requeues reuse slots the
    bucket already held, they never grow the backlog."""
    capacity = 8
    q = _AuditedQueue(capacity=capacity)
    plan = FaultPlan(seed=0)
    sched = PipelinedScheduler(q, wave_size=4, max_in_flight=2,
                               faults=plan, max_retries=1,
                               retry_backoff_s=0.0)
    q.sched = sched
    try:
        poisoned_reqs = [SolveRequest(problems["rastrigin"], seed=70 + i,
                                      max_iters=MAX_ITERS)
                         for i in range(4)]
        clean_reqs = [SolveRequest(problems["quadratic"], seed=80 + i,
                                   max_iters=MAX_ITERS) for i in range(4)]
        poisoned = [sched.submit(r) for r in poisoned_reqs]
        clean = [sched.submit(r) for r in clean_reqs]
        plan.poison_seqs = frozenset({poisoned[2].seq})
        sched.drain()
    finally:
        sched.close()
    assert q.peak <= capacity, \
        f"backlog accounting peaked at {q.peak} > capacity {capacity}"
    assert isinstance(poisoned[2].error, DispatchFailed)
    assert isinstance(poisoned[2].error.__cause__, PoisonError)
    for i, (h, req) in enumerate(zip(poisoned + clean,
                                     poisoned_reqs + clean_reqs)):
        if i == 2:
            continue
        assert h.error is None, h
        (ref,) = solve_many([req])
        _assert_bitwise(h.result(), ref, h)
    m = sched.metrics()
    assert m["bisected_waves"] >= 1
    assert m["completed"] == 7 and m["failed"] == 1


# ---------------------------------------------------------------------------
# fault-plan determinism under threading
# ---------------------------------------------------------------------------

@pytest.mark.timeout(240)
def test_faultplan_deterministic_under_pipelining(problems):
    """Dispatch indices are assigned at SUBMIT time in pop order on the
    scheduler thread, so a seeded FaultPlan replays identically through
    the two-thread pipeline: two identical runs, identical outcomes."""
    def run():
        plan = FaultPlan(seed=5, dispatch_error_rate=0.3,
                         error_dispatches={2}, latency_dispatches={3},
                         latency_s=0.001, max_failures=6)
        with PipelinedScheduler(wave_size=2, max_in_flight=2, faults=plan,
                                max_retries=3,
                                retry_backoff_s=0.0) as sched:
            handles = [sched.submit(SolveRequest(
                problems["rastrigin"], seed=500 + i, max_iters=MAX_ITERS))
                for i in range(6)]
            sched.drain()
        outcomes = []
        for h in handles:
            outcomes.append((
                type(h.error).__name__ if h.error is not None else None,
                h.retries,
                float(h.result().best_f) if h.error is None else None))
        return plan.injected, outcomes

    injected_a, outcomes_a = run()
    injected_b, outcomes_b = run()
    assert injected_a == injected_b >= 1
    assert outcomes_a == outcomes_b


# ---------------------------------------------------------------------------
# worker crash: fail loudly, never strand a caller
# ---------------------------------------------------------------------------

@pytest.mark.timeout(120)
def test_worker_crash_fails_inflight_and_raises_in_drain(problems):
    """A bug past _finalize's own dispatch-failure handler (here: a
    completion-path explosion) must fail the in-flight handles and
    surface in drain() — never a silent hang on result()."""
    sched = PipelinedScheduler(wave_size=2, max_in_flight=2)
    sched._complete_bucket = lambda bucket, results: (
        (_ for _ in ()).throw(RuntimeError("completion-path bug")))
    try:
        h = sched.submit(SolveRequest(problems["rastrigin"], seed=9,
                                      max_iters=MAX_ITERS))
        with pytest.raises(RuntimeError, match="dispatch worker crashed"):
            sched.drain()
    finally:
        sched.close()
    assert h.done() and isinstance(h.error, RuntimeError)
    assert "dispatch worker crashed" in str(h.error)
    assert isinstance(h.error.__cause__, RuntimeError)
    with pytest.raises(RuntimeError):
        h.result()


# ---------------------------------------------------------------------------
# lifecycle: close, restart, context manager
# ---------------------------------------------------------------------------

@pytest.mark.timeout(120)
def test_close_is_idempotent_and_restartable(problems):
    sched = PipelinedScheduler(wave_size=2)
    req = SolveRequest(problems["quadratic"], seed=21, max_iters=MAX_ITERS)
    h1 = sched.submit(req)
    assert sched.drain() == 1
    sched.close()
    sched.close()                           # idempotent
    # the next drain revives the worker lazily
    h2 = sched.submit(req)
    assert sched.drain() == 1
    sched.close()
    _assert_bitwise(h2.result(), h1.result())


@pytest.mark.timeout(120)
def test_context_manager_joins_worker(problems):
    with PipelinedScheduler(wave_size=2) as sched:
        h = sched.submit(SolveRequest(problems["quadratic"], seed=22,
                                      max_iters=MAX_ITERS))
        sched.drain()
        worker = sched._thread
        assert worker is not None and worker.is_alive()
    assert sched._thread is None and not worker.is_alive()
    assert h.error is None


@pytest.mark.timeout(120)
def test_drain_waits_out_inflight_before_returning(problems):
    """drain() must not return while a wave is still on the worker —
    the completion count includes every submitted request."""
    with PipelinedScheduler(wave_size=1, max_in_flight=2) as sched:
        handles = [sched.submit(SolveRequest(
            problems["rastrigin"], seed=600 + i, max_iters=MAX_ITERS))
            for i in range(5)]
        done = sched.drain()
        assert done == 5 and sched.in_flight == 0
        assert all(h.done() for h in handles)
        t0 = time.perf_counter()
        assert sched.drain() == 0, "an idle drain returns immediately"
        assert time.perf_counter() - t0 < 5.0
