"""The serving subsystem: queue/bucket semantics, solve_many parity with
per-request solves (the acceptance contract), retry accounting on failed
dispatches, straggler-fed wave sizing, and the metrics snapshot."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.solver import (
    Batched, Problem, SolveRequest, engine_signature, solve, solve_many,
)
from repro.runtime.failure import FailureInjector, SimulatedFailure
from repro.runtime.straggler import StragglerPolicy
from repro.serving import DispatchFailed, RequestQueue, Scheduler, percentile
from repro.serving.metrics import ServingMetrics

MAX_ITERS = 24


@pytest.fixture(scope="module")
def problems():
    """Three distinct engine signatures, built ONCE (signatures key on
    the objective callable, so per-test rebuilding would defeat both
    bucketing and the compile cache)."""
    return {
        "rastrigin": Problem.get("rastrigin", n=2),
        "quadratic": Problem.get("quadratic", n=3),
        "shekel": Problem.get("shekel", m=5),
    }


def _mixed_requests(problems):
    """≥3 distinct problems; group sizes chosen so a pad_to=2 dispatch
    leaves a partially-filled final bucket for every signature."""
    return [
        SolveRequest(problems["rastrigin"], seed=1, max_iters=MAX_ITERS),
        SolveRequest(problems["quadratic"], x0=[4.0, -3.0, 6.5],
                     max_iters=16),
        SolveRequest(problems["rastrigin"], seed=2, max_iters=MAX_ITERS),
        SolveRequest(problems["shekel"], seed=3, max_iters=MAX_ITERS),
        SolveRequest(problems["rastrigin"], seed=4, max_iters=MAX_ITERS),
    ]


def _reference(req, max_bits=None):
    """The per-request path: an individual solve() through the batched
    engine at width 1 — what a no-batching server would run."""
    x0 = None if req.x0 is None else jnp.asarray(req.x0, jnp.float32)[None]
    return solve(req.problem, Batched(restarts=1, max_bits=max_bits),
                 seed=req.seed, x0=x0, max_iters=req.max_iters)


# ---------------------------------------------------------------------------
# solve_many: the parity acceptance contract
# ---------------------------------------------------------------------------

def test_solve_many_parity_with_per_request_solves(problems):
    """ACCEPTANCE: a mixed workload of 3 distinct problems through the
    bucketed dispatch — including partially-filled final buckets — returns
    per-request results IDENTICAL (bitwise best_x/best_f, same iterations
    and trace) to individual solve() calls."""
    reqs = _mixed_requests(problems)
    outs = solve_many(reqs, pad_to=2)   # rastrigin: full wave + partial;
    #                                     quadratic/shekel: partial waves
    assert len(outs) == len(reqs)
    for req, out in zip(reqs, outs):
        ref = _reference(req)
        assert float(out.best_f) == float(ref.best_f), req
        assert np.array_equal(np.asarray(out.best_x),
                              np.asarray(ref.best_x)), req
        assert out.iterations == ref.iterations, req
        assert np.array_equal(np.asarray(out.trace),
                              np.asarray(ref.trace)), req
        assert out.extras["wave_size"] == 2
        assert (np.diff(out.trace) <= 1e-6).all(), "trace monotone"


def test_solve_many_parity_folded_schedule(problems):
    """Same parity contract on the folded-resolution-schedule engine,
    whose host post-processing skips inactive padding slots — a partial
    wave (2 requests padded to 4) must still match individual solves."""
    reqs = [SolveRequest(problems["rastrigin"], seed=31, max_iters=16),
            SolveRequest(problems["quadratic"], seed=32, max_iters=16)]
    outs = solve_many(reqs, pad_to=4, max_bits=12)
    for req, out in zip(reqs, outs):
        ref = _reference(req, max_bits=12)
        assert float(out.best_f) == float(ref.best_f), req
        assert np.array_equal(np.asarray(out.best_x),
                              np.asarray(ref.best_x)), req
        assert out.iterations == ref.iterations, req
        assert np.array_equal(np.asarray(out.trace),
                              np.asarray(ref.trace)), req


# the sfu-suite configuration's five objectives at CI size, on the
# configuration's boxes (bench/configs/sfu-suite.json)
SFU_CI = {
    "rastrigin:6": (("rastrigin", {"n": 6}), None),
    "ackley:4": (("ackley", {"n": 4}), (-32.768, 32.768)),
    "griewank:10": (("griewank", {"n": 10}), (-600.0, 600.0)),
    "quadratic:9": (("quadratic", {"n": 9}), None),
    "shekel5": (("shekel", {"m": 5}), None),
}


def _same_bits(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and \
        a.tobytes() == b.tobytes()


def _assert_same_result(out, ref, what):
    assert _same_bits(out.best_x, ref.best_x), what
    assert _same_bits(out.best_f, ref.best_f), what
    assert out.iterations == ref.iterations, what
    assert _same_bits(out.trace, ref.trace), what
    assert _same_bits(out.extras["bits"], ref.extras["bits"][0]), what


@pytest.mark.parametrize("spec", sorted(SFU_CI))
def test_wave_slot_is_its_width_one_solve_at_any_fill(spec):
    """The parent evaluation runs inside the wave engine, one row at a
    time: a request is bitwise the same alone at width 1, in a 16-slot
    wave with 5 active slots, and in a full wave (folded 8 -> 12 bits)."""
    (name, kwargs), box = SFU_CI[spec]
    prob = Problem.get(name, **kwargs)
    if box is not None:
        prob = prob.replace(encoding=dataclasses.replace(
            prob.encoding, lo=box[0], hi=box[1]))
    enc = prob.encoding
    rng = np.random.default_rng(14)
    reqs = [SolveRequest(prob, x0=rng.uniform(enc.lo, enc.hi, enc.n_vars),
                         max_iters=12) for _ in range(16)]
    full = solve_many(reqs, pad_to=16, max_bits=12)
    part = solve_many(reqs[:5], pad_to=16, max_bits=12)
    for i, req in enumerate(reqs):
        alone = _reference(req, max_bits=12)
        _assert_same_result(full[i], alone, (spec, "full wave", i))
        if i < 5:
            _assert_same_result(part[i], alone, (spec, "5 of 16", i))


@pytest.mark.parametrize("max_bits", [None, 12])
def test_served_result_fields_keep_dtypes_and_shapes(problems, max_bits):
    """Served fields are numpy slices of the wave's one fetch, with the
    dtypes and shapes they always had: ``bits`` int8 at the final
    resolution, the hygiene flag, the slot and the wave width."""
    prob = problems["quadratic"]
    reqs = [SolveRequest(prob, seed=s, max_iters=8) for s in (3, 4)]
    final_bits = max_bits or prob.encoding.bits
    for slot, res in enumerate(solve_many(reqs, pad_to=4,
                                          max_bits=max_bits)):
        assert np.asarray(res.best_x).dtype == np.float32
        assert np.shape(res.best_x) == (3,)
        assert np.asarray(res.best_f).dtype == np.float32
        assert np.shape(res.best_f) == ()
        assert isinstance(res.iterations, int)
        assert np.asarray(res.trace).dtype == np.float32
        assert res.trace.shape == (res.iterations + 1,)
        bits = res.extras["bits"]
        assert bits.dtype == np.int8 and bits.shape == (3 * final_bits,)
        assert res.extras["finite"] is True
        assert res.extras["wave_slot"] == slot
        assert res.extras["wave_size"] == 4


def test_served_nan_objective_is_flagged():
    """A NaN objective is still caught by the hygiene policy on the
    host-assembled results: flagged by default, raised on request."""
    from repro.core.encoding import Encoding
    from repro.core.solver import NonFiniteResult

    prob = Problem(fn=lambda x: jnp.sum(x) * jnp.nan,
                   encoding=Encoding(n_vars=2, bits=8))
    req = SolveRequest(prob, seed=1, max_iters=4)
    [res] = solve_many([req], pad_to=2)
    assert res.extras["finite"] is False
    with pytest.raises(NonFiniteResult):
        solve_many([req], pad_to=2, on_nonfinite="raise")


def test_solve_many_heterogeneous_caps_share_one_wave(problems):
    """Two requests with different max_iters ride ONE wave (per-slot caps
    are call-time arrays) and each still matches its individual solve."""
    reqs = [SolveRequest(problems["rastrigin"], seed=7, max_iters=6),
            SolveRequest(problems["rastrigin"], seed=8, max_iters=MAX_ITERS)]
    outs = solve_many(reqs)             # no padding: width = 2
    assert outs[0].iterations <= 6
    for req, out in zip(reqs, outs):
        ref = _reference(req)
        assert float(out.best_f) == float(ref.best_f)
        assert out.iterations == ref.iterations


def test_solve_many_validates_inputs(problems):
    with pytest.raises(ValueError, match="pad_to"):
        solve_many([SolveRequest(problems["rastrigin"])], pad_to=0)
    with pytest.raises(ValueError, match="request x0 must be"):
        solve_many([SolveRequest(problems["rastrigin"], x0=[1.0, 2.0, 3.0])])


def test_engine_signature_buckets(problems):
    """Same problem + config -> same bucket; different schedule,
    encoding or objective -> different bucket."""
    a = engine_signature(problems["rastrigin"])
    assert engine_signature(problems["rastrigin"]) == a
    assert engine_signature(problems["quadratic"]) != a
    assert engine_signature(problems["rastrigin"], max_bits=12) != a
    coarse = problems["rastrigin"].replace(
        encoding=problems["rastrigin"].encoding.with_bits(6))
    assert engine_signature(coarse) != a


def test_name_built_requests_share_one_bucket():
    """The README quickstart shape: requests built from a registry NAME
    must share a signature (Problem.get memoizes per spec) — otherwise
    every request lands in its own bucket and pays its own compilation."""
    assert Problem.get("rastrigin", n=2) is Problem.get("rastrigin", n=2)
    a = SolveRequest("rastrigin", seed=0).resolve()
    b = SolveRequest("rastrigin", seed=1).resolve()
    assert engine_signature(a.problem) == engine_signature(b.problem)
    assert Problem.get("rastrigin", n=2) is not Problem.get("rastrigin",
                                                           n=3)
    # defaulted n AND defaulted factory kwargs normalize to one spec
    # (objectives.canonical_spec): one bucket, one compilation
    assert Problem.get("rastrigin") is Problem.get("rastrigin", n=2)
    assert Problem.get("shekel") is Problem.get("shekel", m=5)
    assert Problem.get("shekel", m=7) is not Problem.get("shekel")


def test_bad_x0_rejected_at_submission_not_in_wave(problems):
    """A malformed x0 fails at submit()/resolve() — it can never reach a
    wave and poison the healthy requests bucketed with it."""
    q = RequestQueue()
    with pytest.raises(ValueError, match=r"request x0 must be \(2,\)"):
        q.submit(SolveRequest(problems["rastrigin"], x0=[1.0, 2.0, 3.0]))
    assert len(q) == 0


# ---------------------------------------------------------------------------
# the queue
# ---------------------------------------------------------------------------

def test_queue_priority_and_fifo(problems):
    q = RequestQueue()
    low = q.submit(SolveRequest(problems["rastrigin"], seed=0, priority=0))
    hi = q.submit(SolveRequest(problems["rastrigin"], seed=1, priority=5))
    mid = q.submit(SolveRequest(problems["rastrigin"], seed=2, priority=1))
    low2 = q.submit(SolveRequest(problems["rastrigin"], seed=3, priority=0))
    assert len(q) == 4
    popped = q.pop_bucket(4)
    assert popped == [hi, mid, low, low2]   # priority desc, FIFO within
    assert len(q) == 0


def test_queue_pop_bucket_groups_by_signature(problems):
    q = RequestQueue()
    sched = Scheduler(q, wave_size=4)
    r1 = q.submit(SolveRequest(problems["rastrigin"], seed=0))
    q1 = q.submit(SolveRequest(problems["quadratic"], seed=1))
    r2 = q.submit(SolveRequest(problems["rastrigin"], seed=2))
    bucket = q.pop_bucket(4, key=sched.signature)
    assert bucket == [r1, r2]               # q1 skipped, still queued
    assert len(q) == 1
    assert q.pop_bucket(4, key=sched.signature) == [q1]


def test_queue_submit_coerces_and_validates():
    q = RequestQueue()
    h = q.submit("rastrigin", seed=0, max_iters=4)
    assert isinstance(h.request, SolveRequest)
    assert h.request.problem.name == "rastrigin2d"
    with pytest.raises(ValueError, match="unknown objective"):
        q.submit("warp-drive")
    with pytest.raises(TypeError, match="kwargs"):
        q.submit(SolveRequest("rastrigin"), seed=3)


# ---------------------------------------------------------------------------
# the scheduler loop
# ---------------------------------------------------------------------------

def test_scheduler_drains_mixed_workload(problems):
    sched = Scheduler(wave_size=2)
    reqs = _mixed_requests(problems)
    handles = [sched.submit(r) for r in reqs]
    assert sched.drain() == len(reqs)
    for h, req in zip(handles, reqs):
        assert h.done() and h.error is None
        ref = _reference(req)
        assert float(h.result().best_f) == float(ref.best_f)
    m = sched.metrics()
    assert m["completed"] == len(reqs)
    assert m["failed"] == 0
    assert m["waves"] == 4          # rastrigin 2 waves, quadratic/shekel 1
    assert m["padded_slots"] == 3   # three partially-filled final buckets
    assert m["fill_fraction"] == pytest.approx(5 / 8)
    assert m["latency_p95_ms"] >= m["latency_p50_ms"] > 0
    assert m["cache"]["totals"]["built"] >= 1
    assert m["pending"] == 0


def test_scheduler_warmup_compiles_once(problems):
    from repro.core import cache
    cache.clear()
    sched = Scheduler(wave_size=2)
    n = sched.warmup([problems["rastrigin"], problems["rastrigin"],
                      problems["quadratic"]], max_iters=MAX_ITERS)
    assert n == 2                           # distinct signatures only
    built = cache.get_cache("distributed.engine").stats()["built"]
    for seed in (11, 12, 13):
        sched.submit(SolveRequest(problems["rastrigin"], seed=seed,
                                  max_iters=MAX_ITERS))
    sched.drain()
    # steady-state serving: the warmed engine is reused, nothing rebuilt
    assert cache.get_cache("distributed.engine").stats()["built"] == built
    assert sched.metrics()["warmup_waves"] == 2


def test_scheduler_requeues_and_recovers_after_injected_failure(problems):
    """An injected dispatch failure requeues the bucket with retry
    accounting; once the fault clears the retried requests complete."""
    inj = FailureInjector(rate=1.0, seed=0)
    sched = Scheduler(wave_size=2, injector=inj, max_retries=2)
    h = sched.submit(SolveRequest(problems["rastrigin"], seed=21,
                                  max_iters=MAX_ITERS))
    assert sched.run_wave() == 0            # injected failure -> requeued
    assert h.retries == 1 and not h.done()
    assert len(sched.queue) == 1
    inj.rate = 0.0                          # fault clears
    assert sched.drain() == 1
    assert h.done() and h.error is None
    m = sched.metrics()
    assert m["requeued"] == 1 and m["failed_waves"] == 1
    assert m["injected_failures"] == 1


def test_scheduler_fails_request_after_retry_budget(problems):
    sched = Scheduler(wave_size=2, injector=FailureInjector(rate=1.0),
                      max_retries=1, retry_backoff_s=0.0)
    h = sched.submit(SolveRequest(problems["rastrigin"], seed=22,
                                  max_iters=MAX_ITERS))
    sched.drain()
    assert h.done() and h.retries == 2      # initial try + 1 retry
    # each exhausted handle gets its OWN DispatchFailed chained from the
    # shared dispatch error — never the same exception object across a
    # whole bucket
    assert isinstance(h.error, DispatchFailed)
    assert h.error.seq == h.seq
    assert isinstance(h.error.__cause__, SimulatedFailure)
    with pytest.raises(DispatchFailed):
        h.result()
    assert sched.metrics()["failed"] == 1


def test_straggler_policy_feeds_wave_size():
    """Recent dispatch times are the policy's virtual lanes: a straggling
    dispatch masks lanes and shrinks the next waves (snapped to halvings
    of wave_size, so shrinks cost at most log2(W) compiled widths) until
    the cooldown expires."""
    policy = StragglerPolicy(n_shards=4, factor=2.0, cooldown=2)
    sched = Scheduler(wave_size=8, straggler=policy)
    assert sched.effective_wave_size() == 8
    for t in (0.01, 0.01, 0.01, 0.5):       # one lane 50x the median
        sched._note_dispatch_time(t)
    assert sched.effective_wave_size() == 4  # 3/4 lanes -> snapped to W/2
    for t in [0.01] * 6:    # straggler leaves the window + cooldown decays
        sched._note_dispatch_time(t)
    assert sched.effective_wave_size() == 8


def test_effective_wave_size_halving_sequence():
    """Widths snap DOWN the halving ladder of wave_size as the quorum
    fraction decays — at W=8 exactly 8 -> 4 -> 2 -> 1, never 7 or 3
    (each distinct width is its own compiled engine per signature, so
    free-form shrinks would answer one straggler with recompiles)."""

    class _Quorum:                      # the policy surface the scheduler
        n_shards = 8                    # reads: n_shards + quorum_fraction
        quorum_fraction = 1.0

    sched = Scheduler(wave_size=8, straggler=_Quorum())
    expected = {1.0: 8, 0.9: 4, 0.6: 4, 0.5: 4, 0.3: 2, 0.2: 2, 0.05: 1}
    for frac, width in expected.items():
        sched.straggler.quorum_fraction = frac
        assert sched.effective_wave_size() == width, frac


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_percentile():
    assert percentile([3.0, 1.0, 2.0], 50) == 2.0
    assert percentile([1.0, 2.0], 100) == 2.0
    assert percentile([1.0, 2.0], 0) == 1.0
    assert percentile([1.0, 2.0], 50) == 1.5
    with pytest.raises(ValueError):
        percentile([], 50)
    with pytest.raises(ValueError):
        percentile([1.0], 101)


def test_metrics_snapshot_shape():
    m = ServingMetrics()
    m.record_wave(n_active=3, width=4)
    m.record_phases(dispatch=(0.02, 0.015), fetch_wait=(0.004, 0.001),
                    finalize_host=(0.01, 0.008))
    m.record_phases(dispatch=(0.03, 0.02), fetch_wait=(0.002, 0.0),
                    finalize_host=(0.005, 0.005))
    m.record_completion(0.1)
    m.record_completion(0.3)
    snap = m.snapshot()
    assert snap["completed"] == 2
    assert snap["slots"] == 4 and snap["padded_slots"] == 1
    assert snap["fill_fraction"] == pytest.approx(0.75)
    # per-wave host phases: wall and thread-CPU sums over the timed
    # waves, and the largest single wave (throughput over "busy" time,
    # a sum of overlapping waves, is gone)
    assert "runs_per_s" not in snap and "busy_s" not in snap
    assert snap["timed_waves"] == 2
    assert snap["dispatch_s"] == pytest.approx(0.05)
    assert snap["dispatch_cpu_s"] == pytest.approx(0.035)
    assert snap["dispatch_max_s"] == pytest.approx(0.03)
    assert snap["fetch_wait_s"] == pytest.approx(0.006)
    assert snap["fetch_wait_max_s"] == pytest.approx(0.004)
    assert snap["finalize_host_s"] == pytest.approx(0.015)
    assert snap["finalize_host_cpu_s"] == pytest.approx(0.013)
    assert snap["finalize_host_max_s"] == pytest.approx(0.01)
    assert snap["latency_p50_ms"] == pytest.approx(200.0)
    # the cache snapshot rides along for the serving endpoint
    assert set(snap["cache"]) == {"caches", "totals"}
    assert "evictions" in snap["cache"]["totals"]
    # engine-cache churn is surfaced top-level: big tuning compilations
    # (the subspace-lm family) make evictions the first signal to watch
    assert snap["cache_evictions"] == snap["cache"]["totals"]["evictions"]


def test_unwritable_tile_cache_env_warns(monkeypatch, capsys, tmp_path):
    """An operator-set REPRO_POPSTEP_TILE_CACHE that cannot be written
    must be surfaced at serve startup, not silently degraded to the
    in-process cache (launch/serve audit rode along with the dgolint
    determinism sweep)."""
    from repro.launch.serve import _warn_unwritable_tile_cache

    # unset: silent
    monkeypatch.delenv("REPRO_POPSTEP_TILE_CACHE", raising=False)
    _warn_unwritable_tile_cache()
    assert capsys.readouterr().err == ""

    # writable target: silent
    monkeypatch.setenv("REPRO_POPSTEP_TILE_CACHE",
                       str(tmp_path / "tiles.json"))
    _warn_unwritable_tile_cache()
    assert capsys.readouterr().err == ""

    # unwritable: an ancestor that is a regular file blocks creation
    # of the cache path no matter the uid (chmod-based denial is
    # invisible to root, so this is the portable unwritable case)
    blocker = tmp_path / "blocker"
    blocker.write_text("not a directory")
    monkeypatch.setenv("REPRO_POPSTEP_TILE_CACHE",
                       str(blocker / "sub" / "tiles.json"))
    _warn_unwritable_tile_cache()
    err = capsys.readouterr().err
    assert "REPRO_POPSTEP_TILE_CACHE" in err
    assert "re-tunes" in err and "dgolint" in err


@pytest.mark.parametrize("fault_args,exits", [
    ([], True),                          # a real failure fails the run
    (["--fault-rate", "0.5"], False),    # asked-for chaos does not
])
def test_serve_dgo_exit_code_on_failed_requests(monkeypatch, capsys,
                                                fault_args, exits):
    """``serve --dgo`` reports ``"failed": N`` and exits non-zero for it,
    unless a FaultPlan was asked for on the command line."""
    import json

    from repro.launch import serve
    from repro.serving import scheduler as scheduler_mod

    def dispatch_fails(*args, **kwargs):
        raise RuntimeError("device lost")

    monkeypatch.setattr(scheduler_mod.Scheduler, "warmup",
                        lambda self, problems, max_iters=None: 0)
    monkeypatch.setattr(scheduler_mod, "solve_many", dispatch_fails)
    args = serve.build_parser().parse_args(
        ["--dgo", "--problems", "quadratic:2", "--restarts", "2",
         "--waves", "1", "--max-iters", "8", "--no-pipeline",
         "--max-retries", "0", "--retry-backoff-s", "0", *fault_args])
    if exits:
        with pytest.raises(SystemExit) as err:
            serve.serve_dgo(args)
        assert err.value.code not in (0, None)
    else:
        serve.serve_dgo(args)
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["failed"] == 2 and report["completed"] == 0


@pytest.mark.parametrize("from_env", [False, True])
def test_enable_compile_cache_directory(monkeypatch, tmp_path, from_env):
    """The persistent compile cache sits at a fixed ``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` names one, which is left to JAX."""
    from pathlib import Path

    import jax

    from repro.launch import compile_cache

    root = Path(__file__).resolve().parents[1]
    assert compile_cache.CHECKOUT_CACHE_DIR == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        if from_env:
            want = str(tmp_path / "cache")
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
        else:
            want = str(compile_cache.CHECKOUT_CACHE_DIR)
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == (
            saved[0] if from_env else want)
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          saved[1])


def test_chip_smoke_serving_phase_runs_on_cpu(monkeypatch):
    """``chip_smoke.py``'s serving phase and its wave-parity check at CI
    size on the CPU: the helpers it shares with the serve CLI (its loop
    and its report) keep one signature."""
    import chip_smoke

    monkeypatch.setattr(chip_smoke, "WAVE", 4)
    handles = chip_smoke.serve_closed_loop("quadratic:2,shekel", 2, 4)
    assert len(handles) == 4
    chip_smoke.check_wave_parity(handles)
