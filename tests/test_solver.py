"""The solve() front door: strategy parity, callable adaptation, the
compilation-cache subsystem, and the folded on-device resolution
schedule."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cache
from repro.core.encoding import Encoding, decode
from repro.core.solver import (
    Batched, Clustered, Distributed, Fused, Problem, Sequential,
    SolveResult, as_strategy, solve, strategy_names,
)

MAX_BITS = 12
MAX_ITERS = 64


def _strategies():
    """Every registered strategy, configured for the parity run.

    ``dup`` marks multi-start strategies whose pinned starts duplicate the
    single x0 so their winner must follow the same trajectory.
    """
    return [
        ("sequential", Sequential(max_bits=MAX_BITS), False),
        ("fused", Fused(max_bits=MAX_BITS), False),
        ("clustered", Clustered(n_clusters=2, max_bits=MAX_BITS), True),
        ("distributed", Distributed(max_bits=MAX_BITS), False),
        ("batched", Batched(max_bits=MAX_BITS), True),
    ]


@pytest.mark.parametrize("pname,n,x0", [
    ("quadratic", 3, [4.0, -3.0, 6.5]),
    ("rastrigin", 2, [3.1, -2.2]),
])
def test_strategy_parity(pname, n, x0):
    """solve() under every registered strategy follows the same algorithm:
    from one pinned start they all land on the same value (the paper's
    one-algorithm-many-machines claim as a test)."""
    prob = Problem.get(pname, n=n)
    x0 = jnp.asarray(x0)
    x0s = jnp.stack([x0, x0])       # duplicated start: winner == single run

    finals = {}
    for name, strat, dup in _strategies():
        res = solve(prob, strat, x0=x0s if dup else x0, max_iters=MAX_ITERS)
        assert isinstance(res, SolveResult), name
        assert res.iterations > 0, name
        assert res.best_x.shape == (n,), name
        assert (np.diff(res.trace) <= 1e-6).all(), (name, "trace monotone")
        assert isinstance(res.extras, dict), name
        finals[name] = float(res.best_f)

    spread = max(finals.values()) - min(finals.values())
    assert spread < 1e-3, finals
    # the unimodal problem must actually be solved, not merely agreed on
    if pname == "quadratic":
        assert all(abs(v - prob.f_opt) < prob.tol for v in finals.values()), \
            finals


def test_distributed_schedule_folding_improves_resolution():
    """Distributed(max_bits=...) folds the paper's step-5 escalation into
    the on-device while_loop and must match the fused engine's schedule
    result from the same start."""
    prob = Problem.get("quadratic", n=2)
    x0 = jnp.asarray([4.0, -3.0])
    coarse = solve(prob, Distributed(), x0=x0, max_iters=MAX_ITERS)
    fine = solve(prob, Distributed(max_bits=14), x0=x0, max_iters=MAX_ITERS)
    fused = solve(prob, Fused(max_bits=14), x0=x0, max_iters=MAX_ITERS)
    assert fine.extras["schedule"] == (8, 10, 12, 14)
    assert float(fine.best_f) < float(coarse.best_f)
    assert np.isclose(float(fine.best_f), float(fused.best_f), atol=1e-4)


@pytest.mark.parametrize("pname,n,start", [
    ("rastrigin", 2, (3.1, -2.2)),
    ("ackley", 5, (2.0, -4.0, 1.0, 0.5, -3.0)),
    ("quadratic", 9, (5.0,) * 9),
])
def test_fused_bucketed_matches_single_compilation_bitwise(pname, n, start):
    """Fused(bucketed=True) splits the schedule into coarse/fine width
    buckets (two compilations, smaller coarse buffers) — the trajectory
    must be BITWISE identical to the one-compilation engine."""
    prob = Problem.get(pname, n=n)
    prob = prob.replace(encoding=prob.encoding.with_bits(5))
    x0 = jnp.asarray(start)
    a = solve(prob, Fused(max_bits=13), x0=x0, max_iters=MAX_ITERS)
    b = solve(prob, Fused(max_bits=13, bucketed=True), x0=x0,
              max_iters=MAX_ITERS)
    assert float(a.best_f) == float(b.best_f)
    assert np.array_equal(np.asarray(a.best_x), np.asarray(b.best_x))
    assert np.array_equal(np.asarray(a.trace), np.asarray(b.trace))
    for k in ("bits", "evaluations"):
        assert np.array_equal(np.asarray(a.extras[k]),
                              np.asarray(b.extras[k])), k


def test_bucket_split_and_bucketed_engine_validation():
    from repro.core.dgo import (DGOConfig, bucket_split,
                                make_fused_engine_bucketed)
    prob = Problem.get("quadratic", n=2)

    def cfg(bits, max_bits):
        return DGOConfig(encoding=prob.encoding.with_bits(bits),
                         max_bits=max_bits,
                         max_iters_per_resolution=8)

    # schedule (3,5,7,9,11): coarse = widths at <= half the final (3,5)
    assert bucket_split(cfg(3, 11)) == 2
    # (7,9,11): nothing at <= 5.5 -> no coarse bucket
    assert bucket_split(cfg(7, 11)) == 0
    for bad in (0, 5, -1):
        with pytest.raises(ValueError):
            make_fused_engine_bucketed(prob.fn, cfg(3, 11), n_coarse=bad)


def test_fused_bucketed_degenerate_schedule_falls_back():
    """A schedule with no coarse bucket (or a single resolution) runs the
    plain fused engine — same result object, no error."""
    prob = Problem.get("quadratic", n=2)
    prob = prob.replace(encoding=prob.encoding.with_bits(7))
    x0 = jnp.asarray([4.0, -3.0])
    a = solve(prob, Fused(max_bits=11), x0=x0, max_iters=MAX_ITERS)
    b = solve(prob, Fused(max_bits=11, bucketed=True), x0=x0,
              max_iters=MAX_ITERS)
    assert float(a.best_f) == float(b.best_f)
    assert np.array_equal(np.asarray(a.trace), np.asarray(b.trace))


def _chained_reference(prob, schedule, x0, max_iters, strategy_kw=None):
    """The removed Python-level chaining loop, reconstructed as a test
    oracle: one fixed-resolution solve() per resolution, re-encoding the
    parent between them — what Distributed(max_bits=...) used to do."""
    enc0 = prob.encoding
    x = x0
    history: list[float] = []
    best = None          # (val, x, bits-per-var)
    for i, b in enumerate(schedule):
        enc = enc0.with_bits(b)
        res = solve(prob.replace(encoding=enc),
                    Distributed(**(strategy_kw or {})), x0=x,
                    max_iters=max_iters)
        h = res.extras["history"]
        history.extend(h if i == 0 else h[1:])
        if best is None or float(res.best_f) < best[0]:
            best = (float(res.best_f), res.best_x, b)
        x = decode(res.extras["bits"], enc)
    return best, history


@pytest.mark.parametrize("pname,n,x0", [
    ("quadratic", 3, [4.0, -3.0, 6.5]),
    ("rastrigin", 2, [3.1, -2.2]),
])
def test_folded_schedule_matches_python_chaining(pname, n, x0):
    """The folded on-device schedule is the SAME algorithm the removed
    Python-level chaining ran: identical best value, best resolution and
    per-iteration value history on the parity problems."""
    prob = Problem.get(pname, n=n)
    x0 = jnp.asarray(x0)
    schedule = (8, 10, 12)
    folded = solve(prob, Distributed(max_bits=12), x0=x0,
                   max_iters=MAX_ITERS)
    assert folded.extras["schedule"] == schedule
    (ref_val, ref_x, ref_b), ref_history = _chained_reference(
        prob, schedule, x0, MAX_ITERS)
    assert np.isclose(float(folded.best_f), ref_val, atol=1e-6)
    assert folded.extras["bits_resolution"] == ref_b
    assert np.allclose(np.asarray(folded.best_x), np.asarray(ref_x),
                       atol=1e-6)
    assert len(folded.extras["history"]) == len(ref_history)
    assert np.allclose(folded.extras["history"], ref_history, atol=1e-6)
    # trace tail: both monotone accumulations end at the same best
    assert np.isclose(folded.trace[-1],
                      np.minimum.accumulate(ref_history)[-1], atol=1e-6)


def test_folded_schedule_single_engine_build():
    """Acceptance: the whole schedule is ONE engine compilation (keyed by
    the schedule signature), not one per resolution — and a second solve
    with the same signature reuses it."""
    cache.clear()
    prob = Problem.get("quadratic", n=2)
    x0 = jnp.asarray([4.0, -3.0])
    solve(prob, Distributed(max_bits=14), x0=x0, max_iters=32)
    c = cache.get_cache("distributed.engine")
    assert c.stats()["built"] == 1, c.stats()     # 4 resolutions, 1 build
    solve(prob, Distributed(max_bits=14), x0=x0 + 0.25, max_iters=32)
    assert c.stats()["built"] == 1
    assert c.stats()["hits"] == 1
    # batched schedule: also exactly one additional build for its signature
    solve(prob, Batched(max_bits=14), x0=jnp.stack([x0, x0 + 0.5]),
          max_iters=32)
    assert c.stats()["built"] == 2


def test_solve_string_front_door_and_errors():
    res = solve("quadratic", strategy="fused", seed=0, max_iters=32)
    assert isinstance(res, SolveResult)
    assert set(strategy_names()) == {
        "sequential", "fused", "clustered", "distributed", "batched"}
    assert isinstance(as_strategy(Fused), Fused)
    with pytest.raises(ValueError, match="unknown strategy"):
        solve("quadratic", strategy="warp-drive")
    with pytest.raises(ValueError, match="unknown objective"):
        solve("warp-drive")
    with pytest.raises(TypeError):
        solve(42)
    with pytest.raises(TypeError):
        solve("quadratic", strategy=42)


def test_random_x0_single_and_batched():
    """Problem.random_x0: (n_vars,) draws and the batched (B, n_vars)
    path the serving layer uses, all inside the search box and
    deterministic per key."""
    import jax

    prob = Problem.get("rastrigin", n=3)
    enc = prob.encoding
    key = jax.random.PRNGKey(7)
    single = prob.random_x0(key)
    assert single.shape == (3,)
    batch = prob.random_x0(key, batch=5)
    assert batch.shape == (5, 3)
    for x in (single, batch):
        assert bool(jnp.all(x >= enc.lo)) and bool(jnp.all(x <= enc.hi))
    assert np.array_equal(np.asarray(batch),
                          np.asarray(prob.random_x0(key, batch=5)))
    # the serving contract: a request's seed-derived start is the
    # batch=1 draw, not the unbatched one (shape changes the draw)
    assert batch[0].shape == single.shape


def test_as_problem_and_as_strategy_error_messages():
    """The coercion front doors name what they got AND what they accept —
    these messages are the API's first line of support."""
    from repro.core.solver import as_problem
    with pytest.raises(TypeError, match=r"cannot interpret int as a "
                                        r"Problem.*registry name"):
        as_problem(42)
    with pytest.raises(ValueError, match="unknown objective.*valid names"):
        as_problem("warp-drive")
    with pytest.raises(TypeError, match=r"cannot interpret float as a "
                                        r"Strategy.*string key"):
        as_strategy(1.5)
    with pytest.raises(ValueError, match="unknown strategy.*registered"):
        as_strategy("warp-drive")


def test_multi_start_strategies_validate_x0_shape():
    prob = Problem.get("quadratic", n=2)
    single = jnp.asarray([4.0, -3.0])
    with pytest.raises(ValueError, match="clustered starts"):
        solve(prob, Clustered(n_clusters=2), x0=single)
    with pytest.raises(ValueError, match="batched starts"):
        solve(prob, Batched(), x0=single)


def test_host_adapter_shared_across_problem_instances():
    """Two Problems wrapping the same host objective share one jax
    adapter, so engine compilations cache across per-request Problem
    construction instead of churning."""
    enc = Encoding(n_vars=2, bits=8, lo=-10.0, hi=10.0)

    def f_np(x):
        return float((np.asarray(x) ** 2).sum())

    a = Problem(fn=f_np, encoding=enc)
    b = Problem(fn=f_np, encoding=enc)
    assert a.jax_fn is b.jax_fn


def test_broken_jax_objective_fails_at_construction():
    """A genuinely buggy jax objective must error when the Problem is
    built, not be silently misclassified as a host objective."""
    enc = Encoding(n_vars=2, bits=8, lo=-10.0, hi=10.0)
    W = jnp.ones((5, 5))                    # wrong shape for a 2-vector

    with pytest.raises(ValueError, match="failed to trace"):
        Problem(fn=lambda x: (x @ W).sum(), encoding=enc)


def test_batched_schedule_bits_match_values():
    """Chained-resolution Batched: decode(extras['bits'][r]) must be the
    point whose value extras['values'][r] reports (quantized at the final
    resolution), even when a restart's best came from an earlier
    resolution."""
    from repro.core.encoding import decode
    prob = Problem.get("quadratic", n=2)
    x0s = jnp.asarray([[4.0, -3.0], [7.0, 2.0]])
    res = solve(prob, Batched(max_bits=12), x0=x0s, max_iters=32)
    enc = prob.encoding.with_bits(res.extras["schedule"][-1])
    for r in range(2):
        x_r = decode(res.extras["bits"][r], enc)
        v_r = float(prob.fn(x_r))
        assert abs(v_r - float(res.extras["values"][r])) < 1e-2, r


def test_problem_adapts_both_callable_conventions():
    """Problem adapts numpy->jax (device engines run host objectives via
    pure_callback) and jax->numpy (the sequential loop runs jax
    objectives) — the old convention split is gone."""
    enc = Encoding(n_vars=2, bits=8, lo=-10.0, hi=10.0)

    def f_np(x):                     # host convention: np.ndarray -> float
        return float(((np.asarray(x) - 1.5) ** 2).sum())

    p_np = Problem(fn=f_np, encoding=enc)
    assert p_np.kind == "numpy"
    x0 = np.asarray([4.0, -3.0])
    r_seq = solve(p_np, "sequential", x0=x0, max_iters=32)
    r_fused = solve(p_np, "fused", x0=jnp.asarray(x0), max_iters=32)
    assert np.isclose(float(r_seq.best_f), float(r_fused.best_f), atol=1e-4)

    p_jax = Problem.get("quadratic", n=2)
    assert p_jax.kind == "jax"
    host = p_jax.host_fn()
    assert isinstance(host(np.zeros(2)), float)
    r = solve(p_jax, "sequential", x0=x0, max_iters=32)
    assert isinstance(r, SolveResult)


def test_sequential_max_iters_guard():
    """The sequential engine honours the total-iteration guard the device
    engines carry."""
    prob = Problem.get("quadratic", n=2)
    guarded = solve(prob, Sequential(max_bits=14, max_total_iters=3),
                    x0=np.asarray([4.0, -3.0]))
    assert guarded.iterations <= 3


def test_exactly_one_cache_subsystem_remains():
    """Acceptance guard: no lru_cache / _cached_* engine memo left in
    dgo.py or distributed.py — core/cache.py is the only cache."""
    import inspect
    from repro.core import dgo, distributed
    for mod in (dgo, distributed):
        src = inspect.getsource(mod)
        assert "lru_cache" not in src, mod.__name__
        assert "_cached_" not in src, mod.__name__


# ---------------------------------------------------------------------------
# the cache subsystem itself
# ---------------------------------------------------------------------------

def test_compile_cache_counts_hits_misses_and_evicts():
    c = cache.CompileCache("t", maxsize=2)
    builds = []

    def build(tag):
        def _b():
            builds.append(tag)
            return tag
        return _b

    assert c.get(("a",), build("a")) == "a"
    assert c.get(("a",), build("a2")) == "a"       # hit: no rebuild
    assert c.stats() == {"hits": 1, "misses": 1, "uncached": 0,
                         "built": 1, "evictions": 0, "size": 1}
    # unhashable key: uncached build, counted
    assert c.get(["unhashable"], build("u")) == "u"
    assert c.uncached == 1 and c.built == 2
    # LRU eviction at maxsize=2, counted in stats
    c.get(("b",), build("b"))
    c.get(("c",), build("c"))                       # evicts ("a",)
    assert c.evictions == 1
    c.get(("a",), build("a3"))                      # rebuilt, evicts ("b",)
    assert builds == ["a", "u", "b", "c", "a3"]
    assert c.stats()["evictions"] == 2
    c.clear()
    assert c.stats() == {"hits": 0, "misses": 0, "uncached": 0,
                         "built": 0, "evictions": 0, "size": 0}


def test_cache_snapshot_for_serving_metrics():
    """The observability unit the serving metrics endpoint embeds:
    per-cache identity + counters, plus summed totals."""
    c = cache.CompileCache("snap-test", maxsize=1)
    c.get(("a",), lambda: "a")
    c.get(("b",), lambda: "b")                     # evicts ("a",)
    snap = c.snapshot()
    assert snap["name"] == "snap-test" and snap["maxsize"] == 1
    assert snap["evictions"] == 1 and snap["built"] == 2

    cache.get_cache("dgo.engine")                  # ensure one registered
    module_snap = cache.snapshot()
    assert set(module_snap) == {"caches", "totals"}
    assert "dgo.engine" in module_snap["caches"]
    assert module_snap["caches"]["dgo.engine"]["name"] == "dgo.engine"
    assert "evictions" in module_snap["totals"]


def test_totals_suffix_filters_memo_tables():
    """totals(suffix='.engine') counts compiled-engine caches only —
    Problem memo lookups must not inflate 'engines built' reports."""
    cache.clear()
    Problem.get("rastrigin", n=2)
    Problem.get("rastrigin", n=2)                  # memo hit
    eng = cache.totals(suffix=".engine")
    assert eng["built"] == 0                       # no engine compiled
    assert cache.totals()["hits"] >= 1             # the memo hit exists


def test_engine_cache_reused_across_solves():
    cache.clear()
    prob = Problem.get("ackley", n=2)
    strat = Fused(max_bits=10)
    x0 = jnp.asarray([2.0, -4.0])
    solve(prob, strat, x0=x0, max_iters=16)
    before = cache.get_cache("dgo.engine").stats()
    solve(prob, strat, x0=x0, max_iters=16)
    after = cache.get_cache("dgo.engine").stats()
    assert after["built"] == before["built"]        # no recompilation
    assert after["hits"] == before["hits"] + 1
    assert cache.totals()["built"] >= after["built"]
    assert "dgo.engine" in cache.stats()


def test_unhashable_key_builds_uncached():
    cache.clear()
    c = cache.get_cache("dgo.engine")
    val = c.get((["list"],), lambda: "built")      # tuple-of-list: unhashable
    assert val == "built" and c.uncached == 1
