"""Public-API snapshot: accidental surface changes must fail loudly.

Changing this list is an API decision, not a refactor side effect —
update it deliberately (and the README migration table with it).
"""
import dataclasses

import repro.core as core

PUBLIC_API = [
    # the solver facade (the supported surface)
    "Batched",
    "Clustered",
    "Distributed",
    "Fused",
    "Problem",
    "Sequential",
    "NonFiniteResult",
    "SolveRequest",
    "SolveResult",
    "Strategy",
    "engine_signature",
    "resolve_mesh",
    "result_is_finite",
    "solve",
    "solve_many",
    "strategy_names",
    # shared specs / subsystems
    "DGOConfig",
    "DGOResult",
    "BatchedResult",
    "Encoding",
    "cache",
    "objectives",
    # encoding / population primitives
    "binary_to_gray",
    "decode",
    "dgo_iteration",
    "encode",
    "generate_children",
    "generate_population",
    "gray_to_binary",
    "population_size",
    # engine builders (power users)
    "make_distributed_engine",
    "make_distributed_engine_batched",
    # subspace DGO (LM training path)
    "apply_subspace",
    "make_dgo_train_step",
    "materialize_winner",
]


def test_public_api_snapshot():
    assert sorted(core.__all__) == sorted(PUBLIC_API)


def test_public_api_resolves():
    for name in core.__all__:
        assert hasattr(core, name), name


def test_legacy_entry_points_removed():
    """The five deprecated wrappers completed their removal cycle (PR 3
    deprecation -> PR 4 removal per ROADMAP criteria): gone from the
    facade AND from the engine modules."""
    from repro.core import dgo, distributed
    for name in ("run", "run_clustered", "run_sequential",
                 "run_distributed", "run_distributed_batched"):
        assert not hasattr(core, name), name
        assert not hasattr(dgo, name), name
        assert not hasattr(distributed, name), name


def test_strategy_registry_snapshot():
    assert core.strategy_names() == (
        "batched", "clustered", "distributed", "fused", "sequential")


def test_objective_registry_snapshot():
    assert core.objectives.names() == (
        "ackley", "becker_lago", "griewank", "quadratic", "rastrigin",
        "remote_sensing", "sample2d", "shekel",
        "subspace-lm:codeqwen1.5-7b", "subspace-lm:deepseek-v2-236b",
        "subspace-lm:deepseek-v3-671b", "subspace-lm:gemma3-27b",
        "subspace-lm:granite-34b", "subspace-lm:phi-3-vision-4.2b",
        "subspace-lm:qwen2-1.5b", "subspace-lm:whisper-medium",
        "subspace-lm:xlstm-125m", "subspace-lm:zamba2-1.2b", "xor")


# ---------------------------------------------------------------------------
# SolveResult.extras: the per-strategy key sets are a documented contract
# (SolveResult docstring) — drift must fail here, not in a dashboard
# ---------------------------------------------------------------------------

# every strategy additionally stamps the result-hygiene flag "finite"
# (solve()'s on_nonfinite policy; see SolveResult docstring)
EXTRAS_CONTRACT = {
    "sequential": {"bits", "evaluations", "raw_trace", "finite"},
    "fused": {"bits", "evaluations", "finite"},
    "clustered": {"bits", "evaluations", "cluster_values", "winner",
                  "finite"},
    "distributed": {"bits", "bits_resolution", "history", "schedule",
                    "finite"},
    "batched": {"bits", "values", "restart_iterations", "trace", "best",
                "schedule", "finite"},
}


def test_solveresult_extras_contract_per_strategy():
    import jax.numpy as jnp
    import numpy as np

    prob = core.Problem.get("quadratic", n=2)
    x0 = jnp.asarray([4.0, -3.0])
    strategies = {
        "sequential": (core.Sequential(max_bits=10), np.asarray(x0)),
        "fused": (core.Fused(max_bits=10), x0),
        "clustered": (core.Clustered(n_clusters=2, max_bits=10),
                      jnp.stack([x0, x0 + 0.5])),
        "distributed": (core.Distributed(), x0),
        "batched": (core.Batched(), jnp.stack([x0, x0 + 0.5])),
    }
    assert set(strategies) == set(EXTRAS_CONTRACT) == set(
        core.strategy_names())
    for name, (strat, start) in strategies.items():
        res = core.solve(prob, strat, x0=start, max_iters=8)
        assert set(res.extras) == EXTRAS_CONTRACT[name], name


def test_solve_many_extras_contract():
    req = core.SolveRequest("quadratic", seed=0, max_iters=8)
    (res,) = core.solve_many([req], pad_to=2)
    assert set(res.extras) == {"bits", "schedule", "wave_slot", "wave_size",
                               "finite"}


def test_signature_problems_add_problem_signature_extra():
    """Problems carrying a semantic ``signature`` (the subspace-lm tuning
    family) report it in extras on EVERY solve path; signatureless
    problems keep the per-strategy key sets above exactly."""
    import jax.numpy as jnp

    base = core.Problem.get("quadratic", n=2)
    prob = dataclasses.replace(base, signature=("demo", "quadratic", 2))
    res = core.solve(prob, core.Fused(max_bits=10),
                     x0=jnp.asarray([4.0, -3.0]), max_iters=8)
    assert set(res.extras) == EXTRAS_CONTRACT["fused"] | {
        "problem_signature"}
    assert res.extras["problem_signature"] == ("demo", "quadratic", 2)
    (many,) = core.solve_many(
        [core.SolveRequest(prob, seed=0, max_iters=8)], pad_to=2)
    assert many.extras["problem_signature"] == ("demo", "quadratic", 2)
