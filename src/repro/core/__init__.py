"""DGO core: the paper's contribution as a composable JAX module.

The supported front door is :func:`repro.core.solve` — one call serving
every execution substrate (see ``core/solver.py``).  The legacy per-engine
entry points (``run``, ``run_clustered``, ``run_sequential``,
``run_distributed``, ``run_distributed_batched``) were removed after one
deprecation cycle (PR 3 -> PR 4); see README.md for the migration table.

``__all__`` is the public API snapshot — tests pin it
(``tests/test_api.py``) so accidental surface changes fail loudly.
"""
from repro.core import cache, objectives
from repro.core.encoding import Encoding, binary_to_gray, decode, encode, gray_to_binary
from repro.core.population import generate_children, generate_population, population_size
from repro.core.dgo import DGOConfig, DGOResult, dgo_iteration
from repro.core.distributed import (
    BatchedResult,
    make_distributed_engine,
    make_distributed_engine_batched,
)
from repro.core.solver import (
    Batched,
    Clustered,
    Distributed,
    Fused,
    NonFiniteResult,
    Problem,
    Sequential,
    SolveRequest,
    SolveResult,
    Strategy,
    engine_signature,
    resolve_mesh,
    result_is_finite,
    solve,
    solve_many,
    strategy_names,
)
from repro.core.subspace import apply_subspace, make_dgo_train_step, materialize_winner

__all__ = [
    # the solver facade (the supported surface)
    "Batched",
    "Clustered",
    "Distributed",
    "Fused",
    "NonFiniteResult",
    "Problem",
    "Sequential",
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
