"""One ``solve()`` front door for every DGO execution substrate.

The paper's pitch is ONE algorithm on many machines (sequential SPARC,
SIMD MP-1, MIMD NCUBE).  This module is that pitch as an API: a
:class:`Problem` says *what* to optimize, a :class:`Strategy` says *how*
(which engine / mesh / schedule), and :func:`solve` returns the same
:class:`SolveResult` pytree no matter which substrate did the work — so
strategies can be compared, swapped and registry-selected by string
exactly the way the distributed-GA evaluation literature asks for.

  >>> from repro.core.solver import solve
  >>> res = solve("rastrigin", strategy="clustered", seed=0)
  >>> float(res.best_f)                          # ~0.0

Strategies (string key -> class, see ``strategy_names()``):

  ``sequential``   one-child-at-a-time numpy loop (SPARC baseline)
  ``fused``        whole optimization in one jitted lax.while_loop
  ``clustered``    vmap of the fused engine over multi-starts (MP-1 cluster)
  ``distributed``  shard_map population distribution over a mesh, the
                   whole loop in one dispatch
  ``batched``      R lockstep restarts in one compiled distributed loop
                   (the serving path)

Resolution schedules: the schedule engines (sequential/fused/clustered)
default to the paper's step-5/6 escalation up to ``max_bits=16``.  The
distributed engines are fixed-resolution by default; passing ``max_bits``
to ``Distributed``/``Batched`` configures the ON-DEVICE schedule — the
whole escalation is folded into the engine's single compiled while_loop
via stacked per-resolution tables (paper step 5 on the mesh, one dispatch
per optimization), which is how they join resolution-schedule parity
with the rest.

The legacy entry points (``dgo.run``, ``run_clustered``,
``run_sequential``, ``distributed.run_distributed``,
``run_distributed_batched``) were removed after their deprecation cycle;
see README.md for the migration table.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, ClassVar, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.compat import AxisType, make_mesh, pure_callback
from repro.core import objectives as objectives_registry
from repro.core.cache import get_cache
from repro.core.dgo import DGOConfig
from repro.core.encoding import Encoding
from repro.core.objectives import Objective

__all__ = [
    "Batched", "Clustered", "Distributed", "Fused", "NonFiniteResult",
    "Problem", "Sequential", "SolveRequest", "SolveResult", "Strategy",
    "engine_signature", "result_is_finite", "solve", "solve_many",
    "strategy_names",
]


# ---------------------------------------------------------------------------
# Problem: what to optimize (absorbs objectives.Objective)
# ---------------------------------------------------------------------------

# exceptions that mean "this callable needs concrete arrays" (a host
# objective hitting an abstract tracer), as opposed to a genuinely buggy
# jax objective whose error must surface at construction time
_HOST_CONVENTION_ERRORS = tuple(
    getattr(jax.errors, name) for name in (
        "ConcretizationTypeError", "TracerArrayConversionError",
        "TracerBoolConversionError", "TracerIntegerConversionError")
    if hasattr(jax.errors, name))


def _detect_kind(fn: Callable, n_vars: int) -> str:
    """"jax" if ``fn`` traces on an (n_vars,) float32 abstract value,
    "numpy" if tracing fails only because the callable concretizes its
    argument (np.asarray/float/bool on a tracer).  Any other tracing
    error is a real bug in the objective and propagates."""
    try:
        jax.eval_shape(fn, jax.ShapeDtypeStruct((n_vars,), jnp.float32))
        return "jax"
    except _HOST_CONVENTION_ERRORS:
        return "numpy"
    except Exception as e:
        raise ValueError(
            f"objective failed to trace as a jax function ({type(e).__name__}: "
            f"{e}); if it is a host/numpy objective that cannot trace, pass "
            f"kind='numpy' explicitly") from e


_ADAPTER_ATTR = "__dgo_jax_adapter__"


def _host_to_jax(fn: Callable) -> Callable:
    """Wrap a host/numpy objective as a jax-traceable scalar function via
    ``pure_callback``.

    The adapter is memoized ON the function object itself (its lifetime
    is exactly the objective's — no global registry to leak), so two
    Problems wrapping the same host objective share ONE adapter and the
    engine compile cache keys on a stable callable instead of recompiling
    per Problem instance.  Objects that reject attributes (builtins,
    slotted callables) just get an unshared adapter.
    """
    adapter = getattr(fn, _ADAPTER_ATTR, None)
    if adapter is not None:
        return adapter

    def host(x):
        return np.asarray(fn(np.asarray(x)), np.float32).reshape(())

    def wrapped(x):
        return pure_callback(host, jax.ShapeDtypeStruct((), jnp.float32), x)

    try:
        setattr(fn, _ADAPTER_ATTR, wrapped)
    except (AttributeError, TypeError):
        pass
    return wrapped


@dataclasses.dataclass(frozen=True)
class Problem:
    """An optimization problem: objective + search box/resolution.

    ``fn`` maps ``(n_vars,) -> scalar`` and may follow either calling
    convention — jax-traceable (every device engine) or host/numpy (the
    old ``run_sequential`` contract).  The convention is detected once at
    construction (override with ``kind="jax"|"numpy"``) and adapted in
    both directions: ``jax_fn`` is what device engines consume,
    ``host_fn()`` what the sequential loop consumes.  ``f_opt``/``tol``
    (known optimum and success tolerance) ride along for tests and
    benchmarks, absorbing :class:`repro.core.objectives.Objective`.

    Expensive stateful objectives (the ``subspace-lm:*`` zoo tuning
    family) additionally carry

    * ``signature`` — a hashable SEMANTIC identity: two Problems with
      equal non-None signatures decode to the same objective values, so
      :func:`engine_signature` keys on it instead of the ``fn`` closure
      (independently-built Problems of one tuning spec share an engine
      bucket and one compilation);
    * ``materialize`` — maps a winning search point back to the
      objective's underlying state (winner model parameters, via
      ``core.subspace.materialize_winner``).
    """

    fn: Callable[[Any], Any]
    encoding: Encoding
    name: str = "custom"
    f_opt: float | None = None
    tol: float | None = None
    kind: str | None = None      # "jax" | "numpy" | None = auto-detect
    signature: tuple | None = None
    materialize: Callable[[Any], Any] | None = None

    def __post_init__(self):
        if self.kind is None:
            object.__setattr__(
                self, "kind", _detect_kind(self.fn, self.encoding.n_vars))
        if self.kind not in ("jax", "numpy"):
            raise ValueError(f"kind must be 'jax' or 'numpy', "
                             f"got {self.kind!r}")
        if self.kind == "numpy":
            object.__setattr__(self, "_jax_adapter", _host_to_jax(self.fn))

    @classmethod
    def from_objective(cls, obj: Objective) -> "Problem":
        return cls(fn=obj.fn, encoding=obj.encoding, name=obj.name,
                   f_opt=obj.f_opt, tol=obj.tol, kind="jax",
                   signature=obj.signature, materialize=obj.materialize)

    @classmethod
    def get(cls, name: str, n: int | None = None, **kwargs) -> "Problem":
        """Build from the objective registry: ``Problem.get("rastrigin",
        n=5)``.  Unknown names raise with the list of valid ones.

        Instances are MEMOIZED per semantic spec
        (``objectives.canonical_spec`` — factory defaults filled in, so
        ``get("rastrigin")`` and ``get("rastrigin", n=2)`` are one spec):
        the registry factories close over fresh callables on every call,
        and both the engine compile cache and the serving bucket
        signature key on callable identity — without memoization every
        name-built request would land in its own bucket and pay its own
        compilation.  Problems are frozen, so sharing is safe; unhashable
        kwargs (e.g. an array key) fall back to an unshared build.
        """
        key = objectives_registry.canonical_spec(name, n=n, **kwargs)
        return _PROBLEMS.get(key, lambda: cls.from_objective(
            objectives_registry.get(name, n=n, **kwargs)))

    def replace(self, **changes) -> "Problem":
        """Functional update (e.g. ``problem.replace(encoding=enc)``)."""
        return dataclasses.replace(self, **changes)

    @property
    def jax_fn(self) -> Callable:
        """The objective as a jax-traceable ``(n_vars,) -> ()`` function."""
        if self.kind == "jax":
            return self.fn
        return getattr(self, "_jax_adapter")

    def host_fn(self) -> Callable:
        """The objective as a host ``np.ndarray -> float`` function."""
        if self.kind == "numpy":
            return self.fn
        fn = self.fn

        def f_host(x):
            return float(fn(jnp.asarray(x, jnp.float32)))

        return f_host

    def random_x0(self, key: jax.Array, batch: int | None = None):
        """Uniform start point(s) in the search box."""
        enc = self.encoding
        shape = (enc.n_vars,) if batch is None else (batch, enc.n_vars)
        return jax.random.uniform(key, shape, minval=enc.lo, maxval=enc.hi)


# name-built Problems are shared per spec (see Problem.get): the registry
# would otherwise mint a fresh objective closure per call, splitting the
# engine compile cache and the serving bucket signature on every request
_PROBLEMS = get_cache("solver.problem", maxsize=128)


# ---------------------------------------------------------------------------
# SolveResult: the one result pytree every strategy populates
# ---------------------------------------------------------------------------

class SolveResult(NamedTuple):
    """Uniform result of :func:`solve` across every strategy.

    ``extras`` carries per-strategy detail keyed by short names.  The key
    set is a CONTRACT per strategy (pinned by ``tests/test_api.py`` so
    drift is caught, not discovered by a KeyError in a dashboard):

    =============  ========================================================
    strategy       extras keys
    =============  ========================================================
    sequential     ``bits``, ``evaluations``, ``raw_trace``
    fused          ``bits``, ``evaluations``
    clustered      ``bits``, ``evaluations``, ``cluster_values``, ``winner``
    distributed    ``bits``, ``bits_resolution``, ``history``, ``schedule``
    batched        ``bits``, ``values``, ``restart_iterations``, ``trace``,
                   ``best``, ``schedule``
    solve_many     ``bits``, ``schedule``, ``wave_slot``, ``wave_size``
                   (per-request results from the serving path)
    =============  ========================================================

    Per-restart arrays (``values``, ``restart_iterations``, the (R, T)
    ``trace``) exist ONLY on ``batched`` — every other strategy reports
    its single winner; ``cluster_values``/``winner`` are the clustered
    analogue.  ``schedule`` appears wherever a resolution schedule can be
    configured on the engine (the distributed family).

    Result hygiene: EVERY path (all strategies and ``solve_many``)
    additionally stamps ``finite`` — False when ``best_f`` or any trace
    value is non-finite (see :func:`result_is_finite`); pass
    ``on_nonfinite="raise"`` to :func:`solve`/:func:`solve_many` to turn
    that into a :class:`NonFiniteResult` instead of a flag.

    Subspace-family keys: a Problem carrying a semantic ``signature``
    (the ``subspace-lm:*`` zoo tuning family) adds ``problem_signature``
    — the ``("subspace-lm", arch, d, bits, alpha, batch, seq, seed,
    n_layers)`` spec tuple — to EVERY strategy's extras and to ``solve_many``
    results, so serving logs and checkpoints can name the tuning run
    they came from; the winning parameters themselves come from
    ``problem.materialize(res.best_x)``, not from extras.

    The tuple itself is a pytree, so it can cross jit/pmap boundaries
    and be tree-mapped.
    """

    best_x: jax.Array        # (n_vars,) best point found
    best_f: jax.Array        # () objective value at best_x
    iterations: int          # total accepted/attempted population steps
    trace: np.ndarray        # (T,) monotone best-value-so-far history
    extras: dict             # per-strategy detail (see strategy docstrings)


class NonFiniteResult(RuntimeError):
    """A solve produced a non-finite ``best_f`` or trace value (a NaN/inf
    objective — a real risk for the ``subspace-lm:*`` loss family) and the
    caller asked for ``on_nonfinite="raise"``.  The offending
    :class:`SolveResult` rides along as ``.result`` so callers can still
    inspect the trajectory."""

    def __init__(self, message: str, result: SolveResult):
        super().__init__(message)
        self.result = result


def result_is_finite(res: SolveResult) -> bool:
    """Whether ``best_f`` and every trace value of ``res`` are finite —
    the check behind ``extras["finite"]``.  (Engine trace buffers pad
    past ``iterations`` with the final value, so the whole buffer is
    checked without false alarms.)"""
    return bool(np.isfinite(np.float32(res.best_f))
                and np.isfinite(np.asarray(res.trace, np.float32)).all())


def _apply_result_hygiene(res: SolveResult, on_nonfinite: str,
                          context: str) -> SolveResult:
    """Stamp ``extras["finite"]`` and enforce the ``on_nonfinite`` policy
    (``"flag"`` — record and return; ``"raise"`` — NonFiniteResult), so a
    NaN objective can never masquerade as an optimum."""
    if on_nonfinite not in ("flag", "raise"):
        raise ValueError(f"on_nonfinite must be 'flag' or 'raise', "
                         f"got {on_nonfinite!r}")
    finite = result_is_finite(res)
    res.extras["finite"] = finite
    if not finite and on_nonfinite == "raise":
        raise NonFiniteResult(
            f"{context} produced a non-finite result "
            f"(best_f={float(np.float32(res.best_f))!r})", res)
    return res


# ---------------------------------------------------------------------------
# Strategy hierarchy + registry
# ---------------------------------------------------------------------------

STRATEGIES: dict[str, type] = {}


def _register(cls):
    STRATEGIES[cls.name] = cls
    return cls


def strategy_names() -> tuple[str, ...]:
    """Registered strategy keys, sorted."""
    return tuple(sorted(STRATEGIES))


class Strategy:
    """How to execute DGO.  Subclasses are frozen dataclasses carrying
    engine knobs; ``solve()`` accepts an instance, the class, or its
    string key."""

    name: ClassVar[str] = "abstract"

    def _solve(self, problem: Problem, *, key: jax.Array, x0,
               max_iters: int | None) -> SolveResult:
        raise NotImplementedError

    def _config(self, problem: Problem, max_iters: int | None,
                max_bits: int | None, bits_step: int) -> DGOConfig:
        return DGOConfig(
            encoding=problem.encoding,
            max_bits=16 if max_bits is None else max_bits,
            bits_step=bits_step,
            max_iters_per_resolution=512 if max_iters is None else max_iters)


@_register
@dataclasses.dataclass(frozen=True)
class Sequential(Strategy):
    """The paper's SPARC baseline: one-child-at-a-time numpy loop.

    extras: ``bits`` (final-resolution bit string), ``evaluations``.
    """

    name: ClassVar[str] = "sequential"
    max_bits: int | None = None       # None -> DGOConfig default (16)
    bits_step: int = 2
    time_budget_s: float | None = None
    max_total_iters: int | None = None   # total-iteration guard

    def _solve(self, problem, *, key, x0, max_iters):
        from repro.core import dgo
        cfg = self._config(problem, max_iters, self.max_bits, self.bits_step)
        if x0 is None:
            x0 = problem.random_x0(key)
        r = dgo._sequential_result(problem.host_fn(), cfg, np.asarray(x0),
                                   time_budget_s=self.time_budget_s,
                                   max_iters=self.max_total_iters)
        # the raw history is the parent value after each step, which can
        # rise at a resolution escalation (re-quantization); the uniform
        # SolveResult trace is best-so-far like every other strategy
        return SolveResult(best_x=r.x, best_f=r.value,
                           iterations=int(r.iterations),
                           trace=np.minimum.accumulate(r.trace),
                           extras={"bits": r.bits,
                                   "evaluations": r.evaluations,
                                   "raw_trace": r.trace})


@_register
@dataclasses.dataclass(frozen=True)
class Fused(Strategy):
    """Whole optimization (population steps AND resolution schedule) in
    one jitted ``lax.while_loop`` on one device.

    ``bucketed=True`` splits the schedule into a coarse and a fine width
    bucket compiled separately (``dgo.make_fused_engine_bucketed``):
    coarse resolutions then iterate at their own smaller buffer width
    instead of masking the full-width children matrix.  The trajectory is
    bitwise identical either way; schedules with no worthwhile split run
    the single compilation.

    extras: ``bits``, ``evaluations``.
    """

    name: ClassVar[str] = "fused"
    max_bits: int | None = None
    bits_step: int = 2
    bucketed: bool = False

    def _solve(self, problem, *, key, x0, max_iters):
        from repro.core import dgo
        cfg = self._config(problem, max_iters, self.max_bits, self.bits_step)
        run = dgo._bucketed_result if self.bucketed else dgo._fused_result
        r = run(problem.jax_fn, cfg, x0=x0, key=key)
        return SolveResult(best_x=r.x, best_f=r.value,
                           iterations=int(r.iterations), trace=r.trace,
                           extras={"bits": r.bits,
                                   "evaluations": r.evaluations})


@_register
@dataclasses.dataclass(frozen=True)
class Clustered(Strategy):
    """vmap of the fused engine over independent start points (the
    paper's MP-1 cluster mode); best-of wins.

    ``x0`` may pin heterogeneous starts as an ``(n_clusters, n_vars)``
    array; omitted, starts are drawn from the seed.

    extras: ``bits``, ``evaluations`` (summed), ``cluster_values``
    ((n_clusters,) final value per cluster), ``winner`` (index).
    """

    name: ClassVar[str] = "clustered"
    n_clusters: int = 8
    max_bits: int | None = None
    bits_step: int = 2

    def _solve(self, problem, *, key, x0, max_iters):
        from repro.core import dgo
        cfg = self._config(problem, max_iters, self.max_bits, self.bits_step)
        if x0 is not None:
            x0 = jnp.asarray(x0, jnp.float32)
            if x0.ndim != 2:
                raise ValueError(f"clustered starts must be "
                                 f"(n_clusters, n_vars), got {x0.shape}")
        r, aux = dgo._clustered_result(problem.jax_fn, cfg, self.n_clusters,
                                       key=key, x0s=x0)
        return SolveResult(best_x=r.x, best_f=r.value,
                           iterations=int(r.iterations),
                           trace=aux["winner_trace"],
                           extras={"bits": r.bits,
                                   "evaluations": r.evaluations,
                                   "cluster_values": aux["cluster_values"],
                                   "winner": aux["winner"]})


def _resolution_schedule(enc: Encoding, max_bits: int | None,
                         bits_step: int) -> list[int]:
    """The distributed engines' schedule: fixed at ``enc.bits`` when
    ``max_bits`` is None, else the paper's step-5 escalation."""
    if max_bits is None:
        return [enc.bits]
    cfg = DGOConfig(encoding=enc, max_bits=max_bits, bits_step=bits_step)
    return cfg.resolutions() or [enc.bits]


_DEFAULT_MESH = None


def _default_mesh():
    """All devices on a ("data",) axis — built once per process.

    ``jax.device_count()`` is the *global* count, so under a
    ``jax.distributed`` fleet (``launch/launcher.py --processes K``) this
    mesh spans every process automatically — the same launcher parameter
    that sets the per-process virtual-device count thereby sets the
    engine mesh geometry end to end.
    """
    global _DEFAULT_MESH
    if _DEFAULT_MESH is None:
        _DEFAULT_MESH = make_mesh((jax.device_count(),), ("data",),
                                  axis_types=(AxisType.Auto,))
    return _DEFAULT_MESH


_MESH_AXIS_NAMES = {1: ("data",), 2: ("data", "model"),
                    3: ("pod", "data", "model")}


def resolve_mesh(mesh=None):
    """Normalize a mesh-geometry parameter to a concrete ``Mesh``.

    Mesh geometry is a first-class engine parameter (it is a component of
    every engine cache key and of :func:`engine_signature`); this is the
    one normalization point.  Accepts:

    * ``None`` — all devices on ``("data",)`` (the shared default mesh);
    * an ``int`` N — an N-device ``("data",)`` mesh (N must equal the
      device count; the launcher's ``--devices`` flag is how N devices
      come to exist);
    * a shape tuple — ``(data,)``, ``(data, model)`` or
      ``(pod, data, model)`` with the conventional axis names;
    * ``((name, size), ...)`` pairs — explicit geometry;
    * a ``Mesh`` — passed through.

    ``jax.make_mesh`` caches, so equal geometries resolve to the *same*
    mesh object and compile-cache keys stay stable across calls.
    """
    if mesh is None:
        return _default_mesh()
    if isinstance(mesh, int):
        mesh = (mesh,)
    if isinstance(mesh, (tuple, list)):
        entries = tuple(mesh)
        if entries and all(isinstance(e, (tuple, list)) and len(e) == 2
                           for e in entries):
            names = tuple(str(n) for n, _ in entries)
            shape = tuple(int(s) for _, s in entries)
        elif all(isinstance(e, int) for e in entries):
            if len(entries) not in _MESH_AXIS_NAMES:
                raise ValueError(
                    f"shape-only mesh geometry supports 1-3 axes "
                    f"{tuple(_MESH_AXIS_NAMES.values())}, got {entries}; "
                    f"pass ((name, size), ...) pairs for custom axes")
            names = _MESH_AXIS_NAMES[len(entries)]
            shape = entries
        else:
            raise TypeError(f"bad mesh geometry: {mesh!r}")
        total = 1
        for s in shape:
            total *= s
        if total != jax.device_count():
            raise ValueError(
                f"mesh geometry {tuple(zip(names, shape))} needs {total} "
                f"devices but {jax.device_count()} exist — launch with "
                f"`python -m repro.launch.launcher --devices N -- ...` "
                f"to size the virtual fleet")
        return make_mesh(shape, names,
                         axis_types=(AxisType.Auto,) * len(names))
    return mesh


@_register
@dataclasses.dataclass(frozen=True)
class Distributed(Strategy):
    """Population distribution over a mesh (MP-1/NCUBE): the 2N-1
    children are sharded over ``pop_axes`` and the whole loop runs as one
    dispatch.

    Fixed-resolution by default (the one-entry schedule ``[enc.bits]``);
    setting ``max_bits`` folds the paper's step-5 escalation INTO the
    on-device while_loop (one compiled dispatch for the whole schedule).

    A solve is one engine call and one fetch, and no other device
    program: the engine takes the start point as it is (the
    full-quorum mask is placed on the mesh once), decodes the best point
    and cuts its bit string itself, and one ``device_get`` brings back
    every value the result needs.  ``best_x``, ``best_f`` and
    ``extras["bits"]`` are the engine's own JAX arrays, the host values
    of the first two already fetched; the types are those of before.

    extras: ``bits`` (final parent bit string at the best resolution, a
    JAX int8 array), ``history`` (raw per-iteration parent values, list
    of floats), ``schedule`` (resolutions run), ``bits_resolution``.
    """

    name: ClassVar[str] = "distributed"
    mesh: Any = None                  # None -> all devices on ("data",)
    pop_axes: tuple = ("data",)
    virtual_block: int = 256
    max_bits: int | None = None       # None -> fixed resolution
    bits_step: int = 2
    quorum_mask: Any = None

    def _solve(self, problem, *, key, x0, max_iters):
        from repro.core import distributed
        mesh = resolve_mesh(self.mesh)
        mi = 256 if max_iters is None else max_iters
        enc0 = problem.encoding
        if x0 is None:
            x0 = problem.random_x0(key)

        # the whole schedule goes down in one call, folded into the
        # engine's single compiled while_loop — no facade-level loop
        schedule = _resolution_schedule(enc0, self.max_bits, self.bits_step)
        run = distributed._run_distributed(
            problem.jax_fn, enc0, mesh, x0,
            pop_axes=tuple(self.pop_axes), max_iters=mi,
            virtual_block=self.virtual_block, quorum_mask=self.quorum_mask,
            res_bits=tuple(schedule))
        trace = np.minimum.accumulate(np.asarray(run.history, np.float32))
        return SolveResult(best_x=run.x, best_f=run.val,
                           iterations=len(run.history) - 1, trace=trace,
                           extras={"bits": run.bits,
                                   "bits_resolution": run.bits_resolution,
                                   "history": run.history,
                                   "schedule": tuple(schedule)})


@_register
@dataclasses.dataclass(frozen=True)
class Batched(Strategy):
    """R restarts advancing in lockstep inside ONE compiled distributed
    while_loop — the batched-request serving path (``serve.py --dgo``).

    ``x0`` pins start points as ``(R, n_vars)`` (its leading dim then
    overrides ``restarts``); omitted, ``restarts`` uniform starts are
    drawn from the seed.  Fixed-resolution by default; ``max_bits`` folds
    the resolution schedule into the same single dispatch (the batch
    escalates in lockstep), like :class:`Distributed`.

    extras: ``bits`` ((R, N) per-restart best points as final-resolution
    strings),
    ``values`` ((R,) per-restart best), ``restart_iterations`` ((R,)),
    ``trace`` ((R, T) per-restart monotone histories), ``best`` (winner
    index), ``schedule``.
    """

    name: ClassVar[str] = "batched"
    restarts: int = 8
    mesh: Any = None
    pop_axes: tuple = ("data",)
    virtual_block: int = 256
    max_bits: int | None = None
    bits_step: int = 2
    quorum_mask: Any = None

    def _solve(self, problem, *, key, x0, max_iters):
        from repro.core import distributed
        mesh = resolve_mesh(self.mesh)
        mi = 256 if max_iters is None else max_iters
        enc0 = problem.encoding
        if x0 is None:
            x0 = problem.random_x0(key, batch=self.restarts)
        x0s = np.asarray(x0, np.float32)
        if x0s.ndim != 2:
            raise ValueError(f"batched starts must be (R, n_vars), "
                             f"got {x0s.shape}")
        f = problem.jax_fn

        # one call, one dispatch: a multi-resolution schedule is folded
        # into the batched engine's while_loop (escalation in lockstep
        # across the whole batch) — no facade-level chaining loop
        schedule = _resolution_schedule(enc0, self.max_bits, self.bits_step)
        res = distributed._run_batched(
            f, enc0, mesh, x0s, pop_axes=tuple(self.pop_axes),
            max_iters=mi, virtual_block=self.virtual_block,
            quorum_mask=self.quorum_mask, res_bits=tuple(schedule))
        winner = res.best
        return SolveResult(
            best_x=jnp.asarray(res.best_xs[winner]),
            best_f=jnp.asarray(res.values[winner]),
            iterations=int(res.iterations.max()),
            trace=res.trace[winner],
            extras={"bits": jnp.asarray(res.bits),
                    "values": jnp.asarray(res.values),
                    "restart_iterations": jnp.asarray(res.iterations),
                    "trace": res.trace, "best": winner,
                    "schedule": tuple(schedule)})


# ---------------------------------------------------------------------------
# solve(): the front door
# ---------------------------------------------------------------------------

def as_problem(problem, **kwargs) -> Problem:
    """Coerce a Problem / Objective / registry name into a Problem."""
    if isinstance(problem, Problem):
        return problem
    if isinstance(problem, Objective):
        return Problem.from_objective(problem)
    if isinstance(problem, str):
        return Problem.get(problem, **kwargs)
    raise TypeError(f"cannot interpret {type(problem).__name__} as a "
                    f"Problem (want Problem, Objective, or registry name)")


def as_strategy(strategy) -> Strategy:
    """Coerce a Strategy instance / class / string key into an instance."""
    if isinstance(strategy, Strategy):
        return strategy
    if isinstance(strategy, type) and issubclass(strategy, Strategy):
        return strategy()
    if isinstance(strategy, str):
        if strategy not in STRATEGIES:
            raise ValueError(f"unknown strategy {strategy!r}; registered: "
                             f"{', '.join(strategy_names())}")
        return STRATEGIES[strategy]()
    raise TypeError(f"cannot interpret {type(strategy).__name__} as a "
                    f"Strategy (want Strategy, its class, or a string key)")


def solve(problem, strategy="fused", *, seed: int | jax.Array = 0,
          x0=None, max_iters: int | None = None,
          on_nonfinite: str = "flag") -> SolveResult:
    """Run DGO on ``problem`` under ``strategy``; the one front door.

    ``problem``: a :class:`Problem`, an ``objectives.Objective``, or a
    registry name (``"rastrigin"``).  ``strategy``: a :class:`Strategy`
    instance/class or string key (see ``strategy_names()``).

    ``seed`` drives random start points (an int, or a PRNG key for
    callers threading their own); ``x0`` pins the start instead —
    ``(n_vars,)``, or ``(R, n_vars)`` for clustered/batched.
    ``max_iters`` caps iterations per resolution (strategy default when
    None: 512 for the schedule engines, 256 for the distributed ones).

    ``on_nonfinite`` is the result-hygiene policy: every result is
    checked for non-finite ``best_f``/trace values and stamped with
    ``extras["finite"]``; ``"flag"`` (default) returns the flagged
    result, ``"raise"`` raises :class:`NonFiniteResult` — a NaN
    objective can never masquerade as an optimum either way.

    Every strategy returns the same :class:`SolveResult` pytree.  The
    call runs under the host span ``dgo.solve``.
    """
    with tracing.span("solve"):
        prob = as_problem(problem)
        strat = as_strategy(strategy)
        if x0 is not None:
            key = None               # pinned start: skip key construction
        elif isinstance(seed, (jax.Array, np.ndarray)):
            key = jnp.asarray(seed)
        else:
            key = jax.random.PRNGKey(int(seed))
        res = strat._solve(prob, key=key, x0=x0, max_iters=max_iters)
        if prob.signature is not None:      # subspace-family extras key
            res.extras["problem_signature"] = prob.signature
        return _apply_result_hygiene(res, on_nonfinite,
                                     f"solve({prob.name!r}, {strat.name!r})")


# ---------------------------------------------------------------------------
# solve_many(): heterogeneous requests over the batched engine
# ---------------------------------------------------------------------------

_DEFAULT_REQUEST_ITERS = 256     # the distributed engines' max_iters default


@dataclasses.dataclass(frozen=True)
class SolveRequest:
    """One optimization request for :func:`solve_many` / the serving
    subsystem (``repro.serving``).

    ``problem`` is anything :func:`as_problem` accepts (a
    :class:`Problem`, an ``Objective``, or a registry name).  ``x0`` pins
    the start point; omitted, it is derived from ``seed`` exactly the way
    a per-request ``solve(..., strategy=Batched(restarts=1), seed=seed)``
    would derive it, so batching requests never changes their answers.
    ``max_iters`` caps iterations (per resolution when the dispatch
    configures a schedule); ``priority`` orders the serving queue (higher
    first — ignored by a direct ``solve_many`` call, which preserves
    input order).  ``deadline_s`` is a TTL in seconds, stamped onto the
    serving handle at submit: an expired request fails fast with
    ``serving.DeadlineExceeded`` instead of occupying a wave slot
    (ignored by a direct ``solve_many`` call, which has no queue to
    expire from).
    """

    problem: Any
    seed: int = 0
    x0: Any = None
    max_iters: int | None = None
    priority: int = 0
    deadline_s: float | None = None

    def resolve(self) -> "SolveRequest":
        """Coerce ``problem`` to a :class:`Problem` and validate ``x0``
        against its encoding — errors surface at the submission boundary,
        so one malformed request can never poison the wave it would have
        been bucketed into."""
        prob = as_problem(self.problem)
        if self.x0 is not None:
            _check_request_x0(prob, self.x0)
        if prob is self.problem:
            return self
        return dataclasses.replace(self, problem=prob)


def engine_signature(problem, *, mesh=None, pop_axes=("data",),
                     virtual_block: int = 256, max_bits: int | None = None,
                     bits_step: int = 2) -> tuple:
    """The compile-cache bucket key of the batched engine that would serve
    ``problem`` under the given dispatch configuration.

    Two requests with equal signatures share one compiled engine (the
    tuple is exactly the static part of ``core.cache``'s
    ``distributed.engine`` key: objective identity, base encoding, mesh,
    population axes, virtual block and resolution schedule — everything
    except the wave width and iteration caps, which the serving scheduler
    chooses).  The serving scheduler buckets queued requests by this
    value; :func:`solve_many` groups by it internally.

    Objective identity is ``Problem.signature`` when set (the semantic
    model/subspace spec the zoo tuning family carries — independently
    built Problems of one tuning spec then land in ONE bucket), else the
    ``jax_fn`` callable (name-built toy Problems are memoized per spec by
    ``Problem.get``, so their callables are already shared).
    """
    prob = as_problem(problem)
    schedule = _resolution_schedule(prob.encoding, max_bits, bits_step)
    mesh = resolve_mesh(mesh)
    enc0 = prob.encoding.with_bits(schedule[0])
    fid = prob.signature if prob.signature is not None else prob.jax_fn
    return ("batched", fid, enc0, mesh, tuple(pop_axes),
            virtual_block, tuple(schedule))


def _as_request(req) -> SolveRequest:
    if isinstance(req, SolveRequest):
        return req.resolve()
    return SolveRequest(problem=as_problem(req))


def _check_request_x0(prob: Problem, x0) -> None:
    shape = np.shape(x0)
    if shape != (prob.encoding.n_vars,):
        raise ValueError(
            f"request x0 must be ({prob.encoding.n_vars},) for "
            f"problem {prob.name!r}, got {shape}")


def _request_x0(prob: Problem, req: SolveRequest) -> np.ndarray:
    """The request's start point on the host — pinned, or the SAME
    seed-derived draw a per-request ``solve(Batched(restarts=1),
    seed=...)`` would make (the one device call a request can bring)."""
    if req.x0 is not None:
        _check_request_x0(prob, req.x0)
        return np.asarray(req.x0, np.float32)
    key = jax.random.PRNGKey(int(req.seed))
    return np.asarray(prob.random_x0(key, batch=1))[0]


def _slot_result(res, slot: int, enc0: Encoding, schedule: tuple,
                 wave_size: int) -> SolveResult:
    """Per-slot SolveResult assembly — the same post-processing
    ``Batched._solve`` applies to its winner, applied to one slot, so a
    bucketed request's result is bitwise the per-request one.  Every
    field is a numpy slice of the wave's fetched arrays."""
    iters = int(res.iterations[slot])
    return SolveResult(
        best_x=res.best_xs[slot],
        best_f=res.values[slot],
        iterations=iters,
        trace=res.trace[slot][: iters + 1],
        extras={"bits": res.bits[slot], "schedule": schedule,
                "wave_slot": slot, "wave_size": wave_size})


class PendingWave:
    """One dispatched-but-unfetched wave from :func:`submit_wave`.

    JAX dispatch is asynchronous: the engine call behind
    :func:`submit_wave` returns device arrays whose values are still
    being computed.  :meth:`finalize` does the blocking part — the host
    fetch plus the per-slot result assembly and hygiene
    :func:`solve_many` would apply — and returns the per-request
    :class:`SolveResult` list (input order).  Splitting submission from
    result blocking is the serving pipeline's lever: a scheduler thread
    can assemble and submit the NEXT wave while the device still
    executes this one (``repro.serving.pipeline``).  Results are bitwise
    identical to a blocking :func:`solve_many` call — :meth:`finalize`
    IS the tail of ``solve_many``'s wave loop.

    After :meth:`finalize`, ``fetch`` holds the ``(wall, thread-CPU)``
    seconds it blocked on the device's results and ``host`` those of the
    rest: result post-processing and per-slot assembly.
    """

    def __init__(self, reqs, pending, enc0: Encoding, schedule: tuple,
                 width: int, on_nonfinite: str, contexts):
        self._reqs = reqs
        self._pending = pending
        self._enc0 = enc0
        self._schedule = schedule
        self._width = width
        self._on_nonfinite = on_nonfinite
        self._contexts = contexts
        self.fetch = (0.0, 0.0)
        self.host = (0.0, 0.0)

    def finalize(self) -> list[SolveResult]:
        """Block on the device results and assemble one
        :class:`SolveResult` per (active) request.  Raises whatever the
        dispatch raised — a device-side error surfaces HERE, at the
        fetch, not at submit."""
        start = tracing.now()
        res = self._pending.finish()
        with tracing.span("finalize.assemble"):
            out: list[SolveResult] = []
            for slot, req in enumerate(self._reqs):
                result = _slot_result(res, slot, self._enc0,
                                      self._schedule, self._width)
                if req.problem.signature is not None:
                    result.extras["problem_signature"] = \
                        req.problem.signature
                out.append(_apply_result_hygiene(
                    result, self._on_nonfinite, self._contexts[slot]))
        total = tracing.since(start)
        self.fetch = self._pending.fetch
        self.host = (total[0] - self.fetch[0], total[1] - self.fetch[1])
        return out


def submit_wave(requests, *, mesh=None, pop_axes=("data",),
                virtual_block: int = 256, max_bits: int | None = None,
                bits_step: int = 2, pad_to: int | None = None,
                quorum_mask=None, on_nonfinite: str = "flag",
                contexts=None) -> PendingWave:
    """Dispatch ONE wave of same-signature requests without blocking on
    its results; returns a :class:`PendingWave` whose ``finalize()``
    yields exactly what :func:`solve_many` would (``solve_many`` is this
    plus an immediate ``finalize()`` per wave).

    All requests must share one :func:`engine_signature` under the given
    dispatch configuration (``ValueError`` otherwise — mixed signatures
    need ``solve_many``'s grouping), and they must fit one wave:
    ``pad_to`` (the wave width, padded with inactive slots) must be
    ``>= len(requests)``.  ``contexts`` optionally labels each request
    for hygiene errors (``on_nonfinite="raise"``).
    """
    from repro.core import distributed

    with tracing.span("submit_wave.prepare"):
        reqs = [_as_request(r) for r in requests]
        if not reqs:
            raise ValueError("submit_wave needs at least one request")
        mesh = resolve_mesh(mesh)
        sigs = {engine_signature(req.problem, mesh=mesh,
                                 pop_axes=pop_axes,
                                 virtual_block=virtual_block,
                                 max_bits=max_bits, bits_step=bits_step)
                for req in reqs}
        if len(sigs) > 1:
            raise ValueError(
                f"submit_wave requests span {len(sigs)} engine "
                f"signatures; one wave serves one signature (use "
                f"solve_many to group)")
        width = pad_to if pad_to is not None else len(reqs)
        if width < len(reqs):
            raise ValueError(f"pad_to={pad_to} smaller than the "
                             f"{len(reqs)}-request wave")
        prob: Problem = reqs[0].problem
        schedule = tuple(_resolution_schedule(prob.encoding, max_bits,
                                              bits_step))
        enc0 = prob.encoding.with_bits(schedule[0])
        x0s = [_request_x0(req.problem, req) for req in reqs]
        caps = [req.max_iters if req.max_iters is not None
                else _DEFAULT_REQUEST_ITERS for req in reqs]
        n_pad = width - len(reqs)
        if n_pad:                     # padding: clones of slot 0,
            x0s += [x0s[0]] * n_pad   # masked inactive, zero budget
            caps += [0] * n_pad
        active = np.arange(width) < len(reqs)
        # static cap sizes the trace buffer only (slots gate on their
        # own cap); rounded up so cap mixes don't churn the compile key
        cap = max(64, -(-max(caps) // 64) * 64)
        x0 = np.stack(x0s)
    pending = distributed._submit_batched(
        prob.jax_fn, enc0, mesh, x0,
        pop_axes=tuple(pop_axes), max_iters=cap,
        virtual_block=virtual_block, quorum_mask=quorum_mask,
        res_bits=schedule, active=active, slot_iters=caps)
    if contexts is None:
        contexts = [f"submit_wave request {i} ({prob.name!r})"
                    for i in range(len(reqs))]
    return PendingWave(reqs, pending, enc0, schedule, width,
                       on_nonfinite, list(contexts))


def solve_many(requests, *, mesh=None, pop_axes=("data",),
               virtual_block: int = 256, max_bits: int | None = None,
               bits_step: int = 2, pad_to: int | None = None,
               quorum_mask=None,
               on_nonfinite: str = "flag") -> list[SolveResult]:
    """Solve N heterogeneous requests through the batched engine, one
    dispatch per signature bucket — results in input order.

    Requests are grouped by :func:`engine_signature` (problem spec +
    encoding + resolution schedule + mesh geometry); each group runs as
    waves of lockstep restarts in ONE compiled on-device while_loop with
    per-slot start points and iteration caps.  ``pad_to`` fixes the wave
    width: groups are chunked to it and the final partial wave is padded
    with inactive slots, so every wave of a signature reuses the SAME
    compiled engine (the serving scheduler passes its configured wave
    size).  ``pad_to=None`` dispatches each group at its own width.

    Parity contract: each request's ``best_x``/``best_f``/``iterations``/
    ``trace`` are bitwise identical to a per-request
    ``solve(problem, Batched(restarts=1, ...), ...)`` — slots advance
    independently inside the wave (``tests/test_serving.py`` pins this,
    including a partially-filled final wave).  Per-request extras:
    ``bits``, ``schedule``, ``wave_slot``, ``wave_size``, ``finite``.

    ``on_nonfinite`` applies the result-hygiene policy per request
    (``extras["finite"]`` + ``"flag"``/``"raise"`` — ``"raise"`` throws
    :class:`NonFiniteResult` for the FIRST non-finite request; the
    serving scheduler keeps the default ``"flag"`` and applies its own
    per-handle policy so one NaN cannot fail its wave-mates).
    """
    reqs = [_as_request(r) for r in requests]
    mesh = resolve_mesh(mesh)
    if pad_to is not None and pad_to < 1:
        raise ValueError(f"pad_to must be >= 1, got {pad_to}")

    groups: dict[tuple, list[int]] = {}
    for i, req in enumerate(reqs):
        sig = engine_signature(req.problem, mesh=mesh, pop_axes=pop_axes,
                               virtual_block=virtual_block,
                               max_bits=max_bits, bits_step=bits_step)
        groups.setdefault(sig, []).append(i)

    results: list[SolveResult | None] = [None] * len(reqs)
    for idxs in groups.values():
        prob: Problem = reqs[idxs[0]].problem
        width = pad_to if pad_to is not None else len(idxs)
        for start in range(0, len(idxs), width):
            wave = idxs[start: start + width]
            # submit + immediately finalize: solve_many IS the blocking
            # shape of submit_wave (the pipelined scheduler interleaves
            # the two phases across waves instead)
            pending = submit_wave(
                [reqs[i] for i in wave], mesh=mesh, pop_axes=pop_axes,
                virtual_block=virtual_block, max_bits=max_bits,
                bits_step=bits_step, pad_to=width,
                quorum_mask=quorum_mask, on_nonfinite=on_nonfinite,
                contexts=[f"solve_many request {i} ({prob.name!r})"
                          for i in wave])
            for i, result in zip(wave, pending.finalize()):
                results[i] = result
    return results
