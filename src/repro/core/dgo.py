"""DGO drivers: sequential (SPARC-baseline analogue), vectorized-jit, and
clustered multi-start.

The paper's algorithm (its "Outline of DGO", steps 1-6):

  1. pick an initial parent string, evaluate it;
  2. generate 2N-1 children by Gray-code segment inversion;
  3. take the child with the lowest function value;
  4. if it improves on the parent -> new parent, goto 2;
  5. else increase the resolution (bits per variable);
  6. stop past the maximum resolution.

Three engines live here, all reached through ``solver.solve()``:

* the sequential baseline (``Sequential`` strategy) — literal
  one-child-at-a-time Python/numpy loop. This is the O(n^2)-per-iteration
  baseline used by ``benchmarks/bench_complexity`` (paper Fig. 6) and the
  denominator of every speedup number (the paper's SPARC IV role).
* the fused single-device engine (``Fused`` strategy): the *entire*
  optimization — population generation, decode, evaluation, selection AND
  the resolution schedule — is one jitted ``lax.while_loop`` over a
  max-width bit buffer (``n_vars * max_bits`` bits). The active resolution
  is a loop-carried scalar indexing the stacked per-resolution tables of
  ``population.schedule_tables``; invalid tail children are masked to
  +inf. One compilation per (objective, config) instead of one per
  (N, bits) shape.
* the clustered engine (``Clustered`` strategy) — vmap of the same fused
  engine over independent start points, the paper's "cluster" mode on
  MP-1 (16K PEs >> 2N-1 for small problems).

The multi-device population distribution (shard_map over the mesh) lives in
``core/distributed.py``; it folds the same stacked-table schedule into its
on-device while_loop, and its per-shard inner loop can be the Pallas-fused
population step in ``kernels/popstep`` (the static-shape kernel twin of the
engine here — same generate -> decode -> evaluate -> argmin pass, tiled in
VMEM).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache import get_cache

from repro.core.encoding import Encoding, decode, decode_np
from repro.core.population import (
    generate_population,
    schedule_tables,
    segment_table,
)


@dataclasses.dataclass(frozen=True)
class DGOConfig:
    """Resolution schedule + iteration caps (paper steps 5/6)."""

    encoding: Encoding                 # starting resolution
    max_bits: int = 16                 # maximum resolution (paper step 6)
    bits_step: int = 2                 # resolution increment on stall
    max_iters_per_resolution: int = 512  # safety cap on step-4 loops

    def resolutions(self) -> list[int]:
        return list(range(self.encoding.bits, self.max_bits + 1, self.bits_step))


class DGOState(NamedTuple):
    """Carried across iterations at a fixed resolution."""

    parent_bits: jax.Array   # (N,) int8
    parent_val: jax.Array    # () f32
    improved: jax.Array      # () bool — did the last step improve?
    iters: jax.Array         # () i32


class DGOResult(NamedTuple):
    x: jax.Array             # (n_vars,) best point found
    value: jax.Array         # () f32
    bits: jax.Array          # best point's bits (N,) at the final resolution
    evaluations: int         # total function evaluations
    iterations: int          # total accepted/attempted steps
    trace: np.ndarray        # (iterations,) best value after each step


# ---------------------------------------------------------------------------
# one DGO iteration (paper steps 2-4) — the unit every driver shares
# ---------------------------------------------------------------------------

def dgo_iteration(f_batch: Callable[[jax.Array], jax.Array],
                  enc: Encoding,
                  parent_bits: jax.Array,
                  parent_val: jax.Array) -> DGOState:
    """Generate all 2N-1 children, evaluate, select (steps 2-4).

    ``f_batch`` maps (P, n_vars) -> (P,). Selection keeps the parent when no
    child is strictly better (paper step 4/5 boundary).
    """
    children = generate_population(parent_bits)          # (P, N)
    xs = decode(children, enc)                            # (P, n_vars)
    vals = f_batch(xs)                                    # (P,)
    best = jnp.argmin(vals)
    best_val = vals[best]
    improved = best_val < parent_val
    new_bits = jnp.where(improved, children[best], parent_bits)
    new_val = jnp.where(improved, best_val, parent_val)
    return DGOState(new_bits.astype(jnp.int8), new_val, improved, jnp.int32(1))


def dgo_resolution_step(f_batch: Callable[[jax.Array], jax.Array],
                        enc: Encoding,
                        max_iters: int,
                        parent_bits: jax.Array,
                        parent_val: jax.Array) -> tuple[DGOState, jax.Array]:
    """Run step-2..4 loop at one resolution until stall (jit-friendly).

    Returns the final state and a (max_iters,) trace of parent values
    (padded with the final value after the stall point).
    """

    def cond(carry):
        state, _ = carry
        return jnp.logical_and(state.improved, state.iters < max_iters)

    def body(carry):
        state, trace = carry
        nxt = dgo_iteration(f_batch, enc, state.parent_bits, state.parent_val)
        trace = trace.at[state.iters].set(nxt.parent_val)
        return (DGOState(nxt.parent_bits, nxt.parent_val, nxt.improved,
                         state.iters + 1), trace)

    trace0 = jnp.full((max_iters,), parent_val, dtype=jnp.float32)
    state0 = DGOState(parent_bits, parent_val, jnp.bool_(True), jnp.int32(0))
    (state, trace) = jax.lax.while_loop(cond, body, (state0, trace0))
    # pad the tail of the trace with the final value for clean plotting
    idx = jnp.arange(max_iters)
    trace = jnp.where(idx < state.iters, trace, state.parent_val)
    return state, trace


# ---------------------------------------------------------------------------
# fused single-compilation engine: the whole optimization (population steps
# AND the resolution schedule) inside one jitted lax.while_loop
# ---------------------------------------------------------------------------

class EngineState(NamedTuple):
    """Loop carry of the fused engine (one whole optimization)."""

    res_idx: jax.Array       # () i32 — index into the resolution schedule
    bits: jax.Array          # (n_max,) int8 — parent bit buffer (live prefix)
    val: jax.Array           # () f32 — current parent value
    best_val: jax.Array      # () f32 — monotone best-so-far
    best_x: jax.Array        # (n_vars,) f32 — argbest point
    improved: jax.Array      # () bool — did the last step improve?
    it_in_res: jax.Array     # () i32 — steps taken at this resolution
    iters: jax.Array         # () i32 — total steps
    evals: jax.Array         # () i32 — total function evaluations
    trace: jax.Array         # (T_max,) f32 — best value after each step


class _EngineStatic(NamedTuple):
    """Host-side constants baked into one engine compilation."""

    n_vars: int
    lo: float
    hi: float
    res_bits: tuple          # the resolution schedule (static)
    max_iters: int
    n_max: int               # n_vars * max(res_bits): the bit-buffer width
    p_max: int               # 2 * n_max - 1
    t_max: int               # trace capacity


def _engine_static(cfg: DGOConfig) -> _EngineStatic:
    enc0 = cfg.encoding
    # a degenerate schedule (max_bits < starting bits) still runs the
    # starting resolution instead of crashing
    res_bits = tuple(cfg.resolutions()) or (enc0.bits,)
    n_max = enc0.n_vars * res_bits[-1]
    return _EngineStatic(
        n_vars=enc0.n_vars, lo=enc0.lo, hi=enc0.hi, res_bits=res_bits,
        max_iters=cfg.max_iters_per_resolution, n_max=n_max,
        p_max=2 * n_max - 1,
        t_max=len(res_bits) * cfg.max_iters_per_resolution)


def _engine_tables(cfg: DGOConfig):
    """The engine's stacked per-resolution tables (shared escalation path:
    ``population.schedule_tables`` also backs the folded distributed and
    batched engines in ``core/distributed.py``)."""
    st = _engine_static(cfg)
    return st, schedule_tables(st.n_vars, st.res_bits, st.lo, st.hi)


def _engine_loop(f: Callable[[jax.Array], jax.Array], cfg: DGOConfig, *,
                 t_max: int | None = None):
    """The fused engine's while_loop as a resumable ``loop(s0)``.

    ``make_fused_engine`` wraps it with the standard initial state; the
    bucketed variant below also enters it mid-schedule with a carried
    state.  ``t_max`` overrides the trace-write clip bound (a resumed
    bucket carries the FULL-length trace buffer so its step indices keep
    lining up with the single-compilation engine's).
    """
    st, tables = _engine_tables(cfg)
    cap = st.t_max if t_max is None else t_max
    n_res = tables.n_res
    f_batch = jax.vmap(f)
    child_ids = jnp.arange(st.p_max, dtype=jnp.int32)

    def population_values(bits, res_idx):
        """All children at the current resolution: (vals, children)."""
        children = tables.children(bits, child_ids, res_idx)  # (P_max, N_max)
        xs = tables.decode(children, res_idx)                 # (P_max, n_vars)
        vals = f_batch(xs)                                    # (P_max,)
        vals = jnp.where(child_ids < tables.pop[res_idx], vals, jnp.inf)
        return vals, children

    def iterate(s: EngineState) -> EngineState:
        ri = jnp.minimum(s.res_idx, n_res - 1)
        vals, children = population_values(s.bits, ri)
        best = jnp.argmin(vals)
        best_val = vals[best]
        improved = best_val < s.val
        new_bits = jnp.where(improved, children[best], s.bits)
        new_val = jnp.where(improved, best_val, s.val)
        better_ever = new_val < s.best_val
        best_x = jnp.where(better_ever, tables.decode(new_bits, ri), s.best_x)
        best_run = jnp.where(better_ever, new_val, s.best_val)
        trace = s.trace.at[jnp.clip(s.iters, 0, cap - 1)].set(best_run)
        return EngineState(s.res_idx, new_bits, new_val, best_run, best_x,
                           improved, s.it_in_res + 1, s.iters + 1,
                           s.evals + tables.pop[ri], trace)

    def escalate(s: EngineState) -> EngineState:
        ri = jnp.minimum(s.res_idx, n_res - 1)
        nxt = jnp.minimum(s.res_idx + 1, n_res - 1)
        bits2 = tables.reencode(s.bits, ri, nxt)             # paper step 5
        val2 = f(tables.decode(bits2, nxt))
        better = val2 < s.best_val
        best_x = jnp.where(better, tables.decode(bits2, nxt), s.best_x)
        best_val = jnp.where(better, val2, s.best_val)
        return EngineState(s.res_idx + 1, bits2, val2.astype(jnp.float32),
                           best_val, best_x, jnp.bool_(True), jnp.int32(0),
                           s.iters, s.evals, s.trace)

    def cond(s: EngineState):
        return s.res_idx < n_res

    def body(s: EngineState) -> EngineState:
        stall = jnp.logical_or(~s.improved, s.it_in_res >= st.max_iters)
        return jax.lax.cond(stall, escalate, iterate, s)

    def loop(s0: EngineState) -> EngineState:
        return jax.lax.while_loop(cond, body, s0)

    return st, tables, loop


def make_fused_engine(f: Callable[[jax.Array], jax.Array],
                      cfg: DGOConfig) -> Callable:
    """Build ``engine(bits0, val0) -> EngineState``: full DGO in ONE
    jitted ``lax.while_loop``.

    Children of the current parent are generated at full buffer width by
    XOR against the stacked per-resolution pattern tables
    (``population.schedule_tables`` — the resolution index carried in the
    loop state gathers its table); decode is one exact matmul against the
    stacked weight tables; tail children beyond the live population
    2*n_vars*bits-1 are masked to +inf. This is the engine that the
    ``fused`` strategy drives and ``clustered`` vmaps; ``kernels/popstep``
    is its static-shape Pallas counterpart for the sharded path.
    """
    st, tables, loop = _engine_loop(f, cfg)

    def engine(bits0: jax.Array, val0: jax.Array) -> EngineState:
        s0 = EngineState(
            res_idx=jnp.int32(0), bits=bits0,
            val=val0.astype(jnp.float32), best_val=val0.astype(jnp.float32),
            best_x=tables.decode(bits0, jnp.int32(0)),
            improved=jnp.bool_(True), it_in_res=jnp.int32(0),
            iters=jnp.int32(0), evals=jnp.int32(0),
            trace=jnp.full((st.t_max,), val0, jnp.float32))
        return loop(s0)

    return engine


# engine compilations go through the repo-wide keyed cache subsystem
# (core/cache.py): one (objective, config) pair compiles once per process,
# unhashable objectives build uncached instead of raising, and hit/miss
# counters surface in BENCH_distributed.json
_ENGINES = get_cache("dgo.engine")


def _fused_engine(f: Callable, cfg: DGOConfig):
    return _ENGINES.get(("fused", f, cfg),
                        lambda: jax.jit(make_fused_engine(f, cfg)))


def _clustered_engine(f: Callable, cfg: DGOConfig):
    return _ENGINES.get(("clustered", f, cfg),
                        lambda: jax.jit(jax.vmap(make_fused_engine(f, cfg))))


# ---------------------------------------------------------------------------
# bucketed (two-compilation) fused engine: coarse resolutions at their own
# buffer width
# ---------------------------------------------------------------------------

def bucket_split(cfg: DGOConfig) -> int:
    """Default coarse-bucket length: resolutions at most HALF the final
    one.  Their buffer (and population) width is then <= half the
    single-compilation engine's, so each coarse iteration touches <= a
    quarter of the full-width children matrix.  0 or ``n_res`` means no
    worthwhile split (the bucketed entry points degrade to the plain
    fused engine)."""
    res = tuple(cfg.resolutions()) or (cfg.encoding.bits,)
    return sum(1 for b in res if 2 * b <= res[-1])


def make_fused_engine_bucketed(f: Callable[[jax.Array], jax.Array],
                               cfg: DGOConfig,
                               n_coarse: int | None = None) -> Callable:
    """``engine(bits0, val0) -> EngineState`` in TWO compilations.

    The single-compilation engine (``make_fused_engine``) masks every
    iteration to the maximum buffer width ``2*n_vars*max_bits-1`` even
    while the schedule is still at coarse resolutions.  This variant
    splits the schedule at ``n_coarse`` (default: :func:`bucket_split`):
    the coarse bucket compiles at its own (smaller) width — sharing its
    compilation with a plain fused engine of the truncated schedule —
    then a resume program replays the boundary escalation (paper step 5
    across the two table stacks) and runs the fine bucket, carrying
    best-so-far, counters and the full-length trace.  The trajectory is
    bitwise the single-compilation engine's (pinned by tests); ``bits0``
    must be encoded at the COARSE bucket's width (see
    ``_bucketed_result``).
    """
    res = tuple(cfg.resolutions()) or (cfg.encoding.bits,)
    if n_coarse is None:
        n_coarse = bucket_split(cfg)
    if not 0 < n_coarse < len(res):
        raise ValueError(
            f"n_coarse must split the {len(res)}-resolution schedule, "
            f"got {n_coarse} (no worthwhile split -> use the plain "
            f"fused engine)")
    cfg_a = dataclasses.replace(cfg, max_bits=res[n_coarse - 1])
    cfg_b = dataclasses.replace(
        cfg, encoding=cfg.encoding.with_bits(res[n_coarse]))
    st_full = _engine_static(cfg)
    st_a, tables_a = _engine_tables(cfg_a)
    _, tables_b, loop_b = _engine_loop(f, cfg_b, t_max=st_full.t_max)
    engine_a = _fused_engine(f, cfg_a)     # shared with plain fused(cfg_a)
    ri_a = jnp.int32(n_coarse - 1)
    r0_b = jnp.int32(0)

    def resume(bits_a, best_val, best_x, iters, evals, trace):
        # the single-compilation engine's escalate across the bucket
        # boundary, replayed across the two table stacks (reencode =
        # decode at the last coarse resolution, encode at the first fine
        # one), then the fine-bucket while_loop
        x_edge = tables_a.decode(bits_a, ri_a)
        bits0 = tables_b.encode(x_edge, r0_b)
        val2 = f(tables_b.decode(bits0, r0_b))
        better = val2 < best_val
        s0 = EngineState(
            res_idx=jnp.int32(0), bits=bits0, val=val2.astype(jnp.float32),
            best_val=jnp.where(better, val2, best_val),
            best_x=jnp.where(better, tables_b.decode(bits0, r0_b), best_x),
            improved=jnp.bool_(True), it_in_res=jnp.int32(0),
            iters=iters, evals=evals, trace=trace)
        return loop_b(s0)

    resume_c = _ENGINES.get(("fused-bucket-fine", f, cfg, n_coarse),
                            lambda: jax.jit(resume))
    t_pad = st_full.t_max - st_a.t_max

    def engine(bits0: jax.Array, val0: jax.Array) -> EngineState:
        sa = engine_a(bits0, val0)
        trace = jnp.concatenate(
            [sa.trace, jnp.full((t_pad,), val0, jnp.float32)])
        return resume_c(sa.bits, sa.best_val, sa.best_x, sa.iters,
                        sa.evals, trace)

    return engine


def _best_bits(best_x: jax.Array, cfg: DGOConfig) -> jax.Array:
    """Bit string of the best point, quantized to the final resolution —
    ``decode(result.bits, enc.with_bits(max))`` reconstructs the reported
    solution (up to half a final-lattice step when the best point was found
    at a coarser resolution)."""
    _, tables = _engine_tables(cfg)
    return tables.encode(best_x, jnp.int32(tables.n_res - 1))


def _result_from_state(s: EngineState, cfg: DGOConfig) -> DGOResult:
    iters = int(s.iters)
    trace = (np.asarray(s.trace[:iters]) if iters
             else np.asarray([float(s.best_val)]))
    return DGOResult(x=s.best_x, value=s.best_val,
                     bits=_best_bits(s.best_x, cfg),
                     evaluations=int(s.evals), iterations=iters, trace=trace)


# ---------------------------------------------------------------------------
# vectorized single-device driver (one compilation per optimization)
# ---------------------------------------------------------------------------

def _fused_result(f: Callable[[jax.Array], jax.Array],
                  cfg: DGOConfig,
                  x0: jax.Array | None = None,
                  key: jax.Array | None = None) -> DGOResult:
    """Full DGO through the fused engine: generation, evaluation, selection
    and the resolution schedule all inside one jitted while_loop.

    ``f`` maps (n_vars,) -> scalar; it is vmapped over the population.
    """
    enc0 = cfg.encoding
    if x0 is None:
        if key is None:
            key = jax.random.PRNGKey(0)
        x0 = jax.random.uniform(key, (enc0.n_vars,), minval=enc0.lo,
                                maxval=enc0.hi)
    _, tables = _engine_tables(cfg)
    r0 = jnp.int32(0)
    bits0 = tables.encode(jnp.asarray(x0, jnp.float32), r0)
    val0 = f(tables.decode(bits0, r0))
    state = _fused_engine(f, cfg)(bits0, val0)
    return _result_from_state(state, cfg)


def _bucketed_result(f: Callable[[jax.Array], jax.Array],
                     cfg: DGOConfig,
                     x0: jax.Array | None = None,
                     key: jax.Array | None = None) -> DGOResult:
    """``_fused_result`` through the two-compilation bucketed engine.

    Bitwise the fused result (the bucket boundary replays the same
    escalation); schedules with no worthwhile split (:func:`bucket_split`
    returns 0 or everything) fall back to the plain fused engine.
    """
    res = tuple(cfg.resolutions()) or (cfg.encoding.bits,)
    n_coarse = bucket_split(cfg)
    if not 0 < n_coarse < len(res):
        return _fused_result(f, cfg, x0=x0, key=key)
    enc0 = cfg.encoding
    if x0 is None:
        if key is None:
            key = jax.random.PRNGKey(0)
        x0 = jax.random.uniform(key, (enc0.n_vars,), minval=enc0.lo,
                                maxval=enc0.hi)
    # start bits/value encoded at the COARSE bucket's width — identical
    # live prefix to the full-width encoding (the tail is exact zeros)
    cfg_a = dataclasses.replace(cfg, max_bits=res[n_coarse - 1])
    _, tables_a = _engine_tables(cfg_a)
    r0 = jnp.int32(0)
    bits0 = tables_a.encode(jnp.asarray(x0, jnp.float32), r0)
    val0 = f(tables_a.decode(bits0, r0))
    state = make_fused_engine_bucketed(f, cfg, n_coarse)(bits0, val0)
    return _result_from_state(state, cfg)


# ---------------------------------------------------------------------------
# clustered multi-start (paper's MP-1 cluster mode)
# ---------------------------------------------------------------------------

def _clustered_result(f: Callable[[jax.Array], jax.Array],
                      cfg: DGOConfig,
                      n_clusters: int,
                      key: jax.Array | None = None,
                      x0s: jax.Array | None = None
                      ) -> tuple[DGOResult, dict]:
    """Independent DGO instances from random starts; best-of wins.

    vmap of the fused engine over the cluster axis — every cluster runs its
    entire resolution schedule inside the same compiled while_loop; on
    hardware the cluster axis is laid over spare devices (see
    core/distributed.py: the pod axis).

    ``x0s`` (n_clusters, n_vars) pins heterogeneous start points (the
    single-device analogue of the batched distributed serving path);
    omitted, starts are drawn uniformly from ``key``.

    Returns the legacy-shaped :class:`DGOResult` (``trace`` = per-cluster
    final values) plus an aux dict with the winner's own step trace.
    """
    enc0 = cfg.encoding
    _, tables = _engine_tables(cfg)
    if x0s is None:
        if key is None:
            raise ValueError("clustered DGO needs either key or x0s")
        keys = jax.random.split(key, n_clusters)
        x0s = jax.vmap(lambda k: jax.random.uniform(
            k, (enc0.n_vars,), minval=enc0.lo, maxval=enc0.hi))(keys)
    else:
        x0s = jnp.asarray(x0s, jnp.float32)
        if x0s.shape[0] != n_clusters:
            raise ValueError(f"x0s has {x0s.shape[0]} rows for "
                             f"n_clusters={n_clusters}")
    r0 = jnp.int32(0)
    bits0 = tables.encode(x0s, r0)                           # (C, n_max)
    vals0 = jax.vmap(f)(tables.decode(bits0, r0))

    states = _clustered_engine(f, cfg)(bits0, vals0)
    winner = int(jnp.argmin(states.best_val))
    w_iters = int(states.iters[winner])
    winner_trace = (np.asarray(states.trace[winner][:w_iters]) if w_iters
                    else np.asarray([float(states.best_val[winner])]))
    result = DGOResult(x=states.best_x[winner],
                       value=states.best_val[winner],
                       bits=_best_bits(states.best_x[winner], cfg),
                       evaluations=int(jnp.sum(states.evals)),
                       iterations=int(jnp.max(states.iters)),
                       trace=np.asarray(states.best_val))
    aux = {"cluster_values": np.asarray(states.best_val),
           "winner": winner, "winner_trace": winner_trace}
    return result, aux


# ---------------------------------------------------------------------------
# sequential reference — the paper's SPARC-IV-style baseline
# ---------------------------------------------------------------------------

def _sequential_result(f: Callable[[np.ndarray], float],
                       cfg: DGOConfig,
                       x0: np.ndarray,
                       time_budget_s: float | None = None,
                       max_iters: int | None = None) -> DGOResult:
    """One-child-at-a-time DGO in plain numpy.

    This is deliberately *not* vectorized: per iteration it does 2N-1
    sequential (transform + evaluate) passes of O(N) work each — the O(n^2)
    structure of the paper's Fig. 6. Used as the speedup denominator.

    ``f`` follows the host convention ``np.ndarray -> float`` (the solver
    facade adapts jax objectives via ``Problem.host_fn``).  Children are
    decoded onto the same float32 lattice as every engine
    (``encoding.decode_np``), so the reference and the engines evaluate
    the same points.  ``max_iters``
    caps TOTAL iterations across the whole resolution schedule — the same
    runaway guard the device engines carry.
    """
    enc0 = cfg.encoding

    def np_b2g(b):
        g = b.copy()
        g[1:] ^= b[:-1]
        return g

    def np_g2b(g):
        return np.cumsum(g) % 2

    def np_encode(x, enc):
        level = np.clip(np.round((x - enc.lo) / (enc.hi - enc.lo)
                                 * (enc.levels - 1)), 0, enc.levels - 1)
        level = level.astype(np.int64)
        shifts = np.arange(enc.bits - 1, -1, -1)
        return ((level[:, None] >> shifts) & 1).reshape(-1).astype(np.int8)

    t_start = time.perf_counter()
    bits = np_encode(np.asarray(x0, np.float64), enc0)
    val = float(f(decode_np(bits, enc0)))
    evals, iters = 1, 0
    trace = [val]
    best_run_val, best_run_bits, best_run_enc = val, bits, enc0

    prev_enc = enc0
    for res in cfg.resolutions():
        enc = enc0.with_bits(res)
        if enc.bits != prev_enc.bits:
            bits = np_encode(decode_np(bits, prev_enc), enc)
            val = float(f(decode_np(bits, enc)))
        n = enc.n_bits
        table = segment_table(n)
        improved = True
        it = 0
        while improved and it < cfg.max_iters_per_resolution:
            if max_iters is not None and iters >= max_iters:
                break
            improved = False
            gray = np_b2g(bits)
            best_val, best_bits = val, bits
            for c in range(2 * n - 1):           # the sequential hot loop
                mask = np.zeros(n, np.int8)
                mask[table[c, 0]: table[c, 1]] = 1
                child = np_g2b(gray ^ mask)       # O(N) transform
                v = float(f(decode_np(child, enc)))
                evals += 1
                if v < best_val:
                    best_val, best_bits = v, child
            if best_val < val:
                val, bits = best_val, best_bits
                improved = True
            it += 1
            iters += 1
            trace.append(val)
            if time_budget_s and time.perf_counter() - t_start > time_budget_s:
                break
        # best-so-far across resolutions: step-5 re-quantization can raise
        # the parent value, so remember the best point like the fused
        # engine's monotone tracking does
        if val < best_run_val:
            best_run_val, best_run_bits, best_run_enc = val, bits, enc
        prev_enc = enc
        if time_budget_s and time.perf_counter() - t_start > time_budget_s:
            break
        if max_iters is not None and iters >= max_iters:
            break

    return DGOResult(x=jnp.asarray(decode_np(best_run_bits, best_run_enc)),
                     value=jnp.float32(best_run_val),
                     bits=jnp.asarray(best_run_bits),
                     evaluations=evals, iterations=iters,
                     trace=np.asarray(trace))
