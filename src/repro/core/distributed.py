"""Distributed DGO: the paper's MP-1/NCUBE population distribution on a mesh.

Mapping (DESIGN.md §2):

  MasPar PE array          -> mesh shards (shard_map over population axes)
                              x per-chip vector lanes (vmap inside the shard)
  ACU broadcast of parent  -> parent string replicated into every shard
                              (in_specs=P()); the *winner* is never broadcast
                              as bits — only its child-id travels (cheaper
                              than the paper's string broadcast; children are
                              deterministic so every shard can regenerate it)
  rank() / cube-reduction  -> all_gather of per-shard (value, child-id) pairs
                              — a few bytes per shard, O(log P) on the torus
  NCUBE virtual processing -> ceil(P / n_shards) children per shard, chunked
                              by an inner scan when the per-shard block
                              exceeds ``virtual_block`` (the paper's
                              "each PE simulates ceil((2n-1)/64) processors")
  dropped / straggling PE  -> shard quorum mask: masked shards contribute
                              +inf; the round proceeds and the missed
                              children are regenerated next round (DESIGN §6)

The loop (DESIGN §2, the MP-1 running the whole generate->evaluate->rank
loop on the PE array) is a ``lax.while_loop`` traced *inside*
``shard_map``. Convergence ("no child improved") is decided on device from
the replicated reduce result, and the monotone value history lives in a
device trace buffer fetched once after the loop exits: one dispatch per
optimization. The paper's cluster mode over concurrent requests is the
batched engine: R independent restarts advance in lockstep inside ONE
while_loop, the restart axis riding the shard-local inner loop as a
leading batch dimension, with a single compilation and a single reduce
per iteration.

Resolution schedules (paper step 5) are FOLDED into both engines:
``res_bits`` stacks one XOR-pattern/decode table per resolution
(``population.schedule_tables``) and the while_loop carries a resolution
counter that indexes them, so escalation happens inside ``shard_map`` and
a whole multi-resolution optimization is one compiled dispatch.  A fixed
resolution is the one-entry schedule ``(enc.bits,)``: the loop ends at
its first stall and never escalates.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import tracing
from repro.compat import axis_size, process_index, shard_map
from repro.core.cache import get_cache
from repro.core.encoding import (
    Encoding, decode, decode_np, encode, encode_np, lattice_step,
    place_levels,
)
from repro.core.population import schedule_tables


def _place_inputs(mesh: Mesh, *arrays):
    """Replicate host inputs onto a process-spanning mesh explicitly.

    Single-process meshes let jit place uncommitted arrays itself; under
    a ``jax.distributed`` fleet (launcher ``--processes K``) each worker
    must ``device_put`` its (identical) host copy of the request batch
    onto its own shard of the global device set before the engines run.
    Replicated spec ``P()``: engines shard *populations*, not requests —
    every input is full-size on every device.
    """
    me = process_index()
    if all(d.process_index == me for d in mesh.devices.flat):
        return arrays
    sharding = NamedSharding(mesh, P())
    return tuple(jax.device_put(a, sharding) for a in arrays)


def _flat_axis_index(axis_names: Sequence[str]) -> jax.Array:
    """Row-major flat index of this shard across the given mesh axes."""
    idx = jnp.int32(0)
    for name in axis_names:
        idx = idx * axis_size(name) + jax.lax.axis_index(name)
    return idx


def _axis_prod(mesh: Mesh, axis_names: Sequence[str]) -> int:
    n = 1
    for name in axis_names:
        n *= mesh.shape[name]
    return n


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name``: jit names its program ``jit_<name>``, which
    is how a profile of the device finds the program after a refactor."""
    def named(*args):
        return fn(*args)

    named.__name__ = named.__qualname__ = name
    return named


def _parent_values(f: Callable[[jax.Array], jax.Array], enc: Encoding,
                   x0s: jax.Array, active: jax.Array) -> jax.Array:
    """The objective at each start point of a wave, snapped to the lattice
    of ``enc`` — traced inside the wave engine, under ``dgo.parent_eval``.

    The rows go through ``lax.map``, whose body is the objective at shape
    ``(n_vars,)``: the one objective call of an engine that would otherwise
    follow the wave width R.  XLA's fusion choices vary with batch width
    (batch-1 matvec paths), so a batched ``f_batch(parents)`` at R=1 vs R=2
    can drift by a ULP for reduction-heavy objectives (the subspace-lm
    tuning family), breaking the serving contract that a wave slot is
    bitwise its per-request solve.  Inactive (padding) rows skip the
    objective and read +inf: their results are discarded."""
    with tracing.scope("parent_eval"):
        snapped = decode(encode(x0s, enc), enc)

        def row(args):
            x, live = args
            return jax.lax.cond(
                live, lambda x: jnp.asarray(f(x), jnp.float32),
                lambda x: jnp.float32(jnp.inf), x)

        return jax.lax.map(row, (snapped, active))


class _ShardPlan(NamedTuple):
    """Static population-distribution geometry shared by every driver."""

    n_shards: int
    pop: int
    chunk: int       # children per shard (paper's virtual-processing count)
    n_blocks: int    # inner scan length
    block: int       # children per scan step


def _shard_plan(pop: int, mesh: Mesh, pop_axes: Sequence[str],
                virtual_block: int) -> _ShardPlan:
    n_shards = _axis_prod(mesh, pop_axes)
    chunk = math.ceil(pop / n_shards)
    n_blocks = math.ceil(chunk / virtual_block)
    block = math.ceil(chunk / n_blocks)
    return _ShardPlan(n_shards, pop, chunk, n_blocks, block)


def _resolve_res_bits(enc: Encoding, res_bits) -> tuple:
    """Normalize a schedule argument: ``None`` -> fixed at ``enc.bits``."""
    if res_bits is None:
        return (enc.bits,)
    res_bits = tuple(int(b) for b in res_bits)
    return res_bits or (enc.bits,)


def _build_shard_schedule_step(f_batch: Callable[[jax.Array], jax.Array],
                               tables, plan: _ShardPlan,
                               pop_axes: Sequence[str]):
    """One DGO iteration of the single-solve engine, as seen from inside
    ``shard_map``.

    Returns ``prepare(quorum_mask) -> step(parent_bits, parent_val, it,
    res_idx) -> (new_bits, new_val, improved)``: the quorum lookup is bound
    in ``prepare``, outside the engine's while_loop.  ``it`` rotates the
    virtual-processor assignment: on round ``it`` the shard covers slot
    ``(shard + it) % n_shards``.  With every shard alive the union of slots
    is the whole population each round, so rotation is invisible; with a
    dead shard it guarantees no child is *permanently* shadowed — the
    missed children are "regenerated next round" (DESIGN §6) by a
    surviving shard.  Winner selection is lexicographic (value, child id),
    so the result does not depend on which shard evaluated which slot.

    The step takes the resolution index carried in the engine's while_loop
    state and gathers the active resolution's XOR-pattern/decode tables
    from the stacked ``population.schedule_tables`` arrays — the hoisted
    "fused" inner generalized over the schedule axis.  Geometry (chunk /
    rotation) is planned at the FINEST resolution; at coarser resolutions
    the tail slots fall beyond the live population and are masked to +inf,
    exactly like the fused single-device engine's tail children.
    """
    p_max, chunk, n_blocks, block = (plan.pop, plan.chunk, plan.n_blocks,
                                     plan.block)
    n_shards = plan.n_shards

    def prepare(quorum_mask: jax.Array):
        shard = _flat_axis_index(pop_axes)
        alive = quorum_mask[shard]

        def step(parent_bits: jax.Array, parent_val: jax.Array,
                 it: jax.Array, res_idx: jax.Array):
            pat = tables.patterns[res_idx]            # (p_max, n_max)
            pop = tables.pop[res_idx]                 # () i32, live children
            # per-resolution virtual-processing chunk, computed on device:
            # each shard owns exactly ceil(pop/n_shards) children of the
            # LIVE population (offsets past it are masked), so the
            # child->shard assignment — and therefore the trajectory under
            # any quorum mask — is identical to re-planning per resolution
            chunk_r = jax.lax.div(pop + n_shards - 1, jnp.int32(n_shards))
            base = jax.lax.rem(shard + it, n_shards) * chunk_r

            def block_best(offs):
                """(best value, best id) of one offset block, ties ->
                smallest id."""
                ids = base + offs
                valid = (offs < chunk_r) & (ids < pop) & alive
                ids_c = jnp.minimum(ids, p_max - 1)
                children = jnp.bitwise_xor(parent_bits[None, :], pat[ids_c])
                xs = tables.decode(children, res_idx)
                vals = jnp.where(valid, f_batch(xs), jnp.inf)
                v = jnp.min(vals)
                gid = jnp.min(jnp.where(vals == v, ids_c, p_max))
                return v, gid

            if n_blocks == 1:
                local_val, local_id = block_best(jnp.arange(chunk))
            else:
                def eval_block(carry, b):
                    best_val, best_id = carry
                    v, gid = block_best(b * block + jnp.arange(block))
                    better = jnp.logical_or(
                        v < best_val, (v == best_val) & (gid < best_id))
                    return (jnp.where(better, v, best_val),
                            jnp.where(better, gid, best_id)), None

                init = (jnp.asarray(jnp.inf, jnp.float32), jnp.int32(p_max))
                (local_val, local_id), _ = jax.lax.scan(
                    eval_block, init, jnp.arange(n_blocks))

            # cube-reduction analogue: ONE gather of packed (val, id)
            # pairs over the pop axes — ids are < 2N-1 << 2^24, so the f32
            # round-trip is exact
            packed = jnp.stack([local_val, local_id.astype(jnp.float32)])
            for ax in pop_axes:
                packed = jax.lax.all_gather(packed, ax)
            packed = packed.reshape(-1, 2)
            win_val = jnp.min(packed[:, 0])
            ids = packed[:, 1].astype(jnp.int32)
            win_id = jnp.min(jnp.where(packed[:, 0] == win_val, ids, p_max))

            improved = win_val < parent_val
            win_bits = jnp.bitwise_xor(
                parent_bits, pat[jnp.minimum(win_id, p_max - 1)])
            new_bits = jnp.where(improved, win_bits,
                                 parent_bits).astype(jnp.int8)
            new_val = jnp.where(improved, win_val, parent_val)
            return new_bits, new_val, improved

        return step

    return prepare


def make_distributed_engine(f_batch: Callable[[jax.Array], jax.Array],
                            enc: Encoding,
                            mesh: Mesh,
                            pop_axes: Sequence[str] = ("data",),
                            max_iters: int = 256,
                            virtual_block: int = 256,
                            res_bits: Sequence[int] | None = None):
    """Build the on-device distributed engine: the ENTIRE optimization —
    every population step and the paper's step-5 escalation through the
    resolution schedule ``res_bits`` (``None`` -> the one resolution
    ``enc.bits``) — as one ``lax.while_loop`` traced inside ``shard_map``.

    Returns ``engine(x0, quorum_mask) -> (bits_at, best_val, best_res_idx,
    iters, trace, best_x)`` where ``bits_at[r]`` is the best parent's bit
    string at resolution ``res_bits[r]`` (the live prefix of the max-width
    buffer, ``n_vars * res_bits[r]`` bits; ``bits_at[best_res_idx]`` is
    the one it was found at), ``best_x`` that parent decoded at its own
    resolution, and ``trace`` has capacity ``len(res_bits) * max_iters +
    1`` (raw per-iteration parent values, ``trace[0]`` the starting value
    and entries past ``iters`` padded with the final value; escalation
    re-encodes are not recorded).  The initial encode/evaluation happens
    inside the program and the best point is decoded there, so a solve
    needs no device program after the engine's, and no transfer back to
    the device.  Convergence — the all-gathered winner failing to beat
    the parent — is decided on device from values replicated across
    shards, so every shard exits the loop on the same iteration.  The
    resolution counter rides the while_loop state and indexes the stacked
    ``population.schedule_tables``: the whole schedule is ONE dispatch and
    ONE compilation.
    """
    schedule = _resolve_res_bits(enc, res_bits)
    tables = schedule_tables(enc.n_vars, schedule, enc.lo, enc.hi)
    plan = _shard_plan(tables.p_max, mesh, pop_axes, virtual_block)
    prepare = _build_shard_schedule_step(f_batch, tables, plan,
                                         pop_axes)
    n_shards = plan.n_shards
    n_res = tables.n_res
    t_max = n_res * max_iters + 1
    steps = np.asarray([lattice_step(enc.with_bits(b))
                        for b in schedule], np.float32)

    def shard_schedule_engine(x0, quorum_mask):
        r0 = jnp.int32(0)
        bits0 = tables.encode(x0, r0)
        val0 = f_batch(tables.decode(bits0, r0)[None])[0]
        val0 = val0.astype(jnp.float32)
        one_step = prepare(quorum_mask)
        stall_limit = jnp.where(jnp.all(quorum_mask), 1, n_shards)

        def stalled(s):
            stalls, it_in_res = s[6], s[7]
            return jnp.logical_or(stalls >= stall_limit,
                                  it_in_res >= max_iters)

        def cond(s):
            res_idx = s[0]
            last = res_idx >= n_res - 1
            return ~jnp.logical_and(last, stalled(s))

        def iterate(s):
            (res_idx, bits, val, best_val, best_bits, best_res,
             stalls, it_in_res, iters, trace) = s
            new_bits, new_val, improved = one_step(bits, val,
                                                   it_in_res, res_idx)
            trace = trace.at[iters + 1].set(new_val)
            stalls = jnp.where(improved, 0, stalls + 1)
            better = new_val < best_val
            best_val = jnp.where(better, new_val, best_val)
            best_bits = jnp.where(better, new_bits, best_bits)
            best_res = jnp.where(better, res_idx, best_res)
            return (res_idx, new_bits, new_val, best_val, best_bits,
                    best_res, stalls, it_in_res + 1, iters + 1, trace)

        def escalate(s):
            (res_idx, bits, val, best_val, best_bits, best_res,
             stalls, it_in_res, iters, trace) = s
            nxt = jnp.minimum(res_idx + 1, n_res - 1)
            bits2 = tables.reencode(bits, res_idx, nxt)  # paper step 5
            val2 = f_batch(tables.decode(bits2, nxt)[None])[0]
            val2 = val2.astype(jnp.float32)
            # a finer quantization of the same parent can already beat
            # the best — the chained path caught this via the next
            # resolution's final value, so catch it here too
            better = val2 < best_val
            best_val = jnp.where(better, val2, best_val)
            best_bits = jnp.where(better, bits2, best_bits)
            best_res = jnp.where(better, nxt, best_res)
            return (nxt, bits2, val2, best_val, best_bits, best_res,
                    jnp.int32(0), jnp.int32(0), iters, trace)

        def body(s):
            return jax.lax.cond(stalled(s), escalate, iterate, s)

        trace0 = jnp.full((t_max,), val0, jnp.float32)
        s0 = (jnp.int32(0), bits0, val0, val0, bits0, jnp.int32(0),
              jnp.int32(0), jnp.int32(0), jnp.int32(0), trace0)
        s = jax.lax.while_loop(cond, body, s0)
        (_, _, val, best_val, best_bits, best_res, _, _, iters,
         trace) = s
        idx = jnp.arange(t_max)
        trace = jnp.where(idx <= iters, trace, val)
        # the reported point, rounded as encoding.decode rounds it
        levels = best_bits.astype(jnp.float32) @ tables.wmat[best_res]
        best_x = place_levels(levels, enc.lo,
                              jnp.asarray(steps)[best_res])
        bits_at = tuple(best_bits[: enc.n_vars * b] for b in schedule)
        return bits_at, best_val, best_res, iters, trace, best_x

    replicated = P()
    mapped = shard_map(
        shard_schedule_engine, mesh=mesh,
        in_specs=(replicated, replicated),
        out_specs=((replicated,) * n_res,) + (replicated,) * 5,
        check_vma=False)
    return jax.jit(mapped)


# engine compilations go through the repo-wide keyed cache subsystem
# (core/cache.py): a (objective, mesh, config) pair compiles ONCE per
# process — repeated serving calls (waves of requests, bench reps) reuse the
# compiled program; unhashable objectives build uncached instead of raising,
# and hit/miss counters surface in BENCH_distributed.json
_ENGINES = get_cache("distributed.engine")
# the all-alive quorum mask per (mesh, population axes), already placed
_QUORUMS = get_cache("distributed.quorum")


def _engine_for(f, enc, mesh, pop_axes, max_iters, virtual_block,
                res_bits=None):
    # the schedule signature is part of the key: ONE compilation covers the
    # whole folded resolution schedule, not one per resolution
    return _ENGINES.get(
        ("engine", f, enc, mesh, pop_axes, max_iters, virtual_block,
         res_bits),
        lambda: make_distributed_engine(jax.vmap(f), enc, mesh, pop_axes,
                                        max_iters, virtual_block,
                                        res_bits=res_bits))


def _batched_engine_for(f, enc, mesh, n_restarts, pop_axes, max_iters,
                        virtual_block, res_bits=None):
    return _ENGINES.get(
        ("batched", f, enc, mesh, n_restarts, pop_axes, max_iters,
         virtual_block, res_bits),
        lambda: make_distributed_engine_batched(f, enc, mesh, n_restarts,
                                                pop_axes, max_iters,
                                                virtual_block,
                                                res_bits=res_bits))


def _full_quorum(mesh: Mesh, pop_axes: tuple) -> jax.Array:
    """The all-alive quorum mask of ``pop_axes``, placed on ``mesh`` once
    and reused by every solve there."""
    return _QUORUMS.get(
        (mesh, pop_axes),
        lambda: jax.device_put(np.ones((_axis_prod(mesh, pop_axes),), bool),
                               NamedSharding(mesh, P())))


class DistributedRun(NamedTuple):
    """One distributed optimization's best parent and its history."""

    x: jax.Array           # (n_vars,) the best parent, decoded
    bits: jax.Array        # its bit string at its own resolution
    val: jax.Array         # () its value
    history: list          # raw per-iteration parent values (floats)
    bits_resolution: int   # bits per variable of ``bits``


def _run_distributed(f: Callable[[jax.Array], jax.Array],
                     enc: Encoding,
                     mesh: Mesh,
                     x0,
                     pop_axes: Sequence[str] = ("data",),
                     max_iters: int = 256,
                     virtual_block: int = 256,
                     quorum_mask=None,
                     res_bits: Sequence[int] | None = None) -> DistributedRun:
    """Distributed DGO over the resolution schedule ``res_bits`` (``None``
    -> fixed at ``enc.bits``), the ENTIRE schedule on device in one
    compiled ``lax.while_loop`` (see ``make_distributed_engine``).

    A run touches the device twice: the engine call, which takes the
    start point as it is (the full-quorum mask is placed on the mesh
    once), and ONE ``device_get`` of every value the result needs, with
    no other device program or transfer.  The engine decodes the best
    parent and cuts its bit string at every resolution itself, so ``x``,
    ``val`` and ``bits`` are the engine's own arrays, the host values of
    ``x`` and ``val`` already fetched; the history is cut in numpy.  One
    non-improving round ends a full-quorum resolution, while a degraded
    ``quorum_mask`` needs a full rotation cycle (``n_shards`` consecutive
    non-improving rounds) before a child can be declared unreachable.

    Returns a :class:`DistributedRun`: the best parent decoded (``x``),
    its bit string at its own resolution ``bits_resolution`` (bits per
    variable), its value, and the raw per-iteration value history
    (``history[0]`` the starting value; escalation re-encodes are not
    recorded).
    """
    pop_axes = tuple(pop_axes)
    schedule = _resolve_res_bits(enc, res_bits)
    engine = _engine_for(f, enc.with_bits(schedule[0]), mesh, pop_axes,
                         max_iters, virtual_block, res_bits=schedule)
    with tracing.span("solve.place"):
        # a JAX start point stays one; anything else goes as numpy, which
        # the engine call transfers itself on one process
        x0 = (jnp.asarray(x0, jnp.float32) if isinstance(x0, jax.Array)
              else np.asarray(x0, np.float32))
        quorum = (_full_quorum(mesh, pop_axes) if quorum_mask is None
                  else quorum_mask)
        x0, quorum = _place_inputs(mesh, x0, quorum)
    outs = engine(x0, quorum)
    with tracing.span("solve.fetch"):
        bits, val, res, iters, trace, x = outs
        _, _, res_h, iters_h, trace_h = jax.device_get(
            (x, val, res, iters, trace))
    with tracing.span("solve.assemble"):
        history = trace_h[: int(iters_h) + 1].tolist()
        bits = bits[int(res_h)]
    return DistributedRun(x, bits, val, history, schedule[int(res_h)])


# ---------------------------------------------------------------------------
# batched multi-start engine (paper's cluster mode over the mesh)
# ---------------------------------------------------------------------------

def _build_shard_schedule_step_batched(
        f_batch: Callable[[jax.Array], jax.Array], tables,
        plan: _ShardPlan, pop_axes: Sequence[str], n_restarts: int):
    """Batched twin of ``_build_shard_schedule_step``: a leading restart
    axis R rides the shard-local loop, ONE all_gather per iteration
    carries all R (value, id) pairs, AND the step gathers the active
    resolution's stacked tables from the carried resolution counter."""
    p_max, chunk, n_blocks, block = (plan.pop, plan.chunk, plan.n_blocks,
                                     plan.block)
    n_shards = plan.n_shards

    def prepare(quorum_mask: jax.Array):
        shard = _flat_axis_index(pop_axes)
        alive = quorum_mask[shard]

        def one_step(parent_bits: jax.Array,   # (R, n_max) int8
                     parent_val: jax.Array,    # (R,) f32
                     it: jax.Array,            # () i32 — rotation round
                     res_idx: jax.Array):      # () i32 — schedule position
            with tracing.scope("children"):
                pat = tables.patterns[res_idx]
            pop = tables.pop[res_idx]
            # dynamic per-resolution chunk: same live-population assignment
            # as the single-restart schedule step (see its comment)
            chunk_r = jax.lax.div(pop + n_shards - 1, jnp.int32(n_shards))
            base = jax.lax.rem(shard + it, n_shards) * chunk_r

            def local_best_block(offs):
                """Ties -> smallest id, matching the single-restart path."""
                ids = base + offs
                valid = (offs < chunk_r) & (ids < pop) & alive
                ids_c = jnp.minimum(ids, p_max - 1)
                b = offs.shape[0]
                with tracing.scope("children"):
                    children = jnp.bitwise_xor(
                        parent_bits[:, None, :],
                        pat[ids_c][None])                 # (R, b, n_max)
                    flat = children.reshape(n_restarts * b, -1)
                with tracing.scope("decode"):
                    xs = tables.decode(flat, res_idx)
                with tracing.scope("evaluate"):
                    fx = f_batch(xs).reshape(n_restarts, b)   # (R, b)
                with tracing.scope("select"):
                    vals = jnp.where(valid[None, :], fx, jnp.inf)
                    v = jnp.min(vals, axis=1)                 # (R,)
                    gid = jnp.min(jnp.where(vals == v[:, None], ids_c[None],
                                            p_max), axis=1)
                return v, gid

            if n_blocks == 1:
                local_val, local_id = local_best_block(jnp.arange(chunk))
            else:
                def eval_block(carry, b):
                    best_val, best_id = carry  # (R,), (R,)
                    v, gid = local_best_block(b * block + jnp.arange(block))
                    with tracing.scope("select"):
                        better = jnp.logical_or(
                            v < best_val, (v == best_val) & (gid < best_id))
                        return (jnp.where(better, v, best_val),
                                jnp.where(better, gid, best_id)), None

                init = (jnp.full((n_restarts,), jnp.inf, jnp.float32),
                        jnp.full((n_restarts,), p_max, jnp.int32))
                (local_val, local_id), _ = jax.lax.scan(
                    eval_block, init, jnp.arange(n_blocks))

            with tracing.scope("select"):
                packed = jnp.stack([local_val,
                                    local_id.astype(jnp.float32)])
                for ax in pop_axes:
                    packed = jax.lax.all_gather(packed, ax)
                packed = packed.reshape(-1, 2, n_restarts)
                all_vals = packed[:, 0, :]                    # (S, R)
                all_ids = packed[:, 1, :].astype(jnp.int32)
                win_val = jnp.min(all_vals, axis=0)           # (R,)
                win_id = jnp.min(jnp.where(all_vals == win_val[None],
                                           all_ids, p_max), axis=0)

                improved = win_val < parent_val               # (R,)
                win_bits = jnp.bitwise_xor(
                    parent_bits, pat[jnp.minimum(win_id, p_max - 1)])
                new_bits = jnp.where(improved[:, None], win_bits,
                                     parent_bits).astype(jnp.int8)
                new_val = jnp.where(improved, win_val, parent_val)
            return new_bits, new_val, improved

        return one_step

    return prepare


def make_distributed_engine_batched(
        f: Callable[[jax.Array], jax.Array],
        enc: Encoding,
        mesh: Mesh,
        n_restarts: int,
        pop_axes: Sequence[str] = ("data",),
        max_iters: int = 256,
        virtual_block: int = 256,
        res_bits: Sequence[int] | None = None):
    """On-device engine over R lockstep restarts — one while_loop, one
    compilation, one reduce per iteration for the whole batch.

    The engine takes two per-slot call-time arrays alongside the start
    points (dynamic, so heterogeneous waves share one compilation):
    ``active`` (R,) bool — inactive slots are padding and never step (a
    partially-filled serving wave reuses the full-width engine) — and
    ``slot_iters`` (R,) i32, each slot's own iteration cap (per
    resolution).  A slot's trajectory is a pure function of its
    own x0/cap: it is bitwise independent of which other slots ride the
    wave, which is what lets the serving scheduler promise per-request
    results identical to individual solves.

    ``f`` is the objective of one point, ``(n_vars,) -> ()``.  The engine
    snaps each start point to the first lattice and evaluates it row by
    row (:func:`_parent_values`), so ``trace[0]`` does not depend on the
    compiled batch width; the population steps evaluate ``vmap(f)``.

    The whole batch escalates through the resolution schedule
    ``res_bits`` (``None`` -> the one resolution ``enc.bits``) in lockstep
    inside the same while_loop — when every active restart has stalled or
    hit its per-resolution slot cap (or the static per-resolution cap is
    hit), all restarts re-encode onto the next lattice and resume; at the
    last resolution that ends the loop.  Restarts that stall (or hit
    their slot cap) stop mutating meanwhile: their bits/val/trace freeze
    and their iteration counter stops.  Returns ``engine(x0s, quorum_mask,
    active, slot_iters) -> (bits (R, n_max), vals (R,), best_vals (R,),
    best_bits (R, n_max), best_res (R,), iters (R,),
    trace (R, len(res_bits)*max_iters + 1))`` where ``best_*`` track each
    restart's best parent across resolutions and ``trace`` holds the raw
    per-iteration values (escalation re-encodes not recorded).  ONE
    compilation and ONE dispatch for the entire batch and schedule.
    """
    f_batch = jax.vmap(f)
    schedule = _resolve_res_bits(enc, res_bits)
    tables = schedule_tables(enc.n_vars, schedule, enc.lo, enc.hi)
    plan = _shard_plan(tables.p_max, mesh, pop_axes, virtual_block)
    prepare = _build_shard_schedule_step_batched(
        f_batch, tables, plan, pop_axes, n_restarts)
    n_shards = plan.n_shards
    n_res = tables.n_res
    t_max = n_res * max_iters + 1
    rows = jnp.arange(n_restarts)

    def shard_schedule_engine(x0s, quorum_mask, active, slot_iters):
        r0 = jnp.int32(0)
        bits0 = tables.encode(x0s, r0)                   # (R, n_max)
        vals0 = _parent_values(f, enc.with_bits(schedule[0]), x0s,
                               active)
        one_step = prepare(quorum_mask)
        stall_limit = jnp.where(jnp.all(quorum_mask), 1, n_shards)

        def live_of(stalls, it_in_res):
            # a slot steps while it is real, unstalled and under its
            # own per-resolution cap (the static max_iters only sizes
            # the trace buffer / backstops the loop)
            return active & (stalls < stall_limit) & \
                (it_in_res < slot_iters)

        def res_done(s):
            stalls, it_in_res = s[6], s[7]
            return jnp.logical_or(~jnp.any(live_of(stalls, it_in_res)),
                                  it_in_res >= max_iters)

        def cond(s):
            return ~jnp.logical_and(s[0] >= n_res - 1, res_done(s))

        def iterate(s):
            (res_idx, bits, vals, best_vals, best_bits, best_res,
             stalls, it_in_res, pos, trace) = s
            live = live_of(stalls, it_in_res)            # (R,)
            nb, nv, improved = one_step(bits, vals, it_in_res, res_idx)
            with tracing.scope("select"):
                bits = jnp.where(live[:, None], nb, bits)
                vals = jnp.where(live, nv, vals)
            with tracing.scope("trace"):
                pos = pos + live.astype(jnp.int32)
                trace = trace.at[rows, jnp.clip(pos, 0, t_max - 1)].set(
                    vals)
            with tracing.scope("select"):
                stalls = jnp.where(live & improved, 0,
                                   stalls + live.astype(jnp.int32))
                better = vals < best_vals
                best_vals = jnp.where(better, vals, best_vals)
                best_bits = jnp.where(better[:, None], bits, best_bits)
                best_res = jnp.where(better, res_idx, best_res)
            return (res_idx, bits, vals, best_vals, best_bits,
                    best_res, stalls, it_in_res + 1, pos, trace)

        def escalate(s):
            (res_idx, bits, vals, best_vals, best_bits, best_res,
             stalls, it_in_res, pos, trace) = s
            with tracing.scope("escalate"):
                nxt = jnp.minimum(res_idx + 1, n_res - 1)
                bits2 = tables.reencode(bits, res_idx, nxt)  # step 5
                vals2 = f_batch(tables.decode(bits2, nxt)).astype(
                    jnp.float32)
                better = vals2 < best_vals
                best_vals = jnp.where(better, vals2, best_vals)
                best_bits = jnp.where(better[:, None], bits2, best_bits)
                best_res = jnp.where(better, nxt, best_res)
                return (nxt, bits2, vals2, best_vals, best_bits,
                        best_res, jnp.zeros_like(stalls), jnp.int32(0),
                        pos, trace)

        def body(s):
            return jax.lax.cond(res_done(s), escalate, iterate, s)

        with tracing.scope("trace"):
            trace0 = jnp.tile(vals0[:, None], (1, t_max))
        s0 = (jnp.int32(0), bits0, vals0, vals0, bits0,
              jnp.zeros((n_restarts,), jnp.int32),
              jnp.zeros((n_restarts,), jnp.int32), jnp.int32(0),
              jnp.zeros((n_restarts,), jnp.int32), trace0)
        s = jax.lax.while_loop(cond, body, s0)
        (_, bits, vals, best_vals, best_bits, best_res, _, _, pos,
         trace) = s
        with tracing.scope("trace"):
            idx = jnp.arange(t_max)[None, :]
            trace = jnp.where(idx <= pos[:, None], trace, vals[:, None])
        return bits, vals, best_vals, best_bits, best_res, pos, trace

    replicated = P()
    mapped = shard_map(
        shard_schedule_engine, mesh=mesh,
        in_specs=(replicated,) * 4,
        out_specs=(replicated,) * 7,
        check_vma=False)
    return jax.jit(_named(mapped, "dgo_wave_engine"))


class BatchedResult(NamedTuple):
    """Result of the batched engine (R concurrent restarts), on the host:
    every field is a numpy array fetched in the wave's one transfer."""

    bits: np.ndarray       # (R, N) int8 — final-resolution string per restart
    values: np.ndarray     # (R,) f32 — best value per restart
    iterations: np.ndarray  # (R,) i32 — population steps taken, per restart
    trace: np.ndarray      # (R, T) f32 — monotone value history per restart
    best: int              # index of the winning (active) restart
    best_xs: np.ndarray    # (R, n_vars) — each restart's best point at its
    #                        own resolution


def _prefetch(*arrs) -> None:
    """Enqueue device->host copies for ``arrs`` right behind the compute
    that produces them.  Called at SUBMIT time so the copies sit on each
    device stream before any later wave's dispatch can slot in —
    ``finish()``'s ``device_get`` then completes from already-copied
    buffers instead of waiting out whatever executed next on the device
    (without this, fetching wave N's results queues behind wave N+1's
    compute and the pipeline serializes)."""
    for a in arrs:
        try:
            a.copy_to_host_async()
        except AttributeError:      # non-jax leaf / backend without
            pass                    # async transfers: finish() fetches


def _best_active(vals: np.ndarray, active: np.ndarray) -> int:
    """The winning restart among the active slots: padding slots read
    +inf from the start and their results are discarded."""
    return int(np.argmin(np.where(active, vals, np.inf)))


class PendingBatched:
    """One in-flight batched dispatch from :func:`_submit_batched`: the
    engine call has returned, but its device arrays may still be
    computing.  :meth:`finish` blocks on the host fetch (``fetch()``)
    and runs the post-processing (``post(fetched)``) that turns raw
    engine outputs into a :class:`BatchedResult`; afterwards ``fetch``
    holds the fetch's ``(wall, thread-CPU)`` seconds.  The submit/finish
    split is the serving pipeline's lever (``core.solver.submit_wave``
    wraps it per wave): the caller assembles and dispatches the NEXT
    wave while the device still executes this one.
    """

    __slots__ = ("_fetch", "_post", "fetch")

    def __init__(self, fetch, post):
        self._fetch = fetch
        self._post = post
        self.fetch = (0.0, 0.0)

    def finish(self) -> BatchedResult:
        """Block on the device results and assemble the result.  A
        device-side error surfaces here, at the fetch, not at submit."""
        start = tracing.now()
        with tracing.span("finalize.fetch"):
            fetched = self._fetch()
        self.fetch = tracing.since(start)
        with tracing.span("finalize.post"):
            return self._post(fetched)


def _run_batched(f: Callable[[jax.Array], jax.Array],
                 enc: Encoding,
                 mesh: Mesh,
                 x0s: jax.Array,
                 pop_axes: Sequence[str] = ("data",),
                 max_iters: int = 256,
                 virtual_block: int = 256,
                 quorum_mask=None,
                 res_bits: Sequence[int] | None = None,
                 active=None,
                 slot_iters=None) -> BatchedResult:
    """The blocking shape of :func:`_submit_batched`: dispatch one wave
    and immediately block on its results (submit + ``finish()``)."""
    return _submit_batched(
        f, enc, mesh, x0s, pop_axes=pop_axes, max_iters=max_iters,
        virtual_block=virtual_block, quorum_mask=quorum_mask,
        res_bits=res_bits, active=active, slot_iters=slot_iters).finish()


def _submit_batched(f: Callable[[jax.Array], jax.Array],
                    enc: Encoding,
                    mesh: Mesh,
                    x0s,
                    pop_axes: Sequence[str] = ("data",),
                    max_iters: int = 256,
                    virtual_block: int = 256,
                    quorum_mask=None,
                    res_bits: Sequence[int] | None = None,
                    active=None,
                    slot_iters=None) -> PendingBatched:
    """Batched multi-start distributed DGO: R restarts from ``x0s``
    (R, n_vars) share one compiled on-device while_loop — including every
    escalation of the resolution schedule ``res_bits`` (the whole batch
    and schedule is ONE dispatch).

    ``active`` (R,) bool marks padding slots (False = never stepped —
    a partially-filled serving wave reuses the full-width compilation);
    ``slot_iters`` (R,) i32 gives each slot its own iteration cap (per
    resolution).  Both are call-time arrays: they do
    not enter the compile-cache key, so heterogeneous waves share one
    engine.  Defaults: all slots active, every cap = ``max_iters``.

    This is the batched-request serving path (launch/serve.py --dgo): R
    concurrent requests amortize the per-iteration reduce and the dispatch
    to near single-run wall-clock (see benchmarks/bench_distributed.py).

    A wave touches the device twice: the engine call, which takes the
    host arrays as they are and snaps and evaluates the start points
    itself, and ONE ``device_get`` of every output the results need.
    Everything between is numpy on the host.  Returns WITHOUT blocking:
    JAX dispatch is asynchronous, so the engine call hands back in-flight
    device arrays and the fetch (plus the history post-processing) is deferred to ``PendingBatched.finish()``.
    """
    x0s = np.asarray(x0s, np.float32)
    if x0s.ndim != 2:
        raise ValueError(f"x0s must be (R, n_vars), got {x0s.shape}")
    n_restarts = x0s.shape[0]
    pop_axes = tuple(pop_axes)
    n_shards = _axis_prod(mesh, pop_axes)
    quorum_mask = (np.ones((n_shards,), bool) if quorum_mask is None
                   else np.asarray(quorum_mask, bool))
    active = (np.ones((n_restarts,), bool) if active is None
              else np.asarray(active, bool))
    slot_iters = (np.full((n_restarts,), max_iters, np.int32)
                  if slot_iters is None
                  else np.asarray(slot_iters, np.int32))
    if active.shape != (n_restarts,) or slot_iters.shape != (n_restarts,):
        raise ValueError(
            f"active/slot_iters must be ({n_restarts},), got "
            f"{active.shape}/{slot_iters.shape}")
    schedule = _resolve_res_bits(enc, res_bits)
    enc0 = enc.with_bits(schedule[0])
    # process-spanning meshes need an explicit replicated put per wave;
    # on one process jit transfers the host arrays itself
    with tracing.span("submit_wave.place"):
        args = _place_inputs(mesh, x0s, quorum_mask, active, slot_iters)

    with tracing.span("submit_wave.engine"):
        engine = _batched_engine_for(f, enc0, mesh,
                                     n_restarts, pop_axes, max_iters,
                                     virtual_block, res_bits=schedule)
        # best_vals, best_bits, best_res, iters, trace
        outs = engine(*args)[2:]
        _prefetch(*outs)

    def post(fetched) -> BatchedResult:
        vals_h, bits_h, res_h, iters_h, trace_h = fetched

        # per-restart monotone histories, truncated to the longest run
        # and padded past each restart's own end with its final best.
        # Inactive padding slots skip the host-side accumulate/decode
        # entirely — at low bucket fill most of a wave's post-processing
        # would otherwise be spent on clones whose results are discarded
        t_len = int(iters_h.max()) + 1
        mono = np.repeat(trace_h[:, :1], t_len, axis=1)
        best_xs = np.zeros((n_restarts, enc.n_vars), np.float32)
        for r in np.flatnonzero(active):
            h = np.minimum.accumulate(trace_h[r, : int(iters_h[r]) + 1])
            mono[r, : len(h)] = h
            mono[r, len(h):] = h[-1]
            # each restart's best point decoded at its OWN resolution;
            # the bits field reports them quantized at the FINAL
            # resolution (matching DGOResult.bits on the fused engine)
            b = schedule[int(res_h[r])]
            best_xs[r] = decode_np(bits_h[r][: enc.n_vars * b],
                                   enc.with_bits(b))
        return BatchedResult(
            bits=encode_np(best_xs, enc.with_bits(schedule[-1])),
            values=vals_h, iterations=iters_h, trace=mono,
            best=_best_active(vals_h, active), best_xs=best_xs)
    return PendingBatched(lambda: jax.device_get(outs), post)
