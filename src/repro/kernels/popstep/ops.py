"""Public wrappers for the fused population step.

``population_step``     — full 2N-1 population of one parent -> (val, id).
``population_step_ids`` — an arbitrary id subset (the per-shard /
virtual-processing path used by ``core.distributed``) -> (val, global id).

Both handle the parent's per-variable levels (O(N), once), segment-table
lookup, and padding the child count to the tile size; the per-child
O(P*N + P*cost(f)) work runs fused in the kernel.

Backend policy (``interpret=None`` everywhere by default): the kernel
compiles through mosaic/triton on TPU/GPU and falls back to interpret mode
on CPU, resolved once per process from ``jax.default_backend()``. Tile
widths come from ``autotune_tile_p`` — a one-shot wall-clock sweep over
candidate block widths, keyed by ``(backend, n_vars, bits, exec mode)``
and cached both in-process and on disk
(``~/.cache/repro/popstep_tiles.json``;
override the path with ``$REPRO_POPSTEP_TILE_CACHE``), so a shape is tuned
once per machine, not once per run.
"""
from __future__ import annotations

import json
import os
import time
import weakref
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.encoding import Encoding
from repro.core.population import segment_table
from repro.kernels.popstep.kernel import popstep

DEFAULT_TILE = 128
TILE_CANDIDATES = (32, 64, 128, 256, 512)


def backend() -> str:
    """The platform the kernel will run on ('cpu', 'gpu', 'tpu', ...)."""
    return jax.default_backend()


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> autodetect: compiled mosaic on TPU, interpret elsewhere.

    CPU has no compiled Pallas target worth using; GPU is *deliberately*
    kept on interpret for now — the kernel's stage-5 cross-grid-cell
    (min, argmin) fold requires sequential grid execution, which mosaic
    guarantees via "arbitrary" dimension semantics but Triton does not
    (concurrent cells would race on the fold refs). Pass
    ``interpret=False`` explicitly only for single-tile populations on
    GPU, or after the fold is made associative-reduction-safe."""
    return backend() != "tpu" if interpret is None else interpret


def _parent_levels(parent_bits: jax.Array, enc: Encoding) -> jax.Array:
    """(N,) 0/1 parent -> (n_vars,) int32 levels, one MSB-first field each."""
    b = parent_bits.reshape(enc.n_vars, enc.bits).astype(jnp.int32)
    weights = jnp.left_shift(1, jnp.arange(enc.bits - 1, -1, -1,
                                           dtype=jnp.int32))
    return jnp.sum(b * weights, axis=-1, dtype=jnp.int32)


def _tile(pop: int, tile_p: int) -> int:
    """Shrink the tile for tiny populations so one cell isn't mostly pad."""
    return min(tile_p, max(8, 1 << (pop - 1).bit_length()))


# ---------------------------------------------------------------------------
# tile-size autotuner: one timed sweep per (backend, n_vars, bits)
# ---------------------------------------------------------------------------

_TILE_CACHE: dict[tuple, int] = {}          # in-process
_DISK_CACHE_LOADED = False


def _tile_cache_path() -> str:
    return os.environ.get(
        "REPRO_POPSTEP_TILE_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro",
                     "popstep_tiles.json"))


def _load_disk_cache() -> None:
    global _DISK_CACHE_LOADED
    if _DISK_CACHE_LOADED:
        return
    _DISK_CACHE_LOADED = True
    try:
        with open(_tile_cache_path()) as fh:
            for k, v in json.load(fh).items():
                be, nv, b, mode = k.split(":")
                _TILE_CACHE.setdefault(
                    (be, int(nv), int(b), mode == "interpret"), int(v))
    except (OSError, ValueError):
        pass                                 # no/corrupt cache: tune fresh


def _store_disk_cache() -> None:
    path = _tile_cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        payload = {
            f"{be}:{nv}:{b}:{'interpret' if interp else 'compiled'}": t
            for (be, nv, b, interp), t in sorted(_TILE_CACHE.items())}
        with open(path, "w") as fh:
            json.dump(payload, fh, indent=0, sort_keys=True)
    except OSError:
        pass                                 # read-only FS: in-process only


def autotune_tile_p(f_batch: Callable[[jax.Array], jax.Array],
                    enc: Encoding, *,
                    candidates: tuple[int, ...] = TILE_CANDIDATES,
                    reps: int = 5,
                    interpret: bool | None = None) -> int:
    """Pick the fastest popstep tile width for this (objective shape,
    backend) by timing a full-population step at each candidate width.

    The winner is memoized under ``(backend, n_vars, bits, exec mode)``
    in-process and persisted to the on-disk JSON cache, so the sweep runs
    once per machine per shape. Population sizes smaller than a candidate
    are skipped (the ``_tile`` clamp would alias them to the same
    program).
    """
    _load_disk_cache()
    interpret = resolve_interpret(interpret)
    key = (backend(), enc.n_vars, enc.bits, interpret)
    hit = _TILE_CACHE.get(key)
    if hit is not None:
        return hit

    pop = enc.population
    parent = jnp.zeros((enc.n_bits,), jnp.int8)
    seen: set[int] = set()
    best_t, best_dt = DEFAULT_TILE, float("inf")
    for cand in candidates:
        eff = _tile(pop, cand)
        if eff in seen:
            continue
        seen.add(eff)
        v, i = population_step(f_batch, parent, enc, tile_p=cand,
                               interpret=interpret)
        jax.block_until_ready(v)             # compile outside the clock
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            v, i = population_step(f_batch, parent, enc, tile_p=cand,
                                   interpret=interpret)
            jax.block_until_ready(v)
            times.append(time.perf_counter() - t0)
        dt = sorted(times)[len(times) // 2]  # median
        if dt < best_dt:
            best_t, best_dt = cand, dt
    _TILE_CACHE[key] = best_t
    _store_disk_cache()
    return best_t


def _resolve_tile(tile_p, f_batch, enc, interpret: bool) -> int:
    """Tune under the SAME execution mode the step will run in."""
    if tile_p == "auto":
        return autotune_tile_p(f_batch, enc, interpret=interpret)
    return int(tile_p)


# weak-keyed on the objective so entries (closed jaxprs + hoisted device
# arrays) die with it — callers like run_distributed build a fresh
# jax.vmap(f) per call, and a plain dict would retain every one forever
_CONVERT_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _convert_objective(f_batch, tile_p: int, n_vars: int):
    """Hoist array constants out of ``f_batch``'s closure.

    Pallas refuses kernels that capture device arrays, so objectives like
    shekel (which closes over its foxhole table) are closure-converted: the
    returned ``f_tile(xs, *consts)`` is pure, and ``consts`` ride into the
    kernel as broadcast inputs. Cached per (objective, tile shape) so the
    static ``f_tile`` identity is stable across calls — Pallas/jit caches
    stay warm. Constants that are tracers (objective built inside an outer
    trace) skip the cache: they belong to that trace only.
    """
    key = (tile_p, n_vars)
    hit = _CONVERT_CACHE.get(f_batch, {}).get(key)
    if hit is not None:
        return hit
    example = jax.ShapeDtypeStruct((tile_p, n_vars), jnp.float32)
    closed = jax.make_jaxpr(f_batch)(example)
    consts = tuple(closed.consts)
    shapes = tuple(jnp.shape(c) for c in consts)

    def f_tile(xs, *cs):
        orig = [c.reshape(s) for c, s in zip(cs, shapes)]
        out = jax.core.eval_jaxpr(closed.jaxpr, orig, xs)
        return out[0]

    # interpret-mode pallas handles any rank; canonicalize 0-d to (1, 1) so
    # BlockSpec always has a nonempty shape
    flat = tuple(jnp.reshape(c, (1, 1)) if jnp.ndim(c) == 0 else c
                 for c in consts)
    out = (f_tile, flat)
    if not any(isinstance(c, jax.core.Tracer) for c in consts):
        try:
            _CONVERT_CACHE.setdefault(f_batch, {})[key] = out
        except TypeError:
            pass  # objective not weak-referenceable — skip caching
    return out


def population_step(f_batch: Callable[[jax.Array], jax.Array],
                    parent_bits: jax.Array, enc: Encoding, *,
                    tile_p: int | str = DEFAULT_TILE,
                    interpret: bool | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """(N,) int8 parent + batched objective -> (best value, best child id).

    ``tile_p="auto"`` consults the autotune cache (sweeping once on a cold
    cache); ``interpret=None`` autodetects the backend."""
    interpret = resolve_interpret(interpret)
    tile_p = _resolve_tile(tile_p, f_batch, enc, interpret)
    n = enc.n_bits
    pop = enc.population
    t = _tile(pop, tile_p)
    table = np.asarray(segment_table(n))
    pad = (-pop) % t
    starts = jnp.asarray(np.pad(table[:, 0], (0, pad)))
    ends = jnp.asarray(np.pad(table[:, 1], (0, pad)))

    f_tile, consts = _convert_objective(f_batch, t, enc.n_vars)
    return popstep(_parent_levels(parent_bits, enc), starts, ends, None,
                   consts, f_tile=f_tile, n_vars=enc.n_vars, bits=enc.bits,
                   lo=enc.lo, hi=enc.hi, pop=pop, tile_p=t,
                   interpret=interpret)


def population_step_ids(f_batch: Callable[[jax.Array], jax.Array],
                        parent_bits: jax.Array, child_ids: jax.Array,
                        enc: Encoding, *, valid: jax.Array | None = None,
                        tile_p: int | str = DEFAULT_TILE,
                        interpret: bool | None = None
                        ) -> tuple[jax.Array, jax.Array]:
    """Fused step over an id subset (traced ids, e.g. one shard's chunk).

    ``valid`` (bool, same shape as ``child_ids``) masks rows to +inf
    (quorum loss / tail padding). Returns the *global* child id of the
    winner, gathered back from ``child_ids``. ``tile_p``/``interpret``
    follow the same auto policy as ``population_step``.
    """
    interpret = resolve_interpret(interpret)
    tile_p = _resolve_tile(tile_p, f_batch, enc, interpret)
    n = enc.n_bits
    k = child_ids.shape[0]
    t = _tile(k, tile_p)
    pad = (-k) % t
    table = jnp.asarray(np.asarray(segment_table(n)))
    ids = jnp.clip(child_ids.astype(jnp.int32), 0, 2 * n - 2)
    starts = jnp.pad(table[ids, 0], (0, pad))
    ends = jnp.pad(table[ids, 1], (0, pad))
    ok = jnp.ones((k,), jnp.int32) if valid is None else valid.astype(jnp.int32)
    ok = jnp.pad(ok, (0, pad))

    f_tile, consts = _convert_objective(f_batch, t, enc.n_vars)
    mn, row = popstep(_parent_levels(parent_bits, enc), starts, ends, ok,
                      consts, f_tile=f_tile, n_vars=enc.n_vars,
                      bits=enc.bits, lo=enc.lo, hi=enc.hi, pop=k, tile_p=t,
                      interpret=interpret)
    return mn, ids[jnp.minimum(row, k - 1)]
