"""Pallas kernel: one fused DGO population step.

Per grid cell, for a ``tile_p``-child tile of the 2N-1 population:

  1. each child's binary-space XOR pattern, built per variable field
     from the (start, end) segment tables                       (graycode)
  2. XOR against the parent's per-variable levels + decode to floats
                                                                (fixedpoint)
  3. objective evaluation of the tile                           (new)
  4. running (min, argmin) fold across grid cells               (popmin)

— child generation, decode, evaluation and reduction never leave VMEM, so
the whole paper step 2-4 is one device program per tile instead of four
kernel launches with HBM round-trips between them. This is the TPU analogue
of MP-1 executing the plural transform + evaluate + rank() pipeline on data
held in PE registers.

Stage 1 uses the identity of ``population.segment_patterns``: inverting
Gray bits [s, e) of the parent flips binary bit j iff s <= j < e and
(j - s) is even, or j >= e and (e - s) is odd. Restricted to one
variable's ``bits``-wide field that is a few shifts and masks of the
field's own integer level, so the kernel works on a (tile_p, n_vars)
array of levels: elementwise integer ops only, no cross-lane scan or
gather, which is what the TPU's Pallas compiler (Mosaic) accepts.

The objective ``f_tile`` is traced *into* the kernel body: it must be a pure
jnp function mapping ``(tile_p, n_vars), *consts -> (tile_p,)``. Array
constants the objective closes over cannot be captured by a Pallas trace —
ops.py hoists them with ``jax.closure_convert`` and they arrive here as the
``consts`` kernel inputs (each broadcast to every grid cell).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NO_ROW = jnp.iinfo(jnp.int32).max


def _alternating(bits: int, parity: int) -> int:
    """Field mask of the local bit positions k (MSB-first) with
    k % 2 == parity."""
    return sum(1 << (bits - 1 - k) for k in range(parity, bits, 2))


def _popstep_kernel(parent_ref, start_ref, end_ref, ok_ref, *refs,
                    f_tile: Callable[..., jax.Array], n_vars: int,
                    bits: int, lo: float, hi: float, pop: int, tile_p: int):
    *const_refs, min_ref, idx_ref = refs
    i = pl.program_id(0)
    start = start_ref[...]                         # (TP, 1) int32
    end = end_ref[...]                             # (TP, 1) int32
    tp = start.shape[0]

    # --- 1: XOR pattern per variable field (population.segment_patterns) --
    s0 = jax.lax.broadcasted_iota(jnp.int32, (1, n_vars), 1) * bits
    rel = start - s0                               # (TP, V) segment start
    ls = jnp.clip(rel, 0, bits)                    # ... clipped to the field
    le = jnp.clip(end - s0, 0, bits)               # segment end, clipped
    ones = jnp.full((tp, n_vars), (1 << bits) - 1, jnp.int32)

    def from_pos(k):
        """Field bits at local positions >= k (k in [0, bits])."""
        return jax.lax.shift_right_logical(ones, k)

    alt = jnp.where((rel & 1) == 0, _alternating(bits, 0),
                    _alternating(bits, 1))
    tail = jnp.where(((end - start) & 1) == 1, from_pos(le), 0)
    pattern = (alt & (from_pos(ls) ^ from_pos(le))) | tail

    # --- 2: child levels + fixed-point decode (kernels/fixedpoint) --------
    level = parent_ref[...] ^ pattern              # (TP, V), < 2**bits
    xs = lo + level.astype(jnp.float32) * ((hi - lo) / float(2 ** bits - 1))

    # --- 3: objective ------------------------------------------------------
    consts = tuple(r[...] for r in const_refs)
    vals = f_tile(xs, *consts).astype(jnp.float32).reshape(tp, 1)
    row = i * tile_p + jax.lax.broadcasted_iota(jnp.int32, (tp, 1), 0)
    live = (row < pop) & (ok_ref[...] != 0)
    vals = jnp.where(live, vals, jnp.inf)     # pad / quorum-masked -> +inf

    # --- 4: running (min, argmin) fold (kernels/popmin) --------------------
    # ties -> smallest row, like jnp.argmin and the XLA inners
    local = jnp.min(vals, axis=0, keepdims=True)                  # (1, 1)
    local_i = jnp.min(jnp.where(vals == local, row, _NO_ROW), axis=0,
                      keepdims=True)

    @pl.when(i == 0)
    def _init():
        min_ref[...] = local
        idx_ref[...] = local_i

    @pl.when(i > 0)
    def _fold():
        better = local < min_ref[...]
        min_ref[...] = jnp.where(better, local, min_ref[...])
        idx_ref[...] = jnp.where(better, local_i, idx_ref[...])


@functools.partial(jax.jit, static_argnames=(
    "f_tile", "n_vars", "bits", "lo", "hi", "pop", "tile_p", "interpret"))
def popstep(parent_levels: jax.Array, starts: jax.Array, ends: jax.Array,
            ok: jax.Array | None = None,
            consts: tuple[jax.Array, ...] = (), *,
            f_tile: Callable[..., jax.Array],
            n_vars: int, bits: int, lo: float, hi: float,
            pop: int, tile_p: int = 128,
            interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """(n_vars,) parent levels + (P_pad,) segment bounds -> (min, argmin).

    ``parent_levels`` holds the parent's integer level per variable
    (its ``bits``-wide binary field). ``P_pad`` must be a multiple of
    ``tile_p`` (ops.py pads); rows with index >= ``pop`` — or with ``ok``
    false — are masked to +inf inside the kernel. ``consts`` are
    closure-hoisted objective constants, replicated to every grid cell.
    The returned argmin is the row index into ``starts``/``ends``.
    ``interpret=None`` resolves per backend: compiled Mosaic on TPU only —
    the stage-4 fold needs sequential grid cells, which Triton does not
    guarantee (see ``ops.resolve_interpret``).
    """
    if not 1 <= bits <= 31:
        raise ValueError(f"popstep needs 1 <= bits <= 31, got {bits}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    p_total = starts.shape[0]
    assert p_total % tile_p == 0, (p_total, tile_p)
    if ok is None:
        ok = jnp.ones((p_total,), jnp.int32)

    def _bcast_spec(c):
        nd = c.ndim
        return pl.BlockSpec(c.shape, lambda i, _nd=nd: (0,) * _nd)

    # compiled (Mosaic) path: the stage-4 fold accumulates across grid
    # cells, so the grid axis must run in order ("arbitrary"); keyed on
    # interpret=False, not on the process backend, so a compile for a
    # described-but-unattached TPU builds the same program
    extra = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary",))}

    mn, idx = pl.pallas_call(
        functools.partial(_popstep_kernel, f_tile=f_tile, n_vars=n_vars,
                          bits=bits, lo=lo, hi=hi, pop=pop, tile_p=tile_p),
        grid=(p_total // tile_p,),
        in_specs=[
            pl.BlockSpec((1, n_vars), lambda i: (0, 0)),    # parent (bcast)
            pl.BlockSpec((tile_p, 1), lambda i: (i, 0)),    # starts
            pl.BlockSpec((tile_p, 1), lambda i: (i, 0)),    # ends
            pl.BlockSpec((tile_p, 1), lambda i: (i, 0)),    # validity
            *[_bcast_spec(c) for c in consts],              # objective consts
        ],
        out_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)),
                   pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        interpret=interpret,
        **extra,
    )(parent_levels.astype(jnp.int32)[None, :],
      starts[:, None].astype(jnp.int32), ends[:, None].astype(jnp.int32),
      ok[:, None].astype(jnp.int32), *consts)
    return mn[0, 0], idx[0, 0]
