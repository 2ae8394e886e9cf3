"""Population generation: 2N-1 deterministic children of an N-bit parent.

Paper step 2 transformation, per child:
  1. two's-complement -> Gray code            (whole string)
  2. invert one bit segment                   (segment id = child id)
  3. inverse Gray -> two's-complement

Segment scheme (DESIGN.md §1 "Segment-scheme note"): the paper defers the
segment enumeration to ref. [13] but shows the population generated "in a
tree like structure" (Fig. 1) and sizes it at exactly 2N-1. A binary
*segment tree* over the N bit positions has exactly 2N-1 nodes for every N
(N leaves + N-1 internal nodes) — child c inverts the Gray-code segment of
tree node c. Leaves are single-bit Gray flips (= binary suffix reflections
at every scale); internal nodes invert dyadic runs (= localized
reflections). When bits-per-variable is a power of two the tree aligns with
variable boundaries, so per-variable moves emerge naturally from the
concatenated string. This matches the paper's population size, its O(n^2)
sequential complexity (2N-1 children x O(N) work), and its hypercube
remark (N a power of 2 => 2N a power of 2).

The table of (start, end) segments is a static host-side constant -> chunks
of the population can be generated independently from child ids alone (the
paper's "virtual processing"; also what the Pallas kernel tiles over).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache import get_cache
from repro.core.encoding import binary_to_gray, gray_to_binary

# host-side table memo: small keys, but the schedule_tables entries hold
# device arrays, so the registry (bounded + instrumented) replaces the
# old unbounded lru_cache and its invisible hit/miss behaviour
_TABLES = get_cache("population.tables", maxsize=128)


def segment_table(n_bits: int) -> np.ndarray:
    """(2N-1, 2) int32 array of [start, end) Gray segments, preorder."""
    n_bits = int(n_bits)
    return _TABLES.get(("segment_table", n_bits),
                       lambda: _build_segment_table(n_bits))


def _build_segment_table(n_bits: int) -> np.ndarray:
    segs: list[tuple[int, int]] = []

    def build(lo: int, hi: int) -> None:
        segs.append((lo, hi))
        if hi - lo > 1:
            mid = (lo + hi + 1) // 2
            build(lo, mid)
            build(mid, hi)

    build(0, n_bits)
    table = np.asarray(segs, dtype=np.int32)
    assert table.shape[0] == 2 * n_bits - 1
    return table


def segment_patterns(n_bits: int) -> np.ndarray:
    """(2N-1, N) int8: child c as a *binary-space* XOR pattern.

    The paper's transformation (binary -> Gray, invert segment [s, e),
    Gray -> binary) collapses algebraically: flipping Gray bit i toggles
    every binary bit j >= i (prefix-XOR), so flipping the whole segment
    toggles binary bit j by parity(|{i in [s,e): i <= j}|):

        j <  s : unchanged
        j in [s,e): flipped iff (j - s) even   (alternating 1010...)
        j >= e : flipped iff (e - s) odd       (constant parity tail)

    Hence ``child = parent ^ segment_patterns(N)[c]`` — one XOR, no Gray
    round-trip, no per-child prefix scan. This is the loop-invariant form
    the engines stack per resolution (``schedule_tables``) and gather
    inside their on-device while_loop; ``generate_children`` remains
    the literal three-step reference it is verified against.
    """
    n_bits = int(n_bits)
    return _TABLES.get(("segment_patterns", n_bits),
                       lambda: _build_segment_patterns(n_bits))


def _build_segment_patterns(n_bits: int) -> np.ndarray:
    # the raw builder, NOT the memoized wrapper: _TABLES.get holds the
    # registry lock across build, so a nested get on the same cache
    # would self-deadlock
    table = _build_segment_table(n_bits)
    j = np.arange(n_bits)
    s, e = table[:, :1], table[:, 1:]
    inside = (j >= s) & (j < e)
    pat = (inside & ((j - s) % 2 == 0)) | ((j >= e) & (((e - s) % 2) == 1))
    return pat.astype(np.int8)


def segment_mask(child_ids: jax.Array, n_bits: int) -> jax.Array:
    """(P,) child ids -> (P, N) int8 inversion masks via the segment tree."""
    table = jnp.asarray(segment_table(n_bits))
    ids = jnp.clip(child_ids.astype(jnp.int32), 0, 2 * n_bits - 2)
    start = table[ids, 0][:, None]
    end = table[ids, 1][:, None]
    i = jnp.arange(n_bits, dtype=jnp.int32)[None, :]
    return ((i >= start) & (i < end)).astype(jnp.int8)


def generate_children(parent_bits: jax.Array,
                      child_ids: jax.Array) -> jax.Array:
    """Children for an arbitrary subset of ids — used for chunked /
    virtual-processing generation. parent_bits: (N,), child_ids: (P,)."""
    n = parent_bits.shape[-1]
    gray = binary_to_gray(parent_bits)
    masks = segment_mask(child_ids, n)
    children_gray = jnp.bitwise_xor(gray[None, :], masks)
    return gray_to_binary(children_gray)


def generate_population(parent_bits: jax.Array) -> jax.Array:
    """All 2N-1 children. (N,) -> (2N-1, N) int8."""
    n = parent_bits.shape[-1]
    return generate_children(parent_bits, jnp.arange(2 * n - 1))


def population_size(n_bits: int) -> int:
    return 2 * n_bits - 1


# ---------------------------------------------------------------------------
# stacked multi-resolution tables: the paper's step-5 escalation as data
# ---------------------------------------------------------------------------

class ScheduleTables(NamedTuple):
    """The whole resolution schedule as stacked device tables.

    Every per-resolution constant an engine needs — XOR child patterns,
    decode weights, encode layout, live population size — is padded to the
    width of the FINEST resolution and stacked along a leading schedule
    axis, so a single compiled ``while_loop`` can carry a resolution
    counter and gather the active resolution's tables instead of being
    re-dispatched per resolution.  This is the one escalation
    implementation shared by the fused single-device engine
    (``core/dgo.py``) and the folded distributed / batched engines
    (``core/distributed.py``).

    Layout convention: at resolution ``res_bits[r]`` the live string is
    the first ``n_vars * res_bits[r]`` positions of the ``n_max``-wide bit
    buffer (position ``i`` belongs to variable ``i // res_bits[r]``,
    MSB-first); everything past the live prefix is zero.  Pattern pad rows
    are all-zero (such a child equals the parent) and are additionally
    masked to +inf by the ``pop`` check, so they can never win.
    """

    n_vars: int              # static problem dimension
    lo: float                # static search-box bounds
    hi: float
    res_bits: tuple          # static resolution schedule (bits per var)
    n_max: int               # bit-buffer width: n_vars * max(res_bits)
    p_max: int               # stacked population axis: 2 * n_max - 1
    patterns: jax.Array      # (R, p_max, n_max) int8 binary-space XOR
    wmat: jax.Array          # (R, n_max, n_vars) f32 MSB-first bit weights
    var: jax.Array           # (R, n_max) i32 variable id per position
    shift: jax.Array         # (R, n_max) u32 bit shift per position
    active: jax.Array        # (R, n_max) bool live-prefix mask
    pop: jax.Array           # (R,) i32 live population 2*n_vars*bits - 1
    scale: jax.Array         # (R,) f32 lattice step (hi-lo)/(2^bits - 1)
    max_level: jax.Array     # (R,) f32 2^bits - 1

    @property
    def n_res(self) -> int:
        return len(self.res_bits)

    def decode(self, bits: jax.Array, res_idx: jax.Array) -> jax.Array:
        """(..., n_max) bit buffer -> (..., n_vars) floats at resolution
        ``res_idx``.  The integer matmul is exact in f32 (weights are
        powers of two < 2^24) and the affine map is applied afterwards.
        The step is the float32 quotient of float32 operands, and XLA
        may fuse the map into one multiply-add, so a point can differ in
        its last bit from ``encoding.decode``'s; the engines report their
        best point through ``encoding.place_levels`` instead."""
        levels = bits.astype(jnp.float32) @ self.wmat[res_idx]
        return self.lo + levels * self.scale[res_idx]

    def encode(self, x: jax.Array, res_idx: jax.Array) -> jax.Array:
        """(..., n_vars) floats -> (..., n_max) int8 bit buffer at
        resolution ``res_idx`` (zero past the live prefix)."""
        ml = self.max_level[res_idx]
        lv = jnp.round((x - self.lo) / (self.hi - self.lo) * ml)
        lv = jnp.clip(lv, 0.0, ml).astype(jnp.uint32)
        b = (jnp.take(lv, self.var[res_idx], axis=-1)
             >> self.shift[res_idx]) & jnp.uint32(1)
        return jnp.where(self.active[res_idx], b, 0).astype(jnp.int8)

    def reencode(self, bits: jax.Array, res_idx: jax.Array,
                 next_idx: jax.Array) -> jax.Array:
        """Paper step 5: carry a parent to the next resolution's lattice."""
        return self.encode(self.decode(bits, res_idx), next_idx)

    def children(self, bits: jax.Array, ids: jax.Array,
                 res_idx: jax.Array) -> jax.Array:
        """Children ``ids`` (clipped by the caller) of a (n_max,) parent
        at resolution ``res_idx`` — one XOR against the stacked patterns."""
        return jnp.bitwise_xor(bits[None, :], self.patterns[res_idx, ids])


def schedule_tables(n_vars: int, res_bits: tuple, lo: float,
                    hi: float) -> ScheduleTables:
    """Build (and memoize, one device copy per schedule signature) the
    stacked tables for a resolution schedule ``res_bits``."""
    n_vars, lo, hi = int(n_vars), float(lo), float(hi)
    res_bits = tuple(int(b) for b in res_bits)
    return _TABLES.get(("schedule_tables", n_vars, res_bits, lo, hi),
                       lambda: _build_schedule_tables(n_vars, res_bits,
                                                      lo, hi))


def _build_schedule_tables(n_vars: int, res_bits: tuple, lo: float,
                           hi: float) -> ScheduleTables:
    if not res_bits:
        raise ValueError("res_bits must name at least one resolution")
    n_max = n_vars * max(res_bits)
    p_max = 2 * n_max - 1
    n_res = len(res_bits)

    patterns = np.zeros((n_res, p_max, n_max), np.int8)
    wmat = np.zeros((n_res, n_max, n_vars), np.float32)
    var = np.zeros((n_res, n_max), np.int32)
    shift = np.zeros((n_res, n_max), np.uint32)
    active = np.zeros((n_res, n_max), bool)
    pop = np.zeros((n_res,), np.int32)
    scale = np.zeros((n_res,), np.float32)
    max_level = np.zeros((n_res,), np.float32)

    i = np.arange(n_max)
    for r, b in enumerate(res_bits):
        n_bits = n_vars * b
        # raw builder (not the memoized wrapper): nested gets on the
        # _TABLES registry would self-deadlock — see _build_segment_patterns
        pat = _build_segment_patterns(n_bits)            # (2*n_bits-1, n_bits)
        patterns[r, : pat.shape[0], :n_bits] = pat
        weights = 2.0 ** np.arange(b - 1, -1, -1)
        for v in range(n_vars):
            wmat[r, v * b: (v + 1) * b, v] = weights
        var[r] = np.minimum(i // b, n_vars - 1)
        shift[r] = np.clip(b - 1 - i % b, 0, 31)
        active[r] = i < n_bits
        pop[r] = 2 * n_bits - 1
        max_level[r] = 2.0**b - 1.0
        scale[r] = (hi - lo) / max_level[r]

    return ScheduleTables(
        n_vars=n_vars, lo=float(lo), hi=float(hi), res_bits=res_bits,
        n_max=n_max, p_max=p_max,
        patterns=jnp.asarray(patterns), wmat=jnp.asarray(wmat),
        var=jnp.asarray(var), shift=jnp.asarray(shift),
        active=jnp.asarray(active), pop=jnp.asarray(pop),
        scale=jnp.asarray(scale), max_level=jnp.asarray(max_level))
