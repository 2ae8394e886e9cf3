"""Plain numpy DGO: the reference every cell's results are compared with.

It follows the DGO paper (arXiv 2012.09861, "Outline of DGO", steps 1-6)
and the engines' documented schedule, and imports nothing of the program:

1. encode the start point on the fixed-point lattice of ``bits`` per
   variable (offset binary over [lo, hi], MSB first) and evaluate it;
2. make the 2N-1 children of the N-bit parent: binary -> Gray, invert
   one segment of a binary segment tree over the N positions (preorder),
   Gray -> binary;
3. take the child with the lowest value (ties: the lowest child id);
4. if it is strictly better than the parent it becomes the parent, and
   the step repeats (at most ``max_iters`` steps per resolution);
5. otherwise the parent is re-encoded ``bits_step`` bits finer and
   evaluated there;
6. past ``max_bits`` the run stops.

The result is the best parent seen (including the re-encoded ones), its
value, and the number of steps taken (each resolution ends with its one
non-improving step, or at the cap).

Points live on the engines' float32 lattice: ``lo + level * scale`` with
``scale = float32((hi - lo) / (2**bits - 1))``. The objectives are the
classic unrotated forms. ``dtype`` is the precision of decoding and of
the objective: float32 as the configurations state, or bfloat16 for the
control run that must fail the comparison.

Children are made from the literal three-step transform once per string
length: the transform is linear over GF(2), so child ``c`` of any parent
is the parent XOR ``c``'s pattern, and within one variable's bit field
an XOR of bits is an XOR of the integer levels. The per-step work is
then one XOR of (2N-1, n) integer levels, a decode and the objective.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

try:                      # bfloat16 for the control run only
    from ml_dtypes import bfloat16
except ImportError:       # pragma: no cover - shipped with jax
    bfloat16 = None


# ---------------------------------------------------------------------------
# objectives: the classic unrotated forms, (P, n) -> (P,) in ``dtype``
# ---------------------------------------------------------------------------

def _c(v, dt):
    return np.asarray(v, dt)


def rastrigin(x, dt):
    n = x.shape[-1]
    terms = x * x - _c(10.0, dt) * np.cos(_c(2 * np.pi, dt) * x)
    return _c(10.0 * n, dt) + np.sum(terms, axis=-1, dtype=dt)


def ackley(x, dt):
    n = x.shape[-1]
    s1 = np.sqrt(np.sum(x * x, axis=-1, dtype=dt) / _c(n, dt))
    s2 = np.sum(np.cos(_c(2 * np.pi, dt) * x), axis=-1, dtype=dt) / _c(n, dt)
    return (-_c(20.0, dt) * np.exp(-_c(0.2, dt) * s1) - np.exp(s2)
            + _c(20.0, dt) + _c(np.e, dt))


def griewank(x, dt):
    n = x.shape[-1]
    root_i = np.sqrt(np.arange(1, n + 1, dtype=np.float64)).astype(dt)
    return (_c(1.0, dt) + np.sum(x * x, axis=-1, dtype=dt) / _c(4000.0, dt)
            - np.prod(np.cos(x / root_i), axis=-1, dtype=dt))


def quadratic(x, dt, shift=1.2345):
    d = x - _c(shift, dt)
    return np.sum(d * d, axis=-1, dtype=dt)


# Shekel's foxholes and weights (Dixon & Szego 1978, the 4-D S5/S7/S10)
SHEKEL_A = np.array([[4, 4, 4, 4], [1, 1, 1, 1], [8, 8, 8, 8],
                     [6, 6, 6, 6], [3, 7, 3, 7], [2, 9, 2, 9],
                     [5, 5, 3, 3], [8, 1, 8, 1], [6, 2, 6, 2],
                     [7, 3.6, 7, 3.6]], np.float64)
SHEKEL_C = np.array([0.1, 0.2, 0.2, 0.4, 0.4, 0.6, 0.3, 0.7, 0.5, 0.5])


def shekel(x, dt, m=5):
    a = SHEKEL_A[:m].astype(dt)
    c = SHEKEL_C[:m].astype(dt)
    diff = x[..., None, :] - a                       # (P, m, 4)
    d = np.sum(diff * diff, axis=-1, dtype=dt)        # (P, m)
    return -np.sum(_c(1.0, dt) / (d + c), axis=-1, dtype=dt)


OBJECTIVES = {"rastrigin": rastrigin, "ackley": ackley,
              "griewank": griewank, "quadratic": quadratic,
              "shekel": shekel}


def objective(spec: dict):
    """``(x (P, n), dtype) -> (P,)`` for a configuration's problem entry."""
    fn = OBJECTIVES[spec["objective"]]
    kwargs = dict(spec.get("kwargs", {}))
    return functools.partial(fn, **kwargs) if kwargs else fn


# ---------------------------------------------------------------------------
# the lattice and the population
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Lattice:
    n: int
    bits: int
    lo: float
    hi: float

    @property
    def max_level(self) -> int:
        return 2 ** self.bits - 1

    @property
    def scale(self) -> np.float32:
        return np.float32((self.hi - self.lo) / self.max_level)

    def encode(self, x) -> np.ndarray:
        """float32 points -> (n,) integer levels (round half to even)."""
        x = np.asarray(x, np.float32)
        t = (x - np.float32(self.lo)) / np.float32(self.hi - self.lo)
        lv = np.round(t * np.float32(self.max_level))
        return np.clip(lv, 0, self.max_level).astype(np.int64)

    def decode(self, levels, dt=np.float32) -> np.ndarray:
        x = np.float32(self.lo) + np.asarray(levels).astype(np.float32) \
            * self.scale
        return x.astype(dt)


def segment_table(n_bits: int) -> np.ndarray:
    """(2N-1, 2) [start, end) segments of a binary segment tree over the
    N positions, in preorder (the left half takes the extra position)."""
    segs = []
    stack = [(0, n_bits)]
    while stack:
        lo, hi = stack.pop()
        segs.append((lo, hi))
        if hi - lo > 1:
            mid = (lo + hi + 1) // 2
            stack.append((mid, hi))      # popped after the left subtree
            stack.append((lo, mid))
    return np.asarray(segs, np.int64)


def children_bits(parent: np.ndarray, segments: np.ndarray) -> np.ndarray:
    """The literal three-step transform: (N,) 0/1 parent -> (2N-1, N)."""
    parent = np.asarray(parent, np.uint8)
    gray = parent.copy()
    gray[1:] ^= parent[:-1]
    pos = np.arange(parent.size)
    masks = ((pos >= segments[:, :1]) & (pos < segments[:, 1:])).astype(
        np.uint8)
    return np.bitwise_xor.accumulate(gray[None, :] ^ masks, axis=1)


@functools.lru_cache(maxsize=32)
def level_patterns(n: int, bits: int) -> np.ndarray:
    """(2N-1, n) integer XOR masks: child c's levels are the parent's
    levels XOR row c (the transform of an all-zero parent, read per
    variable field, MSB first)."""
    n_bits = n * bits
    pats = children_bits(np.zeros(n_bits, np.uint8), segment_table(n_bits))
    weights = 2 ** np.arange(bits - 1, -1, -1, dtype=np.int64)
    return pats.reshape(-1, n, bits).astype(np.int64) @ weights


def resolutions(bits0: int, max_bits: int, bits_step: int) -> list[int]:
    return list(range(bits0, max_bits + 1, bits_step)) or [bits0]


# ---------------------------------------------------------------------------
# one DGO run
# ---------------------------------------------------------------------------

@dataclass
class RefResult:
    best_x: np.ndarray       # (n,) float32
    best_f: float
    iterations: int
    per_resolution: list     # steps taken at each resolution


def run(spec: dict, x0, *, max_bits: int, bits_step: int, max_iters: int,
        dtype=np.float32) -> RefResult:
    """DGO from ``x0`` on the problem ``spec`` (a configuration's entry:
    objective, n, lo, hi, bits), in ``dtype``."""
    if dtype == "bfloat16":
        dtype = bfloat16
    f = objective(spec)
    n, lo, hi = int(spec["n"]), float(spec["lo"]), float(spec["hi"])
    schedule = resolutions(int(spec["bits"]), max_bits, bits_step)

    def value(levels, lat):
        return f(lat.decode(levels, dtype)[None, :], dtype)[0]

    lat = Lattice(n, schedule[0], lo, hi)
    levels = lat.encode(x0)
    val = value(levels, lat)
    best = (val, levels, lat)
    iterations, per_res = 0, []
    for r, bits in enumerate(schedule):
        if r:
            nxt = Lattice(n, bits, lo, hi)
            levels = nxt.encode(lat.decode(levels))   # step 5 (float32)
            lat = nxt
            val = value(levels, lat)
            if val < best[0]:
                best = (val, levels, lat)
        pats = level_patterns(n, bits)
        steps = 0
        while steps < max_iters:
            kids = levels[None, :] ^ pats                   # (2N-1, n)
            vals = f(lat.decode(kids, dtype), dtype)
            w = int(np.argmin(vals))                        # lowest id
            steps += 1
            if not vals[w] < val:
                break
            levels, val = kids[w], vals[w]
            if val < best[0]:
                best = (val, levels, lat)
        iterations += steps
        per_res.append(steps)
    best_val, best_levels, best_lat = best
    return RefResult(best_x=best_lat.decode(best_levels),
                     best_f=float(best_val), iterations=iterations,
                     per_resolution=per_res)


def value64(spec: dict, x) -> float:
    """The objective at ``x`` in float64: the value a reported point has."""
    f = objective(spec)
    return float(f(np.asarray(x, np.float64)[None, :], np.float64)[0])
