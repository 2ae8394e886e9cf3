"""The DGO work of a solve, from its shapes and its objective alone.

One iteration at ``b`` bits per variable over ``n`` variables makes and
evaluates every child of the parent:

* ``N = n * b`` bits, ``P = 2 * N - 1`` children;
* operations: ``P * (N + 2 * N + c_obj * n)``: one bit operation per bit
  to form a child (the parent XOR the child's pattern), one multiply-add
  per bit to decode it, and the objective's operations per variable;
* bytes: the parent's ``N`` bytes (one byte a bit), and ``P * 8`` for the
  children's (value, id) pairs that the argmin reads.

The least time of a piece of work is the larger of its operations over the
chip's peak operations per second and its bytes over its peak memory
bandwidth (``peaks.py``). Nothing here names a kernel, an HLO operation or
the program's inner step, so the work reads the same whatever implements
it; the iterations per resolution come from ``reference.run`` on the same
start points.
"""
from __future__ import annotations

from dataclasses import dataclass

# Operations per evaluation of each objective, counted from its formula
# (Surjanovic & Bingham, https://www.sfu.ca/~ssurjano/optimization.html,
# and the DGO paper's quadratic): every add, multiply, divide and
# transcendental counts as one; terms computed once per evaluation, not
# per variable, are left out except where they grow with a kwarg.
#   rastrigin  sum x^2 - 10 cos(2 pi x): square, scale, cos, scale,
#              subtract, accumulate                             -> 6 n
#   ackley     sum x^2 (square, add), sum cos(2 pi x) (scale, cos, add)
#                                                               -> 5 n
#   griewank   sum x^2 / 4000 (square, add), prod cos(x / sqrt(i))
#              (divide, cos, multiply)                          -> 5 n
#   quadratic  sum (x - s)^2: subtract, square, add             -> 3 n
#   shekel     per foxhole: sum (x - a)^2 (3 n), then + c, 1 / ., add
#                                                               -> 3 m n + 3 m
OBJECTIVE_OPS = {
    "rastrigin": lambda n, **_: 6 * n,
    "ackley": lambda n, **_: 5 * n,
    "griewank": lambda n, **_: 5 * n,
    "quadratic": lambda n, **_: 3 * n,
    "shekel": lambda n, m=5, **_: 3 * m * n + 3 * m,
}


@dataclass(frozen=True)
class Work:
    ops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.ops + other.ops, self.bytes + other.bytes)

    def least_s(self, peak: dict) -> float:
        """The least time on a chip with ``peak``'s rates."""
        return max(self.ops / peak["flops_per_s"],
                   self.bytes / peak["hbm_bytes_per_s"])

    def binds(self, peak: dict) -> str:
        """Which of the two rates sets ``least_s``: "ops" or "bytes"."""
        return ("ops" if self.ops / peak["flops_per_s"]
                >= self.bytes / peak["hbm_bytes_per_s"] else "bytes")


def iteration(spec: dict, bits: int) -> Work:
    """One iteration on problem ``spec`` (a configuration's entry) at
    ``bits`` bits per variable."""
    n = int(spec["n"])
    n_bits = n * bits
    pop = 2 * n_bits - 1
    c_obj = OBJECTIVE_OPS[spec["objective"]](n, **spec.get("kwargs", {}))
    return Work(ops=float(pop * (n_bits + 2 * n_bits + c_obj)),
                bytes=float(n_bits + 8 * pop))


def solve(spec: dict, schedule, per_resolution) -> Work:
    """The iterations of one solve: ``per_resolution[r]`` iterations at
    ``schedule[r]`` bits per variable."""
    total = Work(0.0, 0.0)
    for bits, steps in zip(schedule, per_resolution):
        w = iteration(spec, bits)
        total += Work(w.ops * steps, w.bytes * steps)
    return total
