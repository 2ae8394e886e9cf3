"""The one traffic generator: reads a mix's parameters and a seed.

Open loop (``"loop": "open"``): ``rate_per_s * seconds`` requests arrive
in the window. The set of inter-arrival gaps is fixed by the rate and the
window (exponential quantiles, scaled to fill the window) and the counts
of each problem by the mix's ``shares``, one weight per problem of the
configuration, in its order (largest remainders); the seed only orders
gaps and problems and draws the start points. So every seed offers the same amount and kind of work,
with Poisson-like arrivals.

Closed loop (``"loop": "closed"``): one solve in flight, each due the
moment the previous answer reached the host, so the window holds as many
solves as the system finishes. Solve ``i`` starts from
``closed_loop_start(problem, seed, i)``, drawn from ``(seed, 4, i)``
alone: the same seed gives the same starts in the same order, whatever
the pace.

Start points are uniform in the problem's box, in float32.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Arrival:
    due_s: float          # offset from the window's start
    problem: int          # index into the configuration's problems
    x0: np.ndarray        # (n,) float32 start point


def seed_sequence(seed: int, *extra: int) -> np.random.SeedSequence:
    """Any whole number (negative or past 64 bits too) names a stream."""
    return np.random.SeedSequence([int(seed) % 2**64, *extra])


def start_point(spec: dict, rng: np.random.Generator) -> np.ndarray:
    lo, hi, n = float(spec["lo"]), float(spec["hi"]), int(spec["n"])
    return rng.uniform(lo, hi, n).astype(np.float32)


def share_counts(total: int, weights) -> np.ndarray:
    """Counts in proportion to ``weights``, summing to ``total``."""
    shares = np.asarray(weights, np.float64)
    if shares.ndim != 1 or (shares < 0).any() or not shares.sum() > 0:
        raise ValueError(f"shares must be weights >= 0, not {weights!r}")
    raw = shares / shares.sum() * total
    counts = np.floor(raw).astype(np.int64)
    for k in np.argsort(-(raw - counts), kind="stable")[
            : total - counts.sum()]:
        counts[k] += 1
    return counts


def open_loop(traffic: dict, problems: list[dict], seed: int,
              seconds: float, rate_per_s: float | None = None
              ) -> list[Arrival]:
    """The arrivals of one open-loop window, in due order."""
    rate = float(rate_per_s if rate_per_s is not None
                 else traffic["rate_per_s"])
    total = max(1, int(round(rate * seconds)))
    q = (np.arange(total) + 0.5) / total
    gaps = -np.log1p(-q)
    gaps *= seconds / gaps.sum()
    weights = traffic["shares"]
    if len(weights) != len(problems):
        raise ValueError(f"{len(weights)} shares for {len(problems)} "
                         f"problems")
    kinds = np.repeat(np.arange(len(problems)),
                      share_counts(total, weights))
    order_ss, kind_ss, x0_ss = seed_sequence(seed, 0).spawn(3)
    gaps = np.random.default_rng(order_ss).permutation(gaps)
    kinds = np.random.default_rng(kind_ss).permutation(kinds)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    x0_rng = np.random.default_rng(x0_ss)
    return [Arrival(float(t), int(k), start_point(problems[k], x0_rng))
            for t, k in zip(due, kinds)]


def closed_loop_start(problem: dict, seed: int, i: int) -> np.ndarray:
    """The start point of solve ``i`` of a closed-loop window."""
    return start_point(problem,
                       np.random.default_rng(seed_sequence(seed, 4, i)))


def warmup_starts(problem: dict, count: int) -> list[np.ndarray]:
    """Start points for warm-up work: the same in every run, whatever the
    seed, so that set-up does the same work each time."""
    rng = np.random.default_rng(seed_sequence(0, 2))
    return [start_point(problem, rng) for _ in range(count)]
