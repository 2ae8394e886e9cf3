"""End-to-end arithmetic: what a user of the service sees, from the
benchmark's own clock."""
from __future__ import annotations

import math

import numpy as np


def latencies(due, seen, answered, close: float, grace_s: float):
    """Seconds from each request's due time to its answer on the host. A
    request that failed, or never answered, counts as the whole wait the
    benchmark gave it and a millisecond more: beyond every answered one."""
    due = np.asarray(due, np.float64)
    beyond = close + grace_s + 1e-3 - due
    return np.where(np.asarray(answered, bool),
                    np.asarray(seen, np.float64) - due, beyond)


def nearest_rank(values, q: float) -> float:
    """The ``q``-th percentile by nearest rank: the smallest value with
    at least ``q`` percent of the values at or below it."""
    xs = np.sort(np.asarray(values, np.float64))
    if xs.size == 0:
        raise ValueError("percentile of no values")
    k = max(1, math.ceil(q / 100.0 * xs.size))
    return float(xs[k - 1])


METRICS = {
    "latency_p95_ms": lambda run: 1e3 * nearest_rank(run.latencies_s, 95),
    "latency_p50_ms": lambda run: 1e3 * nearest_rank(run.latencies_s, 50),
    "solves_per_s": lambda run: run.completed_in_window / run.window_s,
    "setup_s": lambda run: run.setup_s,
    # closed loop: each latency is one solve's own time
    "solve_ms": lambda run: 1e3 * nearest_rank(run.latencies_s, 50),
    "solve_p95_ms": lambda run: 1e3 * nearest_rank(run.latencies_s, 95),
}
