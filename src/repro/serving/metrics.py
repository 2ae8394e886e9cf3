"""Serving observability: latency percentiles, wave/bucket counters,
per-wave host phase times, and the compile-cache snapshot — one
``snapshot()`` dict the CLI prints and tests assert on.
"""
from __future__ import annotations

import dataclasses
from collections import deque

# latency percentiles are computed over a bounded window of the most
# recent completions — a long-lived scheduler must not grow (or sort)
# an unbounded history on every metrics poll
LATENCY_WINDOW = 4096


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (``q`` in [0, 100]) of a sequence.

    Tiny and dependency-free so the metrics path never imports numpy/jax
    (handles are completed on the dispatch thread; keep it cheap).
    """
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sequence")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"q must be in [0, 100], got {q}")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    frac = pos - lo
    return xs[lo] * (1.0 - frac) + xs[hi] * frac


@dataclasses.dataclass
class PhaseTime:
    """One host phase of a wave, summed over the timed waves: wall
    seconds, thread-CPU seconds (``time.thread_time``), and the largest
    single wave's wall seconds."""

    wall_s: float = 0.0
    cpu_s: float = 0.0
    max_s: float = 0.0

    def add(self, wall_s: float, cpu_s: float) -> None:
        self.wall_s += wall_s
        self.cpu_s += cpu_s
        self.max_s = max(self.max_s, wall_s)


@dataclasses.dataclass
class ServingMetrics:
    """Counters + latency samples for one scheduler's lifetime."""

    completed: int = 0
    failed: int = 0
    requeued: int = 0
    waves: int = 0
    warmup_waves: int = 0
    failed_waves: int = 0
    bisected_waves: int = 0   # quarantine probes of a split failed bucket
    nonfinite: int = 0        # results flagged non-finite (extras["finite"])
    slots: int = 0          # total wave slots dispatched (active + padded)
    padded_slots: int = 0   # inactive padding slots
    backoff_s: float = 0.0  # wall seconds slept waiting out retry backoff
    # pipeline depth accounting (record_inflight, one sample per wave
    # entering the dispatch stage): the synchronous scheduler always
    # records depth 1; the pipelined scheduler records how many waves
    # were in flight the moment it BEGAN assembling each bucket
    submitted_waves: int = 0   # successfully dispatched waves sampled
    overlapped_waves: int = 0  # submissions landing behind >= 1 in flight
    peak_in_flight: int = 0    # deepest observed in-flight depth
    # host phase times of the successful waves the pipelined scheduler
    # finalized (record_phases; the synchronous scheduler blocks inside
    # solve_many and times no phase): ``dispatch`` from the start of the
    # pop to submit_wave's return, ``fetch_wait`` the block on the
    # device's results, ``finalize_host`` the result post-processing,
    # per-slot assembly and handle completion
    timed_waves: int = 0
    dispatch: PhaseTime = dataclasses.field(default_factory=PhaseTime)
    fetch_wait: PhaseTime = dataclasses.field(default_factory=PhaseTime)
    finalize_host: PhaseTime = dataclasses.field(default_factory=PhaseTime)

    def __post_init__(self):
        self._latencies: deque[float] = deque(maxlen=LATENCY_WINDOW)

    def record_wave(self, n_active: int, width: int):
        self.waves += 1
        self.slots += width
        self.padded_slots += width - n_active

    def record_phases(self, dispatch: tuple[float, float],
                      fetch_wait: tuple[float, float],
                      finalize_host: tuple[float, float]):
        """One timed wave's ``(wall, thread-CPU)`` seconds per phase."""
        self.timed_waves += 1
        self.dispatch.add(*dispatch)
        self.fetch_wait.add(*fetch_wait)
        self.finalize_host.add(*finalize_host)

    def record_failed_wave(self):
        self.failed_waves += 1

    def record_completion(self, latency_s: float):
        self.completed += 1
        self._latencies.append(latency_s)

    def record_requeue(self):
        self.requeued += 1

    def record_failure(self):
        self.failed += 1

    def record_warmup(self):
        self.warmup_waves += 1

    def record_bisect(self):
        self.bisected_waves += 1

    def record_nonfinite(self):
        self.nonfinite += 1

    def record_backoff(self, slept_s: float):
        self.backoff_s += slept_s

    def record_inflight(self, depth: int):
        """One wave entered the dispatch stage with ``depth`` waves (it
        included) in flight when its assembly began.  ``overlap_fraction``
        in the snapshot is the fraction of waves whose host-side assembly
        and submission ran while another wave was still on device — 0.0
        for the synchronous scheduler, approaching 1.0 when the pipeline
        keeps the device continuously busy."""
        self.submitted_waves += 1
        if depth > 1:
            self.overlapped_waves += 1
        if depth > self.peak_in_flight:
            self.peak_in_flight = depth

    def snapshot(self) -> dict:
        """Everything a serving endpoint reports: request/wave counters,
        bucket fill, per-wave host phase times, latency percentiles, and
        the compile-cache subsystem snapshot (``core.cache.snapshot()``)."""
        from repro.core import cache

        cache_snap = cache.snapshot()
        out = {
            "completed": self.completed,
            "failed": self.failed,
            "requeued": self.requeued,
            "waves": self.waves,
            "failed_waves": self.failed_waves,
            "bisected_waves": self.bisected_waves,
            "nonfinite_results": self.nonfinite,
            "warmup_waves": self.warmup_waves,
            "slots": self.slots,
            "padded_slots": self.padded_slots,
            "fill_fraction": ((self.slots - self.padded_slots) / self.slots
                              if self.slots else None),
            "backoff_s": self.backoff_s,
            "timed_waves": self.timed_waves,
            # pipeline health: how often submissions overlapped an
            # in-flight wave, and the deepest depth reached (1 == fully
            # synchronous; see record_inflight)
            "overlap_fraction": (self.overlapped_waves
                                 / self.submitted_waves
                                 if self.submitted_waves else None),
            "max_in_flight_depth": self.peak_in_flight,
            # percentiles over the LATENCY_WINDOW most recent completions
            # (p99 is the ROADMAP-requested tail metric — BENCH_serving
            # reports it as p99_latency_s, presence-asserted in CI)
            "latency_p50_ms": None,
            "latency_p95_ms": None,
            "latency_p99_ms": None,
            "cache": cache_snap,
            # surfaced top-level: tuning engines (the subspace-lm family)
            # are big compilations, so LRU churn here is the first sign a
            # workload's signature diversity outgrew the engine cache
            "cache_evictions": cache_snap["totals"]["evictions"],
        }
        for name in ("dispatch", "fetch_wait", "finalize_host"):
            phase = getattr(self, name)
            out[f"{name}_s"] = phase.wall_s
            out[f"{name}_cpu_s"] = phase.cpu_s
            out[f"{name}_max_s"] = phase.max_s
        # snapshot the deque first: a monitoring thread may poll while
        # the dispatch thread appends completions
        latencies = list(self._latencies)
        if latencies:
            out["latency_p50_ms"] = 1e3 * percentile(latencies, 50)
            out["latency_p95_ms"] = 1e3 * percentile(latencies, 95)
            out["latency_p99_ms"] = 1e3 * percentile(latencies, 99)
        return out
