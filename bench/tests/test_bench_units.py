"""Peaks, traffic, latency arithmetic and the per-layer readers: the parts
of the yardstick that need no program."""
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import e2e  # noqa: E402
import harness  # noqa: E402
import peaks  # noqa: E402
import traffic  # noqa: E402

R40 = {"objective": "rastrigin", "n": 40, "lo": -5.12, "hi": 5.12,
       "bits": 8}


def test_unknown_device_is_an_error():
    with pytest.raises(ValueError, match="no peaks"):
        peaks.peaks("TPU v99")


def reader_ctx(counters=None, trace=None, compiles=0):
    run = SimpleNamespace(counters=counters or {}, trace=trace,
                          compiles_in_window=compiles)
    return SimpleNamespace(run=run, cell=None,
                           peak=peaks.peaks("TPU v5 lite"))


def test_readers_read_counters_and_the_trace():
    fill = harness.metric_reader("bucket_fill")
    assert fill(reader_ctx({"slots": 64, "padded_slots": 16})) == 75.0
    idle = harness.metric_reader("device_idle_share.serve")
    assert idle(reader_ctx(trace=SimpleNamespace(idle_share=0.8))) == \
        pytest.approx(80.0)
    built = harness.metric_reader("compiles_in_window")
    assert built(reader_ctx(compiles=3)) == 3


@pytest.mark.parametrize("metric", ["bucket_fill",
                                    "device_idle_share.serve"])
def test_a_reader_that_finds_nothing_returns_none(metric):
    assert harness.metric_reader(metric)(reader_ctx()) is None


def test_open_loop_is_deterministic_for_a_seed():
    mix = {"rate_per_s": 200.0, "shares": [1, 1, 1, 1, 1]}
    probs = [dict(R40, n=n) for n in (40, 20, 10, 9, 4)]
    a = traffic.open_loop(mix, probs, 2**31 + 7, 10.0)
    b = traffic.open_loop(mix, probs, 2**31 + 7, 10.0)
    c = traffic.open_loop(mix, probs, 2**31 + 8, 10.0)
    assert [(x.due_s, x.problem) for x in a] == \
        [(x.due_s, x.problem) for x in b]
    assert all(np.array_equal(x.x0, y.x0) for x, y in zip(a, b))
    assert [x.problem for x in a] != [x.problem for x in c]
    # the same work for every seed: counts and gaps, in another order
    assert sorted(x.problem for x in a) == sorted(x.problem for x in c)


def test_open_loop_shares_and_poisson_rate():
    mix = {"rate_per_s": 300.0, "shares": [4, 2, 1, 1, 2]}
    probs = [dict(R40, n=n) for n in (40, 20, 10, 9, 4)]
    arr = traffic.open_loop(mix, probs, 5, 20.0)
    assert len(arr) == 6000
    counts = np.bincount([a.problem for a in arr], minlength=5)
    assert counts.tolist() == [2400, 1200, 600, 600, 1200]
    due = np.array([a.due_s for a in arr])
    assert due[0] == 0.0 and (np.diff(due) >= 0).all() and due[-1] < 20.0
    gaps = np.diff(due)
    # exponential gaps: mean 1/rate, coefficient of variation 1
    assert gaps.mean() == pytest.approx(1 / 300.0, rel=0.02)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.05)
    # counts per second are Poisson-like: variance ~ mean
    per_s = np.bincount(due.astype(int), minlength=20)
    assert per_s.var() / per_s.mean() == pytest.approx(1.0, abs=0.6)
    with pytest.raises(ValueError):
        traffic.open_loop(dict(mix, shares=[1, 1]), probs, 5, 20.0)
    x0 = np.stack([a.x0 for a in arr if a.problem == 0])
    assert x0.dtype == np.float32 and x0.min() >= -5.12 and x0.max() <= 5.12


def test_latency_times_from_due_and_counts_misses_beyond_every_limit():
    due = np.array([0.0, 1.0, 2.0, 3.0])
    seen = np.array([0.5, 1.2, np.nan, 3.1])
    answered = np.array([True, True, False, False])   # 3: failed
    lat = e2e.latencies(due, seen, answered, close=4.0, grace_s=60.0)
    np.testing.assert_allclose(lat[:2], [0.5, 0.2])
    assert (lat[2:] > 60.0).all() and (lat[2:] > lat[:2].max()).all()
    assert e2e.nearest_rank(lat, 50) == pytest.approx(0.5)
    assert e2e.nearest_rank(lat, 95) == lat[2:].max()
    run = SimpleNamespace(latencies_s=lat)
    assert e2e.METRICS["latency_p50_ms"](run) == pytest.approx(500.0)
    assert e2e.METRICS["latency_p95_ms"](run) == 1e3 * lat[2:].max()


def test_nearest_rank():
    assert e2e.nearest_rank(np.arange(1, 101), 95) == 95
    assert e2e.nearest_rank([7.0], 50) == 7.0
    with pytest.raises(ValueError):
        e2e.nearest_rank([], 50)


def test_rates_over_the_window():
    run = SimpleNamespace(completed_in_window=250, window_s=10.0,
                          setup_s=12.5)
    assert e2e.METRICS["solves_per_s"](run) == 25.0
    assert e2e.METRICS["setup_s"](run) == 12.5
    assert e2e.METRICS["setup_s"](run) == 12.5
