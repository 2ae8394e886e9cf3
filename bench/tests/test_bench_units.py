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
import reference  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402

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


# ---------------------------------------------------------------------------
# the closed loop and the work of a solve
# ---------------------------------------------------------------------------

def test_closed_loop_start_is_deterministic_and_uniform_in_the_box():
    seed = 2**31 + 17
    a = [traffic.closed_loop_start(R40, seed, i) for i in range(500)]
    b = [traffic.closed_loop_start(R40, seed, i) for i in range(500)]
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], a[1])
    assert not np.array_equal(a[0], traffic.closed_loop_start(R40, -seed, 0))
    x = np.stack(a)
    assert x.dtype == np.float32 and x.shape == (500, 40)
    assert x.min() >= -5.12 and x.max() <= 5.12
    # uniform: each quarter of the box holds a quarter of the coordinates
    counts = np.histogram(x, bins=4, range=(-5.12, 5.12))[0] / x.size
    np.testing.assert_allclose(counts, 0.25, atol=0.01)


def test_solve_times_are_nearest_rank_over_every_solve():
    lat = np.arange(1, 101) * 1e-3            # 1 .. 100 ms
    run = SimpleNamespace(latencies_s=lat)
    assert e2e.METRICS["solve_ms"](run) == pytest.approx(50.0)
    assert e2e.METRICS["solve_p95_ms"](run) == pytest.approx(95.0)
    # a failed solve counts beyond every answer
    failed = e2e.latencies([0.0, 1.0], [0.2, np.nan], [True, False],
                           close=2.0, grace_s=60.0)
    run = SimpleNamespace(latencies_s=failed)
    assert e2e.METRICS["solve_p95_ms"](run) > 60e3


def test_work_of_a_solve_is_the_hand_count():
    spec = dict(R40, n=2)
    w = work.iteration(spec, 3)
    # N = 6 bits, P = 11 children, rastrigin 6 ops a variable
    assert w.ops == 11 * (6 + 12 + 6 * 2) == 330
    assert w.bytes == 6 + 11 * 8 == 94
    w4 = work.iteration(spec, 4)               # N = 8, P = 15
    assert (w4.ops, w4.bytes) == (15 * (8 + 16 + 12), 8 + 15 * 8)
    total = work.solve(spec, (3, 4), (2, 1))
    assert (total.ops, total.bytes) == (2 * 330 + 540, 2 * 94 + 128)
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert total.least_s(peak) == pytest.approx(316 / 10.0)
    assert total.binds(peak) == "bytes"
    assert total.binds({"flops_per_s": 1.0, "hbm_bytes_per_s": 1e9}) == "ops"
    shekel = {"objective": "shekel", "n": 4, "kwargs": {"m": 5}}
    assert work.iteration(shekel, 1).ops == 7 * (4 + 8 + 3 * 5 * 4 + 15)


def test_solve_readers_read_the_trace():
    idle = harness.metric_reader("device_idle_share.solve")
    assert idle(reader_ctx(trace=SimpleNamespace(idle_share=0.25))) == \
        pytest.approx(25.0)
    coll = harness.metric_reader("collective_exposed_share")
    t = SimpleNamespace(collective_s=0.3, collective_exposed_s=0.1,
                        window_s=2.0)
    assert coll(reader_ctx(trace=t)) == pytest.approx(5.0)
    t.collective_s = 0.0                       # no collective in the trace
    assert coll(reader_ctx(trace=t)) is None


def test_engine_roofline_is_least_time_over_busy_time():
    roof = harness.metric_reader("engine_roofline")
    spec = dict(R40, n=3)
    cfg = {"problems": [spec], "max_bits": 12, "bits_step": 2,
           "max_iters": 64}
    x0 = traffic.closed_loop_start(spec, 3, 0)
    ref = reference.run(spec, x0, max_bits=12, bits_step=2, max_iters=64)
    least = work.solve(spec, (8, 10, 12), ref.per_resolution).least_s(
        peaks.peaks("TPU v5 lite"))
    trace = SimpleNamespace(busy_s=1e-3, n_devices=4)
    run = SimpleNamespace(trace=trace, answers=[SimpleNamespace(
        problem=0, x0=x0)] * 2, traced=[0, 1])
    ctx = SimpleNamespace(run=run, cell=SimpleNamespace(config=cfg),
                          peak=peaks.peaks("TPU v5 lite"))
    assert roof(ctx) == pytest.approx(100.0 * 2 * least / 4 / 1e-3)
    run.traced = []                            # no solve traced whole
    assert roof(ctx) is None
    run.traced, ctx.peak = [0], {}             # no peaks: not a chip
    assert roof(ctx) is None


@pytest.mark.parametrize("metric", ["device_idle_share.solve",
                                    "collective_exposed_share"])
def test_a_solve_reader_that_finds_nothing_returns_none(metric):
    assert harness.metric_reader(metric)(reader_ctx()) is None
