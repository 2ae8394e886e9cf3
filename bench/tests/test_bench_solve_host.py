"""The solve front door's reader, ``solve_host_ms``, on hand-built spans and
on a real profiler capture of a solve."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import devtrace  # noqa: E402
import harness  # noqa: E402
from devtrace import Event  # noqa: E402
from test_bench_trace import MS, two_devices, with_program  # noqa: E402


def with_solves(raw):
    """``raw`` with the single-solve front door's spans. Main thread:
    solves 10-20 (fetch 12-18), 30-34 (fetch 31-33, place 30-31) and
    90-104, cut by the window's end (fetch 95-99); a fetch 14-16 on the
    worker, inside the first solve's interval but not its thread. Worker:
    a solve 40-50 (fetch 41-44, assemble 44-45)."""
    main, worker = "/host:CPU/0:python3", "/host:CPU/1:python3"
    raw.program = [
        Event(s * MS, e * MS, name, thread) for s, e, name, thread in [
            (10, 20, "dgo.solve", main), (12, 18, "dgo.solve.fetch", main),
            (14, 16, "dgo.solve.fetch", worker),
            (30, 34, "dgo.solve", main), (30, 31, "dgo.solve.place", main),
            (31, 33, "dgo.solve.fetch", main),
            (90, 104, "dgo.solve", main), (95, 99, "dgo.solve.fetch", main),
            (40, 50, "dgo.solve", worker),
            (41, 44, "dgo.solve.fetch", worker),
            (44, 45, "dgo.solve.assemble", worker)]]
    return raw


def test_solve_host_ms_is_a_solve_less_its_fetch():
    ctx = SimpleNamespace(run=SimpleNamespace(
        trace=devtrace.reduce(with_solves(two_devices()))))
    # the whole solves: 10 - 6, 4 - 2 and 10 - 3 ms
    assert harness.metric_reader("solve_host_ms")(ctx) == \
        pytest.approx((4.0 + 2.0 + 7.0) / 3)


def test_solve_host_ms_finds_nothing_without_the_solve_spans():
    read = harness.metric_reader("solve_host_ms")
    assert read(SimpleNamespace(run=SimpleNamespace(trace=None))) is None
    for raw in (two_devices(), with_program(two_devices())):
        ctx = SimpleNamespace(run=SimpleNamespace(trace=devtrace.reduce(raw)))
        assert read(ctx) is None


def test_a_captured_solve_holds_the_front_doors_spans(tmp_path):
    """A profiler capture of a warmed device-driver solve on this host:
    ``dgo.solve`` around its place, fetch and assemble phases, on one
    thread, which the reader turns into the solve's host time."""
    import jax

    from repro.core.solver import Distributed, Problem, solve

    prob, strat = Problem.get("quadratic", n=2), Distributed(max_bits=10)
    solve(prob, strat, x0=[1.5, -2.5], max_iters=16)
    jax.profiler.start_trace(str(tmp_path))
    try:
        solve(prob, strat, x0=[1.0, -2.0], max_iters=16)
    finally:
        jax.profiler.stop_trace()
    [path] = tmp_path.rglob("*.xplane.pb")
    raw = devtrace.load(path)
    by_name = {e.name: e for e in raw.program}
    assert sorted(by_name) == ["dgo.solve", "dgo.solve.assemble",
                               "dgo.solve.fetch", "dgo.solve.place"]
    outer = by_name["dgo.solve"]
    for e in raw.program:
        assert e.thread == outer.thread
        assert outer.start_ns <= e.start_ns <= e.end_ns <= outer.end_ns
    phases = [by_name[f"dgo.solve.{p}"] for p in ("place", "fetch",
                                                  "assemble")]
    assert all(a.end_ns <= b.start_ns for a, b in zip(phases, phases[1:]))
    spans = devtrace.program_spans(raw, outer.start_ns, outer.end_ns + 1)
    trace = devtrace.Summary(
        window_s=(outer.end_ns + 1 - outer.start_ns) * 1e-9, busy_s=0.0,
        idle_share=1.0, top_ops=[], idle_gaps=[], n_devices=0,
        program_spans=spans)
    got = harness.metric_reader("solve_host_ms")(
        SimpleNamespace(run=SimpleNamespace(trace=trace)))
    fetch = by_name["dgo.solve.fetch"]
    assert got == pytest.approx(
        (outer.end_ns - outer.start_ns - fetch.end_ns + fetch.start_ns)
        * 1e-6)
