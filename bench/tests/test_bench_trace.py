"""The trace reduction on a synthetic trace with known answers."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import devtrace  # noqa: E402
from devtrace import Event, RawTrace  # noqa: E402

MS = 1e6  # ns


def op(s, e, name="%fusion.1 = f32[] fusion(f32[] %p)"):
    return Event(s * MS, e * MS, name)


AR = "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %x), to_apply=%add"
LOOP = "%while.7 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), body=%b"


def two_devices():
    """Window 0-100 ms. Device 0: compute 10-30, all-reduce 25-40, compute
    60-70, and a while loop 10-40 around the first two; device 1: compute
    0-50, all-reduce 50-55. Host spans: step 0-45, wait 45-100, submit
    70-80."""
    raw = RawTrace()
    raw.devices["/device:TPU:0"] = {
        devtrace.OPS_LINE: [op(10, 30), op(25, 40, AR), op(60, 70),
                            op(10, 40, LOOP)]}
    raw.devices["/device:TPU:1"] = {
        devtrace.OPS_LINE: [op(0, 50), op(50, 55, AR)]}
    raw.spans = [Event(0, 100 * MS, devtrace.WINDOW_SPAN),
                 Event(0, 45 * MS, "bench.step"),
                 Event(45 * MS, 100 * MS, "bench.wait"),
                 Event(70 * MS, 80 * MS, "bench.submit")]
    return raw


def test_busy_and_idle_share():
    s = devtrace.reduce(two_devices())
    assert s.window_s == pytest.approx(0.100)
    # device 0 busy 10-40 and 60-70 = 40 ms; device 1 busy 0-55 = 55 ms
    assert s.busy_s == pytest.approx((0.040 + 0.055) / 2)
    assert s.idle_share == pytest.approx(1 - 0.0475 / 0.100)
    assert s.n_devices == 2


def test_idle_gaps_labelled_by_host_span():
    s = devtrace.reduce(two_devices())
    # device 0 gaps: 0-10 (step), 40-60 (mid 50: wait), 70-100 (mid 85:
    # wait; the submit span 70-80 is closed by then)
    assert s.idle_gaps == [["wait", pytest.approx(0.030)],
                           ["wait", pytest.approx(0.020)],
                           ["step", pytest.approx(0.010)]]
    assert devtrace.label_at(two_devices(), 75 * MS) == "submit+wait"
    assert devtrace.label_at(two_devices(), 150 * MS) == "no span"


def test_top_ops_by_trace_name():
    s = devtrace.reduce(two_devices())
    names = dict((k, v) for k, v in s.top_ops)
    # fusion.1: (20 + 10 + 50) ms over two devices; all-reduce.3: 15 + 5
    assert names["fusion.1"] == pytest.approx(0.080 / 2)
    assert names["all-reduce.3"] == pytest.approx(0.020 / 2)
    assert s.top_ops[0][0] == "fusion.1"
    # a loop's event spans its body's operations: not an operation itself
    assert "while.7" not in names


def test_window_clips_events():
    raw = two_devices()
    raw.spans[0] = Event(20 * MS, 65 * MS, devtrace.WINDOW_SPAN)
    s = devtrace.reduce(raw)
    # device 0 in 20-65: 20-40 and 60-65 busy = 25 ms; device 1: 20-55
    assert s.busy_s == pytest.approx((0.025 + 0.035) / 2)


@pytest.mark.parametrize("name,want", [
    (LOOP, True),
    (AR, False),
    ("%conditional.2 = f32[] conditional(pred[] %p, f32[] %a, f32[] %b)",
     True),
    ("%fusion.2 = f32[] fusion(f32[] %while.3)", False),
    ("%while.3 = f32[] fusion(f32[] %a)", False),
])
def test_control_flow_by_opcode(name, want):
    assert devtrace.is_container(name) is want


def test_interval_arithmetic():
    assert devtrace.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert devtrace.clip([(0, 3), (5, 9), (10, 12)], 2, 8) == [(2, 3),
                                                                (5, 8)]
    assert devtrace.gaps([(2, 3), (5, 6)], 0, 8) == [(0, 2), (3, 5), (6, 8)]


def test_collective_exposed_time():
    s = devtrace.reduce(two_devices())
    # device 0: all-reduce 25-40, compute 10-30 covers 25-30 (the while
    # loop 10-40 spans its body and covers nothing): 15 ms, 10 exposed;
    # device 1: all-reduce 50-55 after compute 0-50: 5 ms, 5 exposed
    assert s.collective_s == pytest.approx((0.015 + 0.005) / 2)
    assert s.collective_exposed_s == pytest.approx((0.010 + 0.005) / 2)


def test_collective_exposed_time_overlapped_in_part_on_each_device():
    ag = ("%all-gather-start.1 = (f32[2]{0}, f32[8]{0}) "
          "all-gather-start(f32[2]{0} %p), dimensions={0}")
    raw = RawTrace()
    raw.devices["/device:TPU:0"] = {devtrace.OPS_LINE: [
        op(0, 10, ag), op(5, 8), op(20, 30, AR), op(15, 22), op(28, 40)]}
    raw.devices["/device:TPU:1"] = {devtrace.OPS_LINE: [
        op(0, 10, ag), op(2, 4, ag), op(40, 50, AR)]}
    raw.spans = [Event(0, 100 * MS, devtrace.WINDOW_SPAN)]
    s = devtrace.reduce(raw)
    # device 0: collectives 0-10 and 20-30 (20 ms), others cover 5-8,
    # 20-22 and 28-30: 13 exposed; device 1: 0-10 and 40-50, all exposed
    assert s.collective_s == pytest.approx((0.020 + 0.020) / 2)
    assert s.collective_exposed_s == pytest.approx((0.013 + 0.020) / 2)


def test_no_collectives_expose_nothing():
    raw = two_devices()
    for lines in raw.devices.values():
        lines[devtrace.OPS_LINE] = [e for e in lines[devtrace.OPS_LINE]
                                    if not devtrace.is_collective(e.name)]
    s = devtrace.reduce(raw)
    assert s.collective_s == 0.0 and s.collective_exposed_s == 0.0


@pytest.mark.parametrize("name,want", [
    (AR, True),
    ("%all-reduce = f32[8]{0:T(128)S(1)} all-reduce(%maximum_dynamic-"
     "update-slice_fusion), channel_id=2, replica_groups={{0,1,2,3}}", True),
    ("%all-gather-done.2 = f32[8]{0} all-gather-done((f32[2]{0}) %s)", True),
    ("%cp.1 = f32[8]{0} collective-permute(f32[8]{0} %x)", True),
    ("%rs = f32[2]{0} reduce-scatter(f32[8]{0} %x), dimensions={0}", True),
    ("%a2a = f32[8]{0} all-to-all(f32[8]{0} %x), dimensions={0}", True),
    ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %all-reduce.3)", False),
    ("%all-reduce.9 = f32[8]{0} fusion(f32[8]{0} %a)", False),
    (LOOP, False),
])
def test_collectives_by_opcode(name, want):
    assert devtrace.is_collective(name) is want


def test_interval_overlap():
    assert devtrace.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert devtrace.overlap([(0, 1)], [(2, 3)]) == 0
    assert devtrace.overlap([], [(0, 5)]) == 0
