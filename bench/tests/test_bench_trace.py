"""The trace reduction on a synthetic trace with known answers."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import devtrace  # noqa: E402
import harness  # noqa: E402
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


# ---------------------------------------------------------------------------
# the program's spans and scopes
# ---------------------------------------------------------------------------

def with_program(raw):
    """``raw`` with the program's host spans, each device's modules and
    their scopes; no operation added. Device 0 runs module jit_a 0-50 and
    jit_b 55-100; device 1 runs jit_a 0-52 (its all-reduce at 50-55 lies
    outside). In jit_a fusion.1 runs dgo.decode and dgo.evaluate fused,
    and all-reduce.3 dgo.select; jit_b has no scope. Dispatch spans:
    20-26 and 60-64 whole, 95-105 cut by the window's end, 120-130
    outside it."""
    raw.modules = {"/device:TPU:0": [Event(0, 50 * MS, "jit_a(1)"),
                                     Event(55 * MS, 100 * MS, "jit_b(2)")],
                   "/device:TPU:1": [Event(0, 52 * MS, "jit_a(1)")]}
    raw.scopes = {"jit_a(1)": {"fusion.1": "dgo.decode+dgo.evaluate",
                               "all-reduce.3": "dgo.select"},
                  "jit_b(2)": {}}
    main, worker = "/host:CPU/0:python3", "/host:CPU/1:python3"
    raw.program = [
        Event(20 * MS, 26 * MS, "dgo.dispatch", main, (("wave", 1),)),
        Event(60 * MS, 64 * MS, "dgo.dispatch", main, (("wave", 2),)),
        Event(95 * MS, 105 * MS, "dgo.dispatch", main, (("wave", 3),)),
        Event(120 * MS, 130 * MS, "dgo.dispatch", main, (("wave", 4),)),
        Event(30 * MS, 35 * MS, "dgo.finalize", worker, (("wave", 1),))]
    return raw


def test_program_spans_keep_their_thread_and_arguments():
    s = devtrace.reduce(with_program(two_devices()))
    got = {(p.name, p.thread, p.args["wave"]): (p.start_s, p.end_s)
           for p in s.program_spans}
    main, worker = "/host:CPU/0:python3", "/host:CPU/1:python3"
    assert set(got) == {("dgo.dispatch", main, 1), ("dgo.dispatch", main, 2),
                        ("dgo.dispatch", main, 3),
                        ("dgo.finalize", worker, 1)}
    assert got[("dgo.dispatch", main, 3)] == (pytest.approx(0.095),
                                              pytest.approx(0.105))
    assert [p.args["wave"] for p in s.whole_spans("dgo.dispatch")] == [1, 2]


def test_scope_time_sums_ops_by_their_module_scope():
    s = devtrace.reduce(with_program(two_devices()))
    # device 0: fusion.1 10-30 in jit_a (evaluate), all-reduce 25-40 in
    # jit_a (select), fusion.1 60-70 in jit_b (none), the loop left out;
    # device 1: fusion.1 0-50 (evaluate), all-reduce 50-55 outside any
    # module (none)
    assert s.scope_time == {
        "dgo.decode+dgo.evaluate": pytest.approx(0.070 / 2),
        "dgo.select": pytest.approx(0.015 / 2),
        "": pytest.approx(0.015 / 2)}
    assert sum(s.op_time.values()) == pytest.approx(
        sum(s.scope_time.values()))
    # every op, not only the top ten, and per device on average
    assert s.op_time == {"fusion.1": pytest.approx(0.080 / 2),
                         "all-reduce.3": pytest.approx(0.020 / 2)}


def test_module_runs_whole_in_the_window_carry_their_ops_by_label():
    s = devtrace.reduce(with_program(two_devices()))
    got = {(r.device[-1], r.name): (r.start_s, r.end_s, r.scope_time)
           for r in s.module_runs}
    assert set(got) == {("0", "jit_a(1)"), ("0", "jit_b(2)"),
                        ("1", "jit_a(1)")}
    assert got[("0", "jit_a(1)")] == (0.0, pytest.approx(0.050), {
        "dgo.decode+dgo.evaluate": pytest.approx(0.020),
        "dgo.select": pytest.approx(0.015)})
    assert got[("0", "jit_b(2)")][2] == {"": pytest.approx(0.010)}
    # device 1's all-reduce at 50-55 lies in no module run
    assert got[("1", "jit_a(1)")][2] == {
        "dgo.decode+dgo.evaluate": pytest.approx(0.050)}


def test_a_module_run_cut_by_the_window_is_left_out():
    raw = with_program(two_devices())
    raw.modules["/device:TPU:0"][1] = Event(55 * MS, 101 * MS, "jit_b(2)")
    s = devtrace.reduce(raw)
    assert [r.name for r in s.module_runs
            if r.device.endswith("0")] == ["jit_a(1)"]
    # the op still counts in the window's scope time
    assert s.scope_time[""] == pytest.approx(0.015 / 2)


def test_time_in_counts_a_scope_alone_or_fused():
    t = {"dgo.decode+dgo.evaluate": 1.0, "dgo.evaluate": 2.0,
         "dgo.select": 4.0, "": 8.0}
    assert devtrace.time_in(t, ["dgo.evaluate"]) == 3.0
    assert devtrace.time_in(t, ["dgo.decode", "dgo.select"]) == 5.0
    assert devtrace.time_in(t, ["dgo.eval"]) == 0.0
    assert devtrace.time_in(t, [""]) == 8.0       # the unscoped ops


def test_per_wave_readers_read_the_program_spans_and_module_runs():
    ctx = SimpleNamespace(run=SimpleNamespace(
        trace=devtrace.reduce(with_program(two_devices()))))
    # the whole dispatch spans: 6 and 4 ms
    assert harness.metric_reader("dispatch_ms_per_wave")(ctx) == \
        pytest.approx(5.0)
    # the runs that evaluate: jit_a on device 0 (20 ms of the fused
    # decode and evaluate) and on device 1 (50 ms)
    assert harness.metric_reader("evaluate_ms_per_wave")(ctx) == \
        pytest.approx(35.0)


@pytest.mark.parametrize("metric", ["dispatch_ms_per_wave",
                                    "evaluate_ms_per_wave"])
def test_per_wave_readers_find_nothing_without_the_programs_names(metric):
    read = harness.metric_reader(metric)
    assert read(SimpleNamespace(run=SimpleNamespace(trace=None))) is None
    plain = SimpleNamespace(run=SimpleNamespace(
        trace=devtrace.reduce(two_devices())))
    assert read(plain) is None


def test_without_scopes_every_op_counts_under_no_scope():
    s = devtrace.reduce(two_devices())
    assert s.scope_time == {"": pytest.approx(0.100 / 2)}
    assert s.program_spans == []


def test_program_events_change_no_earlier_number():
    plain = devtrace.reduce(two_devices())
    more = devtrace.reduce(with_program(two_devices()))
    for name in ("window_s", "busy_s", "idle_share", "top_ops", "idle_gaps",
                 "n_devices", "collective_s", "collective_exposed_s"):
        assert getattr(more, name) == getattr(plain, name), name


@pytest.mark.parametrize("op_name,want", [
    ("jit(dgo_wave_engine)/while/body/dgo.evaluate/sin", "dgo.evaluate"),
    ("jit(f)/dgo.escalate/cond/branch_1_fun/dgo.select/reduce_min",
     "dgo.select"),
    ("dgo.children", "dgo.children"),
    ("jit(f)/while/body/reduce_sum", ""),
    ("jit(f)/not_dgo.select/x", ""),
    ("", ""),
])
def test_scope_is_the_innermost_dgo_part_of_op_name(op_name, want):
    assert devtrace.scope_of(op_name) == want


def test_load_reads_program_spans_and_hlo_scopes_of_a_real_capture(
        tmp_path):
    """A profiler capture on this host: the dgo.* span with its thread
    and arguments, and the labels of the jitted function's optimized HLO
    from the trace's metadata plane, a fusion with every scope fused
    into it."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def scoped(x, w):
        with jax.named_scope("dgo.decode"):
            y = x.astype(jnp.float32) * 3.0 + w
        with jax.named_scope("dgo.evaluate"):
            f = jnp.sum(jnp.cos(y) * y, axis=1)
        with jax.named_scope("dgo.select"):
            return jnp.argmin(f)

    x, w = jnp.ones((64, 64), jnp.int8), jnp.ones((64, 64))
    scoped(x, w).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("dgo.dispatch", wave=3):
            scoped(x, w).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.step"):
            pass
    finally:
        jax.profiler.stop_trace()
    [path] = tmp_path.rglob("*.xplane.pb")
    raw = devtrace.load(path)
    [span] = raw.program
    assert span.name == "dgo.dispatch" and dict(span.args) == {"wave": 3}
    assert span.thread.startswith("/host:")
    assert [s.name for s in raw.spans] == ["bench.step"]
    [table] = [v for k, v in raw.scopes.items() if k.startswith("jit_scoped(")]
    labels = [set(label.split("+")) for label in table.values()]
    assert set().union(*labels) == {"dgo.decode", "dgo.evaluate",
                                    "dgo.select"}
    fused = [op for op, label in table.items()
             if op.startswith(("fusion", "cosine"))
             and {"dgo.decode", "dgo.evaluate"} <= set(label.split("+"))]
    assert fused, table


# ---------------------------------------------------------------------------
# the HloProtos of the metadata plane, written by hand
# ---------------------------------------------------------------------------

def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n >> 7 else 0))
        n >>= 7
        if not n:
            return bytes(out)


def msg(*fields):
    """A protobuf message of ``(number, value)``: an int as a varint, a
    str or bytes as length-delimited."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def inst(name, op_name="", *called, packed=True):
    fields = [(1, name), (7, msg((2, op_name)))]
    if called and packed:
        fields.append((38, b"".join(varint(c) for c in called)))
    fields += [] if packed else [(38, c) for c in called]
    return msg(*fields)


def hlo_proto():
    """Computation 1, fused: a decode multiply and an evaluate cosine;
    2, a reduction's adder under dgo.evaluate; 3, fused: a reduce that
    calls 2; 4, the entry: fusion.1 (its own op_name evaluate's, calling
    1, the ids packed), fusion.2 (no op_name, calling 3, unpacked), a
    copy with no scope."""
    comps = [
        msg((2, inst("mul.1", "jit(f)/dgo.decode/mul")),
            (2, inst("cos.0", "jit(f)/dgo.evaluate/cos")),
            (2, inst("param_0", "")), (5, 1)),
        msg((2, inst("add.9", "jit(f)/dgo.evaluate/reduce_sum")), (5, 2)),
        msg((2, inst("reduce.3", "", 2)), (5, 3)),
        msg((2, inst("fusion.1", "jit(f)/dgo.evaluate/mul", 1)),
            (2, inst("fusion.2", "", 3, packed=False)),
            (2, inst("copy.4", "jit(f)/copy")), (5, 4)),
    ]
    return msg((1, msg(*[(3, c) for c in comps])))


def xspace(*planes):
    return msg(*[(1, p) for p in planes])


def metadata_plane(module="jit_f(5)"):
    event = msg((1, 7), (2, module), (5, msg((1, 9), (6, hlo_proto()))))
    stat = msg((1, 9), (2, "Hlo Proto"))
    return msg((2, devtrace.METADATA_PLANE), (4, msg((1, 7), (2, event))),
               (5, msg((1, 9), (2, stat))))


def test_an_instruction_is_labelled_with_every_scope_it_calls():
    assert devtrace.module_scopes(hlo_proto()) == {
        "mul.1": "dgo.decode", "cos.0": "dgo.evaluate",
        "add.9": "dgo.evaluate", "reduce.3": "dgo.evaluate",
        "fusion.1": "dgo.decode+dgo.evaluate", "fusion.2": "dgo.evaluate"}


def test_hlo_scopes_leaves_other_planes_at_their_name():
    # a device plane whose body is not read: a field of wire type 3 past
    # its name would be an error if it were
    device = msg((2, "/device:TPU:0")) + varint(3 << 3 | 3)
    got = devtrace.hlo_scopes(xspace(device, metadata_plane()))
    assert list(got) == ["jit_f(5)"]
    assert got["jit_f(5)"]["fusion.1"] == "dgo.decode+dgo.evaluate"


def test_unreadable_scopes_leave_the_trace_without_them(capsys):
    bad = msg((2, devtrace.METADATA_PLANE)) + varint(4 << 3 | 3)
    assert devtrace.read_scopes(xspace(bad)) == {}
    assert "unreadable" in capsys.readouterr().err
