"""Spans, per-request timestamps, per-wave phase counters and engine
scopes of the serving path (``repro.tracing``)."""
from collections import defaultdict
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import distributed
from repro.core.solver import (
    Problem, SolveRequest, resolve_mesh, submit_wave,
)
from repro.runtime.failure import FaultPlan
from repro.serving import PipelinedScheduler, Scheduler

MAX_ITERS = 8

SPANS = ("dgo.pop", "dgo.dispatch", "dgo.submit_wave.prepare",
         "dgo.submit_wave.place", "dgo.submit_wave.engine",
         "dgo.finalize.fetch", "dgo.finalize.post", "dgo.finalize.assemble",
         "dgo.complete")

# the span each one nests in, on its own thread (None: top level)
PARENTS = {
    "pipelined": {
        "dgo.pop": None, "dgo.dispatch": None, "dgo.finalize": None,
        "dgo.submit_wave.prepare": "dgo.dispatch",
        "dgo.submit_wave.place": "dgo.dispatch",
        "dgo.submit_wave.engine": "dgo.dispatch",
        "dgo.finalize.fetch": "dgo.finalize",
        "dgo.finalize.post": "dgo.finalize",
        "dgo.finalize.assemble": "dgo.finalize",
        "dgo.complete": "dgo.finalize"},
    # the blocking scheduler's dispatch span wraps the whole solve_many
    "synchronous": {
        "dgo.pop": None, "dgo.dispatch": None, "dgo.complete": None,
        "dgo.submit_wave.prepare": "dgo.dispatch",
        "dgo.submit_wave.place": "dgo.dispatch",
        "dgo.submit_wave.engine": "dgo.dispatch",
        "dgo.finalize.fetch": "dgo.dispatch",
        "dgo.finalize.post": "dgo.dispatch",
        "dgo.finalize.assemble": "dgo.dispatch"},
}

KINDS = {"pipelined": PipelinedScheduler, "synchronous": Scheduler}


def two_signature_requests(n_each=3, seed=0):
    probs = [Problem.get("quadratic", n=2), Problem.get("rastrigin", n=3)]
    return [SolveRequest(p, seed=seed + i, max_iters=MAX_ITERS)
            for i in range(n_each) for p in probs]


def serve(kind, requests, **kwargs):
    sched = KINDS[kind](wave_size=2, max_bits=10, **kwargs)
    try:
        handles = [sched.submit(r) for r in requests]
        sched.drain()
    finally:
        sched.close()
    return sched, handles


def program_spans(trace_dir):
    """``[(thread, name, start_ns, end_ns, stats)]`` of every ``dgo.``
    host span in the profiler's trace; ``thread`` numbers the host
    plane's lines."""
    [path] = Path(trace_dir).rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for thread, line in enumerate(plane.lines):
            out += [(thread, e.name, e.start_ns, e.end_ns, dict(e.stats))
                    for e in line.events if e.name.startswith("dgo.")]
    return out


def innermost_parent(span, spans):
    thread, _, start, end, _ = span
    around = [s for s in spans if s is not span and s[0] == thread
              and s[2] <= start and end <= s[3]]
    return min(around, key=lambda s: s[3] - s[2])[1] if around else None


@pytest.mark.timeout(300)
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_spans_nest_by_thread_and_tie_waves(kind, tmp_path):
    """A real profiler capture of two-signature waves: every span of the
    serving path appears, each nests in its parent on one thread, and
    each wave's dispatch and finalize spans share its ``wave`` id."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _, handles = serve(kind, two_signature_requests())
    finally:
        jax.profiler.stop_trace()
    spans = program_spans(tmp_path)
    parents = PARENTS[kind]
    assert {s[1] for s in spans} == set(parents)
    assert set(SPANS) <= set(parents)
    for span in spans:
        assert innermost_parent(span, spans) == parents[span[1]], span
    dispatch = {s[4]["wave"]: s for s in spans if s[1] == "dgo.dispatch"}
    assert {h.wave for h in handles} == set(dispatch)
    for s in dispatch.values():
        assert 1 <= s[4]["n"] <= s[4]["width"] == 2
    if kind == "pipelined":
        finalize = {s[4]["wave"]: s for s in spans
                    if s[1] == "dgo.finalize"}
        assert set(finalize) == set(dispatch)
        for wave, s in finalize.items():
            # the worker finalizes what the scheduler thread dispatched
            assert s[0] != dispatch[wave][0]
            assert s[2] >= dispatch[wave][3] - 1e3      # ns of slack
    else:
        assert len({s[0] for s in spans}) == 1


@pytest.mark.timeout(300)
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_request_timestamps_order_and_last_pop(kind):
    """``submitted_at <= popped_at <= dispatched_at <= completed_at`` on
    every answered handle; the wave that failed at dispatch requeues its
    requests, which then carry their last pop and their retry's wave."""
    sched = KINDS[kind](wave_size=2, max_bits=10, retry_backoff_s=0.0,
                        faults=FaultPlan(error_dispatches={1}))
    pops = defaultdict(list)
    next_bucket = sched._next_bucket

    def spy():
        popped = next_bucket()
        if popped is not None:
            for h in popped[0]:
                pops[h.seq].append(h.popped_at)
        return popped

    sched._next_bucket = spy
    try:
        handles = [sched.submit(r) for r in two_signature_requests()]
        sched.drain()
    finally:
        sched.close()
    for h in handles:
        assert h.done() and h.error is None
        assert (h.submitted_at <= h.popped_at <= h.dispatched_at
                <= h.completed_at)
        assert h.popped_at == pops[h.seq][-1]
    retried = [h for h in handles if h.requeues]
    assert retried, "the failed first dispatch requeued nothing"
    for h in retried:
        assert len(pops[h.seq]) >= 2 and h.wave >= 2


@pytest.mark.timeout(300)
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_phase_counters_cover_the_timed_waves(kind):
    """The pipelined scheduler times every wave it served: the counters'
    wave count equals the waves, and each sum is at least its largest
    wave. The blocking scheduler times no phase."""
    sched, handles = serve(kind, two_signature_requests(n_each=5))
    m = sched.metrics()
    assert m["completed"] == len(handles)
    assert m["waves"] == len({h.wave for h in handles}) == 6
    timed = m["waves"] if kind == "pipelined" else 0
    assert m["timed_waves"] == timed
    for phase in ("dispatch", "fetch_wait", "finalize_host"):
        total, top = m[f"{phase}_s"], m[f"{phase}_max_s"]
        assert total >= top >= 0.0
        assert m[f"{phase}_cpu_s"] >= 0.0
        if not timed:
            assert total == 0.0
        elif phase != "fetch_wait":     # a wave may already be fetched
            assert top > 0.0


@pytest.mark.parametrize("res_bits,scopes", [
    (None, ("parent_eval", "children", "decode", "evaluate", "select",
            "trace")),
    ((8, 10), ("parent_eval", "children", "decode", "evaluate", "select",
               "trace", "escalate")),
])
def test_engine_program_names_its_phases(res_bits, scopes):
    """The wave engine compiles as ``jit_dgo_wave_engine``, and its HLO
    names each phase's ``dgo.*`` scope in the operations' metadata."""
    prob = Problem.get("rastrigin", n=3)
    enc = prob.encoding.with_bits(8)
    engine = distributed.make_distributed_engine_batched(
        prob.jax_fn, enc, resolve_mesh(None), 2,
        max_iters=MAX_ITERS, res_bits=res_bits)
    args = (jnp.zeros((2, 3)), jnp.ones(1, bool), jnp.ones(2, bool),
            jnp.full(2, MAX_ITERS, jnp.int32))
    text = engine.lower(*args).compile().as_text()
    assert text.startswith("HloModule jit_dgo_wave_engine")
    for name in scopes:
        assert f"/dgo.{name}/" in text, name


_PROGRAMS_BUILT = []


def _count_programs():
    """Programs built from here on (backend compiles and persistent-cache
    loads, from JAX's monitoring events), as ``chip_smoke.CompileCounter``
    counts them; returns a callable reading the count."""
    if not _PROGRAMS_BUILT:
        _PROGRAMS_BUILT.append(0)

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                _PROGRAMS_BUILT[0] += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                _PROGRAMS_BUILT[0] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
    start = _PROGRAMS_BUILT[0]
    return lambda: _PROGRAMS_BUILT[0] - start


def host_events(trace_dir):
    """Names of every event on the profiler's host plane."""
    [path] = Path(trace_dir).rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    return [e.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


@pytest.mark.timeout(300)
@pytest.mark.parametrize("warm,then", [(16, 3), (3, 16)])
def test_warm_wave_of_a_new_slot_count_builds_no_program(warm, then,
                                                         tmp_path):
    """After one wave of a signature, a wave with another number of
    active slots builds no program and runs exactly one: the wave
    engine. Its host path is one engine call and one fetch, with no
    per-slot device work."""
    prob = Problem.get("griewank", n=4)
    rng = np.random.default_rng(warm)
    enc = prob.encoding

    def requests(n):
        return [SolveRequest(prob, x0=rng.uniform(enc.lo, enc.hi, 4),
                             max_iters=MAX_ITERS) for _ in range(n)]

    submit_wave(requests(warm), max_bits=10, pad_to=16).finalize()
    wave = requests(then)
    built = _count_programs()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        results = submit_wave(wave, max_bits=10, pad_to=16).finalize()
    finally:
        jax.profiler.stop_trace()
    assert built() == 0
    assert [r.extras["wave_slot"] for r in results] == list(range(then))
    events = host_events(tmp_path)
    calls = {e for e in events if e.startswith("PjitFunction(")}
    assert calls == {"PjitFunction(dgo_wave_engine)"}, calls
    assert sum(e.endswith("Executable::Execute") for e in events) == 1


@pytest.mark.timeout(300)
@pytest.mark.parametrize("pipeline", [True, False])
def test_serve_report_reads_phases_and_waits(pipeline, capsys):
    """The serve CLI's report gives the phase counters per timed wave
    (none on the blocking scheduler) and the requests' queue wait and
    in-flight time from their timestamps."""
    import json

    from repro.launch import serve

    args = serve.build_parser().parse_args(
        ["--dgo", "--problems", "quadratic:2,rastrigin:3", "--restarts",
         "2", "--waves", "2", "--max-iters", str(MAX_ITERS),
         *([] if pipeline else ["--no-pipeline"])])
    serve.serve_dgo(args)
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["completed"] == 4 and report["failed"] == 0
    for row in ("dispatch_ms_per_wave", "fetch_wait_ms_per_wave",
                "finalize_ms_per_wave"):
        assert (report[row] is not None) == pipeline, row
        assert report[row] is None or report[row] >= 0.0
    assert report["queue_wait_p95_ms"] >= 0.0
    assert report["in_flight_p95_ms"] >= 0.0
