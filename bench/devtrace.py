"""Device traces: capture a window with JAX's profiler and reduce it.

The reduction works on plain event lists, so that a test can hand it a
synthetic trace with known answers:

* busy time of a device: the union of its operations' intervals ("XLA
  Ops" line) inside the window; idle share = 1 - busy / window;
* the operations with the most device time, by trace name, leaving out
  control flow (``while``, ``conditional``, ``call``), whose events span
  the operations of their bodies;
* idle gaps, each labelled with the benchmark's own host spans (names
  starting ``bench.``) that were open at the gap's middle;
* the exposed time of collectives: on each device, the union of its
  collective operations' intervals (HLO opcode ``all-gather*``,
  ``all-reduce*``, ``collective-permute*``, ``reduce-scatter*`` or
  ``all-to-all*``) inside the window, less the part that any other
  operation on that device covers (control flow left out, as its events
  span their bodies), averaged over the devices;
* the program's own host spans (names starting ``dgo.``), with their
  thread and arguments, for readers of the program's phases;
* device time per operation name, and per ``dgo.*`` named scope of the
  program. A device's op events carry no scope, so each is looked up by
  its instruction name in the optimized HLO of the module that ran it
  (the module the device's "XLA Modules" line shows around the op; its
  HloProto from the trace's metadata plane). An instruction's label is
  every ``dgo.*`` scope that runs in it: the innermost ``dgo.*`` part of
  its own ``op_name`` metadata and of every instruction in the
  computations it calls, so that a fusion names each scope XLA fused into
  it (``dgo.decode+dgo.evaluate``), or ``""`` where none does;
* each module run that lies whole in the window, with its ops' device
  time by label, for readers of time per wave or per solve.
"""
from __future__ import annotations

import bisect
import contextlib
import re
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

SPAN_PREFIX = "bench."
WINDOW_SPAN = SPAN_PREFIX + "window"
PROGRAM_PREFIX = "dgo."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
# the innermost of the program's named scopes in an HLO op_name
_SCOPE = re.compile(r"(?:^|/)(" + re.escape(PROGRAM_PREFIX) + r"[^/]+)")
# control flow whose trace event spans the operations of its body
_CONTAINER = re.compile(r"\s(while|conditional|call)\(")
# collectives by opcode, with their -start/-done halves
_COLLECTIVE = re.compile(r"\s(all-gather|all-reduce|collective-permute"
                         r"|reduce-scatter|all-to-all)(-start|-done)?\(")


@dataclass(frozen=True)
class Event:
    start_ns: float
    end_ns: float
    name: str
    thread: str = ""      # a host event's trace line
    args: tuple = ()      # a host event's (name, value) stats


@dataclass(frozen=True)
class ProgramSpan:
    """One of the program's host spans that overlaps a traced window, in
    seconds from the window's start: below 0 where it began before the
    window, past ``window_s`` where it ended after it."""
    name: str
    thread: str
    start_s: float
    end_s: float
    args: dict = field(default_factory=dict, hash=False)


@dataclass
class RawTrace:
    """Per device plane: its "XLA Ops" events and its "XLA Modules"
    events; the benchmark's host spans and the program's; and per HLO
    module, by the name the trace gives it, the label of each of its
    instructions in which a ``dgo.*`` scope runs."""
    devices: dict = field(default_factory=dict)   # name -> {line: [Event]}
    spans: list = field(default_factory=list)
    program: list = field(default_factory=list)   # [Event], dgo.*
    modules: dict = field(default_factory=dict)   # name -> [Event]
    scopes: dict = field(default_factory=dict)    # module -> {op: label}
    scopes_s: float = 0.0                         # host s to read them


@dataclass(frozen=True)
class ModuleRun:
    """One run of an HLO module on one device that lies whole in a traced
    window, in seconds from the window's start, with its ops' device time
    by label (control flow left out)."""
    device: str
    name: str
    start_s: float
    end_s: float
    scope_time: dict = field(default_factory=dict, hash=False)


@dataclass
class Summary:
    window_s: float
    busy_s: float                       # mean over devices
    idle_share: float                   # mean over devices, 0..1
    top_ops: list                       # [[name, seconds]] mean/device
    idle_gaps: list                     # [[label, seconds]] first device
    n_devices: int
    collective_s: float = 0.0           # union of collectives, mean
    collective_exposed_s: float = 0.0   # mean over devices
    program_spans: list = field(default_factory=list)  # [ProgramSpan]
    op_time: dict = field(default_factory=dict)     # op -> s, mean/device
    scope_time: dict = field(default_factory=dict)  # label -> s, mean
    module_runs: list = field(default_factory=list)  # [ModuleRun]
    read_s: dict = field(default_factory=dict)  # phase -> host s

    def whole_spans(self, name: str) -> list:
        """The program spans named ``name`` that lie whole in the
        window."""
        return [s for s in self.program_spans if s.name == name
                and s.start_s >= 0.0 and s.end_s <= self.window_s]


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def overlap(a, b) -> float:
    """Length of the intersection of two sorted, disjoint interval lists
    (as ``union`` returns them)."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    out, cur = [], lo
    for s, e in union(busy):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

def instruction(name: str) -> str:
    """An HLO instruction's name from its trace name: '%fusion.3 = f32[]
    fusion(...)' -> 'fusion.3'."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def op_name(name: str) -> str:
    """An op's name in ``top_ops``: its instruction's, at most 80
    characters."""
    return instruction(name)[:80]


def is_container(name: str) -> bool:
    return bool(_CONTAINER.search(name))


def is_collective(name: str) -> bool:
    return bool(_COLLECTIVE.search(name))


def collective_time(ops, lo: float, hi: float) -> tuple[float, float]:
    """``(all, exposed)``: nanoseconds in ``[lo, hi]`` in which a
    collective of ``ops`` runs, and those in which no other operation
    (control flow aside) runs with it."""
    coll = union(clip([(e.start_ns, e.end_ns) for e in ops
                       if is_collective(e.name)], lo, hi))
    if not coll:
        return 0.0, 0.0
    other = union(clip([(e.start_ns, e.end_ns) for e in ops
                        if not is_collective(e.name)
                        and not is_container(e.name)], lo, hi))
    return length(coll), length(coll) - overlap(coll, other)


def window_of(raw: RawTrace) -> tuple[float, float]:
    marks = [s for s in raw.spans if s.name == WINDOW_SPAN]
    if marks:
        return marks[0].start_ns, marks[0].end_ns
    evs = [e for lines in raw.devices.values() for line in lines.values()
           for e in line]
    return min(e.start_ns for e in evs), max(e.end_ns for e in evs)


def label_at(raw: RawTrace, t: float) -> str:
    open_spans = sorted({s.name[len(SPAN_PREFIX):] for s in raw.spans
                         if s.name != WINDOW_SPAN
                         and s.start_ns <= t < s.end_ns})
    return "+".join(open_spans) if open_spans else "no span"


def scope_of(name: str) -> str:
    """The innermost ``dgo.*`` named scope in an HLO ``op_name``, or
    ``""``."""
    found = _SCOPE.findall(name)
    return found[-1] if found else ""


def time_in(scope_time: dict, scopes) -> float:
    """The time in ``scope_time`` (label -> time) of the ops in which any
    of ``scopes`` runs, alone or fused with others."""
    want = set(scopes)
    return sum(v for k, v in scope_time.items()
               if want.intersection(k.split("+")))


def program_spans(raw: RawTrace, lo: float, hi: float) -> list:
    return [ProgramSpan(e.name, e.thread, (e.start_ns - lo) * 1e-9,
                        (e.end_ns - lo) * 1e-9, dict(e.args))
            for e in raw.program if e.end_ns > lo and e.start_ns < hi]


def reduce(raw: RawTrace, n_top: int = 10) -> Summary:
    lo, hi = window_of(raw)
    window_ns = hi - lo
    names = sorted(raw.devices)
    if not names or window_ns <= 0:
        raise ValueError("trace holds no device events in its window")
    busy = []
    coll_ns = exposed_ns = 0.0
    op_time: dict[str, float] = {}
    scopes: dict[str, float] = {}
    runs = []
    first_gaps = []
    for i, dev in enumerate(names):
        lines = raw.devices[dev]
        ops = lines.get(OPS_LINE, [])
        iv = clip([(e.start_ns, e.end_ns) for e in ops], lo, hi)
        busy.append(length(union(iv)))
        c_all, c_exposed = collective_time(ops, lo, hi)
        coll_ns += c_all
        exposed_ns += c_exposed
        modules = sorted(raw.modules.get(dev, []), key=lambda m: m.start_ns)
        starts = [m.start_ns for m in modules]
        per_run: list[dict[str, float]] = [{} for _ in modules]
        for e in ops:
            d = min(e.end_ns, hi) - max(e.start_ns, lo)
            if d > 0 and not is_container(e.name):
                op_time[op_name(e.name)] = op_time.get(op_name(e.name),
                                                       0.0) + d
                # the module run around the op, and the op's label there
                mid = (e.start_ns + e.end_ns) / 2
                k = bisect.bisect_right(starts, mid) - 1
                key = ""
                if k >= 0 and mid <= modules[k].end_ns:
                    key = raw.scopes.get(modules[k].name, {}).get(
                        instruction(e.name), "")
                    per_run[k][key] = per_run[k].get(key, 0.0) + d
                scopes[key] = scopes.get(key, 0.0) + d
        runs += [ModuleRun(dev, m.name, (m.start_ns - lo) * 1e-9,
                           (m.end_ns - lo) * 1e-9,
                           {k: v * 1e-9 for k, v in t.items()})
                 for m, t in zip(modules, per_run)
                 if m.start_ns >= lo and m.end_ns <= hi]
        if i == 0:
            first_gaps = gaps(iv, lo, hi)
    n = len(names)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:n_top]
    longest = sorted(first_gaps, key=lambda g: -(g[1] - g[0]))[:n_top]
    return Summary(
        window_s=window_ns * 1e-9,
        busy_s=sum(busy) / n * 1e-9,
        idle_share=1.0 - sum(busy) / n / window_ns,
        top_ops=[[k, v / n * 1e-9] for k, v in top],
        idle_gaps=[[label_at(raw, (s + e) / 2), (e - s) * 1e-9]
                   for s, e in longest],
        n_devices=n,
        collective_s=coll_ns / n * 1e-9,
        collective_exposed_s=exposed_ns / n * 1e-9,
        program_spans=program_spans(raw, lo, hi),
        op_time={k: v / n * 1e-9 for k, v in op_time.items()},
        scope_time={k: v / n * 1e-9 for k, v in scopes.items()},
        module_runs=runs)


# ---------------------------------------------------------------------------
# capture and loading
# ---------------------------------------------------------------------------

def load(path: Path) -> RawTrace:
    """Read an ``.xplane.pb`` written by JAX's profiler."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    raw = RawTrace()
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            lines = {line.name: [Event(e.start_ns, e.end_ns, e.name)
                                 for e in line.events]
                     for line in plane.lines if line.name == OPS_LINE}
            if lines.get(OPS_LINE):
                raw.devices[plane.name] = lines
                raw.modules[plane.name] = [
                    Event(e.start_ns, e.end_ns, e.name)
                    for line in plane.lines if line.name == MODULES_LINE
                    for e in line.events]
        elif plane.name.startswith("/host:"):
            # a thread's line, named by its place in the plane: threads
            # of one process may share a name
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        raw.spans.append(Event(e.start_ns, e.end_ns, e.name))
                    elif e.name.startswith(PROGRAM_PREFIX):
                        raw.program.append(Event(
                            e.start_ns, e.end_ns, e.name,
                            f"{plane.name}/{i}:{line.name}",
                            tuple(e.stats)))
    t0 = time.perf_counter()
    raw.scopes = read_scopes(Path(path).read_bytes())
    raw.scopes_s = time.perf_counter() - t0
    return raw


# ---------------------------------------------------------------------------
# the HLO modules in a trace's metadata plane: protobuf, read by hand
# ---------------------------------------------------------------------------
# Field numbers (tsl/profiler/protobuf/xplane.proto, xla/service/hlo.proto,
# xla/xla_data.proto): XSpace.planes 1; XPlane.name 2, .event_metadata 4
# and .stat_metadata 5 (maps: key 1, value 2); XEventMetadata.name 2,
# .stats 5; XStat.metadata_id 1, .bytes_value 6; XStatMetadata.id 1,
# .name 2; HloProto.hlo_module 1; HloModuleProto.computations 3;
# HloComputationProto.instructions 2, .id 5; HloInstructionProto.name 1,
# .metadata 7, .called_computation_ids 38 (packed or not);
# OpMetadata.op_name 2.

def _varint(buf, pos: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, pos
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited field; fixed-width fields
    are skipped."""
    buf = memoryview(buf)
    pos = 0
    while pos < len(buf):
        key, pos = _varint(buf, pos)
        kind = key & 7
        if kind == 0:
            value, pos = _varint(buf, pos)
        elif kind == 2:
            size, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + size], pos + size
        elif kind in (1, 5):
            pos += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {pos}")
        yield key >> 3, value


def _one(buf, number: int, default=b""):
    """The last value of field ``number`` (protobuf's rule), or
    ``default``."""
    value = default
    for num, v in _fields(buf):
        if num == number:
            value = v
    return value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _ids(values) -> list[int]:
    """The numbers of a repeated integer field, packed or not."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
            continue
        pos = 0
        while pos < len(v):
            x, pos = _varint(v, pos)
            out.append(x)
    return out


def module_scopes(hlo_proto) -> dict[str, str]:
    """``{instruction name: label}`` of a serialized HloProto, for the
    instructions in which a ``dgo.*`` scope runs: the sorted scopes of
    the instruction's own ``op_name`` and of every instruction in the
    computations it calls, joined by ``+``."""
    comps = {}                # id -> [(name, own scope, called ids)]
    for num, comp in _fields(_one(hlo_proto, 1)):
        if num != 3:
            continue
        insts, cid = [], 0
        for num2, v in _fields(comp):
            if num2 == 2:
                name, op, called = b"", b"", []
                for num3, x in _fields(v):
                    if num3 == 1:
                        name = x
                    elif num3 == 7:
                        op = _one(x, 2)
                    elif num3 == 38:
                        called.append(x)
                insts.append((_text(name), scope_of(_text(op)),
                              _ids(called)))
            elif num2 == 5:
                cid = v
        comps[cid] = insts
    memo: dict[int, frozenset] = {}

    def inside(cid: int) -> frozenset:
        """The scopes that run in computation ``cid`` and its callees."""
        if cid not in memo:
            memo[cid] = frozenset().union(
                *(label(i) for i in comps.get(cid, ())))
        return memo[cid]

    def label(inst) -> frozenset:
        _, own, called = inst
        return frozenset([own] if own else []).union(
            *(inside(c) for c in called))

    out = {}
    for insts in comps.values():
        for inst in insts:
            found = label(inst)
            if found:
                out[inst[0]] = "+".join(sorted(found))
    return out


def hlo_scopes(xspace) -> dict[str, dict[str, str]]:
    """Per HLO module that the trace's metadata plane holds, by its name
    there (``jit_f(5)``), the labels of its instructions. A plane is
    left at its name (which protobuf writes before its lines) where it
    is not the metadata plane."""
    out = {}
    for num, plane in _fields(xspace):
        if num != 1:
            continue
        name, entries = "", {4: [], 5: []}
        for num2, v in _fields(plane):
            if num2 == 2:
                name = _text(v)
                if name != METADATA_PLANE:
                    break
            elif num2 in entries:
                entries[num2].append(v)
        if name != METADATA_PLANE:
            continue
        stats = {_text(_one(_one(v, 2), 2)): _one(_one(v, 2), 1, 0)
                 for v in entries[5]}
        if HLO_PROTO_STAT not in stats:
            continue
        for meta in (_one(v, 2) for v in entries[4]):
            for num2, stat in _fields(meta):
                if (num2 == 5 and _one(stat, 1, 0)
                        == stats[HLO_PROTO_STAT]):
                    out[_text(_one(meta, 2))] = module_scopes(
                        _one(stat, 6))
    return out


def read_scopes(xspace) -> dict[str, dict[str, str]]:
    """``hlo_scopes``, or none (with the reason on stderr) where the
    metadata cannot be read: the scopes' readers then find nothing, and
    every other number of the trace stands."""
    try:
        return hlo_scopes(xspace)
    except (ValueError, IndexError) as e:
        print(f"bench: the trace's HLO scopes are unreadable: {e}",
              file=sys.stderr)
        return {}


class Capture:
    """``with Capture() as cap: ... cap.stop()`` traces from entry until
    ``stop()`` (or exit) into a temporary directory, which ``summary()``
    reads and removes."""

    def __init__(self):
        self._dir = None
        self._span = None
        self._read_s = {}
        self.raw = None

    def __enter__(self):
        import jax

        self._dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        return self

    def stop(self) -> None:
        import jax

        if self._span is None:
            return
        self._span.__exit__(None, None, None)
        self._span = None
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        t1 = time.perf_counter()
        try:
            [path] = Path(self._dir).rglob("*.xplane.pb")
            self.raw = load(path)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        self._read_s = {"stop": t1 - t0, "load": time.perf_counter() - t1,
                        "of which scopes": self.raw.scopes_s}

    def __exit__(self, *exc) -> None:
        self.stop()

    def summary(self) -> Summary | None:
        """The window's reduction, with the host time that stopping the
        profiler, loading and reducing took; None where no device ran an
        op in it."""
        if self.raw is None or not self.raw.devices:
            return None
        t0 = time.perf_counter()
        out = reduce(self.raw)
        out.read_s = {**self._read_s, "reduce": time.perf_counter() - t0}
        return out


@contextlib.contextmanager
def span(name: str):
    """A benchmark host span, visible in the profiler's trace."""
    import jax

    with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
        yield
