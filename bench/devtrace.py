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
  span their bodies), averaged over the devices.
"""
from __future__ import annotations

import contextlib
import re
import shutil
import tempfile
from dataclasses import dataclass, field
from pathlib import Path

SPAN_PREFIX = "bench."
WINDOW_SPAN = SPAN_PREFIX + "window"
OPS_LINE = "XLA Ops"
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


@dataclass
class RawTrace:
    """Per device plane: its "XLA Ops" events; plus the benchmark's host
    spans."""
    devices: dict = field(default_factory=dict)   # name -> {line: [Event]}
    spans: list = field(default_factory=list)


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

def op_name(name: str) -> str:
    """An HLO instruction's trace name without its text: '%fusion.3 = f32[]
    fusion(...)' -> 'fusion.3'."""
    head = name.split(" = ", 1)[0].strip()
    return head.lstrip("%")[:80]


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


def reduce(raw: RawTrace, n_top: int = 10) -> Summary:
    lo, hi = window_of(raw)
    window_ns = hi - lo
    names = sorted(raw.devices)
    if not names or window_ns <= 0:
        raise ValueError("trace holds no device events in its window")
    busy = []
    coll_ns = exposed_ns = 0.0
    op_time: dict[str, float] = {}
    first_gaps = []
    for i, dev in enumerate(names):
        lines = raw.devices[dev]
        ops = lines.get(OPS_LINE, [])
        iv = clip([(e.start_ns, e.end_ns) for e in ops], lo, hi)
        busy.append(length(union(iv)))
        c_all, c_exposed = collective_time(ops, lo, hi)
        coll_ns += c_all
        exposed_ns += c_exposed
        for e in ops:
            d = min(e.end_ns, hi) - max(e.start_ns, lo)
            if d > 0 and not is_container(e.name):
                op_time[op_name(e.name)] = op_time.get(op_name(e.name),
                                                       0.0) + d
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
        collective_exposed_s=exposed_ns / n * 1e-9)


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
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                raw.spans += [Event(e.start_ns, e.end_ns, e.name)
                              for e in line.events
                              if e.name.startswith(SPAN_PREFIX)]
    return raw


class Capture:
    """``with Capture() as cap: ... cap.stop()`` traces from entry until
    ``stop()`` (or exit) into a temporary directory, which ``summary()``
    reads and removes."""

    def __init__(self):
        self._dir = None
        self._span = None
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
        jax.profiler.stop_trace()
        try:
            [path] = Path(self._dir).rglob("*.xplane.pb")
            self.raw = load(path)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)

    def __exit__(self, *exc) -> None:
        self.stop()

    def summary(self) -> Summary | None:
        """The window's reduction; None where no device ran an op in it."""
        if self.raw is None or not self.raw.devices:
            return None
        return reduce(self.raw)


@contextlib.contextmanager
def span(name: str):
    """A benchmark host span, visible in the profiler's trace."""
    import jax

    with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
        yield
