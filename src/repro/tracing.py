"""Names on the serving path's host phases and the engine's device phases.

``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation`` named
``dgo.<name>``: while a profiler trace runs it records the span, with
``ids`` as event stats (``wave=7``), on the profiler's clock beside the
device's operations; otherwise it costs about a microsecond. Spans on one
thread nest by time, and the wave's dispatch index (``wave``) ties a
wave's scheduler-thread span to its worker-thread span.

``scope(name)`` is a ``jax.named_scope`` named ``dgo.<name>``: it names
the operations traced inside it in the compiled program's metadata
(``op_name``), and changes nothing the program computes.

``now``/``since`` read wall and thread-CPU seconds together, for the
per-wave phase counters of ``serving.metrics.ServingMetrics``.
"""
from __future__ import annotations

import time

import jax

PREFIX = "dgo."


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A host span ``dgo.<name>`` on the profiler's clock."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **ids)


def scope(name: str):
    """A device scope ``dgo.<name>`` for the operations traced inside."""
    return jax.named_scope(PREFIX + name)


def now() -> tuple[float, float]:
    """``(wall, thread-CPU)`` seconds; subtract two with :func:`since`."""
    return time.perf_counter(), time.thread_time()


def since(start: tuple[float, float]) -> tuple[float, float]:
    """``(wall, thread-CPU)`` seconds elapsed since ``start = now()``."""
    wall, cpu = now()
    return wall - start[0], cpu - start[1]
