"""The single import point for JAX's mesh, sharding and runtime APIs.

Callers never touch ``jax.sharding.AxisType``, ``jax.shard_map``,
``AbstractMesh``, ``jax.lax.axis_size`` or ``jax.distributed`` directly:
they import the names below (dgolint DGL001 and DGL007 enforce it). The
repo supports one JAX line, the installed one (0.9); when a release moves
one of these APIs, the change lands here and nowhere else.

Names provided:

* ``shard_map(f, *, mesh, in_specs, out_specs, check_vma=False)``;
* ``AxisType``, ``axis_size``;
* ``make_mesh(axis_shapes, axis_names, *, axis_types=None, devices=None)``;
* ``mesh_from_devices(devices, axis_names, *, axis_types=None)``;
* ``abstract_mesh(axis_shapes, axis_names)``;
* ``pure_callback(callback, result_shape_dtypes, *args)`` with
  element-at-a-time vmap semantics;
* ``distributed_initialize`` / ``process_index`` / ``process_count`` /
  ``is_multiprocess`` — the multi-process runtime surface.
"""
from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh

__all__ = [
    "AxisType",
    "abstract_mesh",
    "axis_size",
    "distributed_initialize",
    "is_multiprocess",
    "make_mesh",
    "mesh_from_devices",
    "process_count",
    "process_index",
    "pure_callback",
    "shard_map",
]

axis_size = jax.lax.axis_size


def shard_map(f: Callable[..., Any], *, mesh, in_specs, out_specs,
              check_vma: bool = False):
    """``jax.shard_map`` with the repo's default of no VMA checking."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def pure_callback(callback, result_shape_dtypes, *args):
    """``jax.pure_callback`` with element-at-a-time vmap semantics (the
    host callback only ever sees unbatched args)."""
    return jax.pure_callback(callback, result_shape_dtypes, *args,
                             vmap_method="sequential")


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              axis_types: Sequence[AxisType] | None = None,
              devices=None) -> Mesh:
    """``jax.make_mesh`` (which caches: equal geometries give one mesh)."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=None if axis_types is None else tuple(axis_types),
        devices=devices)


def mesh_from_devices(devices, axis_names: Sequence[str], *,
                      axis_types: Sequence[AxisType] | None = None) -> Mesh:
    """``Mesh(device_array, names, axis_types=...)``."""
    if axis_types is None:
        return Mesh(devices, tuple(axis_names))
    return Mesh(devices, tuple(axis_names), axis_types=tuple(axis_types))


def abstract_mesh(axis_shapes: Sequence[int],
                  axis_names: Sequence[str]) -> AbstractMesh:
    """``AbstractMesh(sizes, names)``."""
    return AbstractMesh(tuple(axis_shapes), tuple(axis_names))


# ---------------------------------------------------------------------------
# multi-process runtime
# ---------------------------------------------------------------------------

def distributed_initialize(coordinator_address: str, num_processes: int,
                           process_id: int) -> None:
    """Bring up ``jax.distributed`` for a fleet whose coordinator address,
    size and rank are all given, so JAX looks nothing up. Must run before
    any computation — device state is frozen at first use. CPU fleets use
    JAX's default cross-process collectives (gloo)."""
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def process_index() -> int:
    """This process's rank in the fleet (0 for single-process runs)."""
    return int(jax.process_index())


def process_count() -> int:
    """Number of JAX processes in the fleet (1 for single-process runs)."""
    return int(jax.process_count())


def is_multiprocess() -> bool:
    """True when the runtime spans more than one process."""
    return process_count() > 1
