"""Elastic re-meshing.

DGO is natively elastic: the population has no fixed-size requirement, so
when devices are lost the survivors re-mesh and each takes
ceil((2N-1)/P') children — exactly the paper's NCUBE virtual-processing
mechanism, applied dynamically. Gradient training re-meshes by re-sharding
the latest checkpoint onto the survivor mesh.
"""
from __future__ import annotations

import math

import jax
import numpy as np

from repro.compat import AxisType, mesh_from_devices


def remesh(n_devices: int, model_parallel: int = 1):
    """Largest (data, model) mesh over the surviving devices."""
    usable = (n_devices // model_parallel) * model_parallel
    devices = jax.devices()[:usable]
    arr = np.array(devices).reshape(usable // model_parallel, model_parallel)
    return mesh_from_devices(arr, ("data", "model"),
                             axis_types=(AxisType.Auto, AxisType.Auto))


def elastic_population_plan(n_bits: int, n_shards: int) -> dict:
    """Re-plan DGO population distribution for a new shard count."""
    pop = 2 * n_bits - 1
    virtual = math.ceil(pop / n_shards)
    return {"population": pop, "shards": n_shards,
            "children_per_shard": virtual,
            "idle_slots": virtual * n_shards - pop}


def reshard_tree(tree, shardings):
    """Move a checkpointed pytree onto a new mesh's shardings."""
    return jax.tree.map(jax.device_put, tree, shardings)
