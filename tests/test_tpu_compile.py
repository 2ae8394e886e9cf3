"""Compile the chip's programs for a described TPU v5e, without the chip.

The TPU compiler (Mosaic, for the Pallas kernel) is installed with JAX and
compiles for a topology that is described but not attached, so these tests
catch what interpret mode cannot: a kernel construct Mosaic refuses, or a
mesh program that does not partition. Nothing runs, so results and times
are out of scope here. The topology is described inside a fixture: only the
worker that runs this file loads the TPU library.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.compat import mesh_from_devices
from repro.core import distributed
from repro.core.objectives import (
    ackley, griewank, quadratic_nd, rastrigin, shekel,
)
from repro.kernels.popstep.kernel import popstep
from repro.kernels.popstep.ops import _convert_objective, _tile


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without one: keep the cache out of it
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", saved)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("make_obj,bits,tile_p", [
    (lambda: rastrigin(40), 16, 128),   # BBOB's largest n: 640 bits, pop 1279
    (lambda: rastrigin(40), 16, 512),
    (lambda: quadratic_nd(9), 7, 128),  # the paper's n=9 problem
    (lambda: shekel(5), 8, 128),        # closure-hoisted constants
])
def test_popstep_compiles_for_v5e(one_chip, make_obj, bits, tile_p):
    obj = make_obj()
    enc = obj.encoding.with_bits(bits)
    pop = enc.population
    t = _tile(pop, tile_p)
    p_pad = pop + (-pop) % t
    f_tile, consts = _convert_objective(jax.vmap(obj.fn), t, enc.n_vars)

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    rows = shape((p_pad,), jnp.int32)
    compiled = popstep.lower(
        shape((enc.n_vars,), jnp.int32), rows, rows, rows,
        tuple(shape(c.shape, c.dtype) for c in consts),
        f_tile=f_tile, n_vars=enc.n_vars, bits=enc.bits, lo=enc.lo,
        hi=enc.hi, pop=pop, tile_p=t, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _replicated(mesh):
    rep = NamedSharding(mesh, P())
    return lambda s, dtype: jax.ShapeDtypeStruct(s, dtype, sharding=rep)


def test_popstep_engine_compiles_for_one_v5e(topo):
    """The fixed-resolution engine — the one-entry schedule ``(enc.bits,)``
    that ``Distributed()`` runs on a TPU — compiles for one chip as an
    XLA program, with no Pallas kernel in it."""
    obj = quadratic_nd(9)
    mesh = mesh_from_devices(np.array(topo.devices[:1]), ("data",))
    engine = distributed.make_distributed_engine(
        jax.vmap(obj.fn), obj.encoding, mesh)
    shape = _replicated(mesh)
    compiled = engine.lower(shape((9,), jnp.float32),
                            shape((1,), jnp.bool_)).compile()
    assert len(compiled.out_info) == 6
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("n_chips", [1, 4])
def test_single_solve_engine_compiles_for_v5e(topo, n_chips):
    """The folded single-solve engine at rastrigin:40 and a 8..16-bit
    schedule, which now ends by decoding its best point: six outputs,
    the last the (40,) point, on one chip and over a 2x2 mesh."""
    obj = rastrigin(40)
    mesh = mesh_from_devices(np.array(topo.devices[:n_chips]), ("data",))
    engine = distributed.make_distributed_engine(
        jax.vmap(obj.fn), obj.encoding, mesh, res_bits=(8, 10, 12, 14, 16))
    shape = _replicated(mesh)
    compiled = engine.lower(shape((40,), jnp.float32),
                            shape((n_chips,), jnp.bool_)).compile()
    outs = compiled.out_info
    assert len(outs) == 6 and outs[-1].shape == (40,)


def test_folded_batched_engine_partitions_over_four_v5e(topo):
    """The serving engine at rastrigin:40, 16 slots and a 8..16-bit
    schedule over a 2x2 mesh: the per-iteration gather of (value, id)
    pairs becomes one cross-chip collective over all four chips."""
    obj = rastrigin(40)
    mesh = mesh_from_devices(np.array(topo.devices), ("data",))
    engine = distributed.make_distributed_engine_batched(
        obj.fn, obj.encoding, mesh, 16, res_bits=(8, 10, 12, 14, 16))
    shape = _replicated(mesh)
    lowered = engine.lower(
        shape((16, 40), jnp.float32), shape((4,), jnp.bool_),
        shape((16,), jnp.bool_), shape((16,), jnp.int32))
    assert "all_gather" in lowered.as_text()
    text = lowered.compile().as_text()
    collectives = [ln for ln in text.splitlines()
                   if " all-gather(" in ln or " all-reduce(" in ln]
    assert collectives, "no cross-chip collective in the 4-chip program"


@pytest.mark.parametrize("make_obj", [
    lambda: rastrigin(40), lambda: ackley(20), lambda: griewank(10),
    lambda: quadratic_nd(9), lambda: shekel(5),
], ids=["rastrigin40", "ackley20", "griewank10", "quadratic9", "shekel5"])
def test_serving_engine_compiles_for_one_v5e(topo, make_obj):
    """The 16-slot wave engine of each served objective, its start-point
    evaluation a per-row ``lax.map`` inside it, compiles for one chip."""
    obj = make_obj()
    mesh = mesh_from_devices(np.array(topo.devices[:1]), ("data",))
    engine = distributed.make_distributed_engine_batched(
        obj.fn, obj.encoding, mesh, 16, res_bits=(8, 10, 12, 14, 16))
    shape = _replicated(mesh)
    text = engine.lower(
        shape((16, obj.encoding.n_vars), jnp.float32),
        shape((1,), jnp.bool_), shape((16,), jnp.bool_),
        shape((16,), jnp.int32)).compile().as_text()
    assert "dgo.parent_eval" in text
