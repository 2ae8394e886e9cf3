#!/usr/bin/env python3
"""Whole runs of a solve cell at a size a CPU holds, sound and with the
timed path broken underneath; one JSON line per case on standard output.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 bench/tests/solve_faults.py sound no_exchange ...

Each case builds its engines anew (a planted fault is compiled into them)
and runs ``run.execute`` past the harness's look for a chip: the cell's
own driver, check and readers, on rastrigin:6 with every answer checked.
Cases: ``sound``, ``sound_1chip`` (the cell on one of the devices),
``sound_traced`` (with ``--trace 1``'s path), and the faults
``unchanged_state`` (a step that returns its state unchanged),
``half_population`` (half of each block of children left out),
``no_exchange`` (each chip keeps its own winner: the exchange between
chips left out) and ``altered_answer`` (the answer's point moved where
the strategy produces it).
"""
from __future__ import annotations

import contextlib
import copy
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402

CELL = "rastrigin40-dgo.solve-4chip"
RASTRIGIN6 = {"name": "rastrigin:6", "objective": "rastrigin", "n": 6,
              "lo": -5.12, "hi": 5.12, "bits": 8,
              "registry": {"name": "rastrigin", "n": 6}}


def small(chips: int) -> harness.Cell:
    c = copy.deepcopy(harness.load_cell(CELL))
    c.workload = dict(c.workload, chips=chips)
    c.config["problems"] = [RASTRIGIN6]
    c.config["check_sample"] = 1000       # every answer is checked
    return c


def unchanged_state(build):
    def broken(*args, **kwargs):
        prepare = build(*args, **kwargs)

        def prep(quorum_mask):
            step = prepare(quorum_mask)

            def one_step(bits, val, it, res_idx):
                _, _, improved = step(bits, val, it, res_idx)
                return bits, val, improved & False
            return one_step
        return prep
    return broken


def half_population(build):
    import jax.numpy as jnp

    def broken(f_batch, *args, **kwargs):
        def half(xs):
            keep = jnp.arange(xs.shape[0]) < xs.shape[0] // 2
            return jnp.where(keep, f_batch(xs), jnp.inf)
        return build(half, *args, **kwargs)
    return broken


@contextlib.contextmanager
def planted(case: str):
    """The program with ``case``'s fault in place, its engines built
    anew, and restored afterwards."""
    import jax

    from repro.core import cache, distributed, solver

    patches = []
    if case in ("unchanged_state", "half_population"):
        wrap = globals()[case]
        patches.append((distributed, "_build_shard_schedule_step",
                        wrap(distributed._build_shard_schedule_step)))
    elif case == "no_exchange":
        patches.append((jax.lax, "all_gather",
                        lambda x, axis_name, **_: x[None]))
    elif case == "altered_answer":
        orig = solver.Distributed._solve

        def altered(self, problem, **kwargs):
            r = orig(self, problem, **kwargs)
            enc = problem.encoding
            return r._replace(
                best_x=r.best_x.at[0].add(0.01 * (enc.hi - enc.lo)))
        patches.append((solver.Distributed, "_solve", altered))
    elif not case.startswith("sound"):
        raise ValueError(f"unknown case {case!r}")
    saved = [(obj, name, getattr(obj, name)) for obj, name, _ in patches]
    cache.clear()
    for obj, name, value in patches:
        setattr(obj, name, value)
    try:
        yield
    finally:
        for obj, name, value in saved:
            setattr(obj, name, value)
        cache.clear()


def main(argv) -> int:
    import jax

    import run

    for case in argv:
        chips = 1 if case == "sound_1chip" else jax.device_count()
        with planted(case):
            out = run.execute(small(chips), 2**31 + 29, 2.0,
                              case == "sound_traced", jax.devices()[0],
                              chips, time.perf_counter())
        notes = out.pop("_notes")
        print(json.dumps({"case": case, "notes": notes, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
