#!/usr/bin/env python3
"""The benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Set-up (JAX, the chip, the persistent compile cache in the checkout,
warm-up of every program the cell's traffic uses) is timed as
``setup_s``; then the cell's traffic runs for ``--seconds``. With
``--trace 0`` the last line of standard output reports the cell's
end-to-end metrics; with ``--trace 1`` the last part of the window is
traced and the line reports its per-layer metrics and a ``breakdown``.
Either way the answers are checked by the check the configuration names
(``"check"``: ``bench/checks/<name>.py``), and the numbers compared are
printed with their limits as the last lines of standard error and under
``check`` in the result line. A run is correct where every request due
was answered without an error and the check finds the answers right.

The run fails, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import drive  # noqa: E402
import e2e  # noqa: E402
import harness  # noqa: E402
import peaks  # noqa: E402


DRIVERS = {"serve": drive.serve, "solve": drive.solve}


@dataclass
class Context:
    """What a per-layer metric's reader gets."""
    run: drive.Run
    cell: harness.Cell
    peak: dict


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_chips(cell: harness.Cell):
    """JAX's devices, or None (with the reason on stderr) where they are
    not TPUs or fewer than the cell asks for."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX's first device is "
              f"{devices[0].platform!r})", file=sys.stderr)
        return None
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return None
    return devices


def execute(cell: harness.Cell, seed: int, seconds: float, trace: bool,
            device, device_count: int, t_start: float = T_START) -> dict:
    """Run the cell on whatever JAX has (the chip check is the caller's)
    and return the result line's object."""
    from repro.launch.compile_cache import enable_compile_cache

    peak = peaks.peaks(device.device_kind) if device.platform == "tpu" \
        else {}
    enable_compile_cache()
    compiles = drive.CompileCounter()
    if cell.config["entry"] not in DRIVERS:
        raise ValueError(f"{cell.name}: no driver for entry "
                         f"{cell.config['entry']!r}")
    checker = harness.check_module(cell.config["check"], cell.root)
    run = DRIVERS[cell.config["entry"]](cell, seed, seconds, trace,
                                        compiles, t_start)
    t_check = time.perf_counter()
    verdict, compared = checker.check(cell.config, run, seed)
    run.notes.append(f"check_s {time.perf_counter() - t_check!r}")
    correct = run.failed == 0 and verdict
    out = {"correct": correct, "attempted": run.attempted,
           "failed": run.failed}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": device_count,
           "memory_peak_bytes": run.memory_peak_bytes}
    if trace:
        ctx = Context(run, cell, peak)
        metrics = {}
        for m in cell.per_layer:
            value = harness.metric_reader(m["name"], cell.root)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["metrics"] = metrics
        if run.trace is not None:
            dev["busy_s"] = run.trace.busy_s
            dev["window_s"] = run.trace.window_s
            out["breakdown"] = {"device_ops": run.trace.top_ops,
                                "idle_gaps": run.trace.idle_gaps}
            run.notes.append(
                f"trace read_s {run.trace.read_s!r}; module runs whole "
                f"{len(run.trace.module_runs)}; device s by scope "
                f"{run.trace.scope_time!r}")
    else:
        out["metrics"] = {m["name"]: {"value": e2e.METRICS[m["name"]](run),
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
    out["device"] = dev
    out["check"] = {k: {"value": v, "limit": lim}
                    for k, (v, lim) in compared.items()}
    out["_notes"] = run.notes
    return out


def main(argv=None) -> int:
    args = parse(argv)
    cell = harness.load_cell(args.workload)
    devices = find_chips(cell)
    if devices is None:
        return 2
    out = execute(cell, args.seed, args.seconds, bool(args.trace),
                  devices[0], len(devices))
    for note in out.pop("_notes"):
        print(f"bench: {note}", file=sys.stderr)
    for name, c in out["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
