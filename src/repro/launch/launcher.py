"""Virtual-fleet launcher: configure the process *before* JAX imports.

JAX freezes its device topology at first import: ``XLA_FLAGS`` (the host
virtual-device count), the allocator preload and the log level must all be
in the environment before any ``import jax`` runs.  This module is the
front door that makes that ordering structural instead of a convention —
it assembles the environment, then ``exec``s the real target (a bench, the
serve CLI, ``python -c ...``) so the target's interpreter starts clean::

    python -m repro.launch.launcher --devices 16 -- \\
        python -m repro.launch.serve --dgo --problems rastrigin:2 ...

``--devices N`` pins ``--xla_force_host_platform_device_count=N`` (a real
N-device mesh of *virtual* CPU devices — they time-slice the physical
cores, so this scales the topology, not the FLOPs; see docs/scaling.md)
and ``JAX_PLATFORMS=cpu``, as ``--processes`` does: a machine's chips
are reached by one plain process, not through the launcher.
``--processes K`` additionally spawns K workers, each a JAX process in one
``jax.distributed`` fleet whose global mesh spans all ``K * N`` devices;
workers bring the runtime up through ``repro.compat.distributed_initialize``
(the only sanctioned call site — dgolint DGL007) and then run the python
payload in-process.  Request batches entering the engines are ``device_put``
replicated onto each worker's shard of the global mesh by the engine layer
(``core/distributed.py``), keyed off ``repro.compat.is_multiprocess``.

Env idioms applied (both lifted from production JAX launchers): tcmalloc
via ``LD_PRELOAD`` when present on the box (silently skipped when absent —
the stock allocator fragments under multi-GiB arena churn but correctness
is unaffected), and ``TF_CPP_MIN_LOG_LEVEL=4`` so XLA's C++ chatter does
not drown bench output.

This module never imports jax at module level — that would defeat its
whole purpose.
"""
from __future__ import annotations

import os
import runpy
import socket
import subprocess
import sys
from pathlib import Path

XLA_DEVICE_FLAG = "--xla_force_host_platform_device_count"

# well-known tcmalloc locations, most specific first (the probe takes the
# first that exists; none existing is the documented fallback, not an error)
TCMALLOC_CANDIDATES = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
    "/usr/lib/libtcmalloc_minimal.so.4",
)

# worker-coordination env vars (set by the parent, read by the worker shim)
ENV_COORDINATOR = "DGO_COORDINATOR"
ENV_NUM_PROCESSES = "DGO_NUM_PROCESSES"
ENV_PROCESS_ID = "DGO_PROCESS_ID"


def find_tcmalloc(candidates=TCMALLOC_CANDIDATES) -> str | None:
    """First existing tcmalloc shared object, or None (fallback: skip)."""
    for path in candidates:
        if os.path.exists(path):
            return path
    return None


def _set_device_flag(xla_flags: str, devices: int) -> str:
    """Pin the host device-count flag in an XLA_FLAGS string.

    Other flags the caller already exported are preserved; an existing
    device-count flag is *replaced* — the launcher is the front door and
    its ``--devices`` wins over inherited environment.
    """
    kept = [f for f in xla_flags.split()
            if not f.startswith(f"{XLA_DEVICE_FLAG}=")]
    kept.append(f"{XLA_DEVICE_FLAG}={devices}")
    return " ".join(kept)


def build_env(base_env: dict | None = None, *, devices: int | None = None,
              log_level: int = 4, tcmalloc: bool = True,
              tcmalloc_path: str | None = None,
              coordinator: str | None = None,
              num_processes: int | None = None,
              process_id: int | None = None) -> dict:
    """Assemble the child environment (pure: no process state touched).

    ``devices`` pins the virtual host device count into ``XLA_FLAGS``;
    ``tcmalloc`` prepends the probed allocator to ``LD_PRELOAD`` (no-op
    when the probe finds nothing); the ``coordinator``/``num_processes``/
    ``process_id`` triple exports the worker-coordination variables for
    ``maybe_initialize_from_env``.

    Virtual devices and fleets are CPU tools: either one pins
    ``JAX_PLATFORMS=cpu``, so K workers (or a bench's per-size children)
    never contend for an accelerator that one process at a time may hold.
    """
    env = dict(os.environ if base_env is None else base_env)
    if devices is not None or coordinator is not None:
        env["JAX_PLATFORMS"] = "cpu"
    if devices is not None:
        env["XLA_FLAGS"] = _set_device_flag(env.get("XLA_FLAGS", ""),
                                            devices)
    env["TF_CPP_MIN_LOG_LEVEL"] = str(log_level)
    if tcmalloc:
        path = tcmalloc_path if tcmalloc_path is not None else find_tcmalloc()
        if path is not None:
            parts = env.get("LD_PRELOAD", "").split(":")
            parts = [p for p in parts if p]
            if path not in parts:
                env["LD_PRELOAD"] = ":".join([path] + parts)
    if coordinator is not None:
        env[ENV_COORDINATOR] = coordinator
        env[ENV_NUM_PROCESSES] = str(num_processes)
        env[ENV_PROCESS_ID] = str(process_id)
        # a fresh worker must actually join, even if this parent's own
        # environment carries the joined marker from an enclosing fleet
        env.pop(ENV_FLEET_JOINED, None)
    return env


# process-global idempotence marker for maybe_initialize_from_env: it
# must live in os.environ, not a module global — ``python -m`` runs this
# module as ``__main__`` while the payload re-imports it under its dotted
# name, and the two copies do not share globals
ENV_FLEET_JOINED = "DGO_FLEET_JOINED"


def maybe_initialize_from_env(env=None) -> bool:
    """Bring up ``jax.distributed`` when the launcher exported a fleet.

    Reads the ``DGO_COORDINATOR`` / ``DGO_NUM_PROCESSES`` /
    ``DGO_PROCESS_ID`` triple and routes through
    ``repro.compat.distributed_initialize``.  Returns True when this
    process is part of a fleet, False for plain single-process runs.
    Idempotent — the worker shim joins before the payload runs, and
    payloads that call this themselves (so they also work when launched
    directly) must not trigger a second ``initialize``.
    """
    env = os.environ if env is None else env
    coordinator = env.get(ENV_COORDINATOR)
    if not coordinator:
        return False
    if os.environ.get(ENV_FLEET_JOINED):
        return True
    from repro.compat import distributed_initialize

    distributed_initialize(coordinator,
                           int(env[ENV_NUM_PROCESSES]),
                           int(env[ENV_PROCESS_ID]))
    os.environ[ENV_FLEET_JOINED] = "1"
    return True


def pick_coordinator(host: str = "127.0.0.1") -> str:
    """A free ``host:port`` for the fleet coordinator (best effort)."""
    with socket.socket() as s:
        s.bind((host, 0))
        return f"{host}:{s.getsockname()[1]}"


def split_python_payload(target: list[str]) -> list[str] | None:
    """The interpreter arguments of a ``python ...`` target, else None.

    Multi-process mode re-runs the payload inside the worker shim's own
    interpreter, so only python targets are spawnable across a fleet.
    """
    if not target:
        return None
    head = os.path.basename(target[0])
    if head.startswith("python") or target[0] == sys.executable:
        return target[1:]
    return None


def run_payload(payload: list[str]) -> None:
    """Execute interpreter-style arguments in this process.

    Supports the three spawn shapes: ``-c code [args...]``, ``-m module
    [args...]`` and ``script.py [args...]`` — the same surface the worker
    shim promises for ``--processes`` targets.
    """
    if not payload:
        raise ValueError("empty python payload")
    if payload[0] == "-c":
        if len(payload) < 2:
            raise ValueError("python -c needs a program string")
        sys.argv = ["-c"] + payload[2:]
        exec(compile(payload[1], "<launcher -c>", "exec"),
             {"__name__": "__main__"})
    elif payload[0] == "-m":
        if len(payload) < 2:
            raise ValueError("python -m needs a module name")
        sys.argv = [payload[1]] + payload[2:]
        runpy.run_module(payload[1], run_name="__main__", alter_sys=True)
    else:
        sys.argv = list(payload)
        runpy.run_path(payload[0], run_name="__main__")


def _split_argv(argv: list[str]) -> tuple[list[str], list[str]]:
    """(launcher args, target command) around the ``--`` separator."""
    if "--" in argv:
        i = argv.index("--")
        return argv[:i], argv[i + 1:]
    return argv, []


def _parse_args(own: list[str]):
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.launcher",
        description="Configure XLA/allocator env, then exec the target "
                    "(separate launcher args from the target with --).")
    ap.add_argument("--devices", type=int, default=None, metavar="N",
                    help="virtual host devices per process "
                         f"(pins {XLA_DEVICE_FLAG}=N)")
    ap.add_argument("--processes", type=int, default=1, metavar="K",
                    help="spawn K jax.distributed workers spanning one "
                         "global mesh (target must be a python command)")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="fleet coordinator address "
                         "(default: a free local port)")
    ap.add_argument("--log-level", type=int, default=4,
                    help="TF_CPP_MIN_LOG_LEVEL for the target (default 4)")
    ap.add_argument("--no-tcmalloc", action="store_true",
                    help="skip the tcmalloc LD_PRELOAD probe")
    ap.add_argument("--worker", action="store_true",
                    help=argparse.SUPPRESS)  # internal: fleet worker shim
    return ap, ap.parse_args(own)


def _run_worker(target: list[str]) -> int:
    """Fleet worker: join the distributed runtime, then run the payload."""
    maybe_initialize_from_env()
    payload = split_python_payload(target)
    if payload is None:
        payload = target  # already interpreter-style args
    run_payload(payload)
    return 0


def _spawn_fleet(args, target: list[str]) -> int:
    """Spawn K worker shims sharing one coordinator; wait for all."""
    coordinator = args.coordinator or pick_coordinator()
    # workers import this module before the payload touches jax, so make
    # sure the repro package root survives into their interpreter
    src_root = str(Path(__file__).resolve().parents[2])
    procs = []
    for pid in range(args.processes):
        env = build_env(devices=args.devices, log_level=args.log_level,
                        tcmalloc=not args.no_tcmalloc,
                        coordinator=coordinator,
                        num_processes=args.processes, process_id=pid)
        pypath = env.get("PYTHONPATH", "")
        if src_root not in pypath.split(os.pathsep):
            env["PYTHONPATH"] = (f"{src_root}{os.pathsep}{pypath}"
                                 if pypath else src_root)
        cmd = [sys.executable, "-m", "repro.launch.launcher",
               "--worker", "--"] + target
        procs.append(subprocess.Popen(cmd, env=env))
    rcs = [p.wait() for p in procs]
    return max(rcs) if rcs else 0


def main(argv: list[str] | None = None) -> int:
    own, target = _split_argv(sys.argv[1:] if argv is None else list(argv))
    ap, args = _parse_args(own)
    if args.worker:
        return _run_worker(target)
    if not target:
        ap.error("no target command (separate it with --)")
    if args.devices is not None and args.devices < 1:
        ap.error(f"--devices must be >= 1, got {args.devices}")
    if args.processes < 1:
        ap.error(f"--processes must be >= 1, got {args.processes}")
    if args.processes > 1:
        if split_python_payload(target) is None:
            ap.error("--processes > 1 needs a python target "
                     "(the worker shim re-runs the payload in its own "
                     "interpreter): got " + repr(target[0]))
        return _spawn_fleet(args, target)
    env = build_env(devices=args.devices, log_level=args.log_level,
                    tcmalloc=not args.no_tcmalloc)
    os.execvpe(target[0], target, env)  # no return


if __name__ == "__main__":
    raise SystemExit(main())
