"""Launcher contract: env assembly is pure and exact, the exec path
really yields the requested virtual-device topology, and a ``--processes``
fleet computes the same answers as one process (subprocess tests, so the
rest of the suite keeps seeing 1 device)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.launch import launcher
from repro.launch.launcher import (
    ENV_COORDINATOR,
    ENV_NUM_PROCESSES,
    ENV_PROCESS_ID,
    XLA_DEVICE_FLAG,
    build_env,
    find_tcmalloc,
    pick_coordinator,
    run_payload,
    split_python_payload,
    _set_device_flag,
)

ROOT = Path(__file__).resolve().parents[1]


def launch(args, timeout=420):
    """Run ``python -m repro.launch.launcher <args>`` and return stdout."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.launcher"] + args,
        capture_output=True, text=True, env=env, timeout=timeout)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    return out.stdout


# ---------------------------------------------------------------------------
# env assembly (pure functions, no subprocess)
# ---------------------------------------------------------------------------

def test_set_device_flag_pins_and_replaces():
    assert _set_device_flag("", 16) == f"{XLA_DEVICE_FLAG}=16"
    # an inherited count is replaced, every other flag survives
    prior = f"--xla_cpu_enable_fast_math=false {XLA_DEVICE_FLAG}=8"
    got = _set_device_flag(prior, 32)
    assert got.split() == ["--xla_cpu_enable_fast_math=false",
                           f"{XLA_DEVICE_FLAG}=32"]


def test_build_env_devices_and_log_level():
    env = build_env({"HOME": "/h"}, devices=16, tcmalloc=False)
    assert env["XLA_FLAGS"] == f"{XLA_DEVICE_FLAG}=16"
    assert env["TF_CPP_MIN_LOG_LEVEL"] == "4"
    assert env["HOME"] == "/h"          # base env passes through
    # no devices requested -> XLA_FLAGS untouched
    env2 = build_env({"XLA_FLAGS": "--foo=1"}, tcmalloc=False)
    assert env2["XLA_FLAGS"] == "--foo=1"


def test_build_env_is_pure():
    base = {"XLA_FLAGS": "--foo=1"}
    build_env(base, devices=8, tcmalloc=False, log_level=2)
    assert base == {"XLA_FLAGS": "--foo=1"}


def test_build_env_tcmalloc_prepends_and_dedupes(tmp_path):
    so = tmp_path / "libtcmalloc.so.4"
    so.write_bytes(b"")
    env = build_env({"LD_PRELOAD": "/other.so"}, tcmalloc_path=str(so))
    assert env["LD_PRELOAD"] == f"{so}:/other.so"
    # already-preloaded allocator is not duplicated
    env2 = build_env({"LD_PRELOAD": str(so)}, tcmalloc_path=str(so))
    assert env2["LD_PRELOAD"] == str(so)


def test_build_env_tcmalloc_probe_fallback_is_silent(monkeypatch):
    """No tcmalloc on the box -> LD_PRELOAD untouched, no error."""
    monkeypatch.setattr(launcher, "find_tcmalloc", lambda: None)
    env = build_env({})
    assert "LD_PRELOAD" not in env


def test_find_tcmalloc_first_existing_wins(tmp_path):
    a, b = tmp_path / "a.so", tmp_path / "b.so"
    b.write_bytes(b"")
    assert find_tcmalloc((str(a), str(b))) == str(b)
    assert find_tcmalloc((str(a),)) is None


def test_build_env_exports_fleet_triple():
    env = build_env({}, tcmalloc=False, coordinator="127.0.0.1:9",
                    num_processes=2, process_id=1)
    assert env[ENV_COORDINATOR] == "127.0.0.1:9"
    assert env[ENV_NUM_PROCESSES] == "2"
    assert env[ENV_PROCESS_ID] == "1"
    assert ENV_COORDINATOR not in build_env({}, tcmalloc=False)


def test_build_env_pins_cpu_for_virtual_devices_and_fleets():
    """Virtual devices and fleet workers never reach for an accelerator:
    K processes contending for one chip would fail or hang."""
    tpu = {"JAX_PLATFORMS": "tpu"}
    assert build_env(tpu, devices=4, tcmalloc=False)["JAX_PLATFORMS"] == "cpu"
    fleet = build_env(tpu, tcmalloc=False, coordinator="127.0.0.1:9",
                      num_processes=2, process_id=0)
    assert fleet["JAX_PLATFORMS"] == "cpu"
    # neither asked for: the platform choice is left alone
    assert build_env(tpu, tcmalloc=False)["JAX_PLATFORMS"] == "tpu"
    assert "JAX_PLATFORMS" not in build_env({}, tcmalloc=False)


# ---------------------------------------------------------------------------
# target/payload handling + CLI validation
# ---------------------------------------------------------------------------

def test_split_python_payload_shapes():
    assert split_python_payload(["python", "-c", "x"]) == ["-c", "x"]
    assert split_python_payload(["python3.11", "-m", "m"]) == ["-m", "m"]
    assert split_python_payload([sys.executable, "s.py"]) == ["s.py"]
    assert split_python_payload(["bash", "-c", "x"]) is None
    assert split_python_payload([]) is None


def test_run_payload_dash_c_sets_argv():
    run_payload(["-c", "import sys; assert sys.argv == ['-c', 'a1']", "a1"])
    with pytest.raises(ValueError):
        run_payload([])
    with pytest.raises(ValueError):
        run_payload(["-c"])


def test_pick_coordinator_is_bindable_hostport():
    host, port = pick_coordinator().rsplit(":", 1)
    assert host == "127.0.0.1" and 0 < int(port) < 65536


def test_cli_validation_errors():
    with pytest.raises(SystemExit):        # no target after --
        launcher.main(["--devices", "8"])
    with pytest.raises(SystemExit):        # nonsensical device count
        launcher.main(["--devices", "0", "--", "true"])
    with pytest.raises(SystemExit):        # K < 1
        launcher.main(["--processes", "0", "--", "python", "-c", "pass"])
    with pytest.raises(SystemExit):        # fleets need a python payload
        launcher.main(["--processes", "2", "--", "bash", "-c", "exit"])


# ---------------------------------------------------------------------------
# end-to-end: the exec'd target sees the requested topology
# ---------------------------------------------------------------------------

def test_devices_16_reaches_target():
    out = launch(["--devices", "16", "--", sys.executable, "-c",
                  "import jax; print(jax.device_count())"])
    assert out.strip().splitlines()[-1] == "16"


def test_devices_16_sizes_the_default_mesh_end_to_end():
    """The ISSUE acceptance pin: ``--devices 16`` yields a 16-device data
    mesh through ``resolve_mesh`` with no further plumbing."""
    out = launch(["--devices", "16", "--", sys.executable, "-c",
                  "import json, jax\n"
                  "from repro.core.solver import resolve_mesh\n"
                  "from repro.launch.mesh import mesh_geometry\n"
                  "m = resolve_mesh()\n"
                  "print(json.dumps({'n': jax.device_count(),\n"
                  "                  'geom': mesh_geometry(m)}))"])
    got = json.loads(out.strip().splitlines()[-1])
    assert got == {"n": 16, "geom": [["data", 16]]}


_SOLVE_PAYLOAD = """
import json
from repro.launch.launcher import maybe_initialize_from_env
maybe_initialize_from_env()
import jax, jax.numpy as jnp
from repro.compat import process_index
from repro.core.solver import Distributed, solve
r = solve("rastrigin", Distributed(max_bits=9),
          x0=jnp.asarray([3.1, -2.2]), max_iters=24)
print(json.dumps({"pid": process_index(),
                  "n_dev": jax.device_count(),
                  "best_f": float(r.best_f),
                  "history": [float(v) for v in r.extras["history"]]}))
"""


def _solve_lines(out):
    rows = [json.loads(ln) for ln in out.strip().splitlines()
            if ln.startswith("{")]
    return {row.pop("pid"): row for row in rows}


def test_fleet_of_two_matches_single_process_bitwise():
    """--processes 2 x --devices 4 spans one 8-device global mesh and
    produces the exact trajectory of a single 8-device process."""
    single = _solve_lines(launch(
        ["--devices", "8", "--", sys.executable, "-c", _SOLVE_PAYLOAD]))
    fleet = _solve_lines(launch(
        ["--processes", "2", "--devices", "4", "--",
         sys.executable, "-c", _SOLVE_PAYLOAD]))
    assert set(fleet) == {0, 1}
    assert single[0]["n_dev"] == 8
    for pid in (0, 1):
        assert fleet[pid]["n_dev"] == 8      # global view spans the fleet
        assert fleet[pid] == single[0]       # bitwise: == on float lists
