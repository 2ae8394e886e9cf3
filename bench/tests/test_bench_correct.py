"""How ``correct`` is decided: the plain reference, its bfloat16 control,
and whole runs on the CPU (small shapes) with the timed path sound and
with it broken underneath, for each fault a cell can have."""
import copy
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import control  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402

SERVE = "sfu-suite.open"
SOLVE = "rastrigin40-dgo.solve-4chip"

RASTRIGIN6 = {"name": "rastrigin:6", "objective": "rastrigin", "n": 6,
              "lo": -5.12, "hi": 5.12, "bits": 8,
              "registry": {"name": "rastrigin", "n": 6}}


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_bits", [1, 2, 7, 40, 640])
def test_segment_tree_has_2n_minus_1_segments_in_preorder(n_bits):
    t = reference.segment_table(n_bits)
    assert t.shape == (2 * n_bits - 1, 2)
    assert tuple(t[0]) == (0, n_bits)
    if n_bits > 1:     # left child right after its parent, larger half
        assert tuple(t[1]) == (0, (n_bits + 1) // 2)


@pytest.mark.parametrize("n,bits", [(3, 4), (5, 8), (2, 16)])
def test_level_patterns_are_the_three_step_transform(n, bits):
    rng = np.random.default_rng(n * bits)
    parent = rng.integers(0, 2, n * bits).astype(np.uint8)
    kids = reference.children_bits(parent,
                                   reference.segment_table(n * bits))
    w = 2 ** np.arange(bits - 1, -1, -1)
    kid_levels = kids.reshape(-1, n, bits).astype(np.int64) @ w
    parent_levels = parent.reshape(n, bits).astype(np.int64) @ w
    np.testing.assert_array_equal(
        kid_levels, parent_levels ^ reference.level_patterns(n, bits))


def test_reference_descends_to_the_quadratic_optimum():
    spec = {"objective": "quadratic", "n": 9, "lo": -10.0, "hi": 10.0,
            "bits": 8}
    x0 = np.full(9, 7.0, np.float32)
    r = reference.run(spec, x0, max_bits=16, bits_step=2, max_iters=256)
    assert r.best_f < 1e-3 and len(r.per_resolution) == 5
    assert r.iterations == sum(r.per_resolution)
    assert reference.value64(spec, r.best_x) == pytest.approx(r.best_f,
                                                              rel=1e-5)


def test_bfloat16_control_is_not_correct():
    c = harness.load_cell(SERVE)
    c.config["check_sample"] = 10
    # ten requests of the cell's own traffic, at its shapes
    seconds = 10 / c.traffic.get("rate_per_s", 1.0)
    got, limits = control.control_numbers(c, seed=2**31 + 3,
                                          seconds=seconds)
    assert any(got[k] > limits[k] for k in got), got


def test_bfloat16_control_of_a_solve_cell_is_not_correct():
    c = harness.load_cell(SOLVE)
    c.config["check_sample"] = 4
    # the first five starts of the cell's closed loop, at its shapes
    got, limits = control.control_numbers(c, seed=-(2**31) - 5,
                                          seconds=1.0)
    assert any(got[k] > limits[k] for k in got), got


# The numbers of the check and of its control on fixed seeds, as the
# harness computed them before each configuration named its check (the
# functions then in bench/check.py and bench/control.py): eight of each
# cell's sample, every third answer cut to two iterations a resolution.
SAME_AS_BEFORE = {
    SERVE: (2**31 + 101, 10 / 128,
            {"f_gap": 1.3417367935180664, "x_gap": 0.0965706294600526,
             "iters_gap": 0.7669491525423728},
            {"f_gap": 19.3273286819458, "x_gap": 1.0055956809473798e-06,
             "iters_gap": 0.9494949494949495}),
    SOLVE: (-(2**31) - 103, 1.0,
            {"f_gap": 0.9472942637893917, "x_gap": 0.14344287317755441,
             "iters_gap": 0.757201646090535},
            {"f_gap": 13.586138149523796, "x_gap": 1.0001309276426109e-06,
             "iters_gap": 0.9615384615384616}),
}


@pytest.mark.parametrize("cell_name", sorted(SAME_AS_BEFORE))
def test_dgo_reference_check_reads_the_numbers_it_read_before(cell_name):
    from types import SimpleNamespace

    seed, seconds, want_control, want_check = SAME_AS_BEFORE[cell_name]
    c = harness.load_cell(cell_name)
    assert c.config["check"] == "dgo_reference"
    c.config["check_sample"] = 8
    cfg = c.config
    module = harness.check_module("dgo_reference")
    got, limits = control.control_numbers(c, seed, seconds)
    assert got == want_control
    assert limits == {"f_gap": 1e-3, "x_gap": 1e-3, "iters_gap": 0.3}
    answers = module.starts(c, seed, seconds)
    for i, a in enumerate(answers):
        r = reference.run(cfg["problems"][a.problem], a.x0,
                          max_bits=int(cfg["max_bits"]),
                          bits_step=int(cfg["bits_step"]),
                          max_iters=2 if i % 3 == 0
                          else int(cfg["max_iters"]))
        a.best_x, a.best_f, a.iterations = r.best_x, float(r.best_f), \
            r.iterations
    verdict, compared = module.check(
        cfg, SimpleNamespace(answers=answers, failed=0), seed)
    assert compared == {k: (v, limits[k]) for k, v in want_check.items()}
    assert verdict is False


# ---------------------------------------------------------------------------
# whole runs on the CPU, sound and broken
# ---------------------------------------------------------------------------

def small(cell_name: str) -> harness.Cell:
    """The cell at shapes a CPU test holds: short waves, small problems,
    as many chips as this process has, and every answer checked."""
    import jax

    c = copy.deepcopy(harness.load_cell(cell_name))
    c.workload = dict(c.workload, chips=jax.device_count())
    probs = c.config["problems"]
    # griewank:10 on the source's box, which is not the registry's
    c.config["problems"] = [RASTRIGIN6, probs[2], probs[4]]
    c.traffic["shares"] = [1, 1, 1]
    c.config["wave_size"] = 4
    c.traffic["rate_per_s"] = 300.0       # enough to fill the waves
    c.config["check_sample"] = 1000       # every answer is checked
    return c


def run_small(cell_name: str, seed: int = 2**31 + 11) -> dict:
    import jax

    import run

    return run.execute(small(cell_name), seed, 1.0, False, jax.devices()[0],
                       jax.device_count(), time.perf_counter())


@pytest.fixture
def fresh(monkeypatch, tmp_path):
    """Engines built anew (and no persistent cache written) so that a
    planted fault is compiled into them, and dropped afterwards."""
    from repro.core import cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    cache.clear()
    yield monkeypatch
    cache.clear()


def unchanged_state(build):
    """A step that returns its state unchanged (and reports no gain)."""
    def broken(*args, **kwargs):
        prepare = build(*args, **kwargs)

        def prep(quorum_mask):
            step = prepare(quorum_mask)

            def one_step(bits, vals, it, res_idx):
                _, _, improved = step(bits, vals, it, res_idx)
                return bits, vals, improved & False
            return one_step
        return prep
    return broken


def half_batch(build):
    """A wave step that leaves the second half of its slots out."""
    import jax.numpy as jnp

    def broken(*args, **kwargs):
        prepare = build(*args, **kwargs)

        def prep(quorum_mask):
            step = prepare(quorum_mask)

            def one_step(bits, vals, it, res_idx):
                nb, nv, imp = step(bits, vals, it, res_idx)
                keep = jnp.arange(vals.shape[0]) < vals.shape[0] // 2
                return (jnp.where(keep[:, None], nb, bits),
                        jnp.where(keep, nv, vals), imp & keep)
            return one_step
        return prep
    return broken


def test_sound_serve_run_is_correct(fresh):
    out = run_small(SERVE)
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] == 300
    assert set(out["metrics"]) == {"latency_p95_ms", "latency_p50_ms",
                                   "solves_per_s", "setup_s"}
    assert list(out)[-2:] == ["check", "_notes"]


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "altered_answer"])
def test_serve_fault_is_not_correct(fresh, fault):
    from repro.core import distributed, solver

    if fault == "altered_answer":
        orig = solver._slot_result

        def altered(res, bits_h, iters_h, slot, enc0, schedule, width):
            r = orig(res, bits_h, iters_h, slot, enc0, schedule, width)
            shift = 0.01 * (enc0.hi - enc0.lo)
            return r._replace(best_x=r.best_x.at[0].add(shift))
        fresh.setattr(solver, "_slot_result", altered)
    else:
        wrap = unchanged_state if fault == "unchanged_state" else half_batch
        fresh.setattr(distributed, "_build_shard_schedule_step_batched",
                      wrap(distributed._build_shard_schedule_step_batched))
    out = run_small(SERVE)
    assert not out["correct"], out["check"]


# ---------------------------------------------------------------------------
# the solve cell on four virtual devices, sound and broken
# ---------------------------------------------------------------------------

SOLVE_FAULTS = ["unchanged_state", "half_population", "no_exchange",
                "altered_answer"]


@pytest.fixture(scope="module")
def solve_runs(tmp_path_factory):
    """One process with four CPU devices runs the solve cell small, sound
    and with each fault planted (``solve_faults.py``); its result lines by
    case."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(
                   tmp_path_factory.mktemp("jax-cache")))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " "
                        "--xla_force_host_platform_device_count=4").strip()
    cases = ["sound", "sound_1chip", "sound_traced", *SOLVE_FAULTS]
    out = subprocess.run(
        [sys.executable, str(BENCH / "tests" / "solve_faults.py"), *cases],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    lines = [json.loads(x) for x in out.stdout.splitlines()
             if x.startswith("{")]
    return {r["case"]: r for r in lines}


@pytest.mark.parametrize("case,chips", [("sound", 4), ("sound_1chip", 1)])
def test_sound_solve_run_is_correct(solve_runs, case, chips):
    out = solve_runs[case]
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] >= 10
    assert out["device"]["count"] == chips
    assert set(out["metrics"]) == {"solve_ms", "solve_p95_ms", "setup_s"}
    assert out["metrics"]["solve_p95_ms"]["value"] >= \
        out["metrics"]["solve_ms"]["value"] > 0
    assert list(out)[-1] == "check"
    assert "programs built in the window 0" in out["notes"][0]


def test_traced_solve_run_is_correct(solve_runs):
    out = solve_runs["sound_traced"]
    assert out["correct"], out["check"]
    # the CPU's trace holds no device plane: every reader finds nothing
    assert out["metrics"] == {}


@pytest.mark.parametrize("fault", SOLVE_FAULTS)
def test_solve_fault_is_not_correct(solve_runs, fault):
    out = solve_runs[fault]
    assert not out["correct"], out["check"]
