"""Multi-device behaviour (subprocess with 8 forced host devices so the
rest of the suite keeps seeing 1 device)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest


ROOT = Path(__file__).resolve().parents[1]


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_distributed_dgo_matches_single_device():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, json
        from functools import partial
        from repro.core.dgo import dgo_resolution_step
        from repro.core.encoding import encode, decode
        from repro.core.objectives import rastrigin
        from repro.core.solver import Distributed, solve
        from repro.compat import AxisType, make_mesh
        mesh = make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
        obj = rastrigin(2)
        x0 = jnp.asarray([3.1, -2.2])
        res = solve(obj, strategy=Distributed(mesh=mesh), x0=x0,
                    max_iters=48)
        f_batch = jax.vmap(obj.fn)
        b0 = encode(x0, obj.encoding)
        v0 = obj.fn(decode(b0, obj.encoding))
        state, _ = jax.jit(partial(dgo_resolution_step, f_batch,
                                   obj.encoding, 48))(b0, v0)
        assert np.isclose(float(res.best_f), float(state.parent_val),
                          atol=1e-6), \\
            (float(res.best_f), float(state.parent_val))
        print(json.dumps({"ok": True, "val": float(res.best_f)}))
    """)
    assert json.loads(out.splitlines()[-1])["ok"]


def test_distributed_dgo_quorum_survives_shard_loss():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, json
        from repro.core.objectives import rastrigin
        from repro.core.solver import Distributed, solve
        from repro.compat import AxisType, make_mesh
        mesh = make_mesh((8,), ("data",),
                         axis_types=(AxisType.Auto,))
        obj = rastrigin(2)
        mask = jnp.asarray([True, False, True, True, False, True, True, True])
        res = solve(obj, strategy=Distributed(mesh=mesh, quorum_mask=mask),
                    x0=jnp.asarray([3.1, -2.2]), max_iters=48)
        # still descends despite losing 2/8 shards
        assert float(res.best_f) < res.extras["history"][0]
        print(json.dumps({"ok": True}))
    """)
    assert json.loads(out.splitlines()[-1])["ok"]


def test_on_device_driver_matches_host_driver():
    """A fixed-resolution ``Distributed()`` on 8 shards runs the one-entry
    schedule ``[8]``, the same algorithm as the numpy ``Sequential``
    reference at ``max_bits=8``: the same iterations and best point, and
    on the quadratic the same value and history.  ``Sequential`` evaluates
    the objective eagerly, one point at a time, where the engine evaluates
    a fused batch, so on rastrigin the values differ by a few float32 ULPs
    (about 1.3e-5 at a value of 23) and only the points are compared."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.core.objectives import quadratic_nd, rastrigin
        from repro.core.solver import Distributed, Sequential, solve
        from repro.compat import AxisType, make_mesh
        mesh = make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        for obj, x0, values in ((quadratic_nd(2), [4.0, -3.0], True),
                                (rastrigin(2), [3.1, -2.2], False)):
            x0 = jnp.asarray(x0)
            dist = solve(obj, Distributed(mesh=mesh), x0=x0, max_iters=48)
            seq = solve(obj, Sequential(max_bits=8), x0=x0, max_iters=48)
            assert dist.extras["schedule"] == (8,), dist.extras["schedule"]
            assert dist.iterations == seq.iterations, obj.name
            assert np.array_equal(np.asarray(dist.best_x),
                                  np.asarray(seq.best_x)), obj.name
            h = dist.extras["history"]
            assert len(h) >= 2 and h[-1] < h[0], obj.name
            if values:
                assert np.isclose(float(dist.best_f), float(seq.best_f),
                                  atol=1e-6), \\
                    (float(dist.best_f), float(seq.best_f))
                assert np.allclose(h, seq.extras["raw_trace"], atol=1e-6)
        print(json.dumps({"ok": True}))
    """)
    assert json.loads(out.splitlines()[-1])["ok"]


def test_quorum_masked_mesh_reaches_all_alive_optimum():
    """Losing shards slows DGO down (fewer children per round) but must not
    change where it converges on the paper's quadratic — the missing
    children are a strict subset each round, regenerated deterministically."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.core.objectives import quadratic_nd
        from repro.core.solver import Distributed, solve
        from repro.compat import AxisType, make_mesh
        mesh = make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        obj = quadratic_nd(2)
        x0 = jnp.asarray([4.0, -3.0])
        full = solve(obj, strategy=Distributed(mesh=mesh), x0=x0,
                     max_iters=128)
        mask = jnp.asarray([True, False, True, True,
                            False, True, True, True])
        masked = solve(obj, strategy=Distributed(mesh=mesh,
                                                 quorum_mask=mask),
                       x0=x0, max_iters=128)
        assert float(masked.best_f) < masked.extras["history"][0]
        assert np.isclose(float(masked.best_f), float(full.best_f),
                          atol=1e-5), \\
            (float(masked.best_f), float(full.best_f))
        print(json.dumps({"ok": True, "full": float(full.best_f),
                          "masked": float(masked.best_f)}))
    """)
    assert json.loads(out.splitlines()[-1])["ok"]


def test_folded_schedule_masked_shards_converge():
    """Satellite coverage for the folded on-device schedule: escalation
    inside the while_loop still converges to the all-alive optimum under
    quorum loss (the missed children are regenerated by rotation within
    each resolution, and every shard escalates on the same round)."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.core.objectives import quadratic_nd
        from repro.core.solver import Distributed, solve
        from repro.compat import AxisType, make_mesh
        mesh = make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        obj = quadratic_nd(2)
        x0 = jnp.asarray([4.0, -3.0])
        full = solve(obj, strategy=Distributed(mesh=mesh, max_bits=12),
                     x0=x0, max_iters=128)
        mask = jnp.asarray([True, False, True, True,
                            False, True, True, True])
        masked = solve(obj, strategy=Distributed(mesh=mesh, max_bits=12,
                                                 quorum_mask=mask),
                       x0=x0, max_iters=128)
        assert full.extras["schedule"] == (8, 10, 12)
        assert float(masked.best_f) < masked.extras["history"][0]
        assert np.isclose(float(masked.best_f), float(full.best_f),
                          atol=1e-5), \\
            (float(masked.best_f), float(full.best_f))
        print(json.dumps({"ok": True, "full": float(full.best_f),
                          "masked": float(masked.best_f)}))
    """)
    assert json.loads(out.splitlines()[-1])["ok"]


def test_batched_engine_matches_independent_runs():
    """Batched(R starts) == R independent Distributed trajectories
    (values AND histories), amortized into one compilation."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.core.objectives import rastrigin
        from repro.core.solver import Batched, Distributed, solve
        from repro.compat import AxisType, make_mesh
        mesh = make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        obj = rastrigin(2)
        x0s = jnp.asarray([[3.1, -2.2], [1.0, 1.0],
                           [-4.0, 2.0], [0.5, -0.5]])
        res = solve(obj, strategy=Batched(mesh=mesh), x0=x0s,
                    max_iters=48).extras
        for r in range(x0s.shape[0]):
            single = solve(obj, strategy=Distributed(mesh=mesh),
                           x0=x0s[r], max_iters=48)
            v, h = single.best_f, single.extras["history"]
            assert np.isclose(float(v), float(res["values"][r]),
                              atol=1e-6), \\
                (r, float(v), float(res["values"][r]))
            assert int(res["restart_iterations"][r]) == len(h) - 1, r
            assert np.allclose(res["trace"][r][:len(h)], h, atol=1e-6), r
        assert int(res["best"]) == int(jnp.argmin(res["values"]))
        print(json.dumps({"ok": True}))
    """)
    assert json.loads(out.splitlines()[-1])["ok"]


# Fixed-resolution answers (max_bits None), recorded on the CPU at the
# commit before the fixed-resolution engines were folded into the
# one-entry schedule: per start of _FIXED_STARTS, (best_f, best_x,
# iterations, trace) at max_iters=48.  Distributed() from start 0 and
# Batched over all four starts returned the same answers on 1 and on 8
# devices.
_FIXED_ANSWERS = {
    "rastrigin2d": [
        (2.038858413696289, [0.9838433265686035, 0.9838433265686035], 6,
         [23.091365814208984, 12.043307304382324, 3.6172866821289062,
          2.181488037109375, 2.0388641357421875, 2.038858413696289,
          2.038858413696289]),
        (2.038858413696289, [0.9838433265686035, 0.9838433265686035], 1,
         [2.038858413696289, 2.038858413696289]),
        (5.000173568725586, [0.9838433265686035, 1.987764835357666], 6,
         [19.949430465698242, 9.135225296020508, 6.57859992980957,
          5.142801284790039, 5.000175476074219, 5.000173568725586,
          5.000173568725586]),
        (0.15975189208984375, [0.020078659057617188, -0.020078182220458984], 5,
         [40.502410888671875, 23.92332649230957, 7.344285011291504,
          3.7519969940185547, 0.15975189208984375, 0.15975189208984375]),
    ],
    "quadratic2d": [
        (0.0007078775088302791, [1.2156867980957031, 1.2156867980957031], 6,
         [25.530059814453125, 3.2174737453460693, 0.5560458302497864,
          0.04031858220696449, 0.009810457937419415, 0.0007078775088302791,
          0.0007078775088302791]),
        (0.0007078775088302791, [1.2156867980957031, 1.2156867980957031], 7,
         [98.830322265625, 10.630885124206543, 0.6168530583381653,
          0.049919724464416504, 0.01941159926354885, 0.003908258397132158,
          0.0007078775088302791, 0.0007078775088302791]),
        (0.16925039887428284, [0.8235301971435547, 1.2156867980957031], 3,
         [2.857407808303833, 1.4290574789047241, 0.16925039887428284,
          0.16925039887428284]),
        (0.0007078775088302791, [1.2156867980957031, 1.2156867980957031], 7,
         [119.9977035522461, 37.54179000854492, 6.945560455322266,
          3.890437602996826, 0.8353149890899658, 0.4180114269256592,
          0.0007078775088302791, 0.0007078775088302791]),
    ],
}

_FIXED_STARTS = {"rastrigin2d": [[3.1, -2.2], [1.0, 1.0], [-4.0, 2.0],
                                 [0.5, -0.5]],
                 "quadratic2d": [[4.0, -3.0], [-7.5, 6.0], [0.0, 0.0],
                                 [9.0, 9.0]]}

_FIXED_CODE = """
import json
import jax, numpy as np
from repro.core.objectives import quadratic_nd, rastrigin
from repro.core.solver import (Batched, Distributed, SolveRequest, solve,
                               solve_many)

def floats(a):
    return [float(v) for v in np.asarray(a, np.float32).ravel()]

def answer(r):
    return [float(r.best_f), floats(r.best_x), int(r.iterations),
            floats(r.trace)]

out = {"n_dev": jax.device_count()}
for obj in (rastrigin(2), quadratic_nd(2)):
    x0s = np.asarray(STARTS[obj.name], np.float32)
    if CASE == "distributed":
        r = solve(obj, Distributed(), x0=x0s[0], max_iters=48)
        got = answer(r) + [floats(r.extras["history"])]
    elif CASE == "batched":
        r = solve(obj, Batched(restarts=4), x0=x0s, max_iters=48)
        got = answer(r) + [floats(r.extras["values"]),
                           [int(i) for i in r.extras["restart_iterations"]]]
    else:
        got = [answer(r) for r in solve_many(
            [SolveRequest(obj, x0=x, max_iters=48) for x in x0s],
            max_bits=None)]
    out[obj.name] = got
print(json.dumps(out))
"""


def _close(got, want):
    assert len(got) == len(want), (got, want)
    assert np.allclose(got, want, atol=1e-6, rtol=0), (got, want)


def _check_answer(got, want):
    """``[best_f, best_x, iterations, trace]`` against a pinned answer:
    iterations exactly, values and points at atol 1e-6."""
    _close([got[0]], [want[0]])
    _close(got[1], want[1])
    assert got[2] == want[2], (got[2], want[2])
    _close(got[3], want[3])


@pytest.mark.parametrize("case,n_dev", [
    ("distributed", 1), ("distributed", 8), ("batched", 8),
    ("solve_many", 1)])
def test_fixed_resolution_answers_pinned(case, n_dev):
    """A fixed resolution runs as the one-entry schedule ``(enc.bits,)``
    through the folded engines, and returns what the fixed-resolution
    engines returned: ``Distributed()`` on 1 and 8 devices,
    ``Batched(restarts=4)`` and ``solve_many(max_bits=None)``."""
    code = (f"CASE = {case!r}\nSTARTS = {_FIXED_STARTS!r}\n"
            + _FIXED_CODE)
    got = json.loads(run_with_devices(code, n_dev).splitlines()[-1])
    assert got.pop("n_dev") == n_dev
    assert set(got) == set(_FIXED_ANSWERS)
    for name, want in _FIXED_ANSWERS.items():
        r = got[name]
        if case == "distributed":
            _check_answer(r[:4], want[0])
            _close(r[4], want[0][3])     # the raw history is monotone here
        elif case == "batched":
            values, iters = r[4], r[5]
            _close(values, [w[0] for w in want])
            assert iters == [w[2] for w in want], (name, iters)
            best = int(np.argmin(values))
            trace = list(want[best][3])
            trace += trace[-1:] * (max(iters) + 1 - len(trace))
            _check_answer(r[:4], [want[best][0], want[best][1],
                                  max(iters), trace])
        else:
            assert len(r) == len(want), name
            for got_one, want_one in zip(r, want):
                _check_answer(got_one, want_one)


def test_virtual_processing_chunking_invariance():
    """NCUBE virtual processing: results identical for any virtual_block."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, json
        from repro.core.objectives import ackley
        from repro.core.solver import Distributed, solve
        from repro.compat import AxisType, make_mesh
        mesh = make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        obj = ackley(2)
        vals = []
        for vb in (4, 16, 256):
            res = solve(obj, strategy=Distributed(mesh=mesh,
                                                  virtual_block=vb),
                        x0=jnp.asarray([2.0, -4.0]), max_iters=32)
            vals.append(float(res.best_f))
        assert max(vals) - min(vals) < 1e-6, vals
        print(json.dumps({"ok": True}))
    """)
    assert json.loads(out.splitlines()[-1])["ok"]


def test_compressed_dp_gradients_close_to_exact():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.runtime.compress import (
            make_compressed_dp_grad_fn, init_error_state)
        from repro.compat import AxisType, make_mesh
        mesh = make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        w = {"w": jax.random.normal(jax.random.PRNGKey(0), (16, 4))}
        x = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
        y = jax.random.normal(jax.random.PRNGKey(2), (32, 4))
        def loss(p, batch):
            xx, yy = batch
            return jnp.mean((xx @ p["w"] - yy) ** 2)
        exact = jax.grad(lambda p: loss(p, (x, y)))(w)
        fn = make_compressed_dp_grad_fn(loss, mesh)
        err = init_error_state(w)
        g, err, l = fn(w, (x, y), err)
        rel = float(jnp.linalg.norm(g["w"] - exact["w"])
                    / jnp.linalg.norm(exact["w"]))
        assert rel < 0.05, rel
        print(json.dumps({"ok": True, "rel": rel}))
    """)
    assert json.loads(out.splitlines()[-1])["ok"]


_TRAJECTORY_CODE = """
    import jax, jax.numpy as jnp, json
    from repro.core.solver import Distributed, solve
    res = solve("rastrigin", strategy=Distributed(max_bits=11),
                x0=jnp.asarray([3.1, -2.2]), max_iters=48)
    print(json.dumps({"n_dev": jax.device_count(),
                      "best_f": float(res.best_f),
                      "history": [float(v) for v in
                                  res.extras["history"]]}))
"""


def test_16_device_mesh_trajectory_matches_8_device_bitwise():
    """Mesh-size invariance at the PR-10 scale-out sizes: the default
    (launcher-sized) mesh at 16 virtual devices reproduces the 8-device
    trajectory bit for bit — shard chunking must not leak into results."""
    r8 = json.loads(run_with_devices(_TRAJECTORY_CODE, n=8)
                    .splitlines()[-1])
    r16 = json.loads(run_with_devices(_TRAJECTORY_CODE, n=16)
                     .splitlines()[-1])
    assert (r8["n_dev"], r16["n_dev"]) == (8, 16)
    assert r16["best_f"] == r8["best_f"]
    assert r16["history"] == r8["history"]


def test_resolve_mesh_geometries_and_signature_pinning():
    """resolve_mesh accepts counts/shapes/name-size pairs, rejects
    geometry that cannot tile the device count, and distinct geometries
    produce distinct engine_signatures (the compile-cache key carries
    the mesh)."""
    out = run_with_devices("""
        import jax, json
        import pytest
        from repro.core.solver import (Problem, engine_signature,
                                       resolve_mesh)
        from repro.launch.mesh import mesh_geometry
        assert mesh_geometry(resolve_mesh()) == (("data", 8),)
        assert mesh_geometry(resolve_mesh(8)) == (("data", 8),)
        assert mesh_geometry(resolve_mesh((4, 2))) == (("data", 4),
                                                       ("model", 2))
        assert mesh_geometry(resolve_mesh((("pod", 2), ("data", 4)))) \\
            == (("pod", 2), ("data", 4))
        # geometry-equal resolves give the same (cached) Mesh object,
        # so compile-cache keys that carry the mesh stay stable
        assert resolve_mesh(8) is resolve_mesh(8)
        with pytest.raises(ValueError):
            resolve_mesh(3)          # 3 does not match 8 devices
        with pytest.raises(ValueError):
            resolve_mesh((5, 2))
        prob = Problem.get("rastrigin", n=2)
        sig_flat = engine_signature(prob, mesh=resolve_mesh(8))
        sig_grid = engine_signature(prob, mesh=resolve_mesh((4, 2)))
        assert sig_flat != sig_grid
        assert sig_flat == engine_signature(prob, mesh=resolve_mesh(8))
        print(json.dumps({"ok": True}))
    """)
    assert json.loads(out.splitlines()[-1])["ok"]


def test_subspace_dgo_train_step_descends():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, json
        from jax.sharding import PartitionSpec as P
        from repro.compat import AxisType, make_mesh, shard_map
        from repro.core.encoding import Encoding, encode, decode
        from repro.core.subspace import make_dgo_train_step, apply_subspace
        mesh = make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
        # tiny regression model trained by subspace DGO
        w0 = {"w": jnp.zeros((8, 1))}
        xs = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
        wt = jax.random.normal(jax.random.PRNGKey(1), (8, 1))
        ys = xs @ wt
        def loss(p, batch):
            return jnp.mean((batch[0] @ p["w"] - batch[1]) ** 2)
        enc = Encoding(n_vars=8, bits=6, lo=-2.0, hi=2.0)
        key = jax.random.PRNGKey(7)
        step_fn = make_dgo_train_step(loss, enc, mesh, alpha=4.0)
        mapped = jax.jit(shard_map(
            step_fn, mesh=mesh,
            in_specs=(P(), P(), P(), P(), P()),
            out_specs=(P(), P(), P()), check_vma=False))
        bits = encode(jnp.zeros(8), enc)
        z = decode(bits, enc)
        val = loss(apply_subspace(w0, z, key, 4.0), (xs, ys))
        v0 = float(val)
        for _ in range(25):
            bits, val, improved = mapped(w0, (xs, ys), bits, val, key)
        assert float(val) < 0.5 * v0, (v0, float(val))
        print(json.dumps({"ok": True, "v0": v0, "v": float(val)}))
    """)
    assert json.loads(out.splitlines()[-1])["ok"]


# A device-driver solve's answers as the commit before it became one
# engine call and one fetch returned them (1 and 4 devices alike): start
# points and schedules of _ONE_FETCH_CODE; histories and traces as the
# length and a SHA-256 prefix of their float32 bytes.
_PINNED = {
    "quadratic:3": {
        "best_x": [1.2156867980957031, 1.2941179275512695,
                   1.2156867980957031],
        "best_f": 0.00426219729706645, "iterations": 15,
        "bits": "100011111001000010001111", "bits_resolution": 8,
        "history": [16, "a91bb710a415770a"], "trace": "89b5a6a76c3867bb"},
    "rastrigin:2": {
        "best_x": [0.993992805480957, 0.993992805480957],
        "best_f": 1.9902877807617188, "iterations": 12,
        "bits": "100110001101100110001101", "bits_resolution": 12,
        "history": [13, "a992ee4ba2e1ba1a"], "trace": "a96fe71670a27d3a"},
    "rastrigin:6": {
        "best_x": [-0.0003123283386230469, 0.00031280517578125,
                   -0.0003123283386230469, -1.9898087978363037,
                   0.00031280517578125, -0.0003123283386230469],
        "best_f": 3.9799270629882812, "iterations": 43,
        "bits": "01111111111111100000000000000111111111111101001110"
                "0100001000000000000001111111111111",
        "bits_resolution": 14,
        "history": [44, "492af11f16d89361"], "trace": "f43b96410b18403f"},
    "ackley:3": {
        "best_x": [0.019608020782470703, -0.0196075439453125,
                   -0.0196075439453125],
        "best_f": 0.0988006591796875, "iterations": 12,
        "bits": "100000000111111101111111", "bits_resolution": 8,
        "history": [13, "e11224ba01f2db4c"], "trace": "e11224ba01f2db4c"},
}

_ONE_FETCH_CODE = """
import hashlib, json
import jax, numpy as np
from jax._src import array
from repro.core.encoding import decode
from repro.core.solver import Distributed, Problem, solve

CASES = [("quadratic", 3, [4.0, -3.0, 6.5], 12),
         ("rastrigin", 2, [3.1, -2.2], 12),
         ("rastrigin", 6, [2.5, -1.25, 0.3, 4.4, -3.9, 1.7], 14),
         ("ackley", 3, [2.0, -4.0, 1.0], None)]     # fixed resolution

def digest(values):
    return hashlib.sha256(
        np.asarray(values, np.float32).tobytes()).hexdigest()[:16]

# every device_get, and every host read of an array's value that is not
# one: the CPU's transfer guard lets implicit reads pass, and keeps no
# copy of a fetched value (a chip's fetch caches it), so count reads of
# arrays that no fetch covered
fetches, implicit, inside = [], [], [False]
device_get, value = jax.device_get, array.ArrayImpl._value

def counted_get(x):
    fetches.append(jax.tree.leaves(x))
    inside[0] = True
    try:
        return device_get(x)
    finally:
        inside[0] = False

def watched(self):
    if not inside[0] and not any(a is self for f in fetches for a in f):
        implicit.append(self.shape)
    return value.fget(self)

out = {}
for name, n, x0, max_bits in CASES:
    prob, strat = Problem.get(name, n=n), Distributed(max_bits=max_bits)
    x0 = np.asarray(x0, np.float32)
    solve(prob, strat, x0=x0 + 0.5, max_iters=64)          # warm-up
    jax.device_get, array.ArrayImpl._value = counted_get, property(watched)
    try:
        with jax.transfer_guard_device_to_host("disallow"):
            res = solve(prob, strat, x0=x0, max_iters=64)
            in_solve = list(implicit)
            best_f, best_x = float(res.best_f), np.asarray(res.best_x)
    finally:
        jax.device_get, array.ArrayImpl._value = device_get, value
    b = res.extras["bits_resolution"]
    ref = decode(res.extras["bits"], prob.encoding.with_bits(b))
    out[f"{name}:{n}"] = {
        "fetches": len(fetches), "implicit": in_solve,
        "arrays": [isinstance(a, jax.Array) for a in
                   (res.best_x, res.best_f, res.extras["bits"])],
        "decoded": bool(np.array_equal(best_x, np.asarray(ref))),
        "pinned": {
            "best_x": best_x.tolist(), "best_f": best_f,
            "iterations": res.iterations,
            "bits": "".join(map(str, np.asarray(res.extras["bits"]))),
            "bits_resolution": b,
            "history": [len(res.extras["history"]),
                        digest(res.extras["history"])],
            "trace": digest(res.trace)}}
    fetches.clear()
    implicit.clear()
print(json.dumps({"n_dev": jax.device_count(), "cases": out}))
"""


@pytest.mark.parametrize("n_dev", [1, 4])
def test_device_solve_is_one_engine_call_and_one_fetch(n_dev):
    """A warmed device-driver solve (the folded schedule and the fixed
    resolution) fetches once and reads no value outside that fetch, even
    under the transfer guard; ``best_x``, ``best_f`` and ``bits`` stay
    JAX arrays, ``best_x`` is bitwise the eager decode of ``bits``, and
    every answer is bitwise what the commit before returned."""
    got = json.loads(run_with_devices(_ONE_FETCH_CODE, n_dev)
                     .splitlines()[-1])
    assert got["n_dev"] == n_dev
    assert set(got["cases"]) == set(_PINNED)
    for case, r in got["cases"].items():
        assert r["fetches"] == 1, (case, r["fetches"])
        assert r["implicit"] == [], (case, r["implicit"])
        assert r["arrays"] == [True, True, True], case
        assert r["decoded"], case
        assert r["pinned"] == _PINNED[case], case
