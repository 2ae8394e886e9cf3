"""Runtime: compression error feedback, straggler policy, elastic plan,
failure-injected training restart."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime import (
    StragglerPolicy, dequantize_int8, elastic_population_plan, quantize_int8,
)


def test_quantize_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,))
    q, s = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, s) - x)
    assert float(jnp.max(err)) <= float(s) / 2 + 1e-6


def test_error_feedback_is_unbiased_over_time():
    """Accumulated compressed sum converges to the true sum."""
    x = 0.01 * jax.random.normal(jax.random.PRNGKey(1), (64,))
    err = jnp.zeros_like(x)
    acc_q, acc_true = jnp.zeros_like(x), jnp.zeros_like(x)
    for _ in range(50):
        target = x + err
        q, s = quantize_int8(target)
        deq = dequantize_int8(q, s)
        err = target - deq
        acc_q += deq
        acc_true += x
    rel = float(jnp.linalg.norm(acc_q - acc_true)
                / jnp.linalg.norm(acc_true))
    assert rel < 0.02


def test_straggler_policy_masks_and_recovers():
    pol = StragglerPolicy(n_shards=4, factor=2.0, cooldown=2)
    times = np.asarray([1.0, 1.0, 1.0, 10.0])
    mask = pol.update(times)
    assert mask.tolist() == [True, True, True, False]
    mask = pol.update(np.ones(4))
    assert mask.tolist() == [True, True, True, False]   # cooldown
    mask = pol.update(np.ones(4))
    assert mask.tolist() == [True, True, True, True]    # recovered


def test_straggler_cooldown_expiry_restores_full_quorum():
    """quorum_fraction returns exactly to 1.0 once every masked shard's
    cooldown expires — the serving scheduler keys its wave width off it,
    so a fraction stuck below 1.0 would shrink waves forever."""
    pol = StragglerPolicy(n_shards=4, factor=2.0, cooldown=3)
    pol.update(np.asarray([1.0, 1.0, 1.0, 10.0]))
    assert pol.quorum_fraction == 0.75
    for _ in range(pol.cooldown - 1):
        pol.update(np.ones(4))
        assert pol.quorum_fraction < 1.0        # still cooling down
    pol.update(np.ones(4))
    assert pol.quorum_fraction == 1.0           # exact, not approx


def test_elastic_plan_matches_paper_formula():
    plan = elastic_population_plan(n_bits=63, n_shards=64)
    assert plan["population"] == 125
    assert plan["children_per_shard"] == 2     # ceil(125/64)
    plan = elastic_population_plan(n_bits=63, n_shards=48)
    assert plan["children_per_shard"] == 3


def test_failure_injection_and_training_restart(tmp_path):
    from repro.launch.train import build_argparser, run_training
    args = build_argparser().parse_args([
        "--arch", "qwen2-1.5b", "--reduced", "--steps", "12",
        "--global-batch", "2", "--seq-len", "16", "--ckpt-every", "4",
        "--inject-failure-rate", "0.25", "--ckpt-dir", str(tmp_path),
        "--log-every", "100", "--seed", "3",
    ])
    out = run_training(args)
    assert out["steps"] == 12
    assert out["injected_failures"] > 0        # failures actually happened
    assert out["final_loss"] is not None and np.isfinite(out["final_loss"])
