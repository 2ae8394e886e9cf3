"""Property tests for the bit-level substrate (hypothesis, optional) plus
deterministic fixed-case versions that run without it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import given, settings, st

from repro.core.encoding import (
    Encoding, binary_to_gray, decode, decode_np, encode, encode_np,
    gray_to_binary, pack_bits, unpack_bits,
)
from repro.core.population import (
    generate_children, generate_population, segment_table,
)

bits_arrays = st.integers(1, 200).flatmap(
    lambda n: st.lists(st.integers(0, 1), min_size=n, max_size=n))


@given(bits_arrays)
@settings(max_examples=30, deadline=None)
def test_gray_involution(bits):
    b = jnp.asarray(bits, jnp.int8)
    assert jnp.array_equal(gray_to_binary(binary_to_gray(b)), b)
    assert jnp.array_equal(binary_to_gray(gray_to_binary(b)), b)


@given(bits_arrays)
@settings(max_examples=30, deadline=None)
def test_pack_unpack_roundtrip(bits):
    b = jnp.asarray(bits, jnp.int8)
    assert jnp.array_equal(unpack_bits(pack_bits(b), b.shape[-1]), b)


@given(st.integers(1, 12), st.integers(2, 10))
@settings(max_examples=20, deadline=None)
def test_encode_decode_quantization(n_vars, bits):
    enc = Encoding(n_vars=n_vars, bits=bits, lo=-3.0, hi=5.0)
    x = jnp.linspace(-3.0, 5.0, n_vars)
    err = jnp.max(jnp.abs(decode(encode(x, enc), enc) - x))
    lattice = (enc.hi - enc.lo) / (enc.levels - 1)
    assert float(err) <= lattice / 2 + 1e-6


@given(st.integers(2, 300))
@settings(max_examples=30, deadline=None)
def test_segment_tree_has_2n_minus_1_nodes(n):
    t = segment_table(n)
    assert t.shape == (2 * n - 1, 2)
    # root covers everything; leaves are single bits; every node valid
    assert t[0, 0] == 0 and t[0, 1] == n
    sizes = t[:, 1] - t[:, 0]
    assert (sizes >= 1).all()
    assert (sizes == 1).sum() == n        # exactly N leaves


@given(st.integers(2, 100), st.integers(0, 10**6))
@settings(max_examples=30, deadline=None)
def test_children_deterministic_and_involutive(n, seed):
    key = jax.random.PRNGKey(seed)
    parent = jax.random.bernoulli(key, 0.5, (n,)).astype(jnp.int8)
    pop = generate_population(parent)
    assert pop.shape == (2 * n - 1, n)
    # distinctness: each child differs from every other child
    as_int = np.packbits(np.asarray(pop), axis=1)
    assert len({r.tobytes() for r in as_int}) == 2 * n - 1
    # involution: re-applying the same segment inversion returns the parent
    ids = jnp.arange(2 * n - 1)
    back = jax.vmap(lambda c, i: generate_children(c, i[None])[0])(pop, ids)
    assert jnp.array_equal(back, jnp.broadcast_to(parent, pop.shape))


@given(st.integers(2, 64))
@settings(max_examples=20, deadline=None)
def test_chunked_generation_matches_full(n):
    key = jax.random.PRNGKey(n)
    parent = jax.random.bernoulli(key, 0.5, (n,)).astype(jnp.int8)
    full = generate_population(parent)
    ids = jnp.asarray([0, n // 2, 2 * n - 2])
    chunk = generate_children(parent, ids)
    assert jnp.array_equal(chunk, full[ids])


# ---------------------------------------------------------------------------
# deterministic fixed-case versions — always run, hypothesis or not
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 31, 32, 33, 63, 64, 100, 200])
def test_gray_involution_fixed(n):
    b = jax.random.bernoulli(jax.random.PRNGKey(n), 0.5, (n,)).astype(jnp.int8)
    assert jnp.array_equal(gray_to_binary(binary_to_gray(b)), b)
    assert jnp.array_equal(binary_to_gray(gray_to_binary(b)), b)


@pytest.mark.parametrize("n", [1, 7, 32, 33, 63, 65, 128, 200])
def test_pack_unpack_roundtrip_fixed(n):
    b = jax.random.bernoulli(jax.random.PRNGKey(n), 0.5, (n,)).astype(jnp.int8)
    assert jnp.array_equal(unpack_bits(pack_bits(b), n), b)


@pytest.mark.parametrize("n_vars,bits", [(1, 2), (2, 8), (9, 7), (12, 10)])
def test_encode_decode_quantization_fixed(n_vars, bits):
    enc = Encoding(n_vars=n_vars, bits=bits, lo=-3.0, hi=5.0)
    x = jnp.linspace(-3.0, 5.0, n_vars)
    err = jnp.max(jnp.abs(decode(encode(x, enc), enc) - x))
    lattice = (enc.hi - enc.lo) / (enc.levels - 1)
    assert float(err) <= lattice / 2 + 1e-6


@pytest.mark.parametrize("n", [2, 3, 9, 63, 128, 300])
def test_segment_tree_shape_fixed(n):
    t = segment_table(n)
    assert t.shape == (2 * n - 1, 2)
    assert t[0, 0] == 0 and t[0, 1] == n
    sizes = t[:, 1] - t[:, 0]
    assert (sizes >= 1).all()
    assert (sizes == 1).sum() == n


@pytest.mark.parametrize("n", [2, 9, 63, 100])
def test_children_distinct_and_involutive_fixed(n):
    parent = jax.random.bernoulli(
        jax.random.PRNGKey(n), 0.5, (n,)).astype(jnp.int8)
    pop = generate_population(parent)
    assert pop.shape == (2 * n - 1, n)
    as_int = np.packbits(np.asarray(pop), axis=1)
    assert len({r.tobytes() for r in as_int}) == 2 * n - 1
    ids = jnp.arange(2 * n - 1)
    back = jax.vmap(lambda c, i: generate_children(c, i[None])[0])(pop, ids)
    assert jnp.array_equal(back, jnp.broadcast_to(parent, pop.shape))


@pytest.mark.parametrize("n_vars,bits,lo,hi", [
    (40, 16, -5.12, 5.12), (20, 12, -32.768, 32.768), (10, 16, -600.0, 600.0),
    (4, 10, 0.0, 10.0), (9, 8, -10.0, 10.0),
])
def test_encode_np_is_bitwise_encode(n_vars, bits, lo, hi):
    """The host twin of ``encode`` gives the same bits on random points,
    on every lattice point of a coarser resolution (the schedule's best
    points, re-encoded at the final one) and on the box's edges."""
    enc = Encoding(n_vars=n_vars, bits=bits, lo=lo, hi=hi)
    rng = np.random.default_rng(bits)
    points = [rng.uniform(lo, hi, (64, n_vars)).astype(np.float32),
              np.array([[lo] * n_vars, [hi] * n_vars], np.float32)]
    for coarse in range(2, bits + 1, 2):
        enc_c = enc.with_bits(coarse)
        levels = rng.integers(0, enc_c.levels, (64, n_vars))
        b = (levels[..., None] >> np.arange(coarse - 1, -1, -1)) & 1
        points.append(decode_np(b.reshape(64, -1).astype(np.int8), enc_c))
    for x in points:
        assert np.array_equal(encode_np(x, enc),
                              np.asarray(encode(jnp.asarray(x), enc)))
