"""Fixed-point / Gray-code encoding used by DGO.

The paper encodes each variable as a fixed-point binary string ("two's
complement" in the paper's terminology; we use offset-binary fixed point over
[lo, hi], which is the same lattice shifted — the Gray-code segment-inversion
transformation only sees raw bits, so the choice of signed representation is
immaterial to the algorithm) and concatenates all variables into one string
of N = n_vars * bits bits.

Bit layout: MSB-first per variable, variables concatenated in order.
Bit arrays are int8 arrays of 0/1 with trailing axis N (or (n_vars, bits)).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Encoding:
    """Fixed-point encoding spec for an n_vars-dimensional box [lo, hi]^n."""

    n_vars: int
    bits: int
    lo: float = -10.0
    hi: float = 10.0

    @property
    def n_bits(self) -> int:
        return self.n_vars * self.bits

    @property
    def population(self) -> int:
        """Paper's population size: 2N - 1 children for an N-bit string."""
        return 2 * self.n_bits - 1

    @property
    def levels(self) -> int:
        return 2**self.bits

    def with_bits(self, bits: int) -> "Encoding":
        return dataclasses.replace(self, bits=bits)


# ---------------------------------------------------------------------------
# float <-> bit-array
# ---------------------------------------------------------------------------

def encode(x: jax.Array, enc: Encoding) -> jax.Array:
    """Float vector (..., n_vars) -> bit string (..., n_vars * bits) int8."""
    x = jnp.asarray(x)
    span = enc.hi - enc.lo
    max_level = enc.levels - 1
    level = jnp.round((x - enc.lo) / span * max_level)
    level = jnp.clip(level, 0, max_level).astype(jnp.uint32)
    shifts = jnp.arange(enc.bits - 1, -1, -1, dtype=jnp.uint32)  # MSB first
    bits = (level[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*x.shape[:-1], enc.n_bits).astype(jnp.int8)


def lattice_step(enc: Encoding) -> float:
    """The spacing of ``enc``'s lattice, as :func:`decode` scales by it."""
    return (enc.hi - enc.lo) / (enc.levels - 1)


def decode(bits: jax.Array, enc: Encoding) -> jax.Array:
    """Bit string (..., n_vars * bits) -> float vector (..., n_vars)."""
    b = bits.reshape(*bits.shape[:-1], enc.n_vars, enc.bits).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(enc.bits - 1, -1, -1, dtype=jnp.uint32))
    level = jnp.sum(b * weights, axis=-1).astype(jnp.float32)
    return enc.lo + level * lattice_step(enc)


def place_levels(level: jax.Array, lo: float, step) -> jax.Array:
    """``lo + level * step`` inside a compiled program, rounded as
    :func:`decode` rounds it when its operations run one at a time: the
    product to float32, then the sum, so an engine reports the point an
    eager :func:`decode` of its bits gives.  Where the product meets the
    sum directly, XLA's CPU backend contracts the two into one fused
    multiply-add, which rounds once and moves the last bit of about half
    the points (an optimization barrier is gone by then); the select
    keeps them apart.  It returns the product unchanged unless that is
    NaN, and so only while the compiler cannot prove the product a
    number: ``level`` must come from a computation it cannot see through
    (a matmul), not from integer bits converted in the same fusion."""
    p = level * step
    return lo + jnp.where(p == p, p, 0.0)


def encode_np(x, enc: Encoding) -> np.ndarray:
    """Numpy twin of :func:`encode` (bitwise equal on float32 inputs): the
    same float32 arithmetic on the host, for results already fetched."""
    x = np.asarray(x)
    span = enc.hi - enc.lo
    max_level = enc.levels - 1
    level = np.round((x - enc.lo) / span * max_level)
    level = np.clip(level, 0, max_level).astype(np.uint32)
    shifts = np.arange(enc.bits - 1, -1, -1, dtype=np.uint32)
    bits = (level[..., None] >> shifts) & np.uint32(1)
    return bits.reshape(*x.shape[:-1], enc.n_bits).astype(np.int8)


def decode_np(bits, enc: Encoding) -> np.ndarray:
    """Numpy twin of :func:`decode` for host-side result assembly (no op
    dispatch — the solver facade uses it on already-fetched bit strings)."""
    b = np.asarray(bits)
    b = b.reshape(*b.shape[:-1], enc.n_vars, enc.bits).astype(np.uint32)
    weights = (np.uint32(1) << np.arange(enc.bits - 1, -1, -1)).astype(np.uint32)
    level = (b * weights).sum(axis=-1).astype(np.float32)
    return enc.lo + level * np.float32(lattice_step(enc))


def reencode(bits: jax.Array, enc_from: Encoding, enc_to: Encoding) -> jax.Array:
    """Re-encode a parent at a new resolution (paper step 5: raise resolution)."""
    return encode(decode(bits, enc_from), enc_to)


# ---------------------------------------------------------------------------
# binary <-> Gray on bit arrays (whole-string transform, per the paper)
# ---------------------------------------------------------------------------

def binary_to_gray(bits: jax.Array) -> jax.Array:
    """g[0] = b[0]; g[i] = b[i-1] XOR b[i]  (MSB-first)."""
    shifted = jnp.pad(bits[..., :-1], [(0, 0)] * (bits.ndim - 1) + [(1, 0)])
    return jnp.bitwise_xor(bits, shifted)


def gray_to_binary(bits: jax.Array) -> jax.Array:
    """b[i] = XOR of g[0..i] — prefix-XOR == cumsum mod 2."""
    return (jnp.cumsum(bits.astype(jnp.int32), axis=-1) % 2).astype(jnp.int8)


# ---------------------------------------------------------------------------
# packed-word helpers (uint32 words, used by the Pallas kernel path)
# ---------------------------------------------------------------------------

def pack_bits(bits: jax.Array, n_words: int | None = None) -> jax.Array:
    """(..., N) 0/1 -> (..., W) uint32, bit i of string in word i//32, MSB-first
    within the word (bit position 31 - i%32)."""
    n = bits.shape[-1]
    w = n_words if n_words is not None else (n + 31) // 32
    pad = w * 32 - n
    b = jnp.pad(bits.astype(jnp.uint32), [(0, 0)] * (bits.ndim - 1) + [(0, pad)])
    b = b.reshape(*bits.shape[:-1], w, 32)
    shifts = jnp.arange(31, -1, -1, dtype=jnp.uint32)
    return jnp.sum(b << shifts, axis=-1, dtype=jnp.uint32)


def unpack_bits(words: jax.Array, n: int) -> jax.Array:
    """(..., W) uint32 -> (..., N) int8 of 0/1."""
    shifts = jnp.arange(31, -1, -1, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    bits = bits.reshape(*words.shape[:-1], words.shape[-1] * 32)
    return bits[..., :n].astype(jnp.int8)


def np_random_bits(key: jax.Array, enc: Encoding) -> jax.Array:
    """Random initial parent string (paper step 1, random start)."""
    return jax.random.bernoulli(key, 0.5, (enc.n_bits,)).astype(jnp.int8)
