"""Weights made on the device from ``--seed``, bit-exact wherever they are
made again.

The program is handed bfloat16 weights and the plain reference makes its own
float32 copy from the same seed, in another jitted program, after the
program's state is freed. For the two to hold the same numbers whatever XLA
fuses, every weight is an integer times a power of two:

    w = (2 m - 255) * 2**-(e0 + j),   m in 0..255, j in 0..3 from random bits

An odd integer of at most eight bits times a power of two is exact in
bfloat16 and in float32, and the bits come from threefry, which is defined
bit for bit. The four exponents keep one scale per tensor or channel from
holding every value: an int8 copy of these weights is lossy, as it is for
trained ones. ``std_exponent`` picks e0 for a wanted standard deviation;
the mixture's own is 84.8 * 2**-e0.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_MIX_STD = 84.8  # std of (2m-255) * 2**-j over uniform m, j


def seed_key(seed: int):
    """A threefry key from any whole-number seed (the driver's are above
    2**31: the high bits are folded in, not dropped)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    key = jax.random.key(seed & 0x7FFFFFFF, impl="threefry2x32")
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def std_exponent(std: float) -> int:
    return round(math.log2(_MIX_STD / std))


def realized_std(e0: int) -> float:
    return _MIX_STD * 2.0 ** -e0


def exact_normalish(key, shape, e0: int, dtype):
    """Seeded weights of the form above, in ``dtype`` (exact in bfloat16
    and wider)."""
    bits = jax.random.bits(key, shape, jnp.uint16)
    m = (bits & 0xFF).astype(jnp.float32)
    j = (bits >> 8) & 3
    val = (2.0 * m - 255.0) * (2.0 ** -e0)
    val = val * jnp.where(j & 1, 0.5, 1.0) * jnp.where(j & 2, 0.25, 1.0)
    return val.astype(dtype)
