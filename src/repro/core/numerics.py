"""Bit-exact simulation of the paper's PS(mu) floating-point format.

PS(mu) = sign (1) + exponent (8) + mantissa (mu in 1..23) bits; PS(23) == FP32,
PS(10) == TF32, PS(7) == BF16 (paper Sec. 4.1). We represent PS(mu) values as
FP32 numbers whose trailing (23 - mu) mantissa bits are zero, produced by
round-to-nearest-ties-to-even (RNE) on the FP32 bit pattern -- exactly the
paper's construction.

Also provides stochastic rounding (SR), used by the error-analysis tiers
(c_g ~ sqrt(k) u for SR vs k u for RNE; Connolly-Higham-Mary 2021).

All functions are jit/vmap/scan-safe; `mu` must be a static Python int.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

_F32_MANT_BITS = 23


def _is_nonfinite(bits: jnp.ndarray) -> jnp.ndarray:
    """True where the FP32 bit pattern is Inf or NaN (exponent all ones).
    The mask is built here, not at module level: a Pallas kernel body may
    not capture an array constant."""
    exp_mask = jnp.uint32(0x7F800000)
    return (bits & exp_mask) == exp_mask


@functools.partial(jax.jit, static_argnames=("mu",))
def round_to_mantissa(x: jnp.ndarray, mu: int) -> jnp.ndarray:
    """Round FP32 `x` to `mu` mantissa bits with round-to-nearest-ties-to-even.

    Bit-exact: operates on the uint32 bit pattern. Carries out of the mantissa
    propagate into the exponent (correct RNE behaviour, incl. overflow to Inf
    and subnormal -> smallest-normal promotion). Inf/NaN pass through.
    """
    if not isinstance(mu, int):
        raise TypeError(f"mu must be a static int, got {type(mu)}")
    if not 1 <= mu <= 23:
        raise ValueError(f"mu must be in [1, 23], got {mu}")
    x = jnp.asarray(x, jnp.float32)
    if mu == _F32_MANT_BITS:
        return x
    shift = _F32_MANT_BITS - mu
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    keep_mask = jnp.uint32(~((1 << shift) - 1) & 0xFFFFFFFF)
    rem = bits & jnp.uint32((1 << shift) - 1)
    half = jnp.uint32(1 << (shift - 1))
    lsb = (bits >> shift) & jnp.uint32(1)
    round_up = (rem > half) | ((rem == half) & (lsb == jnp.uint32(1)))
    rounded = (bits & keep_mask) + jnp.where(round_up, jnp.uint32(1 << shift), jnp.uint32(0))
    out_bits = jnp.where(_is_nonfinite(bits), bits, rounded)
    return jax.lax.bitcast_convert_type(out_bits, jnp.float32)


@functools.partial(jax.jit, static_argnames=("mu",))
def round_to_mantissa_stochastic(x: jnp.ndarray, mu: int, key: jax.Array) -> jnp.ndarray:
    """Stochastic rounding of FP32 `x` to `mu` mantissa bits.

    Adds uniform random bits below the kept mantissa then truncates --
    the standard SR construction: P(round up) = fractional part.
    """
    if not 1 <= mu <= 23:
        raise ValueError(f"mu must be in [1, 23], got {mu}")
    x = jnp.asarray(x, jnp.float32)
    if mu == _F32_MANT_BITS:
        return x
    shift = _F32_MANT_BITS - mu
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    noise = jax.random.randint(
        key, bits.shape, 0, 1 << shift, dtype=jnp.uint32
    )
    keep_mask = jnp.uint32(~((1 << shift) - 1) & 0xFFFFFFFF)
    rounded = (bits + noise) & keep_mask
    out_bits = jnp.where(_is_nonfinite(bits), bits, rounded)
    return jax.lax.bitcast_convert_type(out_bits, jnp.float32)


def unit_roundoff(mu: int) -> float:
    """Unit round-off u = 2^-(mu+1) of PS(mu) under RNE."""
    return 2.0 ** -(mu + 1)


def quantize_ps(x: jnp.ndarray, mu: int, *, stochastic: bool = False,
                key: Optional[jax.Array] = None) -> jnp.ndarray:
    """Quantize to the PS(mu) representable set (RNE by default)."""
    if stochastic:
        if key is None:
            raise ValueError("stochastic rounding requires a PRNG key")
        return round_to_mantissa_stochastic(x, mu, key)
    return round_to_mantissa(x, mu)


def is_representable(x: jnp.ndarray, mu: int) -> jnp.ndarray:
    """True where `x` is exactly representable in PS(mu)."""
    return round_to_mantissa(x, mu) == jnp.asarray(x, jnp.float32)


def effective_mantissa_bits(mu: int, recompute_rate: float,
                            high_mu: int = 23) -> float:
    """Paper footnote 3: average mantissa bits per inner product.

    e.g. mu=7, rate=0.083, high=23  ->  1*7 + 0.083*23 = 8.909.
    (The paper counts the low-precision pass for every product plus the
    FP32 recompute for the selected fraction.)
    """
    return 1.0 * mu + recompute_rate * high_mu


# Named formats from the paper (Sec. 4.1).
PS_FORMATS = {
    "fp32": 23,
    "tf32": 10,
    "bf16": 7,
}


def mu_of(format_or_mu) -> int:
    """Accept 'bf16' / 'tf32' / 'fp32' / int mu."""
    if isinstance(format_or_mu, str):
        return PS_FORMATS[format_or_mu]
    return int(format_or_mu)
