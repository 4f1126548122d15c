"""Simulated mixed-precision matrix products (paper Sec 4.1).

The paper multiplies matrices with input/output formats
PS(mu_A) x PS(mu_B) -> PS(mu_C) by accumulating inner products as
``round(c + a*b)`` with the scalar multiply-add in FP32 and the rounding to
mu_C mantissa bits after *every* accumulation step.

We provide three simulation tiers (DESIGN.md Sec 5), selected by
``granularity``:

  granularity = 1   per-FMA rounding    c_g ~ k u      (paper-faithful)
  granularity = g   per-subtile rounding c_g ~ (k/g) u (TPU MXU deployment
                    model: FP32 accumulation inside a K-subtile, rounding
                    when the partial sum leaves the systolic array)
  granularity = 0   cast-only: full FP32 accumulation, one final rounding
                    (what today's MXU does when storing to a mu-bit format)

All tiers share the LAMP selection/recompute path: `matmul_lamp` recomputes
selected output entries with exact FP32 accumulation, which is the paper's
"higher precision" refinement of Sec 2.2.2 (c_g = 0 for recomputed entries).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .numerics import round_to_mantissa, round_to_mantissa_stochastic

# The simulation's products accumulate in true FP32 before its own PS(mu)
# rounding, and the LAMP recompute is FP32: HIGHEST on every dot, or a TPU
# runs an f32 dot as one bfloat16 pass.
_HI = jax.lax.Precision.HIGHEST


def _round(c: jnp.ndarray, mu: int, stochastic: bool, key) -> jnp.ndarray:
    if stochastic:
        return round_to_mantissa_stochastic(c, mu, key)
    return round_to_mantissa(c, mu)


@functools.partial(jax.jit, static_argnames=("mu", "granularity", "stochastic"))
def dot_ps(a: jnp.ndarray, b: jnp.ndarray, mu: int, *, granularity: int = 1,
           stochastic: bool = False, key: Optional[jax.Array] = None) -> jnp.ndarray:
    """Batched matmul a @ b with simulated PS(mu) accumulation.

    a: (..., M, K), b: (..., K, N) -> (..., M, N), float32 values lying on the
    PS(mu) grid (except granularity=0 where only storage would be rounded --
    we still apply the final rounding so the result is a PS(mu) value).

    granularity g: the K axis is cut into ceil(K/g) chunks; each chunk is
    accumulated exactly in FP32 and added to the running PS(mu) accumulator,
    which is re-rounded after each chunk. g=1 reproduces the paper's
    per-step ``round(c + a*b)``.
    """
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    K = a.shape[-1]
    if b.shape[-2] != K:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
    if mu >= 23:
        return jnp.matmul(a, b, precision=_HI)
    if granularity == 0 or granularity >= K:
        return _round(jnp.matmul(a, b, precision=_HI), mu, stochastic,
                      key if key is not None else jax.random.PRNGKey(0))
    g = int(granularity)
    steps = -(-K // g)  # ceil
    pad = steps * g - K
    if pad:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
        b = jnp.pad(b, [(0, 0)] * (b.ndim - 2) + [(0, pad), (0, 0)])
    # (..., M, steps, g) and (..., steps, g, N), scanned over `steps`.
    a_chunks = jnp.moveaxis(a.reshape(*a.shape[:-1], steps, g), -2, 0)
    b_chunks = jnp.moveaxis(b.reshape(*b.shape[:-2], steps, g, b.shape[-1]), -3, 0)

    out_shape = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2]) + (a.shape[-2], b.shape[-1])
    init = jnp.zeros(out_shape, jnp.float32)
    if stochastic:
        if key is None:
            raise ValueError("stochastic dot_ps requires key")
        keys = jax.random.split(key, steps)
    else:
        keys = jnp.zeros((steps, 2), jnp.uint32)

    def body(c, xs):
        ac, bc, k = xs
        c = _round(c + jnp.matmul(ac, bc, precision=_HI), mu, stochastic, k)
        return c, None

    out, _ = jax.lax.scan(body, init, (a_chunks, b_chunks, keys))
    return out


def matmul_lamp(a: jnp.ndarray, b: jnp.ndarray, mu: int,
                mask: jnp.ndarray, *, granularity: int = 1,
                y_low: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """LAMP refinement: PS(mu)-accumulated a @ b with the entries flagged in
    `mask` recomputed by exact FP32 accumulation (Sec 2.2.2, c_g = 0).

    `y_low` lets the caller pass an already-computed low-precision product
    (the LAMP workflow computes y_low first, derives `mask` from it via a
    look-ahead rule, then refines).

    Note: the simulation computes the full FP32 product and selects -- this
    is numerically identical to recomputing only the flagged entries (the
    paper's simulation does the same); the Pallas kernel performs the real
    tile-granular selective recompute.
    """
    if y_low is None:
        y_low = dot_ps(a, b, mu, granularity=granularity)
    y_exact = jnp.matmul(jnp.asarray(a, jnp.float32),
                         jnp.asarray(b, jnp.float32), precision=_HI)
    return jnp.where(mask, y_exact, y_low)


def dot_ps_error_bound(k: int, mu: int, granularity: int = 1) -> float:
    """First-order worst-case relative error coefficient c_g * u for a
    length-k inner product (Higham 2002): ~ ceil(k/g) * u."""
    from .numerics import unit_roundoff
    g = max(int(granularity), 1) if granularity else k
    return -(-k // g) * unit_roundoff(mu)


def lamp_matmul_softmax(a: jnp.ndarray, b: jnp.ndarray, mu: int, tau: float,
                        *, rule: str = "strict", granularity: int = 1,
                        where: Optional[jnp.ndarray] = None,
                        row_lengths: Optional[jnp.ndarray] = None,
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """End-to-end LAMP evaluation of the composition softmax(a @ b).

    Returns (z, y_adaptive, mask): the softmax probabilities computed from the
    adaptively-refined logits, the refined logits, and the recompute mask.
    This is Algorithm 1 specialized to g = matmul, f = softmax.
    """
    from . import lamp as L
    y_low = dot_ps(a, b, mu, granularity=granularity)
    if rule == "strict":
        mask = L.select_softmax_strict(y_low, tau, where=where)
    elif rule == "relaxed":
        mask = L.select_softmax_relaxed(y_low, tau, where=where)
    elif rule == "relaxed_ln":
        if row_lengths is None:
            raise ValueError("relaxed_ln needs row_lengths")
        mask = L.select_softmax_relaxed_ln(y_low, tau, row_lengths, where=where)
    elif rule == "none":
        mask = jnp.zeros(y_low.shape, bool)
    else:
        raise ValueError(f"unknown rule {rule!r}")
    y = matmul_lamp(a, b, mu, mask, granularity=granularity, y_low=y_low)
    z = L.masked_softmax(y, where)
    return z, y, mask
