"""Pallas TPU kernel: blocked matmul with PS(mu) accumulation.

C = A @ B with the paper's PS(mu) output format: each (block_m, block_k) x
(block_k, block_n) MXU pass accumulates in FP32, and the running (block_m,
block_n) accumulator tile in VMEM is rounded to PS(mu) every time a K-subtile
partial sum is folded in. This is the deployable TPU analogue of the paper's
``round(c + a*b)`` (granularity = block_k instead of 1; DESIGN.md Sec 5).

Grid: (n_m, n_n, n_k), K innermost (sequential), accumulator tile carried in
VMEM scratch.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.numerics import round_to_mantissa

from .backend import resolve_interpret


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, mu: int, n_k: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    part = jax.lax.dot(a_ref[...].astype(jnp.float32),
                       b_ref[...].astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    acc = acc_ref[...] + part
    acc_ref[...] = round_to_mantissa(acc, mu) if mu < 23 else acc

    @pl.when(ik == n_k - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "mu", "block_m", "block_n", "block_k", "interpret"))
def ps_matmul(a: jnp.ndarray, b: jnp.ndarray, *, mu: int = 7,
              block_m: int = 128, block_n: int = 128, block_k: int = 128,
              interpret: Optional[bool] = None) -> jnp.ndarray:
    """a (M, K) @ b (K, N) -> (M, N) float32 on the PS(mu) grid."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    block_m = min(block_m, M)
    block_n = min(block_n, N)
    block_k = min(block_k, K)
    if M % block_m or N % block_n or K % block_k:
        raise ValueError(f"{(M, N, K)} not divisible by blocks "
                         f"{(block_m, block_n, block_k)}")
    grid = (M // block_m, N // block_n, K // block_k)
    kernel = functools.partial(_kernel, mu=mu, n_k=grid[2])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(a, b)
