"""Pallas TPU kernel: fused RMSNorm forward (one pass, row-tiled VMEM).

Used by the serving path; also the natural fusion site for the paper's
RMSNorm LAMP rule (Prop 3.2) -- the selection itself needs a sort and stays
in JAX (DESIGN.md Sec 3), but the normalization is fused here.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import resolve_interpret


def _kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    w = w_ref[...].astype(jnp.float32)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * (1.0 + w)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "eps", "interpret"))
def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, *, eps: float = 1e-6,
            block_rows: int = 256,
            interpret: Optional[bool] = None) -> jnp.ndarray:
    """x: (..., d), w: (d,). Rows are tiled block_rows at a time in VMEM."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=((rows + pad) // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(((rows + pad), d), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x2, w)
    return out[:rows].reshape(orig_shape)
