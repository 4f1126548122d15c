"""Pallas TPU kernel pair: LAMP decode attention (exact two-pass rule (9)).

Decode reads one query row against a long KV cache; the relaxed-LAMP
relative threshold needs the global row max of s = y + log|y|, so the op is
split into two VMEM-tiled kernels:

  1. `_smax_kernel`  -- streams K blocks, computes PS(mu) low-precision
     logits, reduces the global max of s per (batch*head).
  2. `_decode_kernel` -- streams K/V blocks again, selects with the exact
     threshold, recomputes selected logits in FP32, online-softmax
     accumulates P@V.

Both kernels recompute y_low identically (same subtile rounding), so the
pair implements rule (9) exactly -- matching `ref.flash_decode_ref`.
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

_NEG = -1e30


def _y_low(q, k, mu, k_subtile):
    D = q.shape[-1]
    n_sub = -(-D // k_subtile)
    acc = jnp.zeros((q.shape[0], k.shape[0]), jnp.float32)
    for s in range(n_sub):
        part = jax.lax.dot_general(
            q[:, s * k_subtile:(s + 1) * k_subtile],
            k[:, s * k_subtile:(s + 1) * k_subtile],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        acc = round_to_mantissa(acc + part, mu) if mu < 23 else acc + part
    return acc


def _smax_kernel(q_ref, k_ref, len_ref, smax_ref, run_ref,
                 *, mu, scale, k_subtile, block_k, n_k):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        run_ref[...] = jnp.full_like(run_ref, _NEG)

    q = q_ref[0].astype(jnp.float32) * scale            # (1, D)
    k = k_ref[0].astype(jnp.float32)                    # (bk, D)
    y = _y_low(q, k, mu, k_subtile)                     # (1, bk)
    kj = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    ok = kj < len_ref[0]
    s = jnp.where(ok, y + jnp.log(jnp.abs(y)), _NEG)
    run_ref[...] = jnp.maximum(run_ref[...], jnp.max(s))

    @pl.when(ik == n_k - 1)
    def _finish():
        smax_ref[0, 0] = run_ref[...]


def _decode_kernel(q_ref, k_ref, v_ref, len_ref, smax_ref, o_ref, nsel_ref,
                   acc_ref, m_ref, l_ref, cnt_ref,
                   *, mu, tau, scale, k_subtile, block_k, n_k):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    q = q_ref[0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    y_low = _y_low(q, k, mu, k_subtile)
    kj = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    ok = kj < len_ref[0]
    s = jnp.where(ok, y_low + jnp.log(jnp.abs(y_low)), _NEG)
    sel = ok & (s > jnp.log(jnp.maximum(tau, 1e-30)) + smax_ref[0, 0])
    y_exact = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y = jnp.where(sel, y_exact, y_low)
    y = jnp.where(ok, y, _NEG)
    cnt_ref[...] += jnp.sum(sel.astype(jnp.float32))

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(y))
    p = jnp.where(ok, jnp.exp(y - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)
        nsel_ref[0, 0] = cnt_ref[...]


@functools.partial(jax.jit, static_argnames=(
    "mu", "tau", "block_k", "k_subtile", "interpret"))
def flash_decode(q, k_cache, v_cache, length, *, mu: int = 7, tau: float = 0.05,
                 block_k: int = 512, k_subtile: int = 32,
                 interpret: Optional[bool] = None,
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """q (B,H,1,D) vs caches (B,H,S,D), length (B,) ->
    (out (B,H,1,D) f32, n_selected)."""
    B, H, _, D = q.shape
    S = k_cache.shape[2]
    scale = D ** -0.5
    block_k = min(block_k, S)
    if S % block_k:
        raise ValueError(f"S={S} % block_k={block_k}")
    n_k = S // block_k
    qf = q.reshape(B * H, 1, D)
    kf = k_cache.reshape(B * H, S, D)
    vf = v_cache.reshape(B * H, S, D)
    lens = jnp.repeat(length.astype(jnp.int32), H).reshape(B * H, 1)

    smax = pl.pallas_call(
        functools.partial(_smax_kernel, mu=mu, scale=scale,
                          k_subtile=k_subtile, block_k=block_k, n_k=n_k),
        grid=(B * H, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, D), lambda i, kk: (i, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda i, kk: (i, kk, 0)),
            pl.BlockSpec((1, 1), lambda i, kk: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i, kk: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(qf, kf, lens)

    out, nsel = pl.pallas_call(
        functools.partial(_decode_kernel, mu=mu, tau=tau, scale=scale,
                          k_subtile=k_subtile, block_k=block_k, n_k=n_k),
        grid=(B * H, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, D), lambda i, kk: (i, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda i, kk: (i, kk, 0)),
            pl.BlockSpec((1, block_k, D), lambda i, kk: (i, kk, 0)),
            pl.BlockSpec((1, 1), lambda i, kk: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, kk: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, D), lambda i, kk: (i, 0, 0)),
            pl.BlockSpec((1, 1), lambda i, kk: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, 1, D), jnp.float32),
            jax.ShapeDtypeStruct((B * H, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((), jnp.float32),
            pltpu.VMEM((), jnp.float32),
            pltpu.VMEM((), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf, lens, smax)
    return out.reshape(B, H, 1, D), jnp.sum(nsel)
