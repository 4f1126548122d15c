"""Pallas TPU kernel: one-pass relaxed-LAMP flash attention.

The paper's future-work target (Sec 4.4): fuse the relaxed relative-threshold
rule (9) into an online-softmax attention kernel. TPU adaptation
(DESIGN.md Sec 3):

  * KQ products are accumulated in FP32 inside K-subtiles of `k_subtile`
    lanes (that is what the MXU gives you), and the running accumulator is
    rounded to PS(mu) each time a subtile's partial sum is folded in --
    the block-granular low-precision-accumulation deployment model.
  * Selection uses the running max of s = y + log|y| (one-pass, conservative:
    early blocks can only over-select relative to rule (9)).
  * Selected logits are replaced by the exact FP32 product (on hardware with
    packed low-precision accumulators the exact product would be a tile
    recompute; in the simulation both values fall out of the same MXU pass).

Grid: (batch*heads, n_q_blocks, n_k_blocks); the k-block axis is the
innermost (sequential on TPU), with the online-softmax state carried in VMEM
scratch across k iterations. BlockSpecs keep one (block_q, D) query tile,
one (block_k, D) K tile and V tile in VMEM at a time.
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


def _kernel(q_ref, k_ref, v_ref, o_ref, nsel_ref,
            acc_ref, m_ref, l_ref, smax_ref, cnt_ref,
            *, mu: int, tau: float, causal: bool, scale: float,
            k_subtile: int, block_q: int, block_k: int, n_k: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        smax_ref[...] = jnp.full_like(smax_ref, _NEG)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    q = q_ref[0].astype(jnp.float32) * scale        # (bq, D)
    k = k_ref[0].astype(jnp.float32)                # (bk, D)
    v = v_ref[0].astype(jnp.float32)                # (bk, D)
    D = q.shape[-1]

    # --- low-precision QK: PS(mu)-rounded subtile accumulation over D ---
    n_sub = -(-D // k_subtile)
    y_low = jnp.zeros((block_q, block_k), jnp.float32)
    for s in range(n_sub):
        part = jax.lax.dot_general(
            q[:, s * k_subtile:(s + 1) * k_subtile],
            k[:, s * k_subtile:(s + 1) * k_subtile],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        y_low = round_to_mantissa(y_low + part, mu) if mu < 23 else y_low + part

    ok = jnp.ones((block_q, block_k), bool)
    if causal:
        iq = pl.program_id(1)
        qi = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kj = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        ok = kj <= qi

    # --- relaxed-LAMP selection against the running row max of y + log|y| ---
    s_crit = jnp.where(ok, y_low + jnp.log(jnp.abs(y_low)), _NEG)
    smax = jnp.maximum(smax_ref[...], jnp.max(s_crit, axis=-1))
    smax_ref[...] = smax
    sel = ok & (s_crit > jnp.log(jnp.maximum(tau, 1e-30)) + smax[:, None])
    cnt_ref[...] += jnp.sum(sel.astype(jnp.float32))

    # --- selective exact recompute (full-precision MXU pass) ---
    y_exact = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y = jnp.where(sel, y_exact, y_low)
    y = jnp.where(ok, y, _NEG)

    # --- online softmax ---
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(y, axis=-1))
    p = jnp.where(ok, jnp.exp(y - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)
        nsel_ref[0, 0] = cnt_ref[...]


@functools.partial(jax.jit, static_argnames=(
    "mu", "tau", "causal", "block_q", "block_k", "k_subtile", "interpret"))
def lamp_flash_attention(q, k, v, *, mu: int = 7, tau: float = 0.05,
                         causal: bool = True, block_q: int = 128,
                         block_k: int = 128, k_subtile: int = 32,
                         interpret: Optional[bool] = None,
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """q,k,v: (B, H, T, D) -> (out (B,H,T,D) f32, n_selected scalar f32)."""
    B, H, T, D = q.shape
    S = k.shape[2]
    scale = D ** -0.5
    block_q = min(block_q, T)
    block_k = min(block_k, S)
    if T % block_q or S % block_k:
        raise ValueError(f"T={T} % block_q={block_q} or S={S} % block_k={block_k}")
    n_q, n_k = T // block_q, S // block_k
    qf = q.reshape(B * H, T, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, D)

    kernel = functools.partial(
        _kernel, mu=mu, tau=tau, causal=causal, scale=scale,
        k_subtile=k_subtile, block_q=block_q, block_k=block_k, n_k=n_k)

    out, nsel = pl.pallas_call(
        kernel,
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda i, j, kk: (i, kk, 0)),
            pl.BlockSpec((1, block_k, D), lambda i, j, kk: (i, kk, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, 1), lambda i, j, kk: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), jnp.float32),
            jax.ShapeDtypeStruct((B * H, n_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),   # acc
            pltpu.VMEM((block_q,), jnp.float32),     # m
            pltpu.VMEM((block_q,), jnp.float32),     # l
            pltpu.VMEM((block_q,), jnp.float32),     # running smax
            pltpu.VMEM((), jnp.float32),             # selection count
        ],
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)
    return out.reshape(B, H, T, D), jnp.sum(nsel)
