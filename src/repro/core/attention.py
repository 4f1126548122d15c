"""LAMP attention: the paper's proof-of-concept composition (Sec 3.3, 4).

Pipeline (per head):
    y_low = dot_ps(q * scale, k^T, mu)        # KQ products, PS(mu) accumulation
    mask  = LAMP rule (8) / (9) / LN-(9)      # look-ahead selection
    y     = where(mask, fp32 q k^T, y_low)    # selective recompute
    z     = softmax(y);  out = z @ v          # everything else in FP32 (paper)

Variants:
  * attention_reference     -- uniform FP32 (the paper's reference model)
  * attention_lamp          -- materialized logits (the paper's "strict"
                               benchmark setting; any rule)
  * chunked_attention       -- online-softmax over KV blocks, O(T) memory
  * chunked_attention_lamp  -- relaxed-LAMP fused with online softmax
                               (two-pass exact threshold, or one-pass
                               conservative running threshold). This is the
                               paper's stated future-work direction (Sec 4.4).
  * decode_attention_lamp   -- single-query decode step against a KV cache.

Shapes: q (B, H, Tq, D), k (B, H, Tk, D), v (B, H, Tk, D). GQA head
repetition happens in the model layer, not here.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


def baseline_mode() -> bool:
    """REPRO_BASELINE=1 re-enables the pre-optimization code paths so the
    EXPERIMENTS Sec Perf before/after measurements stay reproducible."""
    return os.environ.get("REPRO_BASELINE") == "1"

from . import lamp as L
from .mixed_matmul import dot_ps
from .policy import LampSite

_NEG = -1e30
# Every product in this module is FP32 as LAMP defines it: the references,
# the selective recompute, softmax @ V, and the PS(mu) look-ahead pass
# (which rounds on its own). HIGHEST keeps a TPU from running an f32 dot as
# one bfloat16 pass, the default there.
_HI = jax.lax.Precision.HIGHEST


class AttnAux(NamedTuple):
    recompute_rate: jnp.ndarray   # scalar: selected / valid KQ products
    n_selected: jnp.ndarray       # scalar count
    n_valid: jnp.ndarray          # scalar count


def _causal_where(tq: int, tk: int, offset=0,
                  window: Optional[int] = None) -> jnp.ndarray:
    """Validity mask. `offset` = absolute position of query row 0 minus key
    row 0 (for caches / blocks); a scalar, or a (B,) array for batches whose
    rows sit at different absolute positions (partial prefill windows) --
    then the mask is (B, 1, tq, tk). `window` = sliding-window size."""
    qi = jnp.arange(tq)[:, None] + _as_offset(offset)
    kj = jnp.arange(tk)[None, :]
    ok = kj <= qi
    if window is not None:
        ok &= kj > qi - window
    return ok


def _as_offset(offset):
    """Scalar offsets broadcast as-is; (B,) array offsets gain query/key and
    batch/head dims so downstream masks become per-row."""
    if isinstance(offset, (int, float)):
        return offset
    offset = jnp.asarray(offset)
    if offset.ndim == 0:
        return offset
    return offset[:, None, None, None]  # (B,1,1,1) against (tq,1)/(1,tk)


def _select(y: jnp.ndarray, site: LampSite, where, row_lengths=None,
            tau=None) -> jnp.ndarray:
    """`tau` overrides `site.tau`; it may be a traced jax scalar (the policy
    controller's per-layer threshold), in which case it stays out of the jit
    cache key and can move every step without a recompile."""
    if not site.enabled or site.rule == "none":
        return jnp.zeros(y.shape, bool)
    tau = site.tau if tau is None else tau
    if site.rule == "strict":
        return L.select_softmax_strict(y, tau, where=where)
    if site.rule == "relaxed":
        return L.select_softmax_relaxed(y, tau, where=where)
    if site.rule == "relaxed_ln":
        if row_lengths is None:
            raise ValueError("relaxed_ln needs row_lengths")
        return L.select_softmax_relaxed_ln(y, tau, row_lengths,
                                           n_ref=site.n_ref, where=where)
    if site.rule == "random":  # control arm (paper App C.4): caller resamples
        raise ValueError("random rule is handled by attention_lamp(random_key=...)")
    raise ValueError(f"unknown LAMP rule {site.rule!r}")


def attention_reference(q, k, v, *, causal: bool = True, scale: Optional[float] = None,
                        window: Optional[int] = None, offset=0) -> jnp.ndarray:
    """Uniform FP32 attention (paper's reference)."""
    q, k, v = (jnp.asarray(t, jnp.float32) for t in (q, k, v))
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    y = jnp.einsum("bhqd,bhkd->bhqk", q * scale, k, precision=_HI)
    where = _causal_where(q.shape[2], k.shape[2], offset, window) if causal else None
    z = L.masked_softmax(y, where)
    return jnp.einsum("bhqk,bhkd->bhqd", z, v, precision=_HI)


def attention_lamp(q, k, v, site: LampSite, *, causal: bool = True,
                   scale: Optional[float] = None, window: Optional[int] = None,
                   offset=0, random_key: Optional[jax.Array] = None,
                   reduce: bool = True, tau=None) -> Tuple[jnp.ndarray, AttnAux]:
    """Materialized-softmax LAMP attention (the paper's benchmark setting).

    With `random_key`, runs the App C.4 control: the *number* of recomputed
    products matches the LAMP rule, but positions are chosen at random.

    `tau` (optional, possibly traced) overrides `site.tau` -- the serving
    policy controller's live per-layer threshold.

    With `reduce=False`, `aux.n_selected` / `aux.n_valid` are (B, Tq) arrays
    (summed over heads and keys) instead of scalars, so callers serving
    multiple requests in one batch can attribute recompute work per row.

    `offset` may be a (B,) array: row b's queries sit at absolute positions
    offset[b] .. offset[b] + Tq - 1 against keys at 0 .. Tk - 1 (the partial
    prefill window of the paged serving path).
    """
    q, k, v = (jnp.asarray(t, jnp.float32) for t in (q, k, v))
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    scale = scale if scale is not None else D ** -0.5
    where = _causal_where(Tq, Tk, offset, window) if causal else None
    wb = None if where is None else jnp.broadcast_to(where, (B, H, Tq, Tk))

    kt = jnp.swapaxes(k, -1, -2)
    y_low = dot_ps(q * scale, kt, site.mu, granularity=site.granularity)

    if causal:
        off_row = offset if isinstance(offset, (int, float)) \
            else jnp.asarray(offset)[:, None]                     # (B, 1)
        row_lengths = jnp.clip(jnp.arange(Tq) + off_row + 1, 0,
                               window if window is not None else Tk)
        row_lengths = jnp.broadcast_to(
            row_lengths[..., None, :] if row_lengths.ndim == 2 else row_lengths,
            (B, H, Tq))
    else:
        row_lengths = jnp.full((B, H, Tq), Tk)

    mask = _select(y_low, site, wb, row_lengths, tau=tau)
    if random_key is not None:
        # Keep per-row counts, randomize positions among valid slots.
        n_sel = jnp.sum(mask, axis=-1, keepdims=True)
        scores = jax.random.uniform(random_key, y_low.shape)
        scores = jnp.where(wb, scores, -1.0) if wb is not None else scores
        order = jnp.argsort(-scores, axis=-1)
        ranks = jnp.argsort(order, axis=-1)
        mask = ranks < n_sel
        if wb is not None:
            mask &= wb

    y_exact = jnp.einsum("bhqd,bhkd->bhqk", q * scale, k, precision=_HI)
    y = jnp.where(mask, y_exact, y_low)
    z = L.masked_softmax(y, wb)
    out = jnp.einsum("bhqk,bhkd->bhqd", z, v, precision=_HI)

    if reduce:
        n_sel = jnp.sum(mask.astype(jnp.float32))
        n_valid = (jnp.sum(wb.astype(jnp.float32)) if wb is not None
                   else jnp.asarray(float(mask.size), jnp.float32))
        rate = n_sel / jnp.maximum(n_valid, 1)
    else:
        n_sel = jnp.sum(mask.astype(jnp.float32), axis=(1, 3))
        n_valid = (jnp.sum(wb.astype(jnp.float32), axis=(1, 3)) if wb is not None
                   else jnp.full((B, Tq), float(H * Tk), jnp.float32))
        rate = jnp.sum(n_sel) / jnp.maximum(jnp.sum(n_valid), 1)
    aux = AttnAux(rate, n_sel, n_valid)
    return out, aux


# ---------------------------------------------------------------------------
# Online-softmax (FlashAttention-style) variants
# ---------------------------------------------------------------------------

def chunked_attention(q, k, v, *, causal: bool = True, scale: Optional[float] = None,
                      block: int = 512, window: Optional[int] = None,
                      offset: int = 0, q_tiles: int = 8) -> jnp.ndarray:
    """O(T) memory online-softmax attention: scan over KV blocks.
    Causal q-tiling as in chunked_attention_lamp (skip masked KV blocks)."""
    q, k, v = (jnp.asarray(t, jnp.float32) for t in (q, k, v))
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    scale = scale if scale is not None else D ** -0.5
    if causal and q_tiles > 1 and Tq % q_tiles == 0 and Tq // q_tiles >= block:
        tq = Tq // q_tiles
        outs = []
        for t in range(q_tiles):
            q0 = t * tq
            hi = min(Tk, q0 + tq + max(offset, 0))
            kv_hi = min(Tk, -(-hi // block) * block)
            lo = 0
            if window is not None:
                lo = max(0, (q0 + offset - window) // block * block)
            outs.append(chunked_attention(
                q[:, :, q0:q0 + tq], k[:, :, lo:kv_hi], v[:, :, lo:kv_hi],
                causal=True, scale=scale, block=block, window=window,
                offset=offset + q0 - lo, q_tiles=1))
        return jnp.concatenate(outs, axis=2)
    nb = -(-Tk // block)
    pad = nb * block - Tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = jnp.moveaxis(k.reshape(B, H, nb, block, D), 2, 0)
    vb = jnp.moveaxis(v.reshape(B, H, nb, block, D), 2, 0)
    qs = q * scale
    qi = jnp.arange(Tq)[:, None] + offset

    def body(carry, xs):
        m, l, acc = carry
        kc, vc, bi = xs
        y = jnp.einsum("bhqd,bhkd->bhqk", qs, kc, precision=_HI)
        kj = bi * block + jnp.arange(block)[None, :]
        ok = kj < Tk
        if causal:
            ok = ok & (kj <= qi)
            if window is not None:
                ok = ok & (kj > qi - window)
        y = jnp.where(ok, y, _NEG)
        m_new = jnp.maximum(m, jnp.max(y, axis=-1))
        p = jnp.exp(y - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vc,
                                                 precision=_HI)
        return (m_new, l, acc), None

    m0 = jnp.full((B, H, Tq), _NEG)
    l0 = jnp.zeros((B, H, Tq))
    a0 = jnp.zeros((B, H, Tq, D))
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, jnp.arange(nb)))
    return acc / jnp.maximum(l, jnp.finfo(jnp.float32).tiny)[..., None]


def chunked_attention_lamp(q, k, v, site: LampSite, *, causal: bool = True,
                           scale: Optional[float] = None, block: int = 512,
                           window: Optional[int] = None, offset: int = 0,
                           onepass: bool = False, q_tiles: int = 8,
                           ) -> Tuple[jnp.ndarray, AttnAux]:
    """Relaxed-LAMP (rule 9) fused with online softmax (paper Sec 4.4 future
    work). The relative threshold needs max_j |y_j| e^{y_j} per row:

      two-pass (default): pass 1 scans KV blocks accumulating the exact row
      max of s = y + log|y|; pass 2 selects, recomputes, and accumulates the
      online softmax. Exactly matches rule (9).

      one-pass: thresholds each block against the *running* max of s. Since
      the running max only grows, early blocks can only over-select -- a
      conservative relaxation (recompute rate >= two-pass, accuracy >=).

    Causal q-tiling (EXPERIMENTS Sec Perf, hillclimb C): the query axis is
    cut into `q_tiles` tiles; each tile scans only the KV blocks inside its
    causal range, skipping the fully-masked upper-triangle work (~2x at
    long context). Exact -- masked blocks contribute nothing.
    """
    if site.enabled and site.rule not in ("relaxed", "none"):
        raise ValueError("online LAMP requires the relaxed rule (paper Sec 4.4)")
    q, k, v = (jnp.asarray(t, jnp.float32) for t in (q, k, v))
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    scale = scale if scale is not None else D ** -0.5

    # ---- causal q-tiling wrapper --------------------------------------
    if causal and q_tiles > 1 and Tq % q_tiles == 0 and Tq // q_tiles >= block:
        tq = Tq // q_tiles
        outs, nsels, valids = [], [], []
        for t in range(q_tiles):
            q0 = t * tq
            hi = min(Tk, q0 + tq + max(offset, 0))
            kv_hi = min(Tk, -(-hi // block) * block)
            lo = 0
            if window is not None:
                lo = max(0, (q0 + offset - window) // block * block)
            o, aux = chunked_attention_lamp(
                q[:, :, q0:q0 + tq], k[:, :, lo:kv_hi], v[:, :, lo:kv_hi],
                site, causal=True, scale=scale, block=block, window=window,
                offset=offset + q0 - lo, onepass=onepass, q_tiles=1)
            outs.append(o)
            nsels.append(aux.n_selected)
            valids.append(aux.n_valid)
        out = jnp.concatenate(outs, axis=2)
        nsel = sum(nsels)
        valid = sum(valids)
        return out, AttnAux(nsel / jnp.maximum(valid, 1), nsel, valid)

    nb = -(-Tk // block)
    pad = nb * block - Tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = jnp.moveaxis(k.reshape(B, H, nb, block, D), 2, 0)
    vb = jnp.moveaxis(v.reshape(B, H, nb, block, D), 2, 0)
    qs = q * scale
    qi = jnp.arange(Tq)[:, None] + offset
    log_tau = jnp.log(jnp.maximum(site.tau, 1e-30)) if site.enabled else 0.0

    cast_only = site.enabled and site.granularity == 0 and not baseline_mode()

    def block_logits(kc, bi):
        """Returns (y_low, y_exact_or_None, ok). In the cast-only tier
        (granularity=0, the TPU deployment model) the exact product is the
        single MXU pass and y_low = round(y_exact): ONE matmul, not two
        (EXPERIMENTS Sec Perf, hillclimb C)."""
        if cast_only:
            y_exact = jnp.einsum("bhqd,bhkd->bhqk", qs, kc, precision=_HI)
            from repro.core.numerics import round_to_mantissa
            y = round_to_mantissa(y_exact, site.mu)
        elif site.enabled:
            ktc = jnp.swapaxes(kc, -1, -2)
            y = dot_ps(qs, ktc, site.mu, granularity=site.granularity)
            y_exact = None
        else:
            y = jnp.einsum("bhqd,bhkd->bhqk", qs, kc, precision=_HI)
            y_exact = None
        kj = bi * block + jnp.arange(block)[None, :]
        ok = kj < Tk
        if causal:
            ok = ok & (kj <= qi)
            if window is not None:
                ok = ok & (kj > qi - window)
        return y, y_exact, ok

    if site.enabled and not onepass:
        def smax_body(smax, xs):
            kc, bi = xs
            y, _, ok = block_logits(kc, bi)
            s = jnp.where(ok, y + jnp.log(jnp.abs(y)), _NEG)
            return jnp.maximum(smax, jnp.max(s, axis=-1)), None
        smax_exact, _ = jax.lax.scan(
            smax_body, jnp.full((B, H, Tq), _NEG), (kb, jnp.arange(nb)))
    else:
        smax_exact = None

    def body(carry, xs):
        m, l, acc, smax_run, nsel = carry
        kc, vc, bi = xs
        y, y_exact, ok = block_logits(kc, bi)
        if site.enabled:
            s = jnp.where(ok, y + jnp.log(jnp.abs(y)), _NEG)
            if onepass:
                smax_run = jnp.maximum(smax_run, jnp.max(s, axis=-1))
                thr = smax_run
            else:
                thr = smax_exact
            sel = ok & (s > log_tau + thr[..., None])
            if y_exact is None:
                y_exact = jnp.einsum("bhqd,bhkd->bhqk", qs, kc,
                                     precision=_HI)
            y = jnp.where(sel, y_exact, y)
            nsel = nsel + jnp.sum(sel)
        y = jnp.where(ok, y, _NEG)
        m_new = jnp.maximum(m, jnp.max(y, axis=-1))
        p = jnp.exp(y - m_new[..., None])
        p = jnp.where(ok, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vc,
                                                 precision=_HI)
        return (m_new, l, acc, smax_run, nsel), None

    m0 = jnp.full((B, H, Tq), _NEG)
    l0 = jnp.zeros((B, H, Tq))
    a0 = jnp.zeros((B, H, Tq, D))
    s0 = jnp.full((B, H, Tq), _NEG)
    (m, l, acc, _, nsel), _ = jax.lax.scan(
        body, (m0, l0, a0, s0, jnp.zeros((), jnp.float32)),
        (kb, vb, jnp.arange(nb)))
    out = acc / jnp.maximum(l, jnp.finfo(jnp.float32).tiny)[..., None]
    if causal:
        valid = jnp.sum(jnp.clip(qi + 1, 0, window if window else Tk)
                        .astype(jnp.float32)) * B * H
    else:
        valid = jnp.asarray(float(B) * H * Tq * Tk, jnp.float32)
    aux = AttnAux(nsel / jnp.maximum(valid, 1), nsel, valid)
    return out, aux


def decode_attention_lamp(q, k_cache, v_cache, length, site: LampSite,
                          *, scale: Optional[float] = None,
                          window: Optional[int] = None, reduce: bool = True,
                          tau=None) -> Tuple[jnp.ndarray, AttnAux]:
    """Single-token decode: q (B, H, 1, D) against cache (B, H, S, D).

    `length` (B,) = number of valid cache entries per sequence. LAMP rule (9)
    on the single logit row is O(S) -- fully materializable, so decode gets
    the exact relaxed rule at negligible cost.

    With `reduce=False`, aux counts are per-sequence (B,) arrays (summed over
    heads) so the serving engine can report per-request recompute rates.
    `tau` (optional, possibly traced) overrides `site.tau`.
    """
    q = jnp.asarray(q, jnp.float32)
    B, H, Tq, D = q.shape
    S = k_cache.shape[2]
    scale = scale if scale is not None else D ** -0.5
    pos = jnp.arange(S)[None, None, None, :]
    ok = pos < length[:, None, None, None]
    if window is not None:
        ok &= pos > (length[:, None, None, None] - 1 - window)
    kt = jnp.swapaxes(jnp.asarray(k_cache, jnp.float32), -1, -2)
    qs = q * scale
    if site.enabled:
        y_low = dot_ps(qs, kt, site.mu, granularity=site.granularity)
        mask = _select(y_low, site, ok,
                       row_lengths=jnp.broadcast_to(length[:, None, None], (B, H, Tq)),
                       tau=tau)
        y_exact = jnp.matmul(qs, kt, precision=_HI)
        y = jnp.where(mask, y_exact, y_low)
    else:
        y = jnp.matmul(qs, kt, precision=_HI)
        mask = jnp.zeros(y.shape, bool)
    z = L.masked_softmax(y, ok)
    out = jnp.einsum("bhqk,bhkd->bhqd", z, jnp.asarray(v_cache, jnp.float32),
                     precision=_HI)
    if reduce:
        nsel = jnp.sum(mask.astype(jnp.float32))
        n_valid = jnp.sum(ok.astype(jnp.float32)) * H
    else:
        nsel = jnp.sum(mask.astype(jnp.float32), axis=(1, 2, 3))
        n_valid = jnp.sum(ok.astype(jnp.float32), axis=(1, 2, 3)) * H
    rate = jnp.sum(nsel) / jnp.maximum(jnp.sum(n_valid), 1)
    aux = AttnAux(rate, nsel, n_valid)
    return out, aux
