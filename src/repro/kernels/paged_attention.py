"""Fused Pallas paged-attention kernels: gather-free decode + windowed prefill.

Block-index map
---------------
The serving engine stores KV in a per-layer, head-major block arena of
shape (n_blocks, Hkv, block_size, hd); sequence r owns the ordered blocks
``block_tables[r]`` (0 = reserved null block used for padding). The gather
reference path (models/layers.py) materializes each row's view with
``arena[block_tables]`` -- O(n_max * block_size) HBM traffic per row per
step no matter how many tokens are live.

These kernels consume the arena + block tables directly. ``block_tables``
and the per-row lengths/starts are scalar-prefetch operands
(``pltpu.PrefetchScalarGridSpec``), so the K/V BlockSpec index map resolves
grid step (row, kv-block j) to arena block ``block_tables[row, j]`` and the
pipeline DMAs exactly that block into VMEM -- the host-side gather
disappears. The index map clamps j into the row's *live* range [lo, hi]
(past-the-length blocks, and blocks wholly outside the sliding window,
re-map to an already-resident live block, costing no fresh DMA) and the
block's compute is guarded with ``pl.when`` -- fully-masked blocks are
skipped, not summed as zeros.

Head fold: one grid step walks one KV block for a group of hb KV heads.
Causal masks, sliding windows and per-row query counts never read the
head, so every head of a row walks the same blocks with the same liveness,
and a grid step costs a fixed time whatever it holds. The head-major arena
makes a block's hb heads one contiguous (1, hb, block_size, hd) slab, one
DMA, indexed (block_tables[row, j], group, 0, 0). Queries keep their
(B, H, W, hd) layout, blocked (1, hb * rep, wq, hd): the rep query heads of
a KV head are adjacent (kv head = query head // rep), so one DMA of a
group's K/V serves all its query heads and GQA never repeats K/V in HBM.
The per-tile helpers take the folded heads as a leading batch axis: one
head-batched ``dot_general`` per product and one vector op per step of the
softmax, for all heads at once. At GPT-2's decode buckets on a v5e a
rolled ``fori_loop`` over the heads kept 7-30% of the batched body's gain
and a static loop 66-75%; the static loop's tracing also grew with the
heads, about 5 s more set-up per bucket on GPT-2 XL.

hb (``kv_heads_per_step``) follows the shape alone: the largest divisor of
Hkv whose grid step (``grid_step_vmem_bytes``: double-buffered q, out, K, V
and row tiles, the scratch, and every folded head's logits temporaries,
padded to (8, 128) tiles) fits ``VMEM_BUDGET``, three quarters of the
16 MiB of scoped VMEM Mosaic grants a kernel on a v5e. A decode tile
(wq = 1) folds every head of GPT-2 (12, 25); the 1024-wide prefill tile
falls to hb = 1, where one wide tile already amortises its grid step.

Mosaic tiling: every BlockSpec's last two dimensions are whole or tile
multiples. Per-row statistics and selection counts carry a trailing unit
axis ((B, H, W, 1) blocked (1, hb * rep, wq, 1)), and the running softmax
state lives in (hb * rep, wq, 1) / (hb * rep, wq, hd) VMEM scratch.

Precision: every product here names Precision.HIGHEST. The selective
recompute and the softmax @ V product are FP32 as LAMP defines them, and
the PS(mu) look-ahead pass accumulates in true FP32 before its own
rounding -- the same precision ``core.mixed_matmul.dot_ps`` and
``core.attention`` use on the gather path, so the two paths agree on a TPU
as they do on the CPU (a TPU's default f32 dot is one bfloat16 pass).

LAMP two-pass layout
--------------------
The LAMP look-ahead rules threshold against *global* row statistics of the
low-precision logits: the row max of s = y + log|y| for the relaxed rules
(9) / LN-(9), and the softmax normalizer (m, l) for the strict rule (8).
Each variant is therefore a pair of ``pallas_call``s:

  pass 1 (look-ahead): streams live K blocks, computes y_low = PS(mu)
      logits with the same rounding points as ``core.mixed_matmul.dot_ps``
      (granularity 0 = cast-only single MXU pass + final round; g >= 1 =
      FP32 accumulation inside K-chunks of g lanes, re-round per chunk),
      and reduces smax, m = max y_low, l = sum exp(y_low - m) per row.
  pass 2 (recompute): streams live K and V blocks again, recomputes y_low
      identically, selects with the exact rule threshold from the pass-1
      stats, replaces selected logits with the FP32 product, online-softmax
      accumulates P@V, and counts selections per row (the engine's
      per-request recompute telemetry).

Each ``pallas_call`` carries its pass as ``metadata`` (``LOOKAHEAD`` /
``RECOMPUTE``): the compiled op keeps the entry's name
(``paged_prefill_attention.N``) and its text gains
``kernel_metadata={"lamp_pass":"lookahead"}`` or ``...:"recompute"}``, so a
profiler trace tells the two passes apart.

Because both passes recompute y_low identically, the pair implements the
materialized-softmax rules exactly: outputs match the gather reference path
to float32 softmax roundoff and selection counts match bit-for-bit for the
max-based rules (relaxed / relaxed_ln).

Variants:
  paged_decode_attention  -- one query row per sequence at absolute
      position lengths[r] - 1; grid (R * Hkv/hb, n_max).
  paged_prefill_attention -- windowed prefill: query tile x block grid
      ((B * Hkv/hb, W/block_q, n_max)) with absolute-position causal masks
      (query row w of sequence b sits at position starts[b] + w). With the
      optional per-row ``qlens`` scalar-prefetch operand this is also the
      *mixed-row* grid: each row carries its own live query count, so one
      launch covers decode rows (qlen 1), chunked-prefill windows (qlen w)
      and speculative verify rows (qlen k+1) side by side -- the index map
      clamps each row's KV walk to its own live block range and ``pl.when``
      skips tiles/blocks past the row's queries. ``paged_mixed_attention``
      is the documented alias for that calling convention.

The benchmark-only "random" control rule stays on the gather path
(``supports_site``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.numerics import round_to_mantissa
from repro.core.policy import LampSite

from .backend import resolve_interpret

_NEG = -1e30
_TINY = 1.1754944e-38  # float32 tiny: masked_softmax's normalizer clamp
_HI = jax.lax.Precision.HIGHEST
# which LAMP pass a pallas_call is: the tag rides in the compiled op's
# frontend attributes (and so in its profiler trace text) while the op keeps
# the name of the jitted entry
LOOKAHEAD = {"lamp_pass": "lookahead"}
RECOMPUTE = {"lamp_pass": "recompute"}


def supports_site(site: LampSite) -> bool:
    """The fused kernels implement every materialized-softmax LAMP rule the
    serving paths use; the App C.4 'random' control arm (benchmark-only,
    needs a resampled key per call) stays on the gather path."""
    return (not site.enabled) or site.rule in ("none", "strict", "relaxed",
                                               "relaxed_ln")


def _qk(q, k):
    """FP32 q @ k^T per head: (heads, rows, hd) x (heads, keys, hd) ->
    (heads, rows, keys)."""
    return jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _pv(p, v):
    """FP32 softmax-weights @ V per head: (heads, rows, keys) x
    (heads, keys, hd) -> (heads, rows, hd)."""
    return jax.lax.dot_general(p, v, (((2,), (1,)), ((0,), (0,))),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _y_low(q, k, mu: int, granularity: int):
    """PS(mu) q @ k^T, bitwise-matching ``dot_ps``: granularity 0
    (cast-only) = one FP32 pass + final round; g >= 1 = FP32 accumulation
    inside K-chunks of g lanes, re-rounding the running accumulator."""
    if mu >= 23:
        return _qk(q, k)
    D = q.shape[-1]
    if granularity == 0 or granularity >= D:
        return round_to_mantissa(_qk(q, k), mu)
    g = int(granularity)
    acc = jnp.zeros(q.shape[:2] + k.shape[1:2], jnp.float32)
    for s in range(-(-D // g)):
        part = _qk(q[..., s * g:(s + 1) * g], k[..., s * g:(s + 1) * g])
        acc = round_to_mantissa(acc + part, mu)
    return acc


def _select(y_low, ok, smax, m_low, l_low, n_row, *, rule: str, tau,
            n_ref: int):
    """LAMP look-ahead mask on one logits tile from pass-1 row stats.
    smax / m_low / l_low / n_row broadcast against y_low's rows. `tau` may
    be a traced scalar (read off the kernel's scalar-prefetch operand): the
    general log-space comparison then reproduces the static tau == 0 branch
    via log(0) = -inf (threshold -inf selects every finite s)."""
    if rule == "none":
        return jnp.zeros(y_low.shape, bool)
    if rule == "strict":
        z = jnp.where(ok, jnp.exp(y_low - m_low), 0.0) / jnp.maximum(l_low, _TINY)
        return ok & (2.0 * z * (1.0 - z) * jnp.abs(y_low) > tau)
    s = y_low + jnp.log(jnp.abs(y_low))      # -inf at y == 0: never selects
    if rule == "relaxed":
        if isinstance(tau, (int, float)) and tau == 0.0:
            return ok & jnp.isfinite(s)
        return ok & (s > jnp.log(tau) + smax)
    if rule == "relaxed_ln":
        tau_row = tau * jnp.sqrt(n_ref / jnp.maximum(n_row, 1).astype(jnp.float32))
        tau_row = jnp.minimum(tau_row, 1.0 - 1e-6)
        return ok & (s > jnp.log(tau_row) + smax)
    raise ValueError(f"unsupported LAMP rule {rule!r}")


def _rowmax(x):
    return jnp.max(x, axis=-1, keepdims=True)


def _rowsum(x):
    return jnp.sum(x, axis=-1, keepdims=True)


def _stats_block(y, ok, smax_ref, m_ref, l_ref):
    """Pass-1 accumulation of one (heads, rows, keys) logits tile into the
    (heads, rows, 1) running row statistics: smax = max(y + log|y|),
    m = max y, l = sum exp(y - m)."""
    s = jnp.where(ok, y + jnp.log(jnp.abs(y)), _NEG)
    smax_ref[...] = jnp.maximum(smax_ref[...], _rowmax(s))
    m_new = jnp.maximum(m_ref[...], _rowmax(jnp.where(ok, y, _NEG)))
    p = jnp.where(ok, jnp.exp(y - m_new), 0.0)
    l_ref[...] = l_ref[...] * jnp.exp(m_ref[...] - m_new) + _rowsum(p)
    m_ref[...] = m_new


def _softmax_block(y, ok, v, acc_ref, m_ref, l_ref):
    """Pass-2 online-softmax accumulation of one tile into (heads, rows, hd)
    acc and (heads, rows, 1) m / l."""
    y = jnp.where(ok, y, _NEG)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, _rowmax(y))
    p = jnp.where(ok, jnp.exp(y - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + _rowsum(p)
    acc_ref[...] = acc_ref[...] * corr + _pv(p, v)
    m_ref[...] = m_new


def _lamp_logits(q, k, ok, smax, m_low, l_low, n_row, cnt_ref, *, lamp, mu,
                 granularity, rule, tau, n_ref):
    """Pass-2 logits of one tile: the plain FP32 product with LAMP off;
    otherwise the recomputed PS(mu) logits with the look-ahead selection
    replaced by the FP32 product, counting selections per row."""
    if not lamp:
        return _qk(q, k)
    y_low = _y_low(q, k, mu, granularity)
    sel = _select(y_low, ok, smax, m_low, l_low, n_row, rule=rule, tau=tau,
                  n_ref=n_ref)
    cnt_ref[...] += _rowsum(sel.astype(jnp.float32))
    if rule == "none":
        return y_low
    return jnp.where(sel, _qk(q, k), y_low)


# ---------------------------------------------------------------------------
# Head fold: one grid step walks one KV block for a group of hb KV heads
# ---------------------------------------------------------------------------

# VMEM one grid step may fill. Mosaic grants a kernel 16 MiB of scoped VMEM
# by default on a v5e; the fold keeps to three quarters of it, leaving the
# rest to Mosaic's internal scratch and the body's smaller temporaries.
VMEM_BUDGET = 12 * 2 ** 20
# (rows, block_size) float32 temporaries a folded body holds at once: the
# look-ahead logits, the FP32 logits, the softmax weights and the mask
_LOGIT_TILES = 4


def _tile_bytes(rows: int, cols: int) -> int:
    """Bytes of a float32 (rows, cols) array in VMEM, padded to (8, 128)
    tiles."""
    return -(-rows // 8) * 8 * (-(-cols // 128) * 128) * 4


def grid_step_vmem_bytes(hb: int, rep: int, wq: int, block_size: int,
                         head_dim: int) -> int:
    """VMEM of one grid step of the recompute pass (the larger of the two)
    folding hb KV heads (hb * rep query heads) over a wq-row query tile:
    the double-buffered q, out, K, V, row-statistics and count tiles, the
    scratch, and the logits temporaries of every folded head."""
    n = hb * rep
    q = n * _tile_bytes(wq, head_dim)
    row = n * _tile_bytes(wq, 1)
    kv = hb * _tile_bytes(block_size, head_dim)
    return (2 * (2 * q + 2 * kv + 4 * row)      # q, out, K, V, 3 stats, nsel
            + q + 3 * row                       # scratch: acc, m, l, counts
            + _LOGIT_TILES * n * _tile_bytes(wq, block_size))


def kv_heads_per_step(n_kv_heads: int, rep: int, wq: int, block_size: int,
                      head_dim: int) -> int:
    """hb: the largest divisor of n_kv_heads whose grid step fits
    VMEM_BUDGET, else 1. A grid step costs a fixed time whatever it holds,
    and causal masks, windows and per-row query counts never read the head,
    so every head of a row walks the same blocks: folding them divides the
    grid by hb at no change in the arithmetic. Narrow query tiles (decode)
    fold every head; the widest prefill tiles may fall to hb = 1, where one
    wide tile already amortises its grid step."""
    return max([d for d in range(1, n_kv_heads + 1) if n_kv_heads % d == 0
                and grid_step_vmem_bytes(d, rep, wq, block_size, head_dim)
                <= VMEM_BUDGET], default=1)


def _per_query_head(ref, rep: int):
    """A grid step's K or V tile, (1, hb, bs, hd), as one (bs, hd) tile per
    folded query head: (hb * rep, bs, hd), each KV head repeated for its
    rep query heads in VMEM (the DMA moved it once)."""
    x = ref[0].astype(jnp.float32)
    if rep == 1:
        return x
    hb, bs, hd = x.shape
    return jnp.broadcast_to(x[:, None], (hb, rep, bs, hd)).reshape(
        hb * rep, bs, hd)


# ---------------------------------------------------------------------------
# Decode variant: one query row per sequence, grid (R*Hkv/hb, n_max)
# ---------------------------------------------------------------------------

def _dec_mask(j, L, bs, window):
    """(live, ok): whether KV block j intersects the valid range of a row of
    effective length L, and the per-position mask inside the block."""
    kj = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    ok = kj < L
    live = j * bs < L
    if window is not None:
        ok &= kj > L - 1 - window
        live &= (j + 1) * bs > L - window
    return live, ok


def _dec_stats_kernel(bt_ref, len_ref, q_ref, k_ref, smax_o, m_o, l_o,
                      smax_ref, m_ref, l_ref,
                      *, G, n, rep, bs, n_k, mu, granularity, scale, window):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        smax_ref[...] = jnp.full_like(smax_ref, _NEG)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    live, ok = _dec_mask(j, len_ref[i // G], bs, window)

    @pl.when(live)
    def _block():
        q = q_ref[0].astype(jnp.float32) * scale           # (n, 1, hd)
        k = _per_query_head(k_ref, rep)                    # (n, bs, hd)
        _stats_block(_y_low(q, k, mu, granularity), ok, smax_ref, m_ref, l_ref)

    @pl.when(j == n_k - 1)
    def _finish():
        smax_o[0] = smax_ref[...]
        m_o[0] = m_ref[...]
        l_o[0] = l_ref[...]


def _dec_kernel(bt_ref, len_ref, tau_ref, q_ref, k_ref, v_ref,
                smax_in, mlow_in, llow_in, o_ref, nsel_ref,
                acc_ref, m_ref, l_ref, cnt_ref,
                *, G, n, rep, bs, n_k, lamp, mu, granularity, rule, n_ref_ln,
                scale, window):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    L = len_ref[i // G]
    live, ok = _dec_mask(j, L, bs, window)

    @pl.when(live)
    def _block():
        q = q_ref[0].astype(jnp.float32) * scale
        k = _per_query_head(k_ref, rep)
        v = _per_query_head(v_ref, rep)
        y = _lamp_logits(q, k, ok, smax_in[0], mlow_in[0], llow_in[0], L,
                         cnt_ref, lamp=lamp, mu=mu, granularity=granularity,
                         rule=rule, tau=tau_ref[0], n_ref=n_ref_ln)
        _softmax_block(y, ok, v, acc_ref, m_ref, l_ref)

    @pl.when(j == n_k - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], _TINY)
                    ).astype(o_ref.dtype)
        nsel_ref[0] = cnt_ref[...]


@functools.partial(jax.jit, static_argnames=("site", "window", "interpret"))
def paged_decode_attention(q, arena_k, arena_v, block_tables, lengths,
                           site: LampSite, *, tau=None,
                           window: Optional[int] = None,
                           interpret: Optional[bool] = None,
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One decode step straight off the paged arena (no pre-gather).

    q: (R, H, 1, hd); arena_k/v: (n_blocks, Hkv, block_size, hd);
    block_tables: (R, n_max) int32; lengths: (R,) *effective* lengths (the
    new token's KV already written, so valid positions are [0, lengths[r])).
    Returns (out (R, H, 1, hd) float32, n_selected (R,) float32 summed over
    heads) -- the same contract as ``decode_attention_lamp(reduce=False)``.

    `tau` (optional *traced* scalar) overrides the static ``site.tau``: it
    rides into the selection kernel as a third scalar-prefetch operand, so
    the policy controller can move the threshold every step without the jit
    cache key (site is static) ever changing. `interpret=None` follows the
    backend (``backend.resolve_interpret``).
    """
    R, H, Tq, hd = q.shape
    if Tq != 1:
        raise ValueError(f"decode takes one query row, got Tq={Tq}")
    _, Hkv, bs, _ = arena_k.shape
    n_max = block_tables.shape[1]
    rep = H // Hkv
    hb = kv_heads_per_step(Hkv, rep, 1, bs, hd)
    G, n = Hkv // hb, hb * rep           # head groups per row, heads a step
    scale = hd ** -0.5
    interpret = resolve_interpret(interpret)
    bt = block_tables.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)
    tau_arr = jnp.asarray(site.tau if tau is None else tau,
                          jnp.float32).reshape((1,))
    lamp = bool(site.enabled)
    # rule "none" keeps the y_low softmax but selects nothing: the look-ahead
    # stats pass would be dead work, so only run it for a selecting rule
    need_stats = lamp and site.rule != "none"

    def kv_map(i, j, bt_ref, len_ref, *_):
        r = i // G
        L = len_ref[r]
        hi = (L - 1) // bs
        lo = 0 if window is None else jnp.maximum(L - window, 0) // bs
        return (bt_ref[r, jnp.clip(j, lo, hi)], i % G, 0, 0)

    q_spec = pl.BlockSpec((1, n, 1, hd),
                          lambda i, j, *_: (i // G, i % G, 0, 0))
    kv_spec = pl.BlockSpec((1, hb, bs, hd), kv_map)
    row_spec = pl.BlockSpec((1, n, 1, 1),
                            lambda i, j, *_: (i // G, i % G, 0, 0))
    row_shape = jax.ShapeDtypeStruct((R, H, 1, 1), jnp.float32)

    if need_stats:
        stats = pl.pallas_call(
            functools.partial(_dec_stats_kernel, G=G, n=n, rep=rep, bs=bs,
                              n_k=n_max, mu=site.mu,
                              granularity=site.granularity, scale=scale,
                              window=window),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(R * G, n_max),
                in_specs=[q_spec, kv_spec],
                out_specs=[row_spec] * 3,
                scratch_shapes=[pltpu.VMEM((n, 1, 1), jnp.float32)] * 3,
            ),
            out_shape=[row_shape] * 3,
            interpret=interpret,
            metadata=LOOKAHEAD,
        )(bt, lens, q, arena_k)
    else:
        stats = [jnp.zeros((R, H, 1, 1), jnp.float32)] * 3

    out, nsel = pl.pallas_call(
        functools.partial(_dec_kernel, G=G, n=n, rep=rep, bs=bs, n_k=n_max,
                          lamp=lamp, mu=site.mu, granularity=site.granularity,
                          rule=site.rule, n_ref_ln=site.n_ref,
                          scale=scale, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R * G, n_max),
            in_specs=[q_spec, kv_spec, kv_spec] + [row_spec] * 3,
            out_specs=[q_spec, row_spec],
            scratch_shapes=[
                pltpu.VMEM((n, 1, hd), jnp.float32),   # acc
                pltpu.VMEM((n, 1, 1), jnp.float32),    # m
                pltpu.VMEM((n, 1, 1), jnp.float32),    # l
                pltpu.VMEM((n, 1, 1), jnp.float32),    # nsel count
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((R, H, 1, hd), jnp.float32),
            row_shape,
        ],
        interpret=interpret,
        metadata=RECOMPUTE,
    )(bt, lens, tau_arr, q, arena_k, arena_v, *stats)
    return out, jnp.sum(nsel.reshape(R, H), axis=1)


# ---------------------------------------------------------------------------
# Windowed-prefill variant: query tile x block grid (B*Hkv/hb, n_q, n_max)
# ---------------------------------------------------------------------------

def _pre_mask(j, q0, qe, bs, wq, window):
    """(live, ok, qi): block liveness for the q-tile starting at absolute
    position q0 with qe live queries (qe == wq when the row fills the tile),
    and the absolute-position causal mask inside the tile. A block is live
    only if it intersects the causal span of the row's *live* queries, so a
    decode row (qe == 1) in a wide mixed bucket walks exactly the blocks the
    dedicated decode grid would. Pad queries past qe keep the plain causal
    mask; their lanes are discarded by the caller, and every block they
    would have added is a bitwise no-op for live rows (p == 0 everywhere,
    m/l/acc carried through unchanged), so skipping those blocks leaves
    live rows bit-identical to the qe == wq walk."""
    qi = q0 + jax.lax.broadcasted_iota(jnp.int32, (wq, bs), 0)
    kj = j * bs + jax.lax.broadcasted_iota(jnp.int32, (wq, bs), 1)
    ok = kj <= qi
    live = (qe > 0) & (j * bs <= q0 + qe - 1)
    if window is not None:
        ok &= kj > qi - window
        live &= (j + 1) * bs - 1 > q0 - window
    return live, ok, qi


def _pre_stats_kernel(bt_ref, starts_ref, ql_ref, q_ref, k_ref,
                      smax_o, m_o, l_o, smax_ref, m_ref, l_ref,
                      *, G, n, rep, bs, wq, n_k, mu, granularity, scale,
                      window):
    i, t, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        smax_ref[...] = jnp.full_like(smax_ref, _NEG)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q0 = starts_ref[i // G] + t * wq
    qe = jnp.clip(ql_ref[i // G] - t * wq, 0, wq)
    live, ok, _ = _pre_mask(j, q0, qe, bs, wq, window)

    @pl.when(live)
    def _block():
        q = q_ref[0].astype(jnp.float32) * scale           # (n, wq, hd)
        k = _per_query_head(k_ref, rep)                    # (n, bs, hd)
        _stats_block(_y_low(q, k, mu, granularity), ok, smax_ref, m_ref, l_ref)

    @pl.when(j == n_k - 1)
    def _finish():
        smax_o[0] = smax_ref[...]
        m_o[0] = m_ref[...]
        l_o[0] = l_ref[...]


def _pre_kernel(bt_ref, starts_ref, ql_ref, tau_ref, q_ref, k_ref, v_ref,
                smax_in, mlow_in, llow_in, o_ref, nsel_ref,
                acc_ref, m_ref, l_ref, cnt_ref,
                *, G, n, rep, bs, wq, n_k, lamp, mu, granularity, rule,
                n_ref_ln, scale, window, Tk):
    i, t, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    q0 = starts_ref[i // G] + t * wq
    qe = jnp.clip(ql_ref[i // G] - t * wq, 0, wq)
    live, ok, qi = _pre_mask(j, q0, qe, bs, wq, window)

    @pl.when(live)
    def _block():
        q = q_ref[0].astype(jnp.float32) * scale
        k = _per_query_head(k_ref, rep)
        v = _per_query_head(v_ref, rep)
        # row_lengths as in attention_lamp: clip(qi + 1, 0, window|Tk)
        n_row = jnp.clip(qi[:, :1] + 1, 0, Tk if window is None else window)
        y = _lamp_logits(q, k, ok, smax_in[0], mlow_in[0], llow_in[0], n_row,
                         cnt_ref, lamp=lamp, mu=mu, granularity=granularity,
                         rule=rule, tau=tau_ref[0], n_ref=n_ref_ln)
        _softmax_block(y, ok, v, acc_ref, m_ref, l_ref)

    @pl.when(j == n_k - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], _TINY)
                    ).astype(o_ref.dtype)
        nsel_ref[0] = cnt_ref[...]


@functools.partial(jax.jit, static_argnames=("site", "window", "block_q",
                                             "interpret"))
def paged_prefill_attention(q, arena_k, arena_v, block_tables, starts,
                            site: LampSite, *, tau=None, qlens=None,
                            window: Optional[int] = None,
                            block_q: Optional[int] = None,
                            interpret: Optional[bool] = None,
                            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Windowed-prefill / mixed-row attention straight off the paged arena.

    q: (B, H, W, hd) -- query row w of sequence b sits at absolute position
    starts[b] + w and attends causally to positions 0..starts[b]+w of the
    row's block table (the cached prefix plus this window's just-written
    KV); arena_k/v: (n_blocks, Hkv, block_size, hd). Padded rows are
    computed like the gather path and discarded by the caller. Returns
    (out (B, H, W, hd) float32, n_selected (B, W) float32 summed over heads
    and keys) -- the ``attention_lamp(reduce=False)`` telemetry contract.

    `tau` (optional *traced* scalar) overrides the static ``site.tau`` via
    a scalar-prefetch operand into the selection pass, keeping live
    threshold moves out of the jit cache key (see paged_decode_attention).

    `qlens` (optional (B,) int32, traced) gives each row its own live query
    count -- the mixed-row convention: a decode row rides in a wide bucket
    with qlens[b] == 1, a chunked-prefill window with qlens[b] == w, a
    speculative verify row with qlens[b] == k+1. Rows walk (DMA + compute)
    only the KV blocks their live queries can see; results at live query
    positions are bit-identical to qlens == W (skipped blocks are exact
    no-ops for live rows, see `_pre_mask`). ``qlens=None`` means every row
    fills the bucket -- the historical behavior, bit-for-bit.

    On a TPU the query tile `block_q` must be W or a multiple of 8 (Mosaic
    tiling); `interpret=None` follows the backend.
    """
    B, H, W, hd = q.shape
    _, Hkv, bs, _ = arena_k.shape
    n_max = block_tables.shape[1]
    rep = H // Hkv
    scale = hd ** -0.5
    interpret = resolve_interpret(interpret)
    wq = W if block_q is None else min(block_q, W)
    if W % wq:
        raise ValueError(f"W={W} % block_q={wq}")
    n_q = W // wq
    hb = kv_heads_per_step(Hkv, rep, wq, bs, hd)
    G, n = Hkv // hb, hb * rep           # head groups per row, heads a step
    Tk = n_max * bs
    bt = block_tables.astype(jnp.int32)
    st = starts.astype(jnp.int32)
    ql = (jnp.full((B,), W, jnp.int32) if qlens is None
          else qlens.astype(jnp.int32))
    tau_arr = jnp.asarray(site.tau if tau is None else tau,
                          jnp.float32).reshape((1,))
    lamp = bool(site.enabled)
    need_stats = lamp and site.rule != "none"   # as in the decode variant

    def kv_map(i, t, j, bt_ref, starts_ref, ql_ref, *_):
        b = i // G
        q0 = starts_ref[b] + t * wq
        # clamp the walk to the row's live queries in this tile (>= 1 so a
        # dead tile still resolves to a resident block; pl.when skips it)
        qe = jnp.clip(ql_ref[b] - t * wq, 1, wq)
        hi = jnp.minimum((q0 + qe - 1) // bs, n_max - 1)
        lo = 0 if window is None else \
            jnp.minimum(jnp.maximum(q0 - window + 1, 0) // bs, hi)
        return (bt_ref[b, jnp.clip(j, lo, hi)], i % G, 0, 0)

    q_spec = pl.BlockSpec((1, n, wq, hd),
                          lambda i, t, j, *_: (i // G, i % G, t, 0))
    kv_spec = pl.BlockSpec((1, hb, bs, hd), kv_map)
    row_spec = pl.BlockSpec((1, n, wq, 1),
                            lambda i, t, j, *_: (i // G, i % G, t, 0))
    row_shape = jax.ShapeDtypeStruct((B, H, W, 1), jnp.float32)

    if need_stats:
        stats = pl.pallas_call(
            functools.partial(_pre_stats_kernel, G=G, n=n, rep=rep, bs=bs,
                              wq=wq, n_k=n_max, mu=site.mu,
                              granularity=site.granularity, scale=scale,
                              window=window),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(B * G, n_q, n_max),
                in_specs=[q_spec, kv_spec],
                out_specs=[row_spec] * 3,
                scratch_shapes=[pltpu.VMEM((n, wq, 1), jnp.float32)] * 3,
            ),
            out_shape=[row_shape] * 3,
            interpret=interpret,
            metadata=LOOKAHEAD,
        )(bt, st, ql, q, arena_k)
    else:
        stats = [jnp.zeros((B, H, W, 1), jnp.float32)] * 3

    out, nsel = pl.pallas_call(
        functools.partial(_pre_kernel, G=G, n=n, rep=rep, bs=bs, wq=wq,
                          n_k=n_max, lamp=lamp, mu=site.mu,
                          granularity=site.granularity, rule=site.rule,
                          n_ref_ln=site.n_ref, scale=scale, window=window,
                          Tk=Tk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B * G, n_q, n_max),
            in_specs=[q_spec, kv_spec, kv_spec] + [row_spec] * 3,
            out_specs=[q_spec, row_spec],
            scratch_shapes=[
                pltpu.VMEM((n, wq, hd), jnp.float32),  # acc
                pltpu.VMEM((n, wq, 1), jnp.float32),   # m
                pltpu.VMEM((n, wq, 1), jnp.float32),   # l
                pltpu.VMEM((n, wq, 1), jnp.float32),   # nsel counts
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, W, hd), jnp.float32),
            row_shape,
        ],
        interpret=interpret,
        metadata=RECOMPUTE,
    )(bt, st, ql, tau_arr, q, arena_k, arena_v, *stats)
    return out, jnp.sum(nsel[..., 0], axis=1)


def paged_mixed_attention(q, arena_k, arena_v, block_tables, starts, qlens,
                          site: LampSite, *, tau=None,
                          window: Optional[int] = None,
                          block_q: Optional[int] = None,
                          interpret: Optional[bool] = None,
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mixed-row paged attention: one grid over decode rows (qlens[b] == 1),
    chunked-prefill windows (qlens[b] == w) and speculative verify rows
    (qlens[b] == k+1). Alias of ``paged_prefill_attention`` with `qlens`
    required -- the fused serving step's kernel entry."""
    return paged_prefill_attention(q, arena_k, arena_v, block_tables, starts,
                                   site, tau=tau, qlens=qlens, window=window,
                                   block_q=block_q, interpret=interpret)


# ---------------------------------------------------------------------------
# Traffic model (benchmarks): KV bytes DMA'd per decode step, per layer, and
# the KV grid steps one pass walks
# ---------------------------------------------------------------------------

def decode_kv_bytes(lengths, *, n_max: int, block_size: int,
                    bytes_per_token: int, window: Optional[int] = None,
                    lamp: bool = True) -> Tuple[int, int]:
    """(gather_bytes, fused_bytes) of KV traffic for one decode step of one
    layer. The gather path materializes every row's full block-table span
    (K and V); the fused kernels DMA only live blocks -- the LAMP look-ahead
    pass re-reads K, so fused = live_blocks * (2K + V) when LAMP is on.
    ``bytes_per_token`` = Hkv * hd * itemsize."""
    import numpy as np
    L = np.maximum(np.asarray(lengths, np.int64), 1)
    gather = int(L.size) * n_max * block_size * bytes_per_token * 2
    lo = (np.maximum(L - window, 0) // block_size if window is not None
          else np.zeros_like(L))
    hi = (L - 1) // block_size
    live = int((hi - lo + 1).sum())
    fused = live * block_size * bytes_per_token * (3 if lamp else 2)
    return gather, fused


def grid_blocks(starts, qlens, rows: int, Bb: int, n_max: int,
                block_size: int, block_q: Optional[int] = None,
                window: Optional[int] = None,
                width: Optional[int] = None) -> Tuple[int, int]:
    """(live, walked): the KV grid steps of one pass of
    ``paged_prefill_attention`` (one head, one layer) that touch a live
    block, and all the grid steps it walks. Every head group walks the same
    steps, so the kernel's own grid is this one times Hkv / hb, and the
    live share does not depend on the fold.

    The grid is (Bb rows, tiles, n_max blocks); a step is live when its
    block index lies in the [lo, hi] range ``kv_map`` clamps to, for a tile
    that holds live queries. Only the first `rows` of `starts` / `qlens`
    are real: pad rows are walked, never live. ``block_q=None`` is the
    engine's one tile per row; with a `block_q` the tiles per row are
    `width` // block_q, so `width` (the bucket's W) is then required. A
    decode launch is the case starts = lengths - 1, qlens = 1."""
    import numpy as np
    st = np.asarray(starts, np.int64)[:rows, None]
    ql = np.asarray(qlens, np.int64)[:rows, None]
    if block_q is None:
        n_q, q0, qe = 1, st, ql
    else:
        if width is None:
            raise ValueError("grid_blocks: block_q needs the bucket width")
        wq = min(block_q, width)
        n_q = width // wq
        t = np.arange(n_q)[None, :] * wq
        q0, qe = st + t, np.clip(ql - t, 0, wq)
    hi = np.minimum((q0 + np.maximum(qe, 1) - 1) // block_size, n_max - 1)
    lo = (0 if window is None
          else np.minimum(np.maximum(q0 - window + 1, 0) // block_size, hi))
    live = int(np.where(qe > 0, hi - lo + 1, 0).sum())
    return live, int(Bb) * n_q * int(n_max)
