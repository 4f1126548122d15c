"""Shared model layers: norms, RoPE, GQA attention, MLP variants, embeddings.

All layers are pure functions over explicit param pytrees, computed in the
config dtype with FP32 islands where numerics require (norm statistics,
attention logits/softmax via repro.core.attention, final logits).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import attention as A
from repro.core.policy import LampSite


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))
    return out.astype(x.dtype)


def layer_norm(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32) + b.astype(jnp.float32)
    return out.astype(x.dtype)


def apply_norm(cfg, x: jnp.ndarray, p: Dict[str, jnp.ndarray], prefix: str) -> jnp.ndarray:
    if cfg.norm == "layernorm":
        return layer_norm(x, p[f"{prefix}_w"], p[f"{prefix}_b"])
    return rms_norm(x, p[f"{prefix}_w"])


def norm_params(cfg, key, d: int) -> Dict[str, jnp.ndarray]:
    if cfg.norm == "layernorm":
        return {"w": jnp.ones((d,), dtype_of(cfg)), "b": jnp.zeros((d,), dtype_of(cfg))}
    return {"w": jnp.zeros((d,), dtype_of(cfg))}


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
         fraction: float = 1.0) -> jnp.ndarray:
    """x: (B, T, H, D); positions: (B, T) or (T,). Rotates the first
    `fraction` of D (glm4 uses 0.5)."""
    D = x.shape[-1]
    rot = int(D * fraction) // 2 * 2
    if rot == 0:
        return x
    xr, xp = x[..., :rot], x[..., rot:]
    half = rot // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]      # (T, half)
        ang = ang[None, :, None, :]                                        # (1,T,1,half)
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs             # (B,T,half)
        ang = ang[:, :, None, :]                                           # (B,T,1,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = xr[..., :half].astype(jnp.float32), xr[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1)


# ---------------------------------------------------------------------------
# GQA attention sublayer
# ---------------------------------------------------------------------------

def attn_params(cfg, key) -> Dict[str, jnp.ndarray]:
    d, hd = cfg.d_model, cfg.hd
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 4)
    sc = d ** -0.5
    p = {
        "wq": (jax.random.normal(ks[0], (d, H * hd)) * sc).astype(dt),
        "wk": (jax.random.normal(ks[1], (d, Hkv * hd)) * sc).astype(dt),
        "wv": (jax.random.normal(ks[2], (d, Hkv * hd)) * sc).astype(dt),
        "wo": (jax.random.normal(ks[3], (H * hd, d)) * (H * hd) ** -0.5).astype(dt),
    }
    if cfg.qk_norm:
        p["qn_w"] = jnp.zeros((hd,), dt)
        p["kn_w"] = jnp.zeros((hd,), dt)
    return p


def _project_qkv(cfg, p, x, positions):
    from repro.distributed.sharding import shard_hint
    B, T, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    # explicit batch/head sharding hints: without them SPMD propagation can
    # drop the batch sharding inside scan bodies and replicate the full
    # attention compute on every device (EXPERIMENTS Sec Perf, hillclimb C)
    q = shard_hint((x @ p["wq"]).reshape(B, T, H, hd),
                   "batch", None, "model", None)
    k = shard_hint((x @ p["wk"]).reshape(B, T, Hkv, hd),
                   "batch", None, "model", None)
    v = shard_hint((x @ p["wv"]).reshape(B, T, Hkv, hd),
                   "batch", None, "model", None)
    if cfg.qk_norm:
        q = rms_norm(q, p["qn_w"])
        k = rms_norm(k, p["kn_w"])
    if cfg.pos == "rope":
        q = rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
        k = rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    return q, k, v


def _repeat_kv(t: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    if n_rep == 1:
        return t
    return jnp.repeat(t, n_rep, axis=1)


def attention_sublayer(cfg, p, x, *, positions, lamp_site: LampSite,
                       causal: bool = True, attn_impl: str = "auto",
                       block: int = 512, kv: Optional[Tuple] = None,
                       window: Optional[int] = None,
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence attention (train / prefill). Returns (out, recompute_rate).

    `kv`: optional externally-supplied (k, v) in (B, T, Hkv, hd) layout for
    cross-attention (whisper decoder): q comes from x, k/v from the encoder.
    """
    B, T, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q, k, v = _project_qkv(cfg, p, x, positions)
    if kv is not None:
        k, v = kv
    # (B, H, T, hd)
    q = jnp.swapaxes(q, 1, 2)
    k = _repeat_kv(jnp.swapaxes(k, 1, 2), H // Hkv)
    v = _repeat_kv(jnp.swapaxes(v, 1, 2), H // Hkv)
    window = window if window is not None else cfg.window

    if attn_impl == "auto":
        attn_impl = "full" if max(T, k.shape[2]) <= 2048 else "chunked"

    rate = jnp.zeros((), jnp.float32)
    if attn_impl == "full":
        if lamp_site.enabled:
            if lamp_site.rule == "random":
                # App C.4 control arm: LAMP-sized random recompute set
                out, aux = A.attention_lamp(
                    q, k, v, lamp_site.replace(rule="strict"), causal=causal,
                    window=window, random_key=jax.random.PRNGKey(0))
            else:
                out, aux = A.attention_lamp(q, k, v, lamp_site, causal=causal,
                                            window=window)
            rate = aux.recompute_rate
        else:
            out = A.attention_reference(q, k, v, causal=causal, window=window)
    elif attn_impl == "chunked":
        if lamp_site.enabled:
            site = lamp_site if lamp_site.rule == "relaxed" else lamp_site.replace(rule="relaxed")
            out, aux = A.chunked_attention_lamp(q, k, v, site, causal=causal,
                                                block=block, window=window,
                                                onepass=site.onepass)
            rate = aux.recompute_rate
        else:
            out = A.chunked_attention(q, k, v, causal=causal, block=block,
                                      window=window)
    else:
        raise ValueError(f"unknown attn_impl {attn_impl!r}")

    out = jnp.swapaxes(out, 1, 2).reshape(B, T, H * hd).astype(x.dtype)
    return out @ p["wo"], rate


def attention_decode_sublayer(cfg, p, x, *, cache_k, cache_v, length,
                              lamp_site: LampSite, kv_cross: Optional[Tuple] = None,
                              window: Optional[int] = None):
    """Single-token decode. x: (B, 1, d); cache_k/v: (B, S, Hkv, hd).

    Returns (out, new_cache_k, new_cache_v, recompute_rate). The new token's
    k/v are written at position `length` (per sequence).
    """
    B, T, _ = x.shape
    assert T == 1
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    positions = length[:, None]  # (B, 1) absolute position of the new token
    q, k, v = _project_qkv(cfg, p, x, positions)
    if kv_cross is None:
        # scatter new k/v into the cache at `length`
        bidx = jnp.arange(B)
        cache_k = cache_k.at[bidx, length].set(k[:, 0].astype(cache_k.dtype))
        cache_v = cache_v.at[bidx, length].set(v[:, 0].astype(cache_v.dtype))
        use_k, use_v = cache_k, cache_v
        eff_len = length + 1
    else:
        use_k, use_v = kv_cross
        eff_len = jnp.full((B,), use_k.shape[1], jnp.int32)

    qh = jnp.swapaxes(q, 1, 2)                                   # (B,H,1,hd)
    window = window if window is not None else cfg.window

    # sequence-parallel path: when the KV cache's seq axis is sharded over
    # a >1 'model' mesh axis, run the shard_map distributed online softmax
    # (grouped GQA, cache read once, O(B*H*hd) combine) instead of letting
    # XLA all-gather the cache (EXPERIMENTS Sec Perf, hillclimb B).
    am = jax.sharding.get_abstract_mesh()
    names = am.axis_names
    S = use_k.shape[1]
    from repro.core.attention import baseline_mode
    if ("model" in names and am.shape["model"] > 1
            and S % am.shape["model"] == 0 and not baseline_mode()):
        from repro.distributed.collectives import sp_decode_attention
        out = sp_decode_attention(
            am, qh, jnp.moveaxis(use_k, 2, 1), jnp.moveaxis(use_v, 2, 1),
            eff_len, mu=lamp_site.mu if lamp_site.enabled else 23,
            tau=lamp_site.tau, lamp=lamp_site.enabled, window=window)
        rate = jnp.zeros((), jnp.float32)  # not tracked on the sp path
    else:
        kh = _repeat_kv(jnp.moveaxis(use_k, 2, 1), H // Hkv)      # (B,H,S,hd)
        vh = _repeat_kv(jnp.moveaxis(use_v, 2, 1), H // Hkv)
        out, aux = A.decode_attention_lamp(
            qh, kh, vh, eff_len,
            lamp_site if lamp_site.enabled else lamp_site.replace(enabled=False),
            window=window)
        rate = aux.recompute_rate
    out = jnp.swapaxes(out, 1, 2).reshape(B, 1, H * hd).astype(x.dtype)
    return out @ p["wo"], cache_k, cache_v, rate


def write_paged_kv(arena, blk, off, x):
    """Scatter per-token K or V rows x (..., Hkv, hd) into a head-major
    (n_blocks, Hkv, block_size, hd) arena at block `blk`, in-block offset
    `off` (both shaped like x's leading axes)."""
    return arena.at[blk, :, off].set(x.astype(arena.dtype))


def paged_kv_view(arena, block_tables, n_rep: int):
    """Each row's gathered (B, H, n_max * block_size, hd) view of a
    head-major (n_blocks, Hkv, block_size, hd) arena, KV heads repeated
    n_rep times for GQA. Flat index t of the view == absolute position t."""
    B, n_max = block_tables.shape
    _, Hkv, bs, hd = arena.shape
    view = jnp.moveaxis(arena[block_tables], 2, 1)    # (B, Hkv, n_max, bs, hd)
    return _repeat_kv(view.reshape(B, Hkv, n_max * bs, hd), n_rep)


def require_kernel_site(site: LampSite) -> None:
    """kernel="pallas" runs the fused kernel or fails: a site the kernel
    does not implement never runs the gather path in its place."""
    from repro.kernels.paged_attention import supports_site
    if not supports_site(site):
        raise ValueError(f"kernel='pallas' does not implement LAMP rule "
                         f"{site.rule!r}; use kernel='gather'")


def paged_attention_decode_sublayer(cfg, p, x, *, arena_k, arena_v,
                                    block_tables, lengths,
                                    lamp_site: LampSite,
                                    window: Optional[int] = None,
                                    kernel: str = "gather",
                                    tau=None):
    """Single-token decode against a paged KV arena (one layer).

    x: (R, 1, d) hidden states for R slots of a continuous batch.
    arena_k/v: (n_blocks, Hkv, block_size, hd) shared head-major arena.
    block_tables: (R, n_max) int32; row r lists the arena blocks holding
        sequence r's KV in position order (0 = reserved null block for
        padding — never read thanks to the length mask, writes to it are
        scratch).
    lengths: (R,) tokens already cached; the new token's k/v are written at
        absolute position `lengths[r]`, i.e. block `block_tables[r, len//bs]`
        offset `len % bs`.

    kernel="gather" (reference): the per-sequence view reshapes the gathered
    blocks so gathered flat index t == absolute position t, which makes the
    computation bit-identical to the dense-cache path for valid positions.
    kernel="pallas": the fused paged-attention kernel reads live arena
    blocks directly through the block-table index map (no gather, masked
    blocks skipped); a site the kernel does not implement (the
    benchmark-only "random" rule) raises instead of running gather.
    tau: optional traced scalar overriding lamp_site.tau (the serving policy
    controller threads per-layer thresholds through the jitted steps so
    moving them never recompiles).
    Returns (out, arena_k, arena_v, n_selected (R,), n_valid (R,)).
    """
    R = x.shape[0]
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    bs = arena_k.shape[2]
    q, k, v = _project_qkv(cfg, p, x, lengths[:, None])
    ridx = jnp.arange(R)
    blk = block_tables[ridx, lengths // bs]
    off = lengths % bs
    arena_k = write_paged_kv(arena_k, blk, off, k[:, 0])
    arena_v = write_paged_kv(arena_v, blk, off, v[:, 0])
    qh = jnp.swapaxes(q, 1, 2)                                # (R,H,1,hd)
    window = window if window is not None else cfg.window

    if kernel == "pallas":
        from repro.kernels import ops as KOPS
        require_kernel_site(lamp_site)
        eff = lengths + 1
        out, nsel = KOPS.paged_decode_attention(
            qh, arena_k, arena_v, block_tables, eff, lamp_site, tau=tau,
            window=window)
        cap = eff if window is None else jnp.minimum(eff, window)
        nval = (cap * H).astype(jnp.float32)
    else:
        kh = paged_kv_view(arena_k, block_tables, H // Hkv)   # (R,H,S,hd)
        vh = paged_kv_view(arena_v, block_tables, H // Hkv)
        out, aux = A.decode_attention_lamp(qh, kh, vh, lengths + 1, lamp_site,
                                           window=window, reduce=False,
                                           tau=tau)
        nsel, nval = aux.n_selected, aux.n_valid
    out = jnp.swapaxes(out, 1, 2).reshape(R, 1, H * hd).astype(x.dtype)
    return out @ p["wo"], arena_k, arena_v, nsel, nval


# ---------------------------------------------------------------------------
# MLP variants
# ---------------------------------------------------------------------------

def mlp_params(cfg, key, d_ff: Optional[int] = None) -> Dict[str, jnp.ndarray]:
    d = cfg.d_model
    ff = d_ff if d_ff is not None else cfg.d_ff
    dt = dtype_of(cfg)
    k1, k2 = jax.random.split(key)
    gated = cfg.act in ("swiglu", "geglu")
    wi_cols = 2 * ff if gated else ff
    return {
        "wi": (jax.random.normal(k1, (d, wi_cols)) * d ** -0.5).astype(dt),
        "wo": (jax.random.normal(k2, (ff, d)) * ff ** -0.5).astype(dt),
    }


def mlp_apply(cfg, p, x: jnp.ndarray) -> jnp.ndarray:
    h = x @ p["wi"]
    if cfg.act in ("swiglu", "geglu"):
        ff = p["wo"].shape[0]
        g, u = h[..., :ff], h[..., ff:]
        act = jax.nn.silu(g.astype(jnp.float32)) if cfg.act == "swiglu" \
            else jax.nn.gelu(g.astype(jnp.float32), approximate=True)
        h = (act * u.astype(jnp.float32)).astype(x.dtype)
    elif cfg.act == "gelu":
        h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(x.dtype)
    elif cfg.act == "relu2":
        r = jax.nn.relu(h.astype(jnp.float32))
        h = (r * r).astype(x.dtype)
    else:
        raise ValueError(f"unknown act {cfg.act!r}")
    return h @ p["wo"]


# ---------------------------------------------------------------------------
# Embeddings / unembedding
# ---------------------------------------------------------------------------

def embed_params(cfg, key) -> Dict[str, jnp.ndarray]:
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 3)
    p = {"tok": (jax.random.normal(ks[0], (cfg.vocab, cfg.d_model)) * 0.02).astype(dt)}
    if cfg.pos == "learned":
        p["pos"] = (jax.random.normal(ks[1], (cfg.max_seq, cfg.d_model)) * 0.01).astype(dt)
    if not cfg.tie_embeddings:
        p["unembed"] = (jax.random.normal(ks[2], (cfg.d_model, cfg.vocab))
                        * cfg.d_model ** -0.5).astype(dt)
    return p


def embed(cfg, p, tokens: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    from repro.distributed.sharding import shard_hint
    x = p["tok"][tokens]
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if cfg.pos == "learned":
        x = x + p["pos"][positions]
    return shard_hint(x, "batch", None, None)


def unembed(cfg, p, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.tie_embeddings:
        w = p["tok"].T
    else:
        w = p["unembed"]
    return (x.astype(jnp.float32) @ w.astype(jnp.float32))
