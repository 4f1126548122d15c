"""Decoder-only transformer LM (dense / MoE / GPT-2 / LLaVA backbone).

Layers are stacked: every block parameter leaf has a leading (L,) axis and the
stack is driven by jax.lax.scan (keeps the lowered HLO size independent of
depth -- essential for 88-layer configs and fast multi-pod compiles).

The LAMP policy is a first-class runtime switch: `use_lamp=True` routes
attention through the LAMP evaluators (strict rule for materialized softmax,
relaxed rule (9) for the online-softmax path) and MoE routing through the
router-LAMP site.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.policy import LampPolicy, LampSite

from . import layers as LY
from . import moe as MOE


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def block_params(cfg, key) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"attn": LY.attn_params(cfg, ks[0])}
    d = cfg.d_model
    dt = LY.dtype_of(cfg)
    if cfg.norm == "layernorm":
        p["ln1_w"], p["ln1_b"] = jnp.ones((d,), dt), jnp.zeros((d,), dt)
        p["ln2_w"], p["ln2_b"] = jnp.ones((d,), dt), jnp.zeros((d,), dt)
    else:
        p["ln1_w"], p["ln2_w"] = jnp.zeros((d,), dt), jnp.zeros((d,), dt)
    if cfg.family == "moe":
        p["moe"] = MOE.moe_params(cfg, ks[1])
    else:
        p["mlp"] = LY.mlp_params(cfg, ks[1])
    return p


def init_params(cfg, key) -> Dict[str, Any]:
    k_emb, k_blocks, k_f = jax.random.split(key, 3)
    blocks = jax.vmap(lambda k: block_params(cfg, k))(
        jax.random.split(k_blocks, cfg.n_layers))
    p = {"embed": LY.embed_params(cfg, k_emb), "blocks": blocks}
    d, dt = cfg.d_model, LY.dtype_of(cfg)
    if cfg.norm == "layernorm":
        p["lnf_w"], p["lnf_b"] = jnp.ones((d,), dt), jnp.zeros((d,), dt)
    else:
        p["lnf_w"] = jnp.zeros((d,), dt)
    if cfg.family == "llava":
        # frontend stub: projector from (stub) vision embedding space to d.
        p["mm_proj"] = (jax.random.normal(k_f, (d, d)) * d ** -0.5).astype(dt)
    return p


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

class BlockCtx(NamedTuple):
    positions: jnp.ndarray
    lamp_kq: LampSite
    lamp_router: LampSite
    attn_impl: str
    moe_groups: int


def block_apply(cfg, p, x, ctx: BlockCtx):
    # NOTE: a Megatron-style sequence-parallel residual (seq sharded over
    # the model axis between blocks) was tried and REVERTED: it halves the
    # TP all-reduce but the residual all-gathers cost more under the
    # result-bytes traffic metric (EXPERIMENTS Sec Perf, refuted iteration).
    h = LY.apply_norm(cfg, x, p, "ln1")
    a, rate = LY.attention_sublayer(
        cfg, p["attn"], h, positions=ctx.positions, lamp_site=ctx.lamp_kq,
        causal=True, attn_impl=ctx.attn_impl)
    x = x + a
    h = LY.apply_norm(cfg, x, p, "ln2")
    if cfg.family == "moe":
        m, metrics = MOE.moe_dispatch(cfg, p["moe"], h, lamp_site=ctx.lamp_router,
                                      num_groups=ctx.moe_groups)
        aux = {"attn_lamp_rate": rate, **metrics}
    else:
        m = LY.mlp_apply(cfg, p["mlp"], h)
        aux = {"attn_lamp_rate": rate}
    return x + m, aux


def scan_blocks(cfg, blocks, x, ctx: BlockCtx, *, remat: bool = False):
    def body(carry, p_l):
        y, aux = block_apply(cfg, p_l, carry, ctx)
        return y, aux
    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, aux = jax.lax.scan(body, x, blocks)
    return x, jax.tree.map(jnp.mean, aux)


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------

def _ctx(cfg, positions, use_lamp: bool, attn_impl: str, moe_groups: int) -> BlockCtx:
    pol: LampPolicy = cfg.lamp
    off = LampSite(enabled=False)
    return BlockCtx(
        positions=positions,
        lamp_kq=pol.kq if use_lamp and pol.kq.enabled else off,
        lamp_router=pol.router if use_lamp and pol.router.enabled else off,
        attn_impl=attn_impl,
        moe_groups=moe_groups,
    )


def forward(cfg, params, tokens: jnp.ndarray, *,
            image_embeds: Optional[jnp.ndarray] = None,
            use_lamp: bool = False, attn_impl: str = "auto",
            remat: bool = False, moe_groups: int = 1,
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """tokens: (B, S) -> logits (B, S_total, vocab) float32.

    For llava, `image_embeds` (B, P, d) from the stub frontend are projected
    and prepended; logits cover the full (P + S) sequence.
    """
    B, S = tokens.shape
    prefix = 0
    if cfg.family == "llava":
        if image_embeds is None:
            raise ValueError("llava forward requires image_embeds")
        prefix = image_embeds.shape[1]
        img = (image_embeds.astype(LY.dtype_of(cfg)) @ params["mm_proj"])
        positions = jnp.arange(prefix + S)
        x = jnp.concatenate(
            [img, LY.embed(cfg, params["embed"], tokens, positions[prefix:])], axis=1)
    else:
        positions = jnp.arange(S)
        x = LY.embed(cfg, params["embed"], tokens, positions)

    ctx = _ctx(cfg, positions, use_lamp, attn_impl, moe_groups)
    x, aux = scan_blocks(cfg, params["blocks"], x, ctx, remat=remat)
    if cfg.norm == "layernorm":
        x = LY.layer_norm(x, params["lnf_w"], params["lnf_b"])
    else:
        x = LY.rms_norm(x, params["lnf_w"])
    logits = LY.unembed(cfg, params["embed"], x)
    return logits, aux


def loss_fn(cfg, params, batch: Dict[str, jnp.ndarray], *,
            use_lamp: bool = False, attn_impl: str = "auto",
            remat: bool = True, moe_groups: int = 1):
    """Next-token cross entropy. batch: {tokens (B,S), [image_embeds]}."""
    tokens = batch["tokens"]
    logits, aux = forward(cfg, params, tokens,
                          image_embeds=batch.get("image_embeds"),
                          use_lamp=use_lamp, attn_impl=attn_impl,
                          remat=remat, moe_groups=moe_groups)
    if cfg.family == "llava":
        P = batch["image_embeds"].shape[1]
        logits = logits[:, P:]
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    tgt = tokens[:, 1:]
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    loss = jnp.mean(nll)
    if cfg.family == "moe":
        loss = loss + 0.01 * aux["moe_aux_loss"]
    return loss, {"loss": loss, **aux}


# ---------------------------------------------------------------------------
# KV-cache serving
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    L, Hkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((L, batch, max_len, Hkv, hd), dtype),
        "v": jnp.zeros((L, batch, max_len, Hkv, hd), dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def prefill(cfg, params, tokens: jnp.ndarray, cache: Dict[str, Any], *,
            image_embeds: Optional[jnp.ndarray] = None, use_lamp: bool = True,
            attn_impl: str = "auto", moe_groups: int = 1):
    """Run the full prompt, fill the cache, return last-position logits."""
    B, S = tokens.shape
    prefix = 0
    if cfg.family == "llava":
        prefix = image_embeds.shape[1]
        img = image_embeds.astype(LY.dtype_of(cfg)) @ params["mm_proj"]
        positions = jnp.arange(prefix + S)
        x = jnp.concatenate(
            [img, LY.embed(cfg, params["embed"], tokens, positions[prefix:])], axis=1)
    else:
        positions = jnp.arange(S)
        x = LY.embed(cfg, params["embed"], tokens, positions)
    T = prefix + S
    ctx = _ctx(cfg, positions, use_lamp, attn_impl, moe_groups)

    def body(carry, xs):
        xc = carry
        p_l, ck, cv = xs
        h = LY.apply_norm(cfg, xc, p_l, "ln1")
        # compute k/v once here so we can both attend and store them
        q, k, v = LY._project_qkv(cfg, p_l["attn"], h, ctx.positions)
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), 0, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), 0, axis=1)
        H, Hkv = cfg.n_heads, cfg.n_kv_heads
        qh = jnp.swapaxes(q, 1, 2)
        kh = LY._repeat_kv(jnp.swapaxes(k, 1, 2), H // Hkv)
        vh = LY._repeat_kv(jnp.swapaxes(v, 1, 2), H // Hkv)
        from repro.core import attention as CA
        impl = ctx.attn_impl
        if impl == "auto":
            impl = "full" if T <= 2048 else "chunked"
        if impl == "full":
            if ctx.lamp_kq.enabled:
                o, _ = CA.attention_lamp(qh, kh, vh, ctx.lamp_kq, causal=True,
                                         window=cfg.window)
            else:
                o = CA.attention_reference(qh, kh, vh, causal=True, window=cfg.window)
        else:
            if ctx.lamp_kq.enabled:
                site = ctx.lamp_kq if ctx.lamp_kq.rule == "relaxed" \
                    else ctx.lamp_kq.replace(rule="relaxed")
                o, _ = CA.chunked_attention_lamp(qh, kh, vh, site, causal=True,
                                                 window=cfg.window,
                                                 onepass=site.onepass)
            else:
                o = CA.chunked_attention(qh, kh, vh, causal=True, window=cfg.window)
        o = jnp.swapaxes(o, 1, 2).reshape(xc.shape[0], T, -1).astype(xc.dtype)
        xc = xc + o @ p_l["attn"]["wo"]
        h = LY.apply_norm(cfg, xc, p_l, "ln2")
        if cfg.family == "moe":
            m, _ = MOE.moe_dispatch(cfg, p_l["moe"], h, lamp_site=ctx.lamp_router,
                                    num_groups=ctx.moe_groups)
        else:
            m = LY.mlp_apply(cfg, p_l["mlp"], h)
        return xc + m, (ck, cv)

    x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], cache["k"], cache["v"]))
    cache = {"k": ks, "v": vs,
             "length": jnp.full((B,), T, jnp.int32)}
    if cfg.norm == "layernorm":
        x = LY.layer_norm(x, params["lnf_w"], params["lnf_b"])
    else:
        x = LY.rms_norm(x, params["lnf_w"])
    logits = LY.unembed(cfg, params["embed"], x[:, -1:])
    return logits, cache


def decode_step(cfg, params, cache: Dict[str, Any], tokens: jnp.ndarray, *,
                use_lamp: bool = True, moe_dropless: bool = True,
                moe_groups: int = 1):
    """One decode step. tokens: (B, 1). Returns (logits (B,1,V), new cache)."""
    B = tokens.shape[0]
    length = cache["length"]
    x = LY.embed(cfg, params["embed"], tokens, length[:, None])
    pol = cfg.lamp
    site = pol.kq if (use_lamp and pol.kq.enabled) else LampSite(enabled=False)
    r_site = pol.router if (use_lamp and pol.router.enabled) else LampSite(enabled=False)

    def body(carry, xs):
        xc = carry
        p_l, ck, cv = xs
        h = LY.apply_norm(cfg, xc, p_l, "ln1")
        a, ck, cv, _ = LY.attention_decode_sublayer(
            cfg, p_l["attn"], h, cache_k=ck, cache_v=cv, length=length,
            lamp_site=site)
        xc = xc + a
        h = LY.apply_norm(cfg, xc, p_l, "ln2")
        if cfg.family == "moe":
            m, _ = MOE.moe_dispatch(cfg, p_l["moe"], h, lamp_site=r_site,
                                    dropless=moe_dropless, num_groups=moe_groups)
        else:
            m = LY.mlp_apply(cfg, p_l["mlp"], h)
        return xc + m, (ck, cv)

    x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], cache["k"], cache["v"]))
    if cfg.norm == "layernorm":
        x = LY.layer_norm(x, params["lnf_w"], params["lnf_b"])
    else:
        x = LY.rms_norm(x, params["lnf_w"])
    logits = LY.unembed(cfg, params["embed"], x)
    new_cache = {"k": ks, "v": vs, "length": length + 1}
    return logits, new_cache


# ---------------------------------------------------------------------------
# Paged KV-cache serving (block tables over a shared arena)
# ---------------------------------------------------------------------------
#
# Layout: one head-major arena per layer, (L, n_blocks, Hkv, block_size, hd),
# so one (block, kv head) tile is a (block_size, hd) slab the Pallas kernel
# DMAs whole. A sequence owns an ordered list of blocks; flat index t within
# the gathered view of a row's block table == absolute token position t, so
# attention semantics are identical to the dense cache (padded tail masked
# by `lengths`). Block 0 is reserved as a null/scratch block: block-table
# padding points at it and padded slots write into it.

def _serving_site(site: LampSite) -> LampSite:
    """The App C.4 'random' control arm needs a resampled key per call and is
    a benchmark-only configuration; serving maps it to the strict rule."""
    if site.enabled and site.rule == "random":
        return site.replace(rule="strict")
    return site


def init_paged_cache(cfg, n_blocks: int, block_size: int,
                     dtype=jnp.bfloat16) -> Dict[str, Any]:
    L, Hkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((L, n_blocks, Hkv, block_size, hd), dtype),
        "v": jnp.zeros((L, n_blocks, Hkv, block_size, hd), dtype),
    }


def paged_prefill(cfg, params, tokens: jnp.ndarray, arena: Dict[str, Any],
                  block_tables: jnp.ndarray, lengths: jnp.ndarray, *,
                  use_lamp: bool = True, moe_groups: int = 1,
                  kernel: str = "gather", per_layer: bool = False,
                  taus=None):
    """Prefill a padded batch of prompts into the paged arena.

    tokens: (B, S) left-aligned prompts padded to the bucket length S;
    lengths: (B,) true prompt lengths; block_tables: (B, n_max). Padded rows
    (lengths clamped to >= 1 by the caller) write only into the null block.

    Returns (last_logits (B, 1, V), arena, (n_selected (B,), n_valid (B,)))
    with last_logits taken at each row's final *valid* position and LAMP
    counts attributed per request (padded query rows excluded).

    Implemented as the degenerate window of `paged_prefill_window` (every row
    starts at position 0), so the full-prompt and chunked/prefix-cached
    prefill paths share one computation and stay token-identical.
    """
    starts = jnp.zeros_like(lengths)
    return paged_prefill_window(cfg, params, tokens, arena, block_tables,
                                starts, lengths, use_lamp=use_lamp,
                                moe_groups=moe_groups, kernel=kernel,
                                per_layer=per_layer, taus=taus)


def paged_prefill_window(cfg, params, tokens: jnp.ndarray,
                         arena: Dict[str, Any], block_tables: jnp.ndarray,
                         starts: jnp.ndarray, lengths: jnp.ndarray, *,
                         use_lamp: bool = True, moe_groups: int = 1,
                         kernel: str = "gather", per_layer: bool = False,
                         taus=None):
    """Prefill a *window* of each prompt against an existing block table.

    Row b runs tokens at absolute positions starts[b] .. starts[b] +
    lengths[b] - 1; KV for positions < starts[b] must already be in the
    arena through block_tables[b] (a shared prefix-cache hit or an earlier
    chunk of the same prompt). Queries attend to the gathered arena view --
    the cached prefix plus this window's just-written KV -- so per-position
    outputs are identical to a single full prefill no matter how the prompt
    is split into windows or how much of it came from the cache.

    tokens: (B, W) window tokens, left-aligned, padded to the bucket width W;
    starts: (B,) cached tokens per row (0 = fresh prompt); lengths: (B,)
    valid tokens in this window (>= 1; padded rows use starts=0, lengths=1
    and a null block table, writing only into the null block).

    kernel="gather" (reference) pays a constant gathered width (the full
    block-table span, as in decode): that is what buys the identity
    guarantee, but attention over more keys than the prompt needs costs
    extra FLOPs/bytes when max_model_len >> prompt. kernel="pallas" runs
    the fused paged-attention kernel instead: blocks are DMA'd through the
    block-table index map and fully-masked blocks (past each q-tile's
    causal bound) are skipped, with the same row-wise numerics -- outputs
    stay token-identical to the gather path (differential-tested). A site
    the kernel does not implement raises; serving never builds one (the
    "random" control rule maps to strict, see `_serving_site`).

    Returns (last_logits (B, 1, V), arena, (n_selected (B,), n_valid (B,)))
    with last_logits at each row's final valid *window* position (only
    meaningful for rows whose window completes the prompt) and LAMP counts
    covering the KQ products actually computed in this window. With
    `per_layer=True` the counts keep their layer axis -- (L, B) instead of
    (B,) -- so serving can attribute recompute work per layer per request.
    `taus` is an optional (L,) float32 array of per-layer LAMP thresholds
    overriding the static site tau -- a *traced operand*, so the serving
    policy controller can move thresholds every step without recompiling.
    """
    return paged_mixed_step(cfg, params, tokens, arena, block_tables,
                            starts, lengths, use_lamp=use_lamp,
                            moe_groups=moe_groups, kernel=kernel,
                            per_layer=per_layer, taus=taus,
                            all_logits=False)


def paged_verify_window(cfg, params, tokens: jnp.ndarray,
                        arena: Dict[str, Any], block_tables: jnp.ndarray,
                        starts: jnp.ndarray, lengths: jnp.ndarray, *,
                        use_lamp: bool = True, moe_groups: int = 1,
                        kernel: str = "gather", per_layer: bool = False,
                        taus=None):
    """Multi-query decode-verify step: the speculative verifier.

    Identical computation to `paged_prefill_window` -- row b runs `tokens`
    at absolute positions starts[b] .. starts[b] + lengths[b] - 1 against
    its block table, (re)writing the window's KV into the arena -- but
    returns logits for *every* window position (B, W, V) instead of only
    the last valid one, so the caller can score all k drafted tokens plus
    the bonus position in one batched forward pass. Because the windowed
    path is row-wise over a constant gathered key width (gather) or an
    equivalent fused kernel (pallas), position j's logits are exactly what
    a non-speculative decode step at that position would have produced, and
    the rewritten KV is the selective-recompute-quality KV the plain decode
    path would have cached.

    Returns (logits (B, W, V) float32, arena,
    (n_selected (B,), n_valid (B,))). Logits at positions >= lengths[b]
    are computed over padded queries and must be ignored. `per_layer=True`
    keeps the counts' layer axis: (L, B).
    """
    return paged_mixed_step(cfg, params, tokens, arena, block_tables,
                            starts, lengths, use_lamp=use_lamp,
                            moe_groups=moe_groups, kernel=kernel,
                            per_layer=per_layer, taus=taus,
                            all_logits=True)


def paged_mixed_step(cfg, params, tokens: jnp.ndarray,
                     arena: Dict[str, Any], block_tables: jnp.ndarray,
                     starts: jnp.ndarray, lengths: jnp.ndarray, *,
                     use_lamp: bool = True, moe_groups: int = 1,
                     kernel: str = "gather", per_layer: bool = False,
                     taus=None, all_logits: bool = False):
    """One fused serving step over a *mixed* row batch.

    The unification: a decode row is a width-1 window at starts[b] ==
    cache_len, a chunked-prefill row a width-w window at its cursor, a
    speculative verify row a width-(k+1) window at its rollback point --
    all the same computation `_paged_window_apply` already performs. This
    entry therefore subsumes `paged_decode_step`, `paged_prefill_window`
    and `paged_verify_window`: one jitted launch per engine step, whose
    per-row (start, length) metadata rides into the Pallas kernel as
    scalar-prefetch operands (`qlens`) so every row walks exactly its own
    live KV blocks -- no recompile across role mixes, and the gather branch
    is the bit-for-bit CPU/reference twin of the same signature.

    tokens: (B, W) window tokens left-aligned per row, padded to the bucket
    width W; starts: (B,) tokens already cached per row; lengths: (B,) live
    tokens in this window (1 for decode rows, k+1 for verify rows, the
    chunk width for prefill rows; padded rows use starts=0, lengths=1 and a
    null block table).

    `all_logits=False` returns logits (B, 1, V) at each row's last valid
    window position (the sampling position for prefill-completing and
    decode rows); `all_logits=True` returns (B, W, V) so a speculative
    verifier can score every drafted position. Counts are (n_selected,
    n_valid), each (B,) -- or (L, B) with `per_layer=True`.

    MoE caveat: capacity-based (non-dropless) routing is batch-composition
    dependent, so fused-vs-split token identity is only guaranteed for
    dense families and dropless MoE.
    """
    B = tokens.shape[0]
    x, arena, counts = _paged_window_apply(
        cfg, params, tokens, arena, block_tables, starts, lengths,
        use_lamp=use_lamp, moe_groups=moe_groups, kernel=kernel,
        per_layer=per_layer, taus=taus)
    if not all_logits:
        x = x[jnp.arange(B), jnp.maximum(lengths, 1) - 1][:, None]
    logits = LY.unembed(cfg, params["embed"], x)
    return logits, arena, counts


def _paged_window_apply(cfg, params, tokens, arena, block_tables, starts,
                        lengths, *, use_lamp, moe_groups, kernel,
                        per_layer: bool = False, taus=None):
    """Shared window forward: runs the block stack over one window per row
    and returns the final-norm hidden states (B, W, d), the updated arena,
    and per-row LAMP (n_selected, n_valid) -- summed over layers by
    default, or stacked per layer as (L, B) arrays when `per_layer=True`
    (the scan already produces the layer axis; the flag only skips the
    reduction, so the telemetry costs nothing extra on device).

    `taus` ((L,) float32, optional) carries per-layer KQ thresholds as scan
    operands: layer l's attention uses taus[l] instead of the static
    site.tau, so the serving policy controller can retune thresholds
    between steps without changing the jit cache key."""
    B, W = tokens.shape
    n_max = block_tables.shape[1]
    bs = arena["k"].shape[3]
    positions = starts[:, None] + jnp.arange(W)[None, :]              # (B, W)
    x = LY.embed(cfg, params["embed"], tokens, positions)
    ctx = _ctx(cfg, positions, use_lamp, "full", moe_groups)
    site = _serving_site(ctx.lamp_kq)
    valid_tok = jnp.arange(W)[None, :] < lengths[:, None]             # (B, W)
    blk_idx = jnp.clip(positions // bs, 0, n_max - 1)
    blk = jnp.where(valid_tok,
                    jnp.take_along_axis(block_tables, blk_idx, axis=1), 0)
    off = jnp.where(valid_tok, positions % bs, 0)
    qmask = valid_tok.astype(jnp.float32)
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    use_pallas = kernel == "pallas"
    if use_pallas:
        LY.require_kernel_site(site)
    if taus is None:
        taus = jnp.full((cfg.n_layers,), float(site.tau), jnp.float32)

    def body(carry, xs):
        xc = carry
        p_l, ck, cv, tau_l = xs
        h = LY.apply_norm(cfg, xc, p_l, "ln1")
        q, k, v = LY._project_qkv(cfg, p_l["attn"], h, positions)
        ck = LY.write_paged_kv(ck, blk, off, k)
        cv = LY.write_paged_kv(cv, blk, off, v)
        qh = jnp.swapaxes(q, 1, 2)
        from repro.core import attention as CA
        if use_pallas:
            from repro.kernels import ops as KOPS
            # per-row qlens = live window widths: the mixed-row convention
            # (decode rows ride as width-1 windows, verify rows as k+1);
            # rows walk only their own live blocks -- bit-identical at live
            # positions to the full-bucket walk (see paged_attention.py)
            o, nsel_rows = KOPS.paged_prefill_attention(
                qh, ck, cv, block_tables, starts, site, tau=tau_l,
                qlens=lengths, window=cfg.window)
            if site.enabled:
                cap = n_max * bs if cfg.window is None else cfg.window
                nval_rows = jnp.clip(positions + 1, 0, cap
                                     ).astype(jnp.float32) * H
                nsel = jnp.sum(nsel_rows * qmask, axis=1)
                nval = jnp.sum(nval_rows * qmask, axis=1)
            else:
                nsel = jnp.zeros((B,), jnp.float32)
                nval = jnp.zeros((B,), jnp.float32)
        else:
            # gather the full per-row view (cached prefix + this window);
            # gathered flat index t == absolute position t, as in decode
            kh = LY.paged_kv_view(ck, block_tables, H // Hkv)
            vh = LY.paged_kv_view(cv, block_tables, H // Hkv)
            if site.enabled:
                o, aux = CA.attention_lamp(qh, kh, vh, site, causal=True,
                                           window=cfg.window, offset=starts,
                                           reduce=False, tau=tau_l)
                nsel = jnp.sum(aux.n_selected * qmask, axis=1)
                nval = jnp.sum(aux.n_valid * qmask, axis=1)
            else:
                o = CA.attention_reference(qh, kh, vh, causal=True,
                                           window=cfg.window, offset=starts)
                nsel = jnp.zeros((B,), jnp.float32)
                nval = jnp.zeros((B,), jnp.float32)
        o = jnp.swapaxes(o, 1, 2).reshape(xc.shape[0], W, -1).astype(xc.dtype)
        xc = xc + o @ p_l["attn"]["wo"]
        h = LY.apply_norm(cfg, xc, p_l, "ln2")
        if cfg.family == "moe":
            m, _ = MOE.moe_dispatch(cfg, p_l["moe"], h, lamp_site=ctx.lamp_router,
                                    num_groups=ctx.moe_groups)
        else:
            m = LY.mlp_apply(cfg, p_l["mlp"], h)
        return xc + m, (ck, cv, nsel, nval)

    x, (ks, vs, nsel, nval) = jax.lax.scan(
        body, x, (params["blocks"], arena["k"], arena["v"], taus))
    if cfg.norm == "layernorm":
        x = LY.layer_norm(x, params["lnf_w"], params["lnf_b"])
    else:
        x = LY.rms_norm(x, params["lnf_w"])
    if not per_layer:
        nsel, nval = jnp.sum(nsel, axis=0), jnp.sum(nval, axis=0)
    return x, {"k": ks, "v": vs}, (nsel, nval)


def paged_audit_window(cfg, params, tokens, arena, block_tables, starts,
                       lengths, row_mask, *, moe_groups: int = 1,
                       taus=None, top_k: int = 5) -> Dict[str, jnp.ndarray]:
    """Shadow-audit forward: the LAMP serving arm and the FP32 reference arm
    run in lockstep over the same window batch, and only *error telemetry*
    comes back -- never logits to sample from and never an updated arena, so
    calling this can not perturb served tokens (the engine additionally
    passes the arena without donation, leaving the pool buffers untouched).

    Row b replays tokens at absolute positions starts[b] .. starts[b] +
    lengths[b] - 1 against its block table, exactly like
    `paged_mixed_step(kernel="gather")`: decode rows ride as width-1 windows,
    speculative rows as their pre-draft width-1 decode window, prefill rows
    as their chunk window. Three streams per layer:

      * lamp:   the serving computation (LAMP attention, live `taus`),
                carried through the stack -- its KV writes go into a
                functional copy of the arena slice;
      * ref:    the same computation with LAMP disabled (uniform FP32
                attention via `attention_reference`), the high-precision
                oracle, carried separately;
      * shadow: LAMP attention applied to the *ref* carry's input -- its
                divergence from the ref attention isolates layer l's *local*
                KQ-site error, uncontaminated by error inherited from layers
                below (the quantity the componentwise forward-error bound
                composes; see obs/error_model.py).

    `row_mask` (B,) zeroes padded bucket rows out of every reduction.
    Returns a dict of reduced metrics (tiny host transfer):
      kq_err / router_err / cum_err : (L,) mean per-token relative L2 error
        (local KQ-site, local router-site, cumulative hidden-state drift);
      logit_rel / logit_max_abs : (B,) final-position logit error;
      flip : (B,) 1.0 where the greedy argmax token differs;
      topk : (B,) |top-k(lamp) intersect top-k(ref)| / k.
    Per-row entries for padded rows are garbage -- callers slice the live
    prefix. MoE rows also audit the router site; dense families report 0.
    """
    B, W = tokens.shape
    n_max = block_tables.shape[1]
    bs = arena["k"].shape[3]
    positions = starts[:, None] + jnp.arange(W)[None, :]              # (B, W)
    ctx = _ctx(cfg, positions, True, "full", moe_groups)
    site = _serving_site(ctx.lamp_kq)
    r_site = ctx.lamp_router
    off = LampSite(enabled=False)
    valid_tok = jnp.arange(W)[None, :] < lengths[:, None]             # (B, W)
    blk_idx = jnp.clip(positions // bs, 0, n_max - 1)
    blk = jnp.where(valid_tok,
                    jnp.take_along_axis(block_tables, blk_idx, axis=1), 0)
    off_idx = jnp.where(valid_tok, positions % bs, 0)
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    if taus is None:
        taus = jnp.full((cfg.n_layers,), float(site.tau), jnp.float32)

    w = valid_tok.astype(jnp.float32) * row_mask.astype(jnp.float32)[:, None]
    wsum = jnp.maximum(jnp.sum(w), 1.0)

    def werr(a, b):
        # per-token relative L2 error over the feature axis, averaged over
        # live tokens of live rows
        af, bf = a.astype(jnp.float32), b.astype(jnp.float32)
        num = jnp.sqrt(jnp.sum((af - bf) ** 2, axis=-1))
        den = jnp.sqrt(jnp.sum(bf ** 2, axis=-1)) + 1e-30
        return jnp.sum((num / den) * w) / wsum

    from repro.core import attention as CA

    def gathered(ck, cv):
        return (LY.paged_kv_view(ck, block_tables, H // Hkv),
                LY.paged_kv_view(cv, block_tables, H // Hkv))

    def flat(o):
        # (B, H, W, hd) attention layout -> (B, W, H*hd) feature rows
        return jnp.swapaxes(o, 1, 2).reshape(B, W, -1)

    def attn(qh, kh, vh, lamp_site, tau_l):
        if lamp_site.enabled:
            o, _ = CA.attention_lamp(qh, kh, vh, lamp_site, causal=True,
                                     window=cfg.window, offset=starts,
                                     reduce=False, tau=tau_l)
        else:
            o = CA.attention_reference(qh, kh, vh, causal=True,
                                       window=cfg.window, offset=starts)
        return o

    def arm(xc, p_l, ck, cv, lamp_site, tau_l):
        # one residual block of one stream; returns the new carry plus the
        # ref-stream intermediates the shadow computation needs
        h = LY.apply_norm(cfg, xc, p_l, "ln1")
        q, k, v = LY._project_qkv(cfg, p_l["attn"], h, positions)
        ck = LY.write_paged_kv(ck, blk, off_idx, k)
        cv = LY.write_paged_kv(cv, blk, off_idx, v)
        qh = jnp.swapaxes(q, 1, 2)
        kh, vh = gathered(ck, cv)
        o = attn(qh, kh, vh, lamp_site, tau_l)
        xc = xc + flat(o).astype(xc.dtype) @ p_l["attn"]["wo"]
        h2 = LY.apply_norm(cfg, xc, p_l, "ln2")
        if cfg.family == "moe":
            m, _ = MOE.moe_dispatch(cfg, p_l["moe"], h2,
                                    lamp_site=(r_site if lamp_site.enabled
                                               else off),
                                    num_groups=ctx.moe_groups)
        else:
            m = LY.mlp_apply(cfg, p_l["mlp"], h2)
        return xc + m, (qh, kh, vh, o, h2)

    def body(carry, xs):
        x_l, x_r = carry
        p_l, ck, cv, tau_l = xs
        x_l, _ = arm(x_l, p_l, ck, cv, site, tau_l)
        x_r, (qh_r, kh_r, vh_r, o_r, h2_r) = arm(x_r, p_l, ck, cv, off, tau_l)
        # local KQ-site error: LAMP applied to the reference stream's own
        # inputs, against the reference attention on those same inputs
        o_s = attn(qh_r, kh_r, vh_r, site, tau_l)
        kq_err = werr(flat(o_s), flat(o_r))
        if cfg.family == "moe" and r_site.enabled:
            m_s, _ = MOE.moe_dispatch(cfg, p_l["moe"], h2_r, lamp_site=r_site,
                                      num_groups=ctx.moe_groups)
            m_r, _ = MOE.moe_dispatch(cfg, p_l["moe"], h2_r, lamp_site=off,
                                      num_groups=ctx.moe_groups)
            router_err = werr(m_s, m_r)
        else:
            router_err = jnp.float32(0.0)
        cum_err = werr(x_l, x_r)
        return (x_l, x_r), (kq_err, router_err, cum_err)

    x0 = LY.embed(cfg, params["embed"], tokens, positions)
    (x_l, x_r), (kq_err, router_err, cum_err) = jax.lax.scan(
        body, (x0, x0), (params["blocks"], arena["k"], arena["v"], taus))

    def final(x):
        if cfg.norm == "layernorm":
            x = LY.layer_norm(x, params["lnf_w"], params["lnf_b"])
        else:
            x = LY.rms_norm(x, params["lnf_w"])
        x = x[jnp.arange(B), jnp.maximum(lengths, 1) - 1][:, None]
        return LY.unembed(cfg, params["embed"], x)[:, 0].astype(jnp.float32)

    lg_l, lg_r = final(x_l), final(x_r)                               # (B, V)
    diff = lg_l - lg_r
    logit_rel = (jnp.sqrt(jnp.sum(diff ** 2, axis=-1))
                 / (jnp.sqrt(jnp.sum(lg_r ** 2, axis=-1)) + 1e-30))
    logit_max_abs = jnp.max(jnp.abs(diff), axis=-1)
    flip = (jnp.argmax(lg_l, axis=-1)
            != jnp.argmax(lg_r, axis=-1)).astype(jnp.float32)
    k = max(1, min(int(top_k), int(cfg.vocab)))
    _, idx_l = jax.lax.top_k(lg_l, k)
    _, idx_r = jax.lax.top_k(lg_r, k)
    topk = jnp.mean((idx_l[:, :, None] == idx_r[:, None, :]
                     ).any(-1).astype(jnp.float32), axis=-1)
    return {"kq_err": kq_err, "router_err": router_err, "cum_err": cum_err,
            "logit_rel": logit_rel, "logit_max_abs": logit_max_abs,
            "flip": flip, "topk": topk}


def paged_decode_step(cfg, params, arena: Dict[str, Any],
                      block_tables: jnp.ndarray, lengths: jnp.ndarray,
                      tokens: jnp.ndarray, *, use_lamp: bool = True,
                      moe_dropless: bool = True, moe_groups: int = 1,
                      kernel: str = "gather", per_layer: bool = False,
                      taus=None):
    """One continuous-batch decode step over the paged arena.

    tokens: (R, 1) last sampled token per slot; lengths: (R,) cache fill
    (the new token's KV lands at position lengths[r]). kernel selects the
    attention path: "gather" (reference, materializes the block-table span)
    or "pallas" (fused kernel, live blocks only). Returns
    (logits (R, 1, V), arena, (n_selected (R,), n_valid (R,))); counts
    keep their layer axis -- (L, R) -- with `per_layer=True`. `taus`
    ((L,) float32, optional) supplies traced per-layer KQ thresholds --
    see `paged_prefill_window`.
    """
    x = LY.embed(cfg, params["embed"], tokens, lengths[:, None])
    pol = cfg.lamp
    site = _serving_site(pol.kq if (use_lamp and pol.kq.enabled)
                         else LampSite(enabled=False))
    r_site = pol.router if (use_lamp and pol.router.enabled) \
        else LampSite(enabled=False)
    if taus is None:
        taus = jnp.full((cfg.n_layers,), float(site.tau), jnp.float32)

    def body(carry, xs):
        xc = carry
        p_l, ck, cv, tau_l = xs
        h = LY.apply_norm(cfg, xc, p_l, "ln1")
        a, ck, cv, nsel, nval = LY.paged_attention_decode_sublayer(
            cfg, p_l["attn"], h, arena_k=ck, arena_v=cv,
            block_tables=block_tables, lengths=lengths, lamp_site=site,
            kernel=kernel, tau=tau_l)
        xc = xc + a
        h = LY.apply_norm(cfg, xc, p_l, "ln2")
        if cfg.family == "moe":
            m, _ = MOE.moe_dispatch(cfg, p_l["moe"], h, lamp_site=r_site,
                                    dropless=moe_dropless, num_groups=moe_groups)
        else:
            m = LY.mlp_apply(cfg, p_l["mlp"], h)
        return xc + m, (ck, cv, nsel, nval)

    x, (ks, vs, nsel, nval) = jax.lax.scan(
        body, x, (params["blocks"], arena["k"], arena["v"], taus))
    if cfg.norm == "layernorm":
        x = LY.layer_norm(x, params["lnf_w"], params["lnf_b"])
    else:
        x = LY.rms_norm(x, params["lnf_w"])
    logits = LY.unembed(cfg, params["embed"], x)
    if not per_layer:
        nsel, nval = jnp.sum(nsel, axis=0), jnp.sum(nval, axis=0)
    return logits, {"k": ks, "v": vs}, (nsel, nval)
