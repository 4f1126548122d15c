"""Mixture-of-Experts FFN with top-k routing and sort-based capacity dispatch.

The router softmax is a LAMP site (beyond-paper extension, DESIGN.md Sec 6):
router logits are a matmul feeding a softmax, exactly the composition the
paper analyzes -- a "confused" router (near-uniform top-k mass) is where
rounding errors flip expert choices, and rule (8) flags precisely those rows.

Dispatch is sort-based (no T x E x C one-hot matmuls): tokens are ranked
within their expert via a stable sort of expert assignments, truncated at
capacity C = ceil(T * k * capacity_factor / E), scattered to an (E, C, d)
buffer, processed with batched expert matmuls, and combined back weighted by
the (renormalized) router probabilities. Overflowing tokens drop (standard
capacity semantics); the residual path keeps them finite.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import lamp as L
from repro.core.mixed_matmul import dot_ps
from repro.core.policy import LampSite

from .layers import dtype_of

# the router LAMP site's recompute and its LAMP-off reference are FP32
_HI = jax.lax.Precision.HIGHEST


def moe_params(cfg, key) -> Dict[str, jnp.ndarray]:
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 3)
    gated = cfg.act in ("swiglu", "geglu")
    wi_cols = 2 * ff if gated else ff
    return {
        "router": (jax.random.normal(ks[0], (d, E)) * d ** -0.5).astype(jnp.float32),
        "we_in": (jax.random.normal(ks[1], (E, d, wi_cols)) * d ** -0.5).astype(dt),
        "we_out": (jax.random.normal(ks[2], (E, ff, d)) * ff ** -0.5).astype(dt),
    }


def router_probs_lamp(x2d: jnp.ndarray, router_w: jnp.ndarray,
                      site: LampSite) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Router logits with LAMP evaluation. x2d: (T, d). Returns (probs, rate)."""
    xf = x2d.astype(jnp.float32)
    if not site.enabled:
        return jax.nn.softmax(jnp.matmul(xf, router_w, precision=_HI),
                              axis=-1), jnp.zeros(())
    y_low = dot_ps(xf, router_w, site.mu, granularity=site.granularity)
    if site.rule == "relaxed":
        mask = L.select_softmax_relaxed(y_low, site.tau)
    else:
        mask = L.select_softmax_strict(y_low, site.tau)
    y = jnp.where(mask, jnp.matmul(xf, router_w, precision=_HI), y_low)
    return jax.nn.softmax(y, axis=-1), jnp.mean(mask.astype(jnp.float32))


def moe_apply(cfg, p, x: jnp.ndarray, *, lamp_site: LampSite,
              num_groups: int = 1, dropless: bool = False,
              ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B, T, d) -> (B, T, d). `num_groups` splits tokens into independent
    dispatch groups (aligning groups with the data-parallel axis keeps the
    scatter local to a shard). `dropless=True` sizes capacity for the worst
    case (decode steps: exactness over buffer size)."""
    B, T, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    N = B * T
    x2d = x.reshape(N, d)

    probs, rate = router_probs_lamp(x2d, p["router"], lamp_site)
    top_p, top_e = jax.lax.top_k(probs, k)                       # (N, k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)

    G = num_groups
    while N % G:
        G //= 2
    Ng = N // G
    import math
    if dropless:
        cap = Ng * k                     # worst case: exactness (tests, small B)
    elif T == 1:
        # decode at scale: bounded-imbalance capacity -- 4x headroom over
        # perfect balance instead of the E-fold dropless worst case
        # (EXPERIMENTS Sec Perf, hillclimb B)
        cap = min(Ng * k, max(1, math.ceil(Ng * k * 4 / E)))
    else:
        cap = max(1, math.ceil(Ng * k * cfg.capacity_factor / E))

    def dispatch_group(xg, eg, pg):
        # xg: (Ng, d); eg, pg: (Ng, k)
        flat_e = eg.reshape(-1)                                   # (Ng*k,)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        seg_start = jnp.searchsorted(sorted_e, jnp.arange(E))
        pos_sorted = jnp.arange(Ng * k) - seg_start[sorted_e]
        pos = jnp.zeros(Ng * k, jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))
        keep = pos < cap
        pos_c = jnp.minimum(pos, cap - 1)
        tok_idx = jnp.repeat(jnp.arange(Ng), k)
        contrib = jnp.where(keep[:, None], xg[tok_idx], 0).astype(xg.dtype)
        buf = jnp.zeros((E, cap, d), xg.dtype).at[flat_e, pos_c].add(contrib)
        # expert FFN (batched over E)
        h = jnp.einsum("ecd,edf->ecf", buf, p["we_in"])
        if cfg.act in ("swiglu", "geglu"):
            ff = p["we_out"].shape[1]
            g, u = h[..., :ff], h[..., ff:]
            a = jax.nn.silu(g.astype(jnp.float32)) if cfg.act == "swiglu" \
                else jax.nn.gelu(g.astype(jnp.float32), approximate=True)
            h = (a * u.astype(jnp.float32)).astype(h.dtype)
        else:
            h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(h.dtype)
        out_buf = jnp.einsum("ecf,efd->ecd", h, p["we_out"])
        y_tok = out_buf[flat_e, pos_c] * keep[:, None]            # (Ng*k, d)
        w = pg.reshape(-1)[:, None].astype(y_tok.dtype)
        yg = jnp.zeros((Ng, d), y_tok.dtype).at[tok_idx].add(y_tok * w)
        return yg, jnp.mean(keep.astype(jnp.float32))

    if G == 1:
        y, kept = dispatch_group(x2d, top_e, top_p)
    else:
        # groups are B-major, i.e. aligned with the batch shards; make that
        # explicit or SPMD replicates the (G, E, cap, d) dispatch buffers
        # (observed on the multi-pod mesh: 72 GB/dev -> sharded).
        from repro.distributed.sharding import shard_hint
        xg = shard_hint(x2d.reshape(G, Ng, d), "batch", None, None)
        eg = shard_hint(top_e.reshape(G, Ng, k), "batch", None, None)
        pg = shard_hint(top_p.reshape(G, Ng, k), "batch", None, None)
        y, kept = jax.vmap(dispatch_group)(xg, eg, pg)
        y = shard_hint(y, "batch", None, None).reshape(N, d)
        kept = jnp.mean(kept)

    # load-balancing auxiliary loss (Switch-style)
    me = jnp.mean(jax.nn.one_hot(top_e[..., 0], E), axis=0)
    pe = jnp.mean(probs, axis=0)
    aux_loss = E * jnp.sum(me * pe)

    metrics = {"router_lamp_rate": rate, "kept_frac": kept, "moe_aux_loss": aux_loss}
    return y.reshape(B, T, d).astype(x.dtype), metrics


def moe_dispatch(cfg, p, x: jnp.ndarray, *, lamp_site: LampSite,
                 num_groups: int = 1, dropless: bool = False):
    """Pick the dispatch implementation: shard_map expert-parallel when a
    >1 `model` mesh axis is ambient (scales; multi-pod safe), else the
    einsum/scatter path (single device, tests, REPRO_BASELINE=1)."""
    from repro.core.attention import baseline_mode
    am = jax.sharding.get_abstract_mesh()
    names = am.axis_names
    if ("model" in names and am.shape["model"] > 1
            and cfg.n_experts % am.shape["model"] == 0
            and not baseline_mode()):
        baxes = tuple(a for a in ("pod", "data") if a in names)
        n_batch = 1
        for a in baxes:
            n_batch *= am.shape[a]
        if x.shape[0] % max(n_batch, 1) == 0:
            return moe_apply_ep(cfg, p, x, lamp_site=lamp_site, mesh=am)
    return moe_apply(cfg, p, x, lamp_site=lamp_site, num_groups=num_groups,
                     dropless=dropless)


# ---------------------------------------------------------------------------
# shard_map expert-parallel dispatch (EXPERIMENTS Sec Perf / multi-pod fix)
# ---------------------------------------------------------------------------

def moe_apply_ep(cfg, p, x: jnp.ndarray, *, lamp_site: LampSite, mesh,
                 capacity_mult: float = 2.0,
                 ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Expert-parallel MoE via shard_map: no token movement at all.

    Layout: tokens are batch-sharded over (pod, data) and replicated over
    `model`; expert weights are sharded E over `model` (and FSDP over
    `data`, gathered locally). Every device therefore already holds BOTH
    its tokens and its expert shard: it processes its local tokens through
    its local experts and the per-token results are summed over `model`
    with ONE psum -- the same wire pattern as a TP MLP, sidestepping the
    XLA involuntary-remat reshard the einsum-level dispatch hits on the
    multi-pod mesh (EXPERIMENTS Sec Roofline summary).

    Capacity is per local expert: ceil(T_local * k / E * capacity_mult).
    """
    import math
    from jax.sharding import PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    B, T, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    names = mesh.axis_names
    baxes = tuple(a for a in ("pod", "data") if a in names)
    n_model = mesh.shape["model"]
    E_l = E // n_model
    n_batch = 1
    for a in baxes:
        n_batch *= mesh.shape[a]
    Tl = (B // n_batch) * T
    cap = max(1, math.ceil(Tl * k / E * capacity_mult))

    def local(x_l, router_w, we_in_l, we_out_l):
        m_idx = jax.lax.axis_index("model")
        Bl = x_l.shape[0]
        x2d = x_l.reshape(Tl, d)
        probs, rate = router_probs_lamp(x2d, router_w, lamp_site)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
        flat_e = top_e.reshape(-1)
        local_id = flat_e - m_idx * E_l
        mine = (local_id >= 0) & (local_id < E_l)
        key = jnp.where(mine, local_id, E_l)            # foreign -> sentinel
        order = jnp.argsort(key, stable=True)
        sorted_key = key[order]
        seg_start = jnp.searchsorted(sorted_key, jnp.arange(E_l + 1))
        pos_sorted = jnp.arange(Tl * k) - seg_start[jnp.minimum(sorted_key, E_l)]
        pos = jnp.zeros(Tl * k, jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))
        keep = mine & (pos < cap)
        pos_c = jnp.minimum(jnp.maximum(pos, 0), cap - 1)
        lid_c = jnp.minimum(jnp.maximum(local_id, 0), E_l - 1)
        tok_idx = jnp.repeat(jnp.arange(Tl), k)
        contrib = jnp.where(keep[:, None], x2d[tok_idx], 0).astype(x2d.dtype)
        buf = jnp.zeros((E_l, cap, d), x2d.dtype).at[lid_c, pos_c].add(contrib)
        # FSDP: assemble full expert weights for the local expert shard
        w_in = jax.lax.all_gather(we_in_l, "data", axis=1, tiled=True)
        w_out = jax.lax.all_gather(we_out_l, "data", axis=2, tiled=True)
        h = jnp.einsum("ecd,edf->ecf", buf, w_in)
        if cfg.act in ("swiglu", "geglu"):
            ff = w_out.shape[1]
            g, u = h[..., :ff], h[..., ff:]
            a = jax.nn.silu(g.astype(jnp.float32)) if cfg.act == "swiglu" \
                else jax.nn.gelu(g.astype(jnp.float32), approximate=True)
            h = (a * u.astype(jnp.float32)).astype(h.dtype)
        else:
            h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(h.dtype)
        out_buf = jnp.einsum("ecf,efd->ecd", h, w_out)
        y_tok = out_buf[lid_c, pos_c] * keep[:, None]
        w_gate = top_p.reshape(-1)[:, None].astype(y_tok.dtype)
        y_l = jnp.zeros((Tl, d), y_tok.dtype).at[tok_idx].add(y_tok * w_gate)
        y_l = jax.lax.psum(y_l, "model")                 # combine expert shards
        kept = jax.lax.psum(jnp.sum(keep.astype(jnp.float32)), "model") / (Tl * k)
        return y_l.reshape(Bl, T, d), rate, kept

    bspec = baxes if baxes else None
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, None, None), P(), P("model", "data", None),
                  P("model", None, "data")),
        out_specs=(P(bspec, None, None), P(), P()),
        check_rep=False)
    y, rate, kept = fn(x, p["router"], p["we_in"], p["we_out"])
    metrics = {"router_lamp_rate": rate, "kept_frac": kept,
               "moe_aux_loss": jnp.zeros(())}
    return y.astype(x.dtype), metrics
