"""Sharding rules: param/cache/batch PartitionSpecs for the production mesh.

Strategy (DESIGN.md Sec 4): FSDP over the `data` axis x tensor parallelism
over the `model` axis; batch over (`pod`, `data`). Expert parallelism puts
the MoE expert axis on `model`. Rules are path-based so every family's
param tree is covered; any dimension that does not divide evenly falls back
to replication on that axis (checked explicitly -- XLA requires even
sharding).
"""

from __future__ import annotations

import re
from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# (path regex, spec WITHOUT the stacked-layer axis). First match wins.
# "data"/"model" here are logical axis names resolved against the mesh.
_PARAM_RULES: Tuple[Tuple[str, Tuple], ...] = (
    # embeddings
    (r"embed/tok$",       ("model", "data")),     # (V, d): vocab-TP
    (r"embed/pos$",       (None, None)),
    (r"embed/unembed$",   ("data", "model")),     # (d, V)
    (r"enc_pos$",         (None, None)),
    (r"meta$",            (None, None)),
    (r"mm_proj$",         ("data", "model")),
    # attention
    (r"attn/wq$|xattn/wq$", ("data", "model")),
    (r"attn/wk$|xattn/wk$", ("data", "model")),
    (r"attn/wv$|xattn/wv$", ("data", "model")),
    (r"attn/wo$|xattn/wo$", ("model", "data")),
    (r"attn/qn_w$|attn/kn_w$", (None,)),
    # dense MLP
    (r"mlp/wi$",          ("data", "model")),
    (r"mlp/wo$",          ("model", "data")),
    # MoE
    (r"moe/router$",      ("data", None)),
    (r"moe/we_in$",       ("model", "data", None)),   # (E, d, ff)
    (r"moe/we_out$",      ("model", None, "data")),   # (E, ff, d)
    # rwkv time-mix / channel-mix (cm_* before the generic w[rkvg] rule)
    (r"cm_wk$",           ("data", "model")),
    (r"cm_wv$",           ("model", "data")),
    (r"cm_wr$",           ("data", "model")),
    (r"blocks/w[rkvg]$",  ("data", "model")),
    (r"blocks/wo$",       ("model", "data")),
    (r"tm_lora_down$|w_lora_down$", ("data", None)),
    (r"tm_lora_up$",      (None, None, "model")),
    (r"w_lora_up$",       (None, "model")),
    (r"w_base$",          ("model",)),
    (r"tm_mu$|cm_mu$",    (None, None)),
    (r"/u$",              (None, None)),
    (r"ln_x$",            ("model",)),
    # hymba mamba branch (d_inner sharded over model)
    (r"m_in$",            ("data", "model")),
    (r"m_conv$",          (None, "model")),
    (r"m_dt$",            (None, "model")),
    (r"m_dt_bias$",       ("model",)),
    (r"m_bc$",            ("model", None)),
    (r"m_A_log$",         ("model", None)),
    (r"m_D$",             ("model",)),
    (r"m_out$",           ("model", "data")),
    (r"fuse_na$|fuse_ns$", (None,)),
    (r"fuse_beta$",       (None,)),
    # norms and anything else 1-D: replicate
    (r".*",               None),
)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            parts.append(p.name)
        elif isinstance(p, jax.tree_util.SequenceKey):
            parts.append(str(p.idx))
    return "/".join(parts)


def _axis_size(mesh: Mesh, name: Optional[str]) -> int:
    if name is None:
        return 1
    return mesh.shape.get(name, 1) if name in mesh.axis_names else 1


def _fit_spec(shape, raw_spec, mesh: Mesh, stacked: bool) -> P:
    """Build a PartitionSpec, dropping axes that don't divide evenly or
    don't exist in the mesh, and prepending None for the stacked (L,) dim."""
    if raw_spec is None:
        dims = [None] * len(shape)
        return P(*dims)
    dims = list(raw_spec)
    if stacked:
        dims = [None] + dims
    # pad/trim to rank
    while len(dims) < len(shape):
        dims.append(None)
    dims = dims[: len(shape)]
    out = []
    for size, ax in zip(shape, dims):
        if ax is None or ax not in mesh.axis_names or size % _axis_size(mesh, ax):
            out.append(None)
        else:
            out.append(ax)
    return P(*out)


def shard_hint(x, *spec):
    """Best-effort with_sharding_constraint: resolves logical axis names
    against the ambient mesh (trace-time `with mesh:` context); silently
    no-ops when no mesh / axes absent so model code stays mesh-agnostic.
    Spec entries: "batch" -> ("pod","data") as available, or literal axis
    names, or None."""
    env = jax.sharding.get_abstract_mesh()
    names = env.axis_names
    if not names:
        return x
    resolved = []
    for s in spec:
        if s == "batch":
            ax = tuple(a for a in ("pod", "data") if a in names)
            resolved.append(ax if ax else None)
        elif s is None or s in names:
            resolved.append(s)
        else:
            resolved.append(None)
    # drop axes that do not divide the dim evenly
    def size_of(entry):
        if entry is None:
            return 1
        axs = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in axs:
            n *= env.shape[a]
        return n
    final = [e if x.shape[i] % size_of(e) == 0 else None
             for i, e in enumerate(resolved)]
    try:
        return jax.lax.with_sharding_constraint(x, P(*final))
    except Exception:
        return x


def param_specs(params_shape, mesh: Mesh):
    """Map a params shape-pytree to PartitionSpecs via the path rules."""
    def one(path, leaf):
        s = _path_str(path)
        stacked = "blocks/" in s or s.startswith("blocks")
        for pat, raw in _PARAM_RULES:
            if re.search(pat, s):
                return NamedSharding(mesh, _fit_spec(leaf.shape, raw, mesh, stacked))
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map_with_path(one, params_shape)


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Logical batch axes present in this mesh (pod first if multi-pod)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_specs(batch_shape, mesh: Mesh, *, shard_batch: bool = True):
    """Shard the leading batch dim of every batch leaf over (pod, data)."""
    baxes = batch_axes(mesh)
    bsize = 1
    for a in baxes:
        bsize *= mesh.shape[a]

    def one(leaf):
        if not shard_batch or leaf.ndim == 0 or leaf.shape[0] % bsize:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(baxes, *([None] * (leaf.ndim - 1))))
    return jax.tree.map(one, batch_shape)


def cache_specs(cache_shape, mesh: Mesh):
    """KV caches: (L, B, S, Hkv, hd) -> batch over (pod,data), seq over model.
    SSM states: (L, B, ...) -> batch over (pod,data), channel dims over model
    where divisible. `length` replicated."""
    baxes = batch_axes(mesh)
    bsize = 1
    for a in baxes:
        bsize *= mesh.shape[a]
    msize = _axis_size(mesh, "model")

    def one(path, leaf):
        s = _path_str(path)
        if s == "length" or leaf.ndim <= 1:
            return NamedSharding(mesh, P())
        dims = [None] * leaf.ndim
        # leading (L, B, ...)
        bdim = 1 if leaf.ndim >= 2 else 0
        if leaf.shape[bdim] % bsize == 0 and bsize > 1:
            dims[bdim] = baxes
        # KV cache: shard seq (axis 2 of 5) over model; states: shard the
        # largest trailing dim over model if divisible.
        if leaf.ndim == 5 and leaf.shape[2] % msize == 0:
            dims[2] = "model"
        elif leaf.ndim >= 3:
            for ax in range(leaf.ndim - 1, 1, -1):
                if leaf.shape[ax] % msize == 0 and msize > 1:
                    dims[ax] = "model"
                    break
        return NamedSharding(mesh, P(*dims))
    return jax.tree_util.tree_map_with_path(one, cache_shape)


def opt_specs(opt_state_shape, pspecs):
    """AdamW state (step, m, v): m/v shard like params, step replicated."""
    step_s, m_s, v_s = opt_state_shape
    mesh = jax.tree.leaves(pspecs)[0].mesh

    def like(tree):
        return jax.tree.map(lambda sh, sp: sp, tree, pspecs)
    from repro.optim.adamw import AdamWState
    return AdamWState(step=NamedSharding(mesh, P()), m=like(m_s), v=like(v_s))
