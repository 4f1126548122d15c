"""Ahead-of-time compiles of the serving main path for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler that ships with jaxlib
compiles for a v5e topology that is described, not attached, and refuses
what Mosaic would refuse on the chip (block shapes off the (8, 128) tiling,
captured constants, VMEM overruns). Each test asserts the compiled program
holds the Pallas kernel (`tpu_custom_call`).

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test worker
imports this file.
"""

import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.policy import LampPolicy, LampSite
from repro.kernels.paged_attention import (paged_decode_attention,
                                           paged_prefill_attention)
from repro.models import api

# GPT-2 small at its published widths (configs/archs.py), with the engine's
# default 16-token blocks: 257 blocks = 4 max-length (1024) rows + null.
H = HKV = 12
HD = 64
BS = 16
N_BLOCKS = 257
N_MAX = 64

TIERS = {
    # the gpt2 config's own site: paper_default, per-FMA simulation
    "gpt2-strict-g1": LampSite(enabled=True, rule="strict", mu=7, tau=0.1,
                               granularity=1),
    # the deployment tier: relaxed rule, one MXU pass + final rounding
    "deploy-relaxed-g0": LampSite(enabled=True, rule="relaxed", mu=7,
                                  tau=0.05, granularity=0),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("tier", sorted(TIERS))
def test_paged_decode_compiles_for_v5e(one_chip, tier):
    site, R = TIERS[tier], 8
    arena = _spec(one_chip, (N_BLOCKS, HKV, BS, HD))
    _assert_kernel(
        lambda q, k, v, bt, lens: paged_decode_attention(
            q, k, v, bt, lens, site, interpret=False),
        _spec(one_chip, (R, H, 1, HD)), arena, arena,
        _spec(one_chip, (R, N_MAX), jnp.int32),
        _spec(one_chip, (R,), jnp.int32))


@pytest.mark.parametrize("tier", sorted(TIERS))
def test_paged_prefill_compiles_for_v5e(one_chip, tier):
    site, B, W = TIERS[tier], 8, 16
    arena = _spec(one_chip, (N_BLOCKS, HKV, BS, HD))
    rows = _spec(one_chip, (B,), jnp.int32)
    _assert_kernel(
        lambda q, k, v, bt, st, ql: paged_prefill_attention(
            q, k, v, bt, st, site, qlens=ql, interpret=False),
        _spec(one_chip, (B, H, W, HD)), arena, arena,
        _spec(one_chip, (B, N_MAX), jnp.int32), rows, rows)


@pytest.mark.parametrize("kernel", ["prefill", "decode"])
def test_lamp_passes_tagged_for_v5e(one_chip, kernel):
    """Each LAMP pass carries its `lamp_pass` tag in the compiled op's text
    (and so in a profiler trace), and both keep the jitted entry's name,
    which the benchmark's kernel matcher reads."""
    site, B = TIERS["deploy-relaxed-g0"], 8
    arena = _spec(one_chip, (N_BLOCKS, HKV, BS, HD))
    rows = _spec(one_chip, (B,), jnp.int32)
    bt = _spec(one_chip, (B, N_MAX), jnp.int32)
    if kernel == "prefill":
        fn = jax.jit(lambda q, k, v, bt, st, ql: paged_prefill_attention(
            q, k, v, bt, st, site, qlens=ql, interpret=False))
        args = (_spec(one_chip, (B, H, 16, HD)), arena, arena, bt, rows, rows)
    else:
        fn = jax.jit(lambda q, k, v, bt, lens: paged_decode_attention(
            q, k, v, bt, lens, site, interpret=False))
        args = (_spec(one_chip, (B, H, 1, HD)), arena, arena, bt, rows)
    text = fn.lower(*args).compile().as_text()
    ops = re.findall(r"(%paged_\w+_attention\.\d+) = .*?kernel_metadata="
                     r"\{\s*\"lamp_pass\":\"(\w+)\"", text)
    assert sorted(p for _, p in ops) == ["lookahead", "recompute"]
    assert all(n.startswith(f"%paged_{kernel}_attention.") for n, _ in ops)


# The buckets the benchmark's cells run, (heads, rows, width): GPT-2 XL
# decodes 4 rows and prefills up to 1024 tokens; GPT-2 small decodes 64 rows
# and prefills 512-wide buckets of 8 rows. Width 1 folds every head into a
# grid step; the widest buckets fold fewer (kv_heads_per_step), and the
# compile refuses a fold whose tiles overrun the VMEM Mosaic grants.
CELL_BUCKETS = {
    "xl-4x1": (25, 4, 1),
    "xl-4x1024": (25, 4, 1024),
    "small-64x1": (12, 64, 1),
    "small-8x512": (12, 8, 512),
}


@pytest.mark.parametrize("bucket", sorted(CELL_BUCKETS))
def test_paged_mixed_compiles_at_cell_buckets_for_v5e(one_chip, bucket):
    """The mixed-row kernel pair at each cell bucket, on the deployment
    tier the cells run: the Pallas kernel is in the program, and both LAMP
    passes keep their `lamp_pass` tags."""
    heads, B, W = CELL_BUCKETS[bucket]
    site = TIERS["deploy-relaxed-g0"]
    arena = _spec(one_chip, (N_BLOCKS, heads, BS, HD))
    rows = _spec(one_chip, (B,), jnp.int32)
    text = jax.jit(lambda q, k, v, bt, st, ql: paged_prefill_attention(
        q, k, v, bt, st, site, qlens=ql, interpret=False)).lower(
        _spec(one_chip, (B, heads, W, HD)), arena, arena,
        _spec(one_chip, (B, N_MAX), jnp.int32), rows, rows).compile().as_text()
    assert "tpu_custom_call" in text
    passes = re.findall(r"%paged_prefill_attention\.\d+ = .*?kernel_metadata="
                        r"\{\s*\"lamp_pass\":\"(\w+)\"", text)
    assert sorted(passes) == ["lookahead", "recompute"]


def test_fused_mixed_step_compiles_for_v5e(one_chip, monkeypatch):
    """The engine's fused mixed step (gpt2, LAMP on, kernel="pallas") for an
    8-row, 16-wide bucket. The engine's kernels resolve interpret mode from
    the backend, which is the CPU here, so the test pins compiled mode."""
    from repro.kernels import backend
    from repro.serving.engine import _jitted_steps
    from repro.serving.fn_cache import STEP_FNS
    monkeypatch.setattr(backend.jax, "default_backend", lambda: "tpu")
    cfg = get_config("gpt2")
    assert cfg.lamp == LampPolicy.paper_default()
    params = jax.tree.map(
        lambda s: _spec(one_chip, s.shape, s.dtype),
        jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0))))
    B, W, L = 8, 16, cfg.n_layers
    arena = _spec(one_chip, (L, N_BLOCKS, HKV, BS, HD))
    rows = _spec(one_chip, (B,), jnp.int32)
    STEP_FNS.clear()
    jax.clear_caches()      # no kernel trace from an earlier CPU-mode call
    try:
        mixed, _ = _jitted_steps(cfg, True, "pallas")
        text = mixed.lower(
            params, arena, arena, _spec(one_chip, (B, W), jnp.int32),
            _spec(one_chip, (B, N_MAX), jnp.int32), rows, rows,
            _spec(one_chip, (L,)), rows, rows, _spec(one_chip, (B,)),
            rows).compile().as_text()
    finally:
        STEP_FNS.clear()
        jax.clear_caches()  # nor a compiled-mode trace left for later calls
    assert "tpu_custom_call" in text
