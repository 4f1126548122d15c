"""Kernel microbenchmarks: interpret-mode allclose + wall time per call.

Interpret-mode wall time on CPU is NOT TPU performance -- the derived column
carries the correctness deltas and the work size; TPU perf is modeled in the
roofline report (results/dryrun).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.attention import decode_attention_lamp
from repro.core.policy import LampSite
from repro.kernels import ops, ref
from repro.kernels.paged_attention import decode_kv_bytes
from repro.models.layers import paged_kv_view

from .common import emit, timed


def paged_decode_micro(R: int = 8, H: int = 4, Hkv: int = 2, hd: int = 64,
                       bs: int = 16, n_max: int = 16):
    """Gather-vs-fused paged decode at R concurrent ragged sequences."""
    rng = np.random.default_rng(0)
    n_blocks = 1 + R * n_max
    arena_k = jnp.asarray(rng.normal(size=(n_blocks, Hkv, bs, hd)) * 1.5,
                          jnp.float32)
    arena_v = jnp.asarray(rng.normal(size=(n_blocks, Hkv, bs, hd)),
                          jnp.float32)
    lengths = jnp.asarray(rng.integers(1, n_max * bs, size=R), jnp.int32)
    perm = rng.permutation(np.arange(1, n_blocks))
    bt = np.zeros((R, n_max), np.int32)
    for r in range(R):
        nb = -(-int(lengths[r]) // bs)
        bt[r, :nb] = perm[r * n_max:r * n_max + nb]
    bt = jnp.asarray(bt)
    q = jnp.asarray(rng.normal(size=(R, H, 1, hd)) * 1.5, jnp.float32)
    site = LampSite(enabled=True, rule="relaxed", mu=7, tau=0.05,
                    granularity=0)

    @jax.jit
    def gather_decode(q, ak, av, bt, lengths):
        kh = paged_kv_view(ak, bt, H // Hkv)
        vh = paged_kv_view(av, bt, H // Hkv)
        out, aux = decode_attention_lamp(q, kh, vh, lengths, site,
                                         reduce=False)
        return out, aux.n_selected

    us_g, (out_g, nsel_g) = timed(
        lambda: gather_decode(q, arena_k, arena_v, bt, lengths))
    us_f, (out_f, nsel_f) = timed(
        lambda: ops.paged_decode_attention(q, arena_k, arena_v, bt, lengths,
                                           site, interpret=True))
    err = float(jnp.max(jnp.abs(out_f - out_g)))
    b_gather, b_fused = decode_kv_bytes(
        np.asarray(lengths), n_max=n_max, block_size=bs,
        bytes_per_token=Hkv * hd * 4, lamp=True)
    emit("kernel_paged_decode_gather", us_g,
         f"bytes_kv={b_gather};nsel={int(jnp.sum(nsel_g))}")
    emit("kernel_paged_decode_fused", us_f,
         f"bytes_kv={b_fused};nsel={int(jnp.sum(nsel_f))};max_err={err:.2e};"
         f"bytes_saved={1.0 - b_fused / b_gather:.1%}")


def kernels_micro():
    key = jax.random.PRNGKey(0)

    # lamp_flash_attention
    B, H, T, D = 1, 4, 256, 64
    q = jax.random.normal(key, (B, H, T, D)) * 1.5
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, T, D)) * 1.5
    v = jax.random.normal(jax.random.PRNGKey(2), (B, H, T, D))
    kw = dict(mu=7, tau=0.05, causal=True, block_q=64, block_k=64, k_subtile=32)
    us, (out, nsel) = timed(
        lambda: ops.lamp_flash_attention(q, k, v, interpret=True, **kw))
    want, nref = ref.lamp_flash_attention_ref(q, k, v, **kw)
    err = float(jnp.max(jnp.abs(out - want)))
    emit("kernel_lamp_attention_256", us,
         f"max_err={err:.2e};nsel={int(nsel)};nsel_ref={int(nref)};"
         f"flops={4 * B * H * T * T * D}")

    # flash_decode
    S = 2048
    qd = jax.random.normal(key, (2, 4, 1, 64)) * 1.5
    kc = jax.random.normal(jax.random.PRNGKey(3), (2, 4, S, 64)) * 1.5
    vc = jax.random.normal(jax.random.PRNGKey(4), (2, 4, S, 64))
    length = jnp.array([S, S - 100])
    us, (out, nsel) = timed(
        lambda: ops.flash_decode(qd, kc, vc, length, mu=7, tau=0.05,
                                 block_k=256, k_subtile=32, interpret=True))
    want, nref = ref.flash_decode_ref(qd, kc, vc, length, mu=7, tau=0.05,
                                      block_k=256, k_subtile=32)
    emit("kernel_flash_decode_2k", us,
         f"max_err={float(jnp.max(jnp.abs(out - want))):.2e};"
         f"nsel={int(nsel)};nsel_ref={int(nref)}")

    # paged decode: gather reference vs fused kernel over one block arena.
    # Interpret-mode wall time is not TPU perf; the decisive column is the
    # modeled KV bytes DMA'd per step (the gather path always moves the
    # full block-table span, the fused kernel only live blocks).
    paged_decode_micro()

    # ps_matmul
    a = jax.random.normal(key, (256, 256))
    b = jax.random.normal(jax.random.PRNGKey(5), (256, 256))
    us, out = timed(lambda: ops.ps_matmul(a, b, mu=7, interpret=True))
    want = ref.ps_matmul_ref(a, b, 7, 128)
    emit("kernel_ps_matmul_256", us,
         f"max_err={float(jnp.max(jnp.abs(out - want))):.2e};"
         f"flops={2 * 256 ** 3}")

    # rmsnorm
    x = jax.random.normal(key, (1024, 512)).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(6), (512,)) * 0.1
    us, out = timed(lambda: ops.rmsnorm(x, w, interpret=True))
    want = ref.rmsnorm_ref(x, w)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    emit("kernel_rmsnorm_1024x512", us, f"max_err={err:.2e}")
