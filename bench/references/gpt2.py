"""GPT-2, written out plainly: the benchmark's weights and its reference.

This file imports nothing of the program. It holds two things for every
configuration whose `family` is "gpt2":

* `make_params(model, seed)`: the weights of a run, drawn from the seed on
  the device in one jitted call, in float32 and in the layout the serving
  engine takes (stacked per layer). The benchmark hands them to the program,
  and draws them again from the same seed for the reference once the
  program's state is freed.
* `gaps(...)`: the plain forward pass of GPT-2 (Radford et al., 2019) over
  one padded sequence, every matmul at HIGHEST precision, and the gap by
  which each next token's logit lies below the position's best logit. With
  `low` it also runs the same forward in a lower precision and reads, under
  the float32 logits, the gap of the token that the lower precision puts
  first: "bfloat16" stores weights and activations in bfloat16 (products
  accumulated in float32, layer norms and softmax in float32); "fp8" rounds
  both operands of every linear layer and of the unembedding to float8
  e4m3 (weights scaled per tensor, activations per token, by their largest
  magnitude) and accumulates in float32.

Departures from the published model, all shared with the program: the
linear layers carry no bias (the published checkpoint's biases are
equivalently zero here), and the weights are random. The rest is GPT-2:
learned positions, pre-norm blocks, LayerNorm with eps 1e-5, tanh GELU,
causal softmax attention scaled by 1/sqrt(head_dim), and the unembedding
tied to the token embedding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5


def _dims(model: dict):
    d = int(model["n_embd"])
    ff = int(model.get("n_inner") or 4 * d)
    return (int(model["n_layer"]), d, int(model["n_head"]), ff,
            int(model["vocab_size"]), int(model["n_positions"]))


def seed_key(seed: int):
    """A PRNG key for any whole seed, including ones above 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31),
                              seed // 2 ** 31)


@functools.lru_cache(maxsize=None)
def _maker(L, d, H, ff, V, P):
    def make(key):
        ks = iter(jax.random.split(key, 16))

        def normal(shape, std):
            return jax.random.normal(next(ks), shape, jnp.float32) * std

        def around_one(shape):
            return 1.0 + normal(shape, 0.05)

        return {
            "embed": {"tok": normal((V, d), 0.02), "pos": normal((P, d), 0.01)},
            "blocks": {
                "attn": {"wq": normal((L, d, d), d ** -0.5),
                         "wk": normal((L, d, d), d ** -0.5),
                         "wv": normal((L, d, d), d ** -0.5),
                         "wo": normal((L, d, d), d ** -0.5)},
                "ln1_w": around_one((L, d)), "ln1_b": normal((L, d), 0.05),
                "ln2_w": around_one((L, d)), "ln2_b": normal((L, d), 0.05),
                "mlp": {"wi": normal((L, d, ff), d ** -0.5),
                        "wo": normal((L, ff, d), ff ** -0.5)},
            },
            "lnf_w": around_one((d,)), "lnf_b": normal((d,), 0.05),
        }
    return jax.jit(make)


def make_params(model: dict, seed: int):
    """Float32 weights of one run, made on the device from `seed`."""
    return _maker(*_dims(model))(seed_key(seed))


def _layer_norm(x, w, b):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + LN_EPS) * w + b).astype(x.dtype)


def _mm(a, b):
    """a @ b: float32 at HIGHEST; bfloat16 operands accumulate in float32
    and store bfloat16."""
    if a.dtype == jnp.float32:
        return jnp.matmul(a, b, precision=HI)
    return jnp.matmul(a, b, preferred_element_type=jnp.float32).astype(a.dtype)


def _fp8(x, axis):
    """x rounded to float8 e4m3, scaled by its largest magnitude along
    `axis` (None: the whole tensor)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm_fp8(a, b):
    return jnp.matmul(_fp8(a, -1), _fp8(b, None), precision=HI)


def _forward(params, tokens, H, mm=_mm):
    """Logits (T, V) in float32 of one sequence `tokens` (T,)."""
    dt = params["embed"]["tok"].dtype
    T = tokens.shape[0]
    x = params["embed"]["tok"][tokens] + params["embed"]["pos"][:T]
    d = x.shape[-1]
    hd = d // H
    causal = jnp.tril(jnp.ones((T, T), bool))

    def block(x, p):
        h = _layer_norm(x, p["ln1_w"], p["ln1_b"])
        q = mm(h, p["attn"]["wq"]).reshape(T, H, hd)
        k = mm(h, p["attn"]["wk"]).reshape(T, H, hd)
        v = mm(h, p["attn"]["wv"]).reshape(T, H, hd)
        s = jnp.einsum("thd,shd->hts", q, k, precision=HI,
                       preferred_element_type=jnp.float32) * hd ** -0.5
        s = jnp.where(causal[None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(dt)
        o = jnp.einsum("hts,shd->thd", w, v, precision=HI,
                       preferred_element_type=jnp.float32).astype(dt)
        x = x + mm(o.reshape(T, d), p["attn"]["wo"])
        h = _layer_norm(x, p["ln2_w"], p["ln2_b"])
        a = jax.nn.gelu(mm(h, p["mlp"]["wi"]).astype(jnp.float32),
                        approximate=True).astype(dt)
        return x + mm(a, p["mlp"]["wo"]), None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    x = _layer_norm(x, params["lnf_w"], params["lnf_b"])
    unembed = mm if mm is _mm_fp8 else functools.partial(jnp.matmul,
                                                         precision=HI)
    return unembed(x.astype(jnp.float32),
                   params["embed"]["tok"].astype(jnp.float32).T)


@functools.partial(jax.jit, static_argnames=("n_head", "low"))
def gaps(params, tokens, *, n_head: int, low: str = ""):
    """Per position t of `tokens` (T,): the float32 reference's best logit
    minus its logit of tokens[t + 1] (the last position reads 0). With
    `low` ("bfloat16" or "fp8"), also the same gap for the token that
    forward puts first at each position."""
    ref = _forward(params, tokens, n_head)
    best = jnp.max(ref, axis=-1)
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    gap = best - jnp.take_along_axis(ref, nxt[:, None], axis=-1)[:, 0]
    gap = gap.at[-1].set(0.0)
    if not low:
        return gap
    if low == "bfloat16":
        lowp = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        logits = _forward(lowp, tokens, n_head)
    elif low == "fp8":
        logits = _forward(params, tokens, n_head, mm=_mm_fp8)
    else:
        raise ValueError(f"unknown control precision {low!r}")
    pick = jnp.argmax(logits, axis=-1)
    return gap, best - jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
