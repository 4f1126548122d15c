"""Bring-up smoke of the LAMP serving engine on one TPU chip.

    python chip_smoke.py [--seed N]

Runs GPT-2 small at its published widths (12 layers, d_model 768, 12 heads,
vocab 50257, configs/archs.py) with parameters initialised from --seed, in
this one process on one chip, through the entry points a user calls:

  (a) refuses to run anywhere but a TPU;
  (b) reports the device and the compile-cache directory;
  (c) checks that the Pallas kernels resolve to compiled Mosaic and that
      the engine's fused mixed step with kernel="pallas" holds the kernel
      (`tpu_custom_call` in the compiled program);
  (d) LAMP off: paged prefill plus paged decode steps on the kernels
      against the plain full forward, every product at HIGHEST precision;
  (e) LAMP on: the same greedy prompts through the engine on the gather
      path and on the Pallas kernels -- identical tokens, recompute rates
      in (0, 1) that agree between the two;
  (f) two short Poisson streams through `repro.launch.serve.main`: the
      fused step on the kernels, and speculative decoding (the draft scan
      is the served user of the decode kernel). Every request finishes,
      with no health-guard recovery and no failed request.

Any failure exits non-zero. The last line of stdout is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

# (d) Both sides compute every product at HIGHEST (f32-accurate); what
# differs is summation order and the kernels' blockwise online softmax,
# ~1e-6 relative per operation. Over 12 layers that stays below ~1e-4 of
# the logits' scale; 1e-3 leaves a margin of ten, while a block-table,
# mask or layout fault moves whole attention rows.
LOGIT_RTOL = 1e-3
# (e) A LAMP selection differs between the kernels only where XLA's and
# Mosaic's FP32 products differ in the last bit and that bit decides a
# PS(7) rounding or the strict criterion against tau: a few products among
# the ~10^5 each layer judges. A wrong mask, threshold or row statistic
# moves a rate by far more than 2% of itself plus 2e-4.
RATE_RTOL, RATE_ATOL = 0.02, 2e-4


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def phase_c(cfg, params):
    from repro.kernels.backend import resolve_interpret
    from repro.serving.engine import _jitted_steps
    check(resolve_interpret() is False,
          "(c) Pallas kernels resolve to interpret mode on the TPU")
    mixed, _ = _jitted_steps(cfg, True, "pallas")
    B, W, n_blocks, n_max = 8, 16, 257, 64
    arena = jax.ShapeDtypeStruct(
        (cfg.n_layers, n_blocks, cfg.n_kv_heads, 16, cfg.hd), jnp.float32)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    text = mixed.lower(params, arena, arena, i32(B, W), i32(B, n_max),
                       i32(B), i32(B), f32(cfg.n_layers), i32(B), i32(B),
                       f32(B), i32(B)).compile().as_text()
    check("tpu_custom_call" in text,
          "(c) compiled fused mixed step holds no tpu_custom_call")
    log(f"(c) kernels compiled (interpret=False); fused mixed step "
        f"({B}x{W} bucket) holds {text.count('tpu_custom_call')} "
        f"tpu_custom_call sites")


def phase_d(cfg, params, rng):
    from repro.models import transformer
    B, P, n_dec, bs, n_max = 2, 40, 4, 16, 4
    n_blocks = 1 + B * n_max
    perm = rng.permutation(np.arange(1, n_blocks))
    bt = jnp.asarray(perm.reshape(B, n_max).astype(np.int32))
    prompts = rng.integers(0, cfg.vocab, size=(B, P)).astype(np.int32)
    lengths = jnp.full((B,), P, jnp.int32)
    arena = transformer.init_paged_cache(cfg, n_blocks, bs, jnp.float32)
    with jax.default_matmul_precision("highest"):
        prefill = jax.jit(lambda p, a, t, b, n: transformer.paged_prefill(
            cfg, p, t, a, b, n, use_lamp=False, kernel="pallas"))
        decode = jax.jit(lambda p, a, b, n, t: transformer.paged_decode_step(
            cfg, p, a, b, n, t, use_lamp=False, kernel="pallas"))
        forward = jax.jit(lambda p, t: transformer.forward(
            cfg, p, t, use_lamp=False)[0])
        logits, arena, _ = prefill(params, arena, jnp.asarray(prompts), bt,
                                   lengths)
        got = [logits[:, -1]]
        gen = []
        for i in range(n_dec):
            tok = jnp.argmax(got[-1], axis=-1).astype(jnp.int32)
            gen.append(tok)
            logits, arena, _ = decode(params, arena, bt, lengths + i,
                                      tok[:, None])
            got.append(logits[:, -1])
        full = jnp.concatenate([jnp.asarray(prompts), jnp.stack(gen, 1)], 1)
        ref = forward(params, full)[:, P - 1:]
    got = np.asarray(jnp.stack(got, 1))
    ref = np.asarray(ref)
    check(got.shape == ref.shape == (B, n_dec + 1, cfg.vocab),
          f"(d) logit shapes {got.shape} vs {ref.shape}")
    check(np.isfinite(got).all(), "(d) non-finite paged logits")
    err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    log(f"(d) LAMP off, paged prefill + {n_dec} decode steps on the kernels "
        f"vs full forward (HIGHEST): max|dlogit|/max|logit| = {err!r} "
        f"(limit {LOGIT_RTOL})")
    check(err <= LOGIT_RTOL, f"(d) logit error {err} > {LOGIT_RTOL}")


def _engine_run(cfg, params, prompts, kernel):
    from repro.serving import EngineConfig, LampEngine, SamplingParams
    eng = LampEngine(cfg, params, EngineConfig(kernel=kernel,
                                               max_model_len=128))
    for i, p in enumerate(prompts):
        eng.add_request(p, SamplingParams(max_new_tokens=12,
                                          temperature=0.0, seed=i))
    outs = eng.run_to_completion()
    s = eng.stats()
    check(len(outs) == len(prompts) and all(o.error is None for o in outs),
          f"(e) {kernel}: not every request finished cleanly")
    check(s["recoveries"] == 0 and s["failed_requests"] == 0,
          f"(e) {kernel}: recoveries={s['recoveries']} "
          f"failed_requests={s['failed_requests']}")
    return {o.req_id: o.tokens for o in outs}, s


def phase_e(cfg, params, rng):
    prompts = [rng.integers(0, cfg.vocab, size=n).tolist()
               for n in (24, 37, 50, 61)]
    runs = {k: _engine_run(cfg, params, prompts, k)
            for k in ("gather", "pallas")}
    (tok_g, s_g), (tok_p, s_p) = runs["gather"], runs["pallas"]
    check(tok_g == tok_p, f"(e) greedy tokens differ: gather {tok_g} "
                          f"vs pallas {tok_p}")
    pairs = [("aggregate", s_g["lamp_recompute_rate"],
              s_p["lamp_recompute_rate"])]
    pairs += [(f"L{i}", g, p) for i, (g, p) in
              enumerate(zip(s_g["lamp_layer_rates"], s_p["lamp_layer_rates"]))]
    check(len(pairs) == cfg.n_layers + 1, "(e) per-layer rates missing")
    for name, g, p in pairs:
        log(f"(e) recompute rate {name}: gather {g!r} pallas {p!r}")
        check(0.0 < g < 1.0 and 0.0 < p < 1.0,
              f"(e) {name} recompute rate outside (0, 1)")
        check(abs(g - p) <= RATE_RTOL * max(g, p) + RATE_ATOL,
              f"(e) {name} recompute rates disagree: {g} vs {p}")
    log(f"(e) LAMP on ({cfg.lamp.kq.rule}, mu={cfg.lamp.kq.mu}, "
        f"g={cfg.lamp.kq.granularity}): {len(prompts)} greedy requests, "
        f"{sum(map(len, tok_g.values()))} tokens identical on gather and "
        f"pallas")


def phase_f(seed):
    from repro.launch import serve
    base = ["--arch", "gpt2", "--kernel", "pallas", "--num-requests", "8",
            "--qps", "20", "--min-prompt", "17", "--max-prompt", "32",
            "--min-new", "8", "--max-new", "16", "--seed", str(seed)]
    for name, extra in (("fused", []), ("speculative", ["--speculative"])):
        try:
            s = serve.main(base + extra)
        except SystemExit as e:
            raise SmokeFailure(f"(f) {name} stream exited {e.code}")
        check(s["num_finished"] == 8,
              f"(f) {name}: {s['num_finished']}/8 requests finished")
        check(s["recoveries"] == 0 and s["failed_requests"] == 0,
              f"(f) {name}: recoveries={s['recoveries']} "
              f"failed_requests={s['failed_requests']}")
        log(f"(f) {name} stream: 8/8 finished, recoveries=0, "
            f"failed_requests=0")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # (a) the chip, or nothing
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[smoke] (a) FAIL: JAX found no TPU (platform "
              f"{dev.platform!r}); this smoke runs only on the chip",
              file=sys.stderr)
        return 2

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import init_compile_cache
    from repro.models import api

    # (b)
    cache_dir = init_compile_cache()
    log(f"(b) platform={dev.platform} device_kind={dev.device_kind!r} "
        f"devices={len(devices)} compile_cache={cache_dir}")

    cfg = get_config("gpt2")
    params = api.init_params(cfg, jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    phases = (("c", lambda: phase_c(cfg, params)),
              ("d", lambda: phase_d(cfg, params, rng)),
              ("e", lambda: phase_e(cfg, params, rng)),
              ("f", lambda: phase_f(args.seed)))
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except SmokeFailure as e:
            print(f"[smoke] FAIL {e}", file=sys.stderr, flush=True)
            return 1
        log(f"({name}) passed in {time.perf_counter() - t0:.1f}s wall "
            f"(compiles included)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
