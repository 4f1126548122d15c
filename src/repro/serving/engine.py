"""The continuous-batching LAMP serving engine.

Step loop: `add_request()` enqueues, `step()` runs one scheduler-composed
batch (a bucketed prefill or a bucketed decode) through cached jitted model
functions over the paged KV pool, samples one token per sequence, and
returns the requests that finished this step.

Fixed-shape jit discipline: batch and sequence dims are padded to
power-of-two buckets and the block-table width is a compile-time constant
(blocks_for(max_model_len)), so the number of compiled shapes is bounded by
O(log(max_batch) * log(max_prefill_len)) per (cfg, use_lamp).

Prefill runs through the *window* path (`transformer.paged_prefill_window`):
each sequence runs the un-cached suffix of its prompt -- possibly one
`max_prefill_tokens`-sized chunk of it -- at its absolute positions against
the gathered arena view. Because every per-position computation is row-wise
and the gathered key width is constant, outputs are token-identical whether
a prompt is prefilled whole, in chunks, or on top of a shared prefix.

Fused step (`EngineConfig.fused_step`): the scheduler emits one *mixed*
StepPlan per step -- prefill windows, plain decode rows (width-1 windows at
start = cache_len) and speculative verify rows (width kd+1 windows) side by
side -- and the engine runs it as ONE bucketed jitted launch through
`transformer.paged_mixed_step` (plus the sequential draft scan when any row
drafted). Per-row (start, qlen) metadata is scalar-prefetched into the
paged-attention grid, so every mix of roles reuses the same compiled
(rows, max_window) bucket: the jit cache is keyed on one signature instead
of three. Because all three legacy paths are special cases of the same
row-wise window computation, fused outputs are token-identical to the
split paths; `mixed_exec="split"` executes the *same* mixed plans through
the legacy sub-steps as the differential-testing twin
(tests/test_fused_step.py locks the equivalence down).

Sampling is inside the jitted step and keyed per request as
fold_in(PRNGKey(seed), num_generated): a request's sample stream is
deterministic regardless of how it was batched, bucketed, or preempted.

Observability (src/repro/obs/): every step phase runs inside an
`obs.span(...)`, feeding per-phase duration histograms (always on), the
profiler's timeline as `engine.<phase>` annotations (so `--jax-profile` lays
each phase beside the device ops), and, with `ObsConfig.trace`, a
ring-buffered Chrome-trace exporter. A step is `step` (args `step`, `kind`)
around schedule (block alloc inside it), build (host batch arrays), the
launch span -- mixed, prefill, decode, draft, verify, each holding `inputs`
(host->device puts) and `launch` (the jitted call) where it does its own
puts -- then sync (`wait` for the device, `readback` of the outputs),
account (LAMP counts and per-row bookkeeping), emit and policy; `mixed`
carries rows, bucket, tokens and, while recording, the kernel grid's
kv_live / kv_walked and kv_heads_per_step (the KV heads one grid step
folds). The engine's counters live in the obs metrics registry (`stats()`
is a view over it; the legacy attribute names are properties over the same
counters). LAMP recompute counts are threaded per layer: the jitted steps
return (L, B) selected/valid counts, accumulated into per-layer counters, a
bounded recompute-rate time series, and per-request per-layer breakdowns. Jit
compiles are detected per call (the bucketed step cache growing) and logged
with their bucket shape and wall time -- recompile storms are the canonical
silent perf killer of fixed-shape serving.
"""

from __future__ import annotations

import dataclasses
import json
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import grid_blocks, kv_heads_per_step
from repro.models import transformer
from repro.obs import ObsConfig, Observability
from repro.obs.audit import AuditConfig, ShadowAuditor

from . import sampling
from .faults import (FaultConfig, FaultInjector, StepLaunchFault)
from .fn_cache import STEP_FNS
from .kv_pool import PagedKVPool
from .policy import PolicyConfig, PolicyController, PolicySignals
from .request import SamplingParams, Sequence, SequenceStatus
from .scheduler import Scheduler, StepPlan
from .speculative import SpecConfig, spec_step_fns, speculative_accept

# families the paged-KV engine can serve (no per-request side inputs, no
# state-space cache); launchers use this to filter the arch registry.
TEXT_FAMILIES = ("dense", "moe", "gpt2")


class QueueFullError(RuntimeError):
    """Raised by `add_request` when the bounded admission queue
    (`EngineConfig.max_queue`) is full: explicit backpressure instead of an
    unbounded waiting deque under overload."""


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    block_size: int = 16
    n_blocks: int = 0               # 0 = auto-size from max_model_len
    max_model_len: int = 0          # 0 = cfg.max_seq
    max_prefill_batch: int = 8
    max_prefill_tokens: int = 2048  # prefill-step token budget = chunk size
    max_decode_batch: int = 32
    kv_dtype: str = "float32"
    use_lamp: bool = True
    # prefix caching: requests sharing a prompt prefix map their block
    # tables onto the same arena rows (refcounted, copy-on-write)
    prefix_cache: bool = True
    # chunked prefill: long prompts prefill max_prefill_tokens per step so
    # decode steps interleave and decode latency stays bounded
    chunked_prefill: bool = True
    # attention path over the paged arena: "gather" materializes each row's
    # full block-table span (reference, bit-identical to the dense cache);
    # "pallas" runs the fused paged-attention kernel (live blocks DMA'd
    # through the block-table index map, masked blocks skipped) -- the fast
    # path on TPU, interpret mode on CPU
    kernel: str = "gather"
    # LAMP self-draft speculative decoding: decode rounds draft `draft_len`
    # tokens per sequence with the pure low-precision forward (LAMP rule
    # "none"), then verify all draft_len+1 positions in one multi-token
    # paged forward with the configured LAMP rule. Greedy outputs are
    # bit-identical to non-speculative decoding; sampled outputs follow the
    # same distribution (standard accept/residual-resample rule).
    speculative: bool = False
    draft_len: int = 4
    # fused serving step: the scheduler emits one mixed StepPlan per step
    # (prefill windows + decode rows + speculative verify rows together)
    # and the engine executes it as a single bucketed jitted launch over
    # `transformer.paged_mixed_step` (plus the sequential draft scan when
    # any row drafted). On by default since the shadow-audit burn-in
    # showed zero audited-error delta fused-vs-split (serving_bench
    # --audit-only gates this); fused_step=False restores the
    # phase-segregated pre-fusion plans
    fused_step: bool = True
    # how mixed plans execute: "fused" (one launch) or "split" (the same
    # plan through the legacy prefill/decode/spec sub-steps) -- the
    # differential-testing twin; only consulted when fused_step is on
    mixed_exec: str = "fused"
    # observability: the metrics registry and per-phase histograms are
    # always on; obs.trace additionally records step-phase spans for
    # Chrome-trace export (see repro.obs.ObsConfig)
    obs: ObsConfig = ObsConfig()
    # shadow-audit subsystem (repro.obs.audit): on a deterministic sample
    # of steps, re-run up to audit.max_rows rows through the LAMP-vs-FP32
    # lockstep forward (gather path, non-donated arena) and record
    # realized-error telemetry -- lamp_audit_* metrics, stats()["audit"],
    # and (with the policy on) error-model-calibrated per-layer targets.
    # rate=0 disables the subsystem entirely
    audit: AuditConfig = AuditConfig()
    # adaptive LAMP policy loop (serving/policy.py): per-layer thresholds
    # actuated toward target recompute rates every step (traced operands,
    # never a recompile), with load-aware degradation of draft length and
    # rule tier under pool pressure. Off by default: the engine then runs
    # the static site tau, token-identical to pre-policy behavior
    policy: PolicyConfig = PolicyConfig()
    # finished RequestOutputs retained for exact end-of-run percentiles;
    # older entries age out so a long-lived engine's memory stays bounded
    finished_retention: int = 1024
    # -- fault tolerance (serving/faults.py) --------------------------------
    # deterministic fault injection: seeded chaos behind named sites; the
    # default (enabled=False) constructs no injector at all
    faults: FaultConfig = FaultConfig()
    # numerical health guard: every jitted step returns a per-row health
    # scalar (max |final logit| over the row's live positions, in-jit, so
    # the check itself costs one reduce); the guard quarantines non-finite
    # rows host-side and retries them through the recovery ladder
    # (retry -> strict rule -> gather kernel -> FP32 reference, bounded by
    # max_retries) before failing the request alone. health_max_abs > 0
    # additionally treats |logit| above it as unhealthy (0 = finite-only)
    health_guard: bool = True
    health_max_abs: float = 0.0
    max_retries: int = 4
    # bounded admission queue: add_request raises QueueFullError once this
    # many requests are waiting (0 = unbounded, the historical behavior)
    max_queue: int = 0
    # stall watchdog: consecutive no-progress steps run_to_completion
    # tolerates before attempting recovery (evict the stalled rows,
    # continue) and, only if recovery changes nothing, raising
    stall_patience: int = 64
    # paranoid mode: run pool.check_invariants() against every live
    # sequence after every step (recovery paths always check)
    paranoid: bool = False


@dataclasses.dataclass
class RequestOutput:
    req_id: int
    prompt: List[int]
    tokens: List[int]
    finish_reason: str
    latency: float
    ttft: float
    num_preemptions: int
    lamp_selected: float
    lamp_valid: float
    num_cached_tokens: int = 0      # prompt tokens served from prefix cache
                                    # (cross-request hits only)
    num_resume_cached_tokens: int = 0  # own-KV hits on preemption resume
    spec_drafted: int = 0           # tokens drafted for this request
    spec_accepted: int = 0          # drafted tokens the verifier accepted
    # per-layer LAMP breakdown (length n_layers; sums to the scalars above)
    lamp_layer_selected: Optional[List[float]] = None
    lamp_layer_valid: Optional[List[float]] = None
    # shadow-audit accumulation: steps this request was audited in, summed
    # final-logit relative error across them, and argmax flips observed
    audit_samples: int = 0
    audit_err_sum: float = 0.0
    audit_flips: int = 0
    # set only on individually-failed requests (finish_reason "timeout" /
    # "unhealthy" / "stalled"): the diagnostic the engine failed them with
    error: Optional[str] = None

    @property
    def lamp_recompute_rate(self) -> float:
        return self.lamp_selected / self.lamp_valid if self.lamp_valid else 0.0

    @property
    def lamp_layer_rates(self) -> List[float]:
        """Per-layer recompute rate for this request (empty if no LAMP)."""
        if not self.lamp_layer_selected:
            return []
        return [s / v if v else 0.0 for s, v in
                zip(self.lamp_layer_selected, self.lamp_layer_valid)]

    @property
    def spec_acceptance_rate(self) -> float:
        return (self.spec_accepted / self.spec_drafted
                if self.spec_drafted else 0.0)


def _bucket(n: int, cap: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return min(b, cap) if cap else b


def _cache_size(fn) -> int:
    """Compiled-signature count of a jitted function; -1 when the runtime
    does not expose it (compile events are then simply not recorded)."""
    try:
        return fn._cache_size()
    except Exception:
        return -1


# jitted step functions live in the shared bounded fn_cache.STEP_FNS store
# (one keyed LRU for the step/spec/mixed builders), shared across engine
# instances so re-instantiation (benchmarks, tests) never recompiles. The KV
# arenas are donated: the per-step .at[].set() updates alias the pool buffers
# in place instead of copying the whole arena every token. Sampling routes
# through the shared serving/sampling.py primitives (same key schedule as
# before: fold_in(PRNGKey(seed), num_generated)).


def _jitted_steps(cfg, use_lamp: bool, kernel: str = "gather",
                  use_topk: bool = False):
    """`use_topk` is a static trace-time switch: the per-row top-k filter
    needs a vocab sort per row per step, so batches where every request has
    top_k == 0 (the common case) use the variant that skips it entirely.
    At most two variants compile per (cfg, use_lamp, kernel). LAMP counts
    come back per layer ((L, B) arrays); the host side reduces them.
    `taus` is a traced (L,) float32 operand carrying the live per-layer
    LAMP thresholds -- deliberately *outside* the jit cache key, so the
    policy controller can move thresholds every step for free.

    The prefill fn doubles as the fused mixed step for plans without draft
    rows: a decode row is a width-1 prefill window at start = cache_len
    (`paged_prefill_window` delegates to `paged_mixed_step`), so fused mode
    adds zero new compiled functions on the no-draft path -- only new
    (rows, max_window) bucket shapes of this one signature."""
    def build():
        def _prefill(params, k, v, tokens, bt, starts, lengths, taus, seeds,
                     counts, temps, topks):
            logits, arena, (nsel, nval) = transformer.paged_prefill_window(
                cfg, params, tokens, {"k": k, "v": v}, bt, starts, lengths,
                use_lamp=use_lamp, kernel=kernel, per_layer=True, taus=taus)
            lg = logits[:, -1]
            nxt = sampling.sample_rows(lg, seeds, counts, temps,
                                       top_k=topks if use_topk else None)
            # per-row numerical health for the engine's guard: max |final
            # logit| (NaN/Inf propagate through the reduce). One in-jit
            # reduction -- the guard's whole device-side cost
            health = jnp.max(jnp.abs(lg), axis=-1)
            return nxt, health, arena["k"], arena["v"], nsel, nval

        def _decode(params, k, v, bt, lengths, tokens, taus, seeds, counts,
                    temps, topks):
            logits, arena, (nsel, nval) = transformer.paged_decode_step(
                cfg, params, {"k": k, "v": v}, bt, lengths, tokens,
                use_lamp=use_lamp, kernel=kernel, per_layer=True, taus=taus)
            lg = logits[:, -1]
            nxt = sampling.sample_rows(lg, seeds, counts, temps,
                                       top_k=topks if use_topk else None)
            health = jnp.max(jnp.abs(lg), axis=-1)
            return nxt, health, arena["k"], arena["v"], nsel, nval

        return (jax.jit(_prefill, donate_argnums=(1, 2)),
                jax.jit(_decode, donate_argnums=(1, 2)))

    return STEP_FNS.get_or_build(("step", cfg, use_lamp, kernel, use_topk),
                                 build)


def _mixed_spec_step(cfg, use_lamp: bool, kernel: str, spec: SpecConfig,
                     use_topk: bool = False):
    """The fused mixed step for plans with draft rows: one jitted call runs
    every row (prefill windows, plain decode rows, verify rows) through
    `paged_mixed_step` with all window logits kept, samples the next token
    at each row's last valid position (prefill / plain-decode rows), and
    runs `speculative_accept` over the first k+1 positions (verify rows).
    The host picks per role; unused lanes cost only the tiny sampling tail.

    Draft tokens/logits arrive over the draft bucket (R rows) and scatter
    into the mixed batch via `dec_pos` (mixed-row index per draft row; pad
    rows point out of range and mode="drop" discards them), so the draft
    scan keeps its own compact bucket while the verify shares the mixed
    launch."""
    k = spec.draft_len

    def build():
        def _mixed(params, ak, av, tokens, bt, starts, qlens, kd, dec_pos,
                   d_toks, d_logits, taus, seeds, counts, temps, topks):
            B = tokens.shape[0]
            tokens = tokens.at[dec_pos, 1:k + 1].set(d_toks, mode="drop")
            dt = jnp.zeros((B, k), d_toks.dtype)
            dt = dt.at[dec_pos].set(d_toks, mode="drop")
            dl = jnp.zeros((B,) + d_logits.shape[1:], d_logits.dtype)
            dl = dl.at[dec_pos].set(d_logits, mode="drop")
            logits, arena, (nsel, nval) = transformer.paged_mixed_step(
                cfg, params, tokens, {"k": ak, "v": av}, bt, starts, qlens,
                use_lamp=use_lamp, kernel=kernel, per_layer=True, taus=taus,
                all_logits=True)
            last = logits[jnp.arange(B), jnp.maximum(qlens, 1) - 1]
            nxt = sampling.sample_rows(last, seeds, counts, temps,
                                       top_k=topks if use_topk else None)
            emit, n_acc = speculative_accept(
                logits[:, :k + 1], dt, dl, kd, seeds, counts, temps,
                topks if use_topk else None)
            # per-row health over each row's *live* window positions only
            # (all_logits=True keeps kernel garbage past qlens[b], which
            # must not poison the check)
            live = jnp.arange(logits.shape[1])[None, :] < qlens[:, None]
            health = jnp.max(
                jnp.where(live[..., None], jnp.abs(logits), 0.0),
                axis=(1, 2))
            return nxt, emit, n_acc, health, arena["k"], arena["v"], \
                nsel, nval

        return jax.jit(_mixed, donate_argnums=(1, 2))

    return STEP_FNS.get_or_build(
        ("mixed", cfg, use_lamp, kernel, spec, use_topk), build)


def _audit_step_fn(cfg, top_k: int):
    """The shadow-audit launch: `paged_audit_window` jitted WITHOUT arena
    donation -- the pool buffers must survive the call untouched (the
    zero-token-perturbation guarantee), and only reduced error metrics come
    back. Cached per (cfg, top_k); audited row batches ride small
    power-of-two (rows, window) buckets of this one signature."""
    def build():
        def _audit(params, k, v, tokens, bt, starts, lengths, row_mask,
                   taus):
            return transformer.paged_audit_window(
                cfg, params, tokens, {"k": k, "v": v}, bt, starts, lengths,
                row_mask, taus=taus, top_k=top_k)
        return jax.jit(_audit)

    return STEP_FNS.get_or_build(("audit", cfg, top_k), build)


def reset_step_caches() -> None:
    """Benchmark/test helper: drop the shared step-function cache AND JAX's
    compiled-computation caches, so compile counts (obs compile events)
    measure from a cold start instead of riding earlier runs' work."""
    STEP_FNS.clear()
    jax.clear_caches()


class LampEngine:
    def __init__(self, cfg, params, econfig: EngineConfig = EngineConfig(),
                 *, clock: Optional[Callable[[], float]] = None):
        if cfg.family not in TEXT_FAMILIES:
            raise ValueError(
                f"serving engine supports the paged-KV text families "
                f"{TEXT_FAMILIES}, got {cfg.family!r} (state-space / "
                f"modality-frontend families need their own cache layout; "
                f"see ROADMAP open items)")
        if min(econfig.max_prefill_tokens, econfig.max_prefill_batch,
               econfig.max_decode_batch) < 1:
            raise ValueError(
                "max_prefill_tokens, max_prefill_batch and max_decode_batch "
                "must all be >= 1 (a zero prefill budget cannot make "
                "progress)")
        if econfig.kernel not in ("gather", "pallas"):
            raise ValueError(
                f"kernel must be 'gather' or 'pallas', got "
                f"{econfig.kernel!r}")
        if econfig.speculative and econfig.draft_len < 1:
            raise ValueError(
                f"speculative decoding needs draft_len >= 1, got "
                f"{econfig.draft_len}")
        if econfig.mixed_exec not in ("fused", "split"):
            raise ValueError(
                f"mixed_exec must be 'fused' or 'split', got "
                f"{econfig.mixed_exec!r}")
        self.cfg = cfg
        self.params = params
        self.econfig = econfig
        self.max_model_len = econfig.max_model_len or cfg.max_seq
        bs = econfig.block_size
        self.blocks_per_seq = -(-self.max_model_len // bs)
        n_blocks = econfig.n_blocks or 4 * self.blocks_per_seq + 1
        if n_blocks - 1 < self.blocks_per_seq:
            raise ValueError(
                f"n_blocks={n_blocks} (one reserved for the null block) "
                f"cannot hold one max-length sequence: need "
                f"{self.blocks_per_seq + 1} for max_model_len="
                f"{self.max_model_len} at block_size={bs}")
        # all engine timestamps (arrivals, ttft, latency, trace spans) come
        # from this single injectable clock: no clock-domain mixing, and a
        # fake clock makes every timing-dependent path testable
        self.obs = Observability(econfig.obs, clock=clock)
        self._now = self.obs.now
        self.pool = PagedKVPool(cfg, n_blocks=n_blocks, block_size=bs,
                                dtype=jnp.dtype(econfig.kv_dtype),
                                enable_prefix_cache=econfig.prefix_cache)
        self.scheduler = Scheduler(
            self.pool, max_prefill_batch=econfig.max_prefill_batch,
            max_prefill_tokens=econfig.max_prefill_tokens,
            max_decode_batch=econfig.max_decode_batch,
            chunked_prefill=econfig.chunked_prefill,
            spec_draft_len=econfig.draft_len if econfig.speculative else 0,
            mixed=econfig.fused_step,
            obs=self.obs)
        self._next_id = 0
        # _seqs holds only *live* sequences: finished ones are pruned in
        # _collect_finished (their cached-token tallies fold into counters)
        # so a long-lived engine does not accumulate every request ever
        self._seqs: Dict[int, Sequence] = {}
        self._finished: Deque[RequestOutput] = deque(
            maxlen=max(1, econfig.finished_retention))
        # streaming mean of pool utilization (was an unbounded sample list)
        self._util_sum = 0.0
        self._util_n = 0
        self._start: Optional[float] = None
        self._last_step_wall = 0.0

        # -- metrics registry: the single source of truth for the engine's
        # cumulative counters (stats() and the legacy attribute properties
        # below are views over it); children resolved once, so the per-step
        # cost is a float add
        reg = self.obs.registry
        steps = reg.counter("engine_steps_total",
                            help="engine steps by kind", labels=("kind",))
        self._c_prefill_steps = steps.labels("prefill")
        self._c_decode_steps = steps.labels("decode")
        self._c_spec_rounds = steps.labels("spec")
        self._c_mixed_steps = steps.labels("mixed")
        # role presence per mixed step, so the legacy prefill/decode step
        # views stay meaningful under fused plans (a mixed step with any
        # prefill row counts as a prefill step, etc.)
        mixed_roles = reg.counter(
            "engine_mixed_steps_total",
            help="mixed fused steps containing each row role",
            labels=("role",))
        self._c_mixed_prefill = mixed_roles.labels("prefill")
        self._c_mixed_decode = mixed_roles.labels("decode")
        self._c_mixed_verify = mixed_roles.labels("verify")
        launches = reg.counter(
            "engine_launches_total",
            help="jitted step-function invocations (the fused step's "
                 "reason to exist: fewer of these per engine step)",
            labels=("fn",))
        self._c_launches = {name: launches.labels(name) for name in
                            ("prefill", "decode", "draft", "verify",
                             "mixed", "audit")}
        self._c_prefill_chunks = reg.counter(
            "engine_prefill_chunks_total",
            help="partial prefill windows (prompt continued next step)")
        self._c_prefill_tokens = reg.counter(
            "engine_prefill_tokens_total",
            help="prompt tokens actually computed", unit="tokens")
        self._c_generated = reg.counter(
            "engine_generated_tokens_total", help="tokens emitted",
            unit="tokens")
        self._c_finished = reg.counter(
            "engine_requests_finished_total", help="requests completed")
        cached = reg.counter(
            "engine_cached_tokens_total",
            help="prompt tokens served from cached KV (prefix = "
                 "cross-request hits, resume = own KV after preemption)",
            unit="tokens", labels=("kind",))
        self._c_cached_prefix = cached.labels("prefix")
        self._c_cached_resume = cached.labels("resume")
        spec = reg.counter("engine_spec_tokens_total",
                           help="speculative-decoding token flow",
                           labels=("event",))
        self._c_spec_drafted = spec.labels("drafted")
        self._c_spec_accepted = spec.labels("accepted")
        self._c_spec_emitted = spec.labels("emitted")
        lamp = reg.counter("lamp_kq_products_total",
                           help="KQ products by layer and disposition "
                                "(selected = recomputed in high precision)",
                           labels=("layer", "kind"))
        L = cfg.n_layers
        self._c_lamp_sel = [lamp.labels(str(l), "selected") for l in range(L)]
        self._c_lamp_val = [lamp.labels(str(l), "valid") for l in range(L)]
        vspec = reg.counter("lamp_verify_products_total",
                            help="LAMP counts of speculative verify passes",
                            labels=("kind",))
        self._c_verify_sel = vspec.labels("selected")
        self._c_verify_val = vspec.labels("valid")
        self._h_latency = reg.histogram(
            "engine_request_latency_seconds",
            help="request arrival -> finish", unit="s")
        self._h_ttft = reg.histogram(
            "engine_request_ttft_seconds",
            help="request arrival -> first token", unit="s")
        # -- fault tolerance: recovery actions by kind, failed requests by
        # cause (the fault-injection counter itself lives in FaultInjector)
        self._c_recover_fam = reg.counter(
            "engine_recoveries_total",
            help="recovery actions absorbed without failing the engine "
                 "(retry rungs, alloc deferrals, split fallbacks, stall "
                 "evictions)", labels=("action",))
        self._c_recover: Dict[str, Any] = {}
        self._c_failed_fam = reg.counter(
            "engine_requests_failed_total",
            help="requests individually failed (engine kept serving)",
            labels=("reason",))
        # per-layer accumulators mirrored into the counters above (numpy so
        # the per-step update is one vector add), plus a bounded time series
        # of instantaneous per-layer recompute rates
        self._layer_sel = np.zeros((L,), np.float64)
        self._layer_val = np.zeros((L,), np.float64)
        self.layer_rate_series = deque(maxlen=econfig.obs.series_capacity)

        self.spec_config = (SpecConfig(draft_len=econfig.draft_len)
                            if econfig.speculative else None)

        # -- adaptive policy loop: live per-layer thresholds (always
        # threaded into the jitted steps as a traced operand; without a
        # controller they simply stay at the static site tau, which is
        # bit-identical to the pre-policy engine) and, when enabled, the
        # feedback controller that moves them
        self._taus = np.full((L,), float(cfg.lamp.kq.tau), np.float32)
        self._active_rule: Optional[str] = None
        self._cfg_cache: Dict[str, Any] = {}
        self.policy: Optional[PolicyController] = None
        if econfig.policy.enabled:
            base_rule = cfg.lamp.kq.rule
            if base_rule == "random":   # serving maps the control arm
                base_rule = "strict"
            self.policy = PolicyController(
                econfig.policy, L, self._taus, base_rule=base_rule,
                base_draft_len=(econfig.draft_len if econfig.speculative
                                else 0),
                obs=self.obs)

        # -- shadow audit: realized-error telemetry on a deterministic
        # sample of steps (obs/audit.py). Only meaningful with LAMP on --
        # the audit measures LAMP-vs-reference divergence, which is
        # identically zero without LAMP
        self.auditor: Optional[ShadowAuditor] = None
        if econfig.audit.rate > 0 and econfig.use_lamp:
            self.auditor = ShadowAuditor(econfig.audit, L, self.obs)

        # -- fault tolerance: deterministic injector (None when disabled:
        # zero hot-path cost), the quarantine of rows the health guard
        # pulled out of this step, failures to merge into step() output,
        # and host tallies the watchdog / policy read
        self.faults: Optional[FaultInjector] = (
            FaultInjector(econfig.faults, self.obs)
            if econfig.faults.enabled else None)
        self._quarantine: List[tuple] = []
        self._step_failures: List[RequestOutput] = []
        self._n_failed = 0
        self._n_recoveries = 0
        self._last_alloc_degrades = 0
        self._has_deadlines = False

    # -- legacy counter attributes: views over the metrics registry ----------

    @property
    def prefill_steps(self) -> int:
        # fused mixed steps containing prefill rows count as prefill steps,
        # so the legacy view stays meaningful under fused_step
        return int(self._c_prefill_steps.value
                   + self._c_mixed_prefill.value)

    @property
    def decode_steps(self) -> int:
        # speculative rounds are decode steps too (one round == one step),
        # as are mixed steps containing any decode/verify row
        return int(self._c_decode_steps.value + self._c_spec_rounds.value
                   + self._c_mixed_decode.value)

    @property
    def total_steps(self) -> int:
        # raw step-kind counters: a mixed step counts ONCE even when its
        # rows span roles (the derived views above may both claim it)
        return int(self._c_prefill_steps.value + self._c_decode_steps.value
                   + self._c_spec_rounds.value + self._c_mixed_steps.value)

    @property
    def mixed_steps(self) -> int:
        return int(self._c_mixed_steps.value)

    @property
    def launches(self) -> int:
        """Jitted step-function invocations across all step kinds."""
        return int(sum(c.value for c in self._c_launches.values()))

    @property
    def prefill_chunks(self) -> int:
        return int(self._c_prefill_chunks.value)

    @property
    def prefill_tokens_run(self) -> int:
        return int(self._c_prefill_tokens.value)

    @property
    def generated_tokens(self) -> int:
        return int(self._c_generated.value)

    @property
    def agg_lamp_selected(self) -> float:
        return float(self._layer_sel.sum())

    @property
    def agg_lamp_valid(self) -> float:
        return float(self._layer_val.sum())

    @property
    def spec_rounds(self) -> int:
        # mixed steps that verified drafts are speculative rounds too
        return int(self._c_spec_rounds.value + self._c_mixed_verify.value)

    @property
    def spec_drafted(self) -> int:
        return int(self._c_spec_drafted.value)

    @property
    def spec_accepted(self) -> int:
        return int(self._c_spec_accepted.value)

    @property
    def spec_emitted(self) -> int:
        return int(self._c_spec_emitted.value)

    @property
    def spec_verify_selected(self) -> float:
        return self._c_verify_sel.value

    @property
    def spec_verify_valid(self) -> float:
        return self._c_verify_val.value

    @property
    def compile_events(self):
        return self.obs.compile_events

    # step functions resolve per batch: `use_topk` selects the jit variant
    # with/without the per-row top-k vocab sort (global caches dedupe, so
    # at most two variants compile per step kind)

    def _serving_cfg(self):
        """The model config the next step traces with: the base config,
        unless the policy controller degraded the LAMP rule tier. A rule
        change swaps a *static* trace argument -- one recompile per tier
        per bucket, the deliberate last rung of the degradation ladder
        (tau and draft-length moves are recompile-free)."""
        rule = self._active_rule
        if rule is None or not self.cfg.lamp.kq.enabled:
            return self.cfg
        if rule == self.cfg.lamp.kq.rule:
            return self.cfg
        cfg = self._cfg_cache.get(rule)
        if cfg is None:
            pol = self.cfg.lamp
            cfg = self.cfg.replace(
                lamp=pol.replace(kq=pol.kq.replace(rule=rule)))
            self._cfg_cache[rule] = cfg
        return cfg

    def _step_fns(self, seqs: List[Sequence]):
        use_topk = any(s.sampling.top_k > 0 for s in seqs)
        return _jitted_steps(self._serving_cfg(), self.econfig.use_lamp,
                             self.econfig.kernel, use_topk)

    def _spec_fns(self, seqs: List[Sequence]):
        use_topk = any(s.sampling.top_k > 0 for s in seqs)
        return spec_step_fns(self._serving_cfg(), self.econfig.use_lamp,
                             self.econfig.kernel, self.spec_config,
                             use_topk)

    # -- request intake -----------------------------------------------------

    def add_request(self, prompt: List[int],
                    sampling: SamplingParams = SamplingParams(),
                    arrival_time: Optional[float] = None) -> int:
        if not prompt:
            raise ValueError("empty prompt")
        if sampling.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {sampling.max_new_tokens}")
        if len(prompt) + sampling.max_new_tokens > self.max_model_len:
            raise ValueError(
                f"prompt({len(prompt)}) + max_new_tokens"
                f"({sampling.max_new_tokens}) exceeds max_model_len "
                f"{self.max_model_len}")
        if self.econfig.max_queue and \
                len(self.scheduler.waiting) >= self.econfig.max_queue:
            self._c_failed_fam.labels("queue_full").inc()
            if self.obs.tracer.enabled:
                self.obs.tracer.instant("reject", cat="fault",
                                        reason="queue_full")
            raise QueueFullError(
                f"admission queue full ({self.econfig.max_queue} waiting); "
                f"retry later or raise EngineConfig.max_queue")
        req_id = self._next_id
        self._next_id += 1
        seq = Sequence(req_id, prompt, sampling,
                       arrival_time if arrival_time is not None
                       else self._now())
        if sampling.deadline_s > 0:
            self._has_deadlines = True
        self._seqs[req_id] = seq
        self.scheduler.add(seq)
        return req_id

    def has_unfinished(self) -> bool:
        return self.scheduler.has_work()

    # -- the step loop ------------------------------------------------------

    def step(self) -> List[RequestOutput]:
        """Run one engine step; returns requests finished by this step
        (successfully or -- with `RequestOutput.error` set -- failed)."""
        with self.obs.span("step", step=self.total_steps) as sp:
            return self._step(sp)

    def _step(self, sp) -> List[RequestOutput]:
        if self._start is None:
            self._start = self._now()
        t0 = self._now()
        step_id = self.total_steps
        inj = self.faults
        if inj is not None and inj.maybe_stall(step_id):
            # injected stall: the step schedules nothing and reports the
            # configured latency spike, so the policy sees the pressure and
            # the run_to_completion watchdog sees no progress
            sp.set(kind="stall")
            self._last_step_wall = inj.config.stall_s
            if self.policy is not None:
                with self.obs.span("policy"):
                    self._policy_update()
            return self._drain_failures()
        if self._has_deadlines:
            self._expire_deadlines()
        if inj is not None and inj.fires(step_id, "alloc"):
            self.pool.arm_alloc_failure(1)
            inj.record(step_id, "alloc")
        with self.obs.span("schedule"):
            plan = self.scheduler.schedule()
        d_alloc = (self.scheduler.alloc_fault_degrades
                   - self._last_alloc_degrades)
        if d_alloc:
            self._last_alloc_degrades = self.scheduler.alloc_fault_degrades
            self._recover("alloc_defer", n=d_alloc)
        sp.set(kind=plan.kind if plan is not None else "none")
        if plan is None:
            return self._drain_failures()
        # audit rows are *captured* before the sub-step runs (it mutates
        # cursors, tokens and -- via rollback -- block tables) and *executed*
        # after it, against the post-step arena: the audited window rewrites
        # its own KV inside the shadow launch, and the prefix below `starts`
        # is identical before and after the step
        audit_batch = (self._audit_capture(plan)
                       if self.auditor is not None else None)
        if plan.kind == "prefill":
            self._step_prefill(plan.seqs, plan.windows)
            self._c_prefill_steps.inc()
        elif plan.kind == "mixed":
            if self.econfig.mixed_exec == "split":
                self._step_mixed_split(plan)
            else:
                try:
                    if inj is not None and inj.fires(step_id, "step"):
                        inj.record(step_id, "step")
                        raise StepLaunchFault(
                            "injected fused-step launch failure")
                    self._step_mixed(plan)
                except StepLaunchFault:
                    # fused-step anomaly: degrade this step to the split
                    # twin -- same plan, same tokens, two/three launches.
                    # Only the *injected* fault type is caught (it is
                    # raised before any launch, so no bookkeeping or
                    # donated-arena state has moved); real exceptions stay
                    # loud rather than risk re-running a half-applied step
                    self._recover("split_fallback")
                    self._step_mixed_split(plan)
            self._c_mixed_steps.inc()
            roles = plan.roles or []
            if any(r == "prefill" for r in roles):
                self._c_mixed_prefill.inc()
            if any(r != "prefill" for r in roles):
                self._c_mixed_decode.inc()
            if self.econfig.speculative and any(plan.draft_lens):
                self._c_mixed_verify.inc()
        elif self.econfig.speculative and any(plan.draft_lens):
            self._step_spec(plan.seqs, plan.draft_lens)
            self._c_spec_rounds.inc()
        else:
            # no draft budget anywhere (spec off, block pressure shed it,
            # or every sequence is at its token limit): the plain decode
            # step is the same progress at a fraction of the compute
            self._step_decode(plan.seqs)
            self._c_decode_steps.inc()
        if self._quarantine:
            # rows the health guard pulled out of the sub-step: retry each
            # through the recovery ladder (or fail it alone), then prove
            # the pool survived the surgery
            with self.obs.span("recover", rows=len(self._quarantine)):
                self._drain_quarantine()
            self.pool.check_invariants(self._seqs.values())
        self._util_sum += self.pool.utilization
        self._util_n += 1
        if audit_batch is not None:
            # before _collect_finished, so a request audited on its
            # finishing step still folds into its cumulative histogram
            self._run_audit(audit_batch)
        with self.obs.span("emit"):
            done = self._collect_finished(plan.seqs)
        done.extend(self._drain_failures())
        if self.econfig.paranoid:
            self.pool.check_invariants(self._seqs.values())
        self._last_step_wall = self._now() - t0
        if self.policy is not None:
            with self.obs.span("policy"):
                self._policy_update()
        return done

    def _policy_update(self) -> None:
        """Feed this step's telemetry to the controller and apply what it
        actuated: per-layer thresholds (traced operands, free), the
        scheduler's draft budget (host int, free), and -- only under SHED
        -- the LAMP rule tier (a static swap; recompiles once per tier)."""
        # _account_lamp stamps entries with the step count *before* the
        # step counter increments (the inc happens after the sub-step
        # returns), so the entry this step just produced reads
        # total_steps - 1; anything older means this step had no LAMP
        # counts (e.g. use_lamp off) and the controller holds its EMA
        rates = None
        if (self.layer_rate_series
                and self.layer_rate_series[-1][0] == self.total_steps - 1):
            rates = self.layer_rate_series[-1][1]
        drafted = self.spec_drafted
        sig = PolicySignals(
            layer_rates=rates,
            utilization=self.pool.utilization,
            preemptions=self.scheduler.num_preemptions,
            step_latency_s=self._last_step_wall,
            spec_acceptance=(self.spec_accepted / drafted
                            if drafted else 0.0),
            recoveries=self._n_recoveries)
        act = self.policy.update(sig)
        if self.policy.config.frozen:
            return
        self._taus = np.asarray(act.taus, np.float32)
        self._active_rule = act.rule
        if self.econfig.speculative:
            self.scheduler.spec_draft_len = act.draft_len

    # -- shadow audit -------------------------------------------------------

    def _audit_capture(self, plan: StepPlan) -> Optional[Dict[str, Any]]:
        """Select and snapshot this step's audited rows (or None).

        Row selection hashes (step, request, salt) -- replayable across
        runs of the same stream. Every input the shadow launch needs is
        copied *now*: the sub-step advances prefill cursors, appends
        tokens, and rolls back block tables before the audit executes.
        Decode and speculative rows are audited as their width-1 pre-draft
        decode window (same query the serving step's first position ran);
        prefill rows replay their whole chunk window."""
        step_id = self.total_steps
        seqs = plan.seqs
        idx = self.auditor.select(step_id, [s.req_id for s in seqs])
        if not idx:
            return None
        roles = list(plan.roles or [None] * len(seqs))
        rows: List[Any] = []
        for i in idx:
            seq = seqs[i]
            if plan.kind == "prefill" or roles[i] == "prefill":
                w = plan.windows[i]
                cur = seq.prefill_cursor
                toks = list(seq.prefill_tokens()[cur:cur + w])
                start = cur
            else:
                toks = [seq.last_token]
                start = seq.cache_len
            rows.append((seq, start, toks))
        Bb = _bucket(len(rows), 0)
        Wb = _bucket(max(len(t) for _, _, t in rows), 0)
        tokens = np.zeros((Bb, Wb), np.int32)
        starts = np.zeros((Bb,), np.int32)
        lengths = np.ones((Bb,), np.int32)   # pad rows: 1 token, null table
        row_mask = np.zeros((Bb,), np.float32)
        bt = np.zeros((Bb, self.blocks_per_seq), np.int32)
        for j, (seq, start, toks) in enumerate(rows):
            tokens[j, :len(toks)] = toks
            starts[j] = start
            lengths[j] = len(toks)
            row_mask[j] = 1.0
            bt[j, :len(seq.block_ids)] = seq.block_ids
        return {"step": step_id, "seqs": [r[0] for r in rows],
                "tokens": tokens, "starts": starts, "lengths": lengths,
                "row_mask": row_mask, "bt": bt, "bucket": (Bb, Wb)}

    def _run_audit(self, batch: Dict[str, Any]) -> None:
        """Execute one captured audit batch as a single extra jitted launch
        (non-donated arena: the pool buffers -- and therefore every served
        token -- are untouched), then fold the returned error metrics into
        the auditor and, when a live policy controller is attached, run the
        error-model calibration pass."""
        Bb, Wb = batch["bucket"]
        fn = _audit_step_fn(self._serving_cfg(), self.econfig.audit.top_k)
        n0 = _cache_size(fn)
        with self.obs.span("audit", rows=len(batch["seqs"]),
                           bucket=[Bb, Wb]) as sp:
            out = fn(self.params, self.pool.k, self.pool.v,
                     jnp.asarray(batch["tokens"]), jnp.asarray(batch["bt"]),
                     jnp.asarray(batch["starts"]),
                     jnp.asarray(batch["lengths"]),
                     jnp.asarray(batch["row_mask"]),
                     jnp.asarray(self._taus))
            jax.block_until_ready(out)
        self._c_launches["audit"].inc()
        if n0 >= 0 and _cache_size(fn) > n0:
            self.obs.record_compile("audit", (Bb, Wb), sp.elapsed,
                                    self.total_steps)
        metrics = {k: np.asarray(v) for k, v in out.items()}
        self.auditor.account(batch["step"], batch["seqs"], metrics)
        if self.policy is not None and not self.policy.config.frozen:
            self.auditor.maybe_calibrate(self.policy)

    def _batch_arrays(self, seqs: List[Sequence], Bb: int):
        bt = np.zeros((Bb, self.blocks_per_seq), np.int32)
        seeds = np.zeros((Bb,), np.int32)
        counts = np.zeros((Bb,), np.int32)
        temps = np.zeros((Bb,), np.float32)
        topks = np.zeros((Bb,), np.int32)
        for i, seq in enumerate(seqs):
            bt[i, :len(seq.block_ids)] = seq.block_ids
            seeds[i] = seq.sampling.seed
            counts[i] = seq.num_generated
            temps[i] = seq.sampling.temperature
            topks[i] = seq.sampling.top_k
        return bt, seeds, counts, temps, topks

    @staticmethod
    def _put(*arrays) -> List[Any]:
        """Host batch arrays -> device arrays (the `inputs` phase)."""
        return [jnp.asarray(a) for a in arrays]

    def _sync(self, out, *arrays) -> tuple:
        """Wait for a launch's outputs, then copy `arrays` (some of them)
        to the host: the `sync` phase, split into `wait` and `readback`."""
        with self.obs.span("sync"):
            with self.obs.span("wait"):
                jax.block_until_ready(out)
            with self.obs.span("readback"):
                return tuple(np.asarray(a) for a in arrays)

    def _account_lamp(self, seqs: List[Sequence], nsel: np.ndarray,
                      nval: np.ndarray, *, verify: bool = False,
                      verify_cols: Optional[List[int]] = None
                      ) -> None:
        """Fold one step's per-layer (L, B) LAMP counts into the per-layer
        counters, the recompute-rate time series, and each sequence's
        per-layer breakdown. `verify=True` credits the whole batch to the
        verify counters (a pure spec round); `verify_cols` credits only
        those columns (a fused mixed step whose decode rows verified while
        its prefill rows did not)."""
        sel_l = nsel.sum(axis=1)
        val_l = nval.sum(axis=1)
        self._layer_sel += sel_l
        self._layer_val += val_l
        for l in range(len(sel_l)):
            self._c_lamp_sel[l].inc(float(sel_l[l]))
            self._c_lamp_val[l].inc(float(val_l[l]))
        if verify:
            self._c_verify_sel.inc(float(sel_l.sum()))
            self._c_verify_val.inc(float(val_l.sum()))
        elif verify_cols:
            self._c_verify_sel.inc(float(nsel[:, verify_cols].sum()))
            self._c_verify_val.inc(float(nval[:, verify_cols].sum()))
        if val_l.sum() > 0:
            rates = np.divide(sel_l, val_l, out=np.zeros_like(sel_l),
                              where=val_l > 0)
            self.layer_rate_series.append((self.total_steps, rates))
            if self.obs.tracer.enabled:
                self.obs.tracer.counter(
                    "lamp_recompute_rate",
                    **{f"layer{l}": round(float(r), 6)
                       for l, r in enumerate(rates)})
        for i, seq in enumerate(seqs):
            seq.lamp.add_layers(nsel[:, i], nval[:, i])

    # -- fault tolerance ----------------------------------------------------

    def _recover(self, action: str, n: int = 1, **detail) -> None:
        """Account one absorbed recovery action (metric + trace + the host
        tally the policy ladder and stats() read)."""
        c = self._c_recover.get(action)
        if c is None:
            c = self._c_recover[action] = self._c_recover_fam.labels(action)
        c.inc(n)
        self._n_recoveries += n
        if self.obs.tracer.enabled:
            self.obs.tracer.instant(f"recover:{action}", cat="fault",
                                    **detail)

    def _unhealthy(self, h: float) -> bool:
        h = float(h)
        if not np.isfinite(h):
            return True
        cap = self.econfig.health_max_abs
        return cap > 0 and h > cap

    def _inject_nan(self, seqs: List[Sequence], health: np.ndarray,
                    spans: List[tuple]) -> np.ndarray:
        """Fault site "nan": poison one deterministic victim row -- its
        health value goes NaN and the arena KV positions it wrote this step
        (`spans[row]` = (start, width), exactly what its recovery retry
        rewrites) are overwritten with NaN. With the guard off, the
        corruption propagates like a real kernel fault would."""
        inj = self.faults
        step_id = self.total_steps
        if inj is None or not inj.fires(step_id, "nan"):
            return health
        row = inj.pick_row(step_id, "nan", [s.req_id for s in seqs])
        if row is None:
            return health
        seq = seqs[row]
        start, width = spans[row]
        bs = self.pool.block_size
        pos = [p for p in range(start, start + width)
               if p // bs < len(seq.block_ids)]
        if pos:
            blocks = jnp.asarray([seq.block_ids[p // bs] for p in pos])
            offs = jnp.asarray([p % bs for p in pos])
            self.pool.k = self.pool.k.at[:, blocks, :, offs].set(jnp.nan)
            self.pool.v = self.pool.v.at[:, blocks, :, offs].set(jnp.nan)
        health = np.array(health, np.float64)
        health[row] = np.nan
        inj.record(step_id, "nan", req=seq.req_id, start=start, width=width)
        return health

    def _inject_draft(self, dseqs: List[Sequence], kdv, d_toks, vocab: int):
        """Fault site "draft": corrupt one drafting row's proposals (each
        token bumped mod vocab). No dedicated recovery: the verify pass IS
        the recovery -- corrupted proposals disagree with the verifier and
        are rejected (greedy streams stay token-identical; a sampled
        stream's accept coin may keep a corrupt but plausible token, which
        is exactly the corruption-tolerance boundary this site probes)."""
        inj = self.faults
        step_id = self.total_steps
        if inj is None or not inj.fires(step_id, "draft"):
            return d_toks
        rows = [j for j in range(len(dseqs)) if int(kdv[j]) > 0]
        if not rows:
            return d_toks
        pick = inj.pick_row(step_id, "draft",
                            [dseqs[j].req_id for j in rows])
        j = rows[pick]
        d_toks = d_toks.at[j].set((d_toks[j] + 1) % jnp.int32(vocab))
        inj.record(step_id, "draft", req=dseqs[j].req_id)
        return d_toks

    def _retry_ladder(self) -> List[tuple]:
        """(action, cfg, use_lamp, kernel) escalation rungs for retrying a
        quarantined row, cheapest first: (0) plain re-run of the step's own
        configuration -- transient faults (and every injected one) recover
        here bit-identically, because sampling is keyed on
        (seed, num_generated), not on wall time or batch shape; (1) the
        strict LAMP rule -- maximal selective recompute; (2) the gather
        reference kernel -- rules out the fused Pallas path; (3) FP32
        reference -- no LAMP at all. Bounded by EngineConfig.max_retries."""
        e = self.econfig
        ladder = [("retry", self._serving_cfg(), e.use_lamp, e.kernel)]
        if e.use_lamp and self.cfg.lamp.kq.enabled \
                and self.cfg.lamp.kq.rule != "strict":
            pol = self.cfg.lamp
            strict = self.cfg.replace(
                lamp=pol.replace(kq=pol.kq.replace(rule="strict")))
            ladder.append(("strict", strict, True, e.kernel))
        if e.kernel != "gather":
            _, pcfg, plamp, _ = ladder[-1]
            ladder.append(("gather", pcfg, plamp, "gather"))
        ladder.append(("fp32", self.cfg, False, "gather"))
        return ladder[:max(1, e.max_retries)]

    def _retry_row(self, seq: Sequence, kind: str, window: int,
                   rcfg, rlamp: bool, rkernel: str) -> bool:
        """Re-run one quarantined row's window as a single-row prefill
        launch (decode is a width-1 window) under a ladder rung's
        configuration. Healthy result: apply the normal bookkeeping the
        quarantine skipped and return True; still unhealthy: leave the
        sequence untouched for the next rung."""
        prefill_fn, _ = _jitted_steps(rcfg, rlamp, rkernel,
                                      seq.sampling.top_k > 0)
        Wb = _bucket(window, 0)
        tokens = np.zeros((1, Wb), np.int32)
        if kind == "prefill":
            start = seq.prefill_cursor
            tokens[0, :window] = \
                seq.prefill_tokens()[start:start + window]
        else:
            start = seq.cache_len
            tokens[0, 0] = seq.last_token
        bt, seeds, counts, temps, topks = self._batch_arrays([seq], 1)
        with self.obs.span("retry", req=seq.req_id, kind=kind,
                           window=window):
            out = prefill_fn(
                self.params, self.pool.k, self.pool.v, jnp.asarray(tokens),
                jnp.asarray(bt), jnp.asarray(np.asarray([start], np.int32)),
                jnp.asarray(np.asarray([window], np.int32)),
                jnp.asarray(self._taus), jnp.asarray(seeds),
                jnp.asarray(counts), jnp.asarray(temps),
                jnp.asarray(topks))
            jax.block_until_ready(out)
            nxt, health, self.pool.k, self.pool.v, nsel, nval = out
        self._c_launches["prefill"].inc()
        if self._unhealthy(np.asarray(health)[0]):
            return False
        now = self._now()
        self._account_lamp([seq], np.asarray(nsel), np.asarray(nval))
        if kind == "prefill":
            seq.prefill_cursor += window
            seq.cache_len = seq.prefill_cursor
            self._c_prefill_tokens.inc(window)
            if self.econfig.prefix_cache:
                self.pool.register_prefix(seq.prefill_tokens(),
                                          seq.block_ids, seq.cache_len,
                                          hashes=seq.prefix_hashes)
            if seq.prefill_remaining == 0:
                seq.status = SequenceStatus.DECODE
                seq.on_token(int(np.asarray(nxt)[0]), now)
                self._c_generated.inc()
            else:
                self._c_prefill_chunks.inc()
        else:
            seq.cache_len += 1
            seq.on_token(int(np.asarray(nxt)[0]), now)
            self._c_generated.inc()
        return True

    def _drain_quarantine(self) -> None:
        """Walk every row the health guard quarantined this step through
        the recovery ladder; a row no rung can produce healthy logits for
        fails alone (diagnostic RequestOutput.error), never the engine."""
        q, self._quarantine = self._quarantine, []
        ladder = self._retry_ladder()
        for seq, kind, window in q:
            recovered = False
            for action, rcfg, rlamp, rkernel in ladder:
                if self._retry_row(seq, kind, window, rcfg, rlamp, rkernel):
                    self._recover(action, req=seq.req_id)
                    recovered = True
                    break
            if not recovered:
                self._fail_seq(
                    seq, "unhealthy",
                    f"non-finite or out-of-range logits persisted through "
                    f"{len(ladder)} recovery rung(s) "
                    f"[{'/'.join(a for a, *_ in ladder)}] at {kind} "
                    f"window={window} cache_len={seq.cache_len}")

    def _fail_seq(self, seq: Sequence, reason: str, error: str) -> None:
        """Terminal per-request failure: cancel it wherever it sits, free
        its blocks, and emit a diagnostic RequestOutput. The engine --
        and every other request -- keeps serving."""
        now = self._now()
        seq.finish(reason, now)
        self.scheduler.cancel(seq)
        self._c_failed_fam.labels(reason).inc()
        if self.obs.tracer.enabled:
            self.obs.tracer.instant("request_failed", cat="fault",
                                    req=seq.req_id, reason=reason)
        out = RequestOutput(
            req_id=seq.req_id, prompt=seq.prompt, tokens=seq.generated,
            finish_reason=reason, latency=seq.latency() or 0.0,
            ttft=seq.ttft() or 0.0,
            num_preemptions=seq.num_preemptions,
            lamp_selected=seq.lamp.selected, lamp_valid=seq.lamp.valid,
            num_cached_tokens=seq.num_cached_tokens,
            num_resume_cached_tokens=seq.num_resume_cached_tokens,
            spec_drafted=seq.spec_drafted,
            spec_accepted=seq.spec_accepted,
            audit_samples=seq.audit_samples,
            audit_err_sum=seq.audit_err_sum,
            audit_flips=seq.audit_flips,
            error=error)
        self._finished.append(out)
        self._n_failed += 1
        self._seqs.pop(seq.req_id, None)
        self._step_failures.append(out)

    def _drain_failures(self) -> List[RequestOutput]:
        if not self._step_failures:
            return []
        out, self._step_failures = self._step_failures, []
        return out

    def _expire_deadlines(self) -> None:
        """Cancel requests whose wall-clock TTL elapsed (blocks released,
        finish_reason "timeout"); runs before scheduling so an expired
        request never costs another step of compute."""
        now = self._now()
        for seq in [s for s in self._seqs.values()
                    if s.sampling.deadline_s > 0 and not s.is_finished
                    and now - s.arrival_time > s.sampling.deadline_s]:
            self._fail_seq(
                seq, "timeout",
                f"deadline_s={seq.sampling.deadline_s} exceeded after "
                f"{now - seq.arrival_time:.3f}s "
                f"({seq.num_generated} tokens generated)")

    def _stall_recover(self) -> bool:
        """The watchdog's recovery attempt after `stall_patience` steps
        without progress. Cheapest plausible fix first: clear an injected
        stall; else evict every running row (recompute-style, so resumed
        token streams are identical); else fail the oldest waiting request.
        Returns False when nothing changed -- the caller then raises."""
        if self.faults is not None and self.faults.stalled:
            self.faults.clear_stall()
            self._recover("stall_clear")
            return True
        acted = False
        evicted = 0
        while self.scheduler._preempt_youngest():
            evicted += 1
        if evicted:
            self._recover("stall_evict", n=evicted)
            acted = True
        elif self.scheduler.waiting:
            self._fail_seq(
                self.scheduler.waiting[0], "stalled",
                f"no step progress for {self.econfig.stall_patience} "
                f"steps with the request still queued")
            acted = True
        self.pool.check_invariants(self._seqs.values())
        return acted

    def _step_prefill(self, seqs: List[Sequence],
                      windows: List[int]) -> None:
        """Run one prefill window per sequence: the whole remaining prompt,
        or a `max_prefill_tokens`-bounded chunk of it. A sequence whose
        window completes its prompt samples its first token and moves to
        DECODE; otherwise it stays PREFILL with its cursor advanced."""
        Wb = _bucket(max(windows), 0)
        Bb = _bucket(len(seqs), self.econfig.max_prefill_batch)
        with self.obs.span("build"):
            tokens = np.zeros((Bb, Wb), np.int32)
            starts = np.zeros((Bb,), np.int32)
            lengths = np.ones((Bb,), np.int32)  # pad rows: 1 token, null block
            for i, (seq, w) in enumerate(zip(seqs, windows)):
                cur = seq.prefill_cursor
                tokens[i, :w] = seq.prefill_tokens()[cur:cur + w]
                starts[i] = cur
                lengths[i] = w
            bt, seeds, counts, temps, topks = self._batch_arrays(seqs, Bb)
        prefill_fn, _ = self._step_fns(seqs)
        n0 = _cache_size(prefill_fn)
        with self.obs.span("prefill", rows=len(seqs), bucket=[Bb, Wb],
                           tokens=int(sum(windows))) as sp:
            with self.obs.span("inputs"):
                args = self._put(tokens, bt, starts, lengths, self._taus,
                                 seeds, counts, temps, topks)
            with self.obs.span("launch"):
                out = prefill_fn(self.params, self.pool.k, self.pool.v,
                                 *args)
        self._c_launches["prefill"].inc()
        nxt, health, self.pool.k, self.pool.v, nsel, nval = out
        nxt, health, nsel, nval = self._sync(out, nxt, health, nsel, nval)
        if n0 >= 0 and _cache_size(prefill_fn) > n0:
            self.obs.record_compile("prefill", (Bb, Wb), sp.elapsed,
                                    self.total_steps)
        with self.obs.span("account"):
            health = self._inject_nan(
                seqs, health,
                [(s.prefill_cursor, w) for s, w in zip(seqs, windows)])
            guard = self.econfig.health_guard
            now = self._now()
            self._account_lamp(seqs, nsel, nval)
            for i, (seq, w) in enumerate(zip(seqs, windows)):
                if guard and self._unhealthy(health[i]):
                    # skip ALL bookkeeping: cursor stays, the retry rewrites
                    # the same window over the same (possibly poisoned) blocks
                    self._quarantine.append((seq, "prefill", w))
                    continue
                seq.prefill_cursor += w
                seq.cache_len = seq.prefill_cursor
                self._c_prefill_tokens.inc(w)
                if self.econfig.prefix_cache:
                    # the window's full blocks now hold real KV: make them
                    # matchable by later arrivals (and by our own resume); the
                    # admission-time chain hashes avoid rehashing per chunk
                    self.pool.register_prefix(seq.prefill_tokens(),
                                              seq.block_ids, seq.cache_len,
                                              hashes=seq.prefix_hashes)
                if seq.prefill_remaining == 0:
                    seq.status = SequenceStatus.DECODE
                    seq.on_token(int(nxt[i]), now)
                    self._c_generated.inc()
                else:
                    self._c_prefill_chunks.inc()

    def _step_decode(self, seqs: List[Sequence]) -> None:
        Rb = _bucket(len(seqs), self.econfig.max_decode_batch)
        with self.obs.span("build"):
            tokens = np.zeros((Rb, 1), np.int32)
            lengths = np.zeros((Rb,), np.int32)  # pad rows: the null block
            for i, seq in enumerate(seqs):
                tokens[i, 0] = seq.last_token
                lengths[i] = seq.cache_len
            bt, seeds, counts, temps, topks = self._batch_arrays(seqs, Rb)
        _, decode_fn = self._step_fns(seqs)
        n0 = _cache_size(decode_fn)
        with self.obs.span("decode", rows=len(seqs), bucket=[Rb]) as sp:
            with self.obs.span("inputs"):
                args = self._put(bt, lengths, tokens, self._taus, seeds,
                                 counts, temps, topks)
            with self.obs.span("launch"):
                out = decode_fn(self.params, self.pool.k, self.pool.v, *args)
        self._c_launches["decode"].inc()
        nxt, health, self.pool.k, self.pool.v, nsel, nval = out
        nxt, health, nsel, nval = self._sync(out, nxt, health, nsel, nval)
        if n0 >= 0 and _cache_size(decode_fn) > n0:
            self.obs.record_compile("decode", (Rb,), sp.elapsed,
                                    self.total_steps)
        with self.obs.span("account"):
            health = self._inject_nan(seqs, health,
                                      [(s.cache_len, 1) for s in seqs])
            guard = self.econfig.health_guard
            now = self._now()
            self._account_lamp(seqs, nsel, nval)
            for i, seq in enumerate(seqs):
                if guard and self._unhealthy(health[i]):
                    self._quarantine.append((seq, "decode", 1))
                    continue
                seq.cache_len += 1
                seq.on_token(int(nxt[i]), now)
                self._c_generated.inc()

    def _step_spec(self, seqs: List[Sequence],
                   draft_lens: List[int]) -> None:
        """One speculative round over the decode batch: draft up to
        `draft_lens[i]` tokens per sequence with the low-precision
        self-draft, verify every drafted position (plus the bonus slot) in
        one multi-token LAMP forward, emit the accepted prefix + one
        verifier token, and roll back the blocks that held rejected draft
        KV. A sequence with draft budget 0 runs a verify-only round, which
        is exactly one plain decode step's progress."""
        Rb = _bucket(len(seqs), self.econfig.max_decode_batch)
        with self.obs.span("build"):
            tok0 = np.zeros((Rb,), np.int32)
            lengths = np.zeros((Rb,), np.int32)  # pad rows: the null block
            kd = np.zeros((Rb,), np.int32)
            for i, seq in enumerate(seqs):
                tok0[i] = seq.last_token
                lengths[i] = seq.cache_len
                kd[i] = draft_lens[i]
            bt, seeds, counts, temps, topks = self._batch_arrays(seqs, Rb)
        with self.obs.span("inputs"):
            bt, lengths, tok0, kd, taus, seeds, counts, temps, topks = \
                self._put(bt, lengths, tok0, kd, self._taus, seeds, counts,
                          temps, topks)
        draft_fn, verify_fn = self._spec_fns(seqs)
        n0d, n0v = _cache_size(draft_fn), _cache_size(verify_fn)
        with self.obs.span("draft", rows=len(seqs), bucket=[Rb]) as spd:
            d_toks, d_logits, self.pool.k, self.pool.v = draft_fn(
                self.params, self.pool.k, self.pool.v, bt, lengths, tok0,
                kd, taus, seeds, counts, temps, topks)
        self._c_launches["draft"].inc()
        d_toks = self._inject_draft(seqs, draft_lens, d_toks,
                                    d_logits.shape[-1])
        with self.obs.span("verify", rows=len(seqs), bucket=[Rb]) as spv:
            out = verify_fn(
                self.params, self.pool.k, self.pool.v, tok0, d_toks,
                d_logits, bt, lengths, kd, taus, seeds, counts, temps,
                topks)
        self._c_launches["verify"].inc()
        emit, n_acc, health, self.pool.k, self.pool.v, nsel, nval = out
        emit, n_acc, health, nsel, nval = self._sync(out, emit, n_acc, health,
                                                     nsel, nval)
        if n0d >= 0 and _cache_size(draft_fn) > n0d:
            self.obs.record_compile("draft", (Rb,), spd.elapsed,
                                    self.total_steps)
        if n0v >= 0 and _cache_size(verify_fn) > n0v:
            self.obs.record_compile("verify", (Rb,), spv.elapsed,
                                    self.total_steps)
        with self.obs.span("account"):
            # poison width 1 even for drafted rows: the quarantine retries a
            # verify row as a plain decode, which rewrites only position
            # cache_len -- poison past it would outlive the recovery (the
            # rolled-back draft positions can share the kept tail block, and
            # the gather kernel streams the whole block span)
            health = self._inject_nan(seqs, health,
                                      [(s.cache_len, 1) for s in seqs])
            guard = self.econfig.health_guard
            now = self._now()
            self._account_lamp(seqs, nsel, nval, verify=True)
            for i, seq in enumerate(seqs):
                if guard and self._unhealthy(health[i]):
                    # discard the whole round for this row (no drafted/accepted
                    # accounting), free the draft-span blocks -- keeping one
                    # slot past cache_len so the width-1 retry's write position
                    # stays covered -- and retry as a plain decode step
                    seq.block_ids = self.pool.rollback(seq.block_ids,
                                                       seq.cache_len + 1)
                    self._quarantine.append((seq, "decode", 1))
                    continue
                a = int(n_acc[i])
                seq.spec_drafted += int(draft_lens[i])
                self._c_spec_drafted.inc(int(draft_lens[i]))
                # emit accepted drafts + the verifier's token, stopping at the
                # request's own limits (surplus accepted tokens are dropped and
                # their cache rolls back with the rejected ones)
                appended = 0
                for t in emit[i, :a + 1]:
                    seq.on_token(int(t), now)
                    appended += 1
                    self._c_generated.inc()
                    if seq.should_stop():
                        break
                # acceptance accounting covers only drafts actually *kept*: a
                # stop token (or token limit) inside the accepted prefix drops
                # the surplus, and counting those would overstate the
                # acceptance rate the policy/scheduler steer by. An early stop
                # at position j < a keeps j+1 tokens, all of them drafts; a
                # full emit keeps a drafts + the verifier's token.
                kept_accepted = min(a, appended)
                seq.spec_accepted += kept_accepted
                self._c_spec_accepted.inc(kept_accepted)
                seq.cache_len += appended
                self._c_spec_emitted.inc(appended)
                seq.block_ids = self.pool.rollback(seq.block_ids,
                                                   seq.cache_len)

    def _step_mixed(self, plan: StepPlan) -> None:
        """Run one mixed plan as a single fused launch: prefill windows,
        plain decode rows (width-1 windows at start = cache_len) and
        speculative verify rows (width kd+1 windows) share one bucketed
        (rows, max_window) batch through `transformer.paged_mixed_step`.
        Per-row (start, qlen) metadata is scalar-prefetched into the paged
        attention grid, so every role mix reuses the same compiled bucket.

        Plans without draft rows reuse the prefill step function verbatim
        (a mixed no-draft plan IS a prefill-window batch): one launch.
        Plans with drafts run the sequential draft scan over the decode
        rows' compact bucket first, then one mixed launch that verifies,
        samples, and accepts for every role at once: two launches, versus
        the split path's three (prefill + draft + verify)."""
        seqs, windows = plan.seqs, list(plan.windows)
        roles = list(plan.roles or ["decode"] * len(seqs))
        draft_lens = list(plan.draft_lens)
        spec_round = self.econfig.speculative and any(draft_lens)
        dec_rows = [i for i, r in enumerate(roles) if r != "prefill"]
        cap = (self.econfig.max_prefill_batch
               + self.econfig.max_decode_batch)
        Bb = _bucket(len(seqs), cap)
        Wb = _bucket(max(windows), 0)
        if spec_round:
            # the accept rule reads k+1 window positions per verify row
            Wb = max(Wb, self.spec_config.verify_width)
        with self.obs.span("build"):
            tokens = np.zeros((Bb, Wb), np.int32)
            starts = np.zeros((Bb,), np.int32)
            qlens = np.ones((Bb,), np.int32)   # pad rows: 1 token, null block
            for i, seq in enumerate(seqs):
                w = windows[i]
                if roles[i] == "prefill":
                    cur = seq.prefill_cursor
                    tokens[i, :w] = seq.prefill_tokens()[cur:cur + w]
                    starts[i] = cur
                else:
                    # decode/verify: the window is [last_token, drafts...] at
                    # the decode tail (drafts scatter in-jit after the scan)
                    tokens[i, 0] = seq.last_token
                    starts[i] = seq.cache_len
                qlens[i] = w
            bt, seeds, counts, temps, topks = self._batch_arrays(seqs, Bb)
        mixed_args = dict(rows=len(seqs), bucket=[Bb, Wb],
                          tokens=int(sum(windows)))
        if self.obs.recording:
            # the paged kernels' KV grid: steps on live blocks / all steps
            mixed_args["kv_live"], mixed_args["kv_walked"] = grid_blocks(
                starts, qlens, len(seqs), Bb, self.blocks_per_seq,
                self.econfig.block_size, window=self.cfg.window)
            mixed_args["kv_heads_per_step"] = kv_heads_per_step(
                self.cfg.n_kv_heads, self.cfg.n_heads // self.cfg.n_kv_heads,
                Wb, self.econfig.block_size, self.cfg.hd)
        emit = n_acc = None
        if not spec_round:
            mixed_fn, _ = self._step_fns(seqs)
            n0 = _cache_size(mixed_fn)
            with self.obs.span("mixed", **mixed_args) as sp:
                with self.obs.span("inputs"):
                    args = self._put(tokens, bt, starts, qlens, self._taus,
                                     seeds, counts, temps, topks)
                with self.obs.span("launch"):
                    out = mixed_fn(self.params, self.pool.k, self.pool.v,
                                   *args)
            self._c_launches["mixed"].inc()
            nxt, health, self.pool.k, self.pool.v, nsel, nval = out
            nxt, health, nsel, nval = self._sync(out, nxt, health, nsel,
                                                 nval)
        else:
            dseqs = [seqs[i] for i in dec_rows]
            Rb = _bucket(len(dseqs), self.econfig.max_decode_batch)
            with self.obs.span("build"):
                tok0 = np.zeros((Rb,), np.int32)
                dlens = np.zeros((Rb,), np.int32)
                kdv = np.zeros((Rb,), np.int32)
                for j, i in enumerate(dec_rows):
                    tok0[j] = seqs[i].last_token
                    dlens[j] = seqs[i].cache_len
                    kdv[j] = draft_lens[i]
                dbt, dseeds, dcounts, dtemps, dtopks = self._batch_arrays(
                    dseqs, Rb)
                # draft-row -> mixed-row scatter map; pad draft rows point
                # out of range, which scatter mode="drop" discards
                dec_pos = np.full((Rb,), Bb, np.int32)
                dec_pos[:len(dec_rows)] = dec_rows
                kd_full = np.zeros((Bb,), np.int32)
                for i in dec_rows:
                    kd_full[i] = draft_lens[i]
            draft_fn, _ = self._spec_fns(dseqs)
            n0d = _cache_size(draft_fn)
            with self.obs.span("draft", rows=len(dseqs),
                               bucket=[Rb]) as spd:
                with self.obs.span("inputs"):
                    dargs = self._put(dbt, dlens, tok0, kdv)
                    taus = jnp.asarray(self._taus)
                    sargs = self._put(dseeds, dcounts, dtemps, dtopks)
                with self.obs.span("launch"):
                    d_toks, d_logits, self.pool.k, self.pool.v = draft_fn(
                        self.params, self.pool.k, self.pool.v, *dargs, taus,
                        *sargs)
            self._c_launches["draft"].inc()
            if n0d >= 0 and _cache_size(draft_fn) > n0d:
                self.obs.record_compile("draft", (Rb,), spd.elapsed,
                                        self.total_steps)
            d_toks = self._inject_draft(dseqs, kdv, d_toks,
                                        d_logits.shape[-1])
            mixed_fn = _mixed_spec_step(
                self._serving_cfg(), self.econfig.use_lamp,
                self.econfig.kernel, self.spec_config,
                any(s.sampling.top_k > 0 for s in seqs))
            n0 = _cache_size(mixed_fn)
            with self.obs.span("mixed", **mixed_args) as sp:
                with self.obs.span("inputs"):
                    args = self._put(tokens, bt, starts, qlens, kd_full,
                                     dec_pos)
                    sargs = self._put(seeds, counts, temps, topks)
                with self.obs.span("launch"):
                    out = mixed_fn(self.params, self.pool.k, self.pool.v,
                                   *args, d_toks, d_logits, taus, *sargs)
            self._c_launches["mixed"].inc()
            (nxt, emit, n_acc, health, self.pool.k, self.pool.v, nsel,
             nval) = out
            nxt, emit, n_acc, health, nsel, nval = self._sync(
                out, nxt, emit, n_acc, health, nsel, nval)
        if n0 >= 0 and _cache_size(mixed_fn) > n0:
            self.obs.record_compile("mixed", (Bb, Wb), sp.elapsed,
                                    self.total_steps)
        with self.obs.span("account"):
            # decode/verify rows poison width 1 (what the decode retry
            # rewrites -- see _step_spec); prefill rows poison their window
            health = self._inject_nan(
                seqs, health,
                [(s.prefill_cursor, windows[i]) if roles[i] == "prefill"
                 else (s.cache_len, 1) for i, s in enumerate(seqs)])
            guard = self.econfig.health_guard
            now = self._now()
            self._account_lamp(seqs, nsel, nval,
                               verify_cols=dec_rows if spec_round else None)
            for i, seq in enumerate(seqs):
                w = windows[i]
                if guard and self._unhealthy(health[i]):
                    if roles[i] == "prefill":
                        self._quarantine.append((seq, "prefill", w))
                    else:
                        # discard this row's speculative round (if any), keep
                        # one slot past cache_len for the width-1 retry's write
                        # position, and retry it as a plain decode step
                        seq.block_ids = self.pool.rollback(seq.block_ids,
                                                           seq.cache_len + 1)
                        self._quarantine.append((seq, "decode", 1))
                    continue
                if roles[i] == "prefill":
                    seq.prefill_cursor += w
                    seq.cache_len = seq.prefill_cursor
                    self._c_prefill_tokens.inc(w)
                    if self.econfig.prefix_cache:
                        self.pool.register_prefix(seq.prefill_tokens(),
                                                  seq.block_ids, seq.cache_len,
                                                  hashes=seq.prefix_hashes)
                    if seq.prefill_remaining == 0:
                        seq.status = SequenceStatus.DECODE
                        seq.on_token(int(nxt[i]), now)
                        self._c_generated.inc()
                    else:
                        self._c_prefill_chunks.inc()
                elif spec_round:
                    # identical bookkeeping to _step_spec (see the acceptance
                    # accounting rationale there)
                    a = int(n_acc[i])
                    seq.spec_drafted += int(draft_lens[i])
                    self._c_spec_drafted.inc(int(draft_lens[i]))
                    appended = 0
                    for t in emit[i, :a + 1]:
                        seq.on_token(int(t), now)
                        appended += 1
                        self._c_generated.inc()
                        if seq.should_stop():
                            break
                    kept_accepted = min(a, appended)
                    seq.spec_accepted += kept_accepted
                    self._c_spec_accepted.inc(kept_accepted)
                    seq.cache_len += appended
                    self._c_spec_emitted.inc(appended)
                    seq.block_ids = self.pool.rollback(seq.block_ids,
                                                       seq.cache_len)
                else:
                    seq.cache_len += 1
                    seq.on_token(int(nxt[i]), now)
                    self._c_generated.inc()

    def _step_mixed_split(self, plan: StepPlan) -> None:
        """Execute a mixed plan through the legacy phase-segregated
        sub-steps -- `_step_mixed`'s differential-testing twin: same rows,
        same windows, same draft budgets, the same per-request tokens and
        telemetry, but two or three launches instead of one or two."""
        roles = list(plan.roles or ["decode"] * len(plan.seqs))
        pre = [i for i, r in enumerate(roles) if r == "prefill"]
        dec = [i for i, r in enumerate(roles) if r != "prefill"]
        if pre:
            self._step_prefill([plan.seqs[i] for i in pre],
                               [plan.windows[i] for i in pre])
        if dec:
            dseqs = [plan.seqs[i] for i in dec]
            dkd = [plan.draft_lens[i] for i in dec]
            if self.econfig.speculative and any(dkd):
                self._step_spec(dseqs, dkd)
            else:
                self._step_decode(dseqs)

    def _collect_finished(self, seqs: List[Sequence]) -> List[RequestOutput]:
        done = []
        now = self._now()
        for seq in seqs:
            reason = seq.should_stop()
            if reason is None:
                continue
            seq.finish(reason, now)
            self.scheduler.finish(seq)
            lamp_l_sel = lamp_l_val = None
            if seq.lamp.by_layer_selected is not None:
                lamp_l_sel = [float(s) for s in seq.lamp.by_layer_selected]
                lamp_l_val = [float(v) for v in seq.lamp.by_layer_valid]
            out = RequestOutput(
                req_id=seq.req_id, prompt=seq.prompt, tokens=seq.generated,
                finish_reason=reason, latency=seq.latency(),
                ttft=seq.ttft(), num_preemptions=seq.num_preemptions,
                lamp_selected=seq.lamp.selected, lamp_valid=seq.lamp.valid,
                num_cached_tokens=seq.num_cached_tokens,
                num_resume_cached_tokens=seq.num_resume_cached_tokens,
                spec_drafted=seq.spec_drafted,
                spec_accepted=seq.spec_accepted,
                lamp_layer_selected=lamp_l_sel,
                lamp_layer_valid=lamp_l_val,
                audit_samples=seq.audit_samples,
                audit_err_sum=seq.audit_err_sum,
                audit_flips=seq.audit_flips)
            if self.auditor is not None:
                self.auditor.finish_request(seq)
            self._finished.append(out)
            self._c_finished.inc()
            self._c_cached_prefix.inc(seq.num_cached_tokens)
            self._c_cached_resume.inc(seq.num_resume_cached_tokens)
            self._h_latency.observe(out.latency)
            self._h_ttft.observe(out.ttft)
            # prune the live-sequence map: its cached-token tallies now
            # live in the counters above, so stats() stays O(live) and the
            # engine's memory is bounded no matter how many requests it
            # has ever served
            self._seqs.pop(seq.req_id, None)
            done.append(out)
        return done

    # -- maintenance / metrics ---------------------------------------------

    def defrag(self) -> None:
        with self.obs.span("defrag"):
            self.pool.defrag(sorted(self.scheduler.running,
                                    key=lambda s: s.arrival_time))

    @property
    def num_preemptions(self) -> int:
        return self.scheduler.num_preemptions

    def _sync_gauges(self) -> None:
        """Publish point-in-time state (pool, scheduler) into the registry
        so snapshots/exposition carry it; counters update in the hot path."""
        reg = self.obs.registry
        g = reg.gauge("engine_live_requests",
                      help="requests queued or running")
        g.set(len(self.scheduler.waiting) + len(self.scheduler.running))
        reg.gauge("kv_blocks_used", help="arena blocks in use").set(
            self.pool.num_used)
        reg.gauge("kv_util", help="arena utilization").set(
            self.pool.utilization)
        reg.gauge("kv_util_peak").set(self.pool.peak_used
                                      / self.pool.num_total)
        reg.gauge("engine_preemptions", help="recompute-style evictions"
                  ).set(self.scheduler.num_preemptions)
        reg.gauge("kv_blocks_allocated_total").set(self.pool.total_allocs)
        reg.gauge("kv_blocks_prefix_hits_total").set(self.pool.hit_blocks)
        reg.gauge("kv_cow_copies_total").set(self.pool.cow_copies)
        reg.gauge("kv_cache_evictions_total").set(self.pool.evictions)

    def metrics_snapshot(self) -> Dict[str, Any]:
        """JSON-serializable dump of the whole metrics registry."""
        self._sync_gauges()
        return self.obs.registry.snapshot()

    def lamp_layer_rates(self) -> List[float]:
        """Cumulative per-layer recompute rate (len n_layers)."""
        return [float(s / v) if v else 0.0
                for s, v in zip(self._layer_sel, self._layer_val)]

    def stats(self, exact: bool = False) -> Dict[str, Any]:
        """Cumulative serving stats (a view over the metrics registry).

        Latency/TTFT percentiles come from the streaming histograms --
        O(buckets) per call, safe to poll under a live stream. Pass
        `exact=True` for end-of-run reporting: percentiles are then
        computed exactly over the retained finished requests (the last
        `finished_retention`; O(n log n))."""
        elapsed = (self._now() - self._start) if self._start else 0.0
        if exact:
            lat = [o.latency for o in self._finished]
            ttft = [o.ttft for o in self._finished]
            lat_p50 = float(np.percentile(lat, 50)) if lat else 0.0
            lat_p99 = float(np.percentile(lat, 99)) if lat else 0.0
            ttft_p50 = float(np.percentile(ttft, 50)) if ttft else 0.0
        else:
            lat_p50 = self._h_latency.quantile(0.5)
            lat_p99 = self._h_latency.quantile(0.99)
            ttft_p50 = self._h_ttft.quantile(0.5)
        # finished sequences' tallies live in the counters (_seqs holds
        # only live requests); resume self-hits are reported separately
        # and excluded from the cross-request hit rate
        cached = int(self._c_cached_prefix.value) + sum(
            s.num_cached_tokens for s in self._seqs.values())
        resume_cached = int(self._c_cached_resume.value) + sum(
            s.num_resume_cached_tokens for s in self._seqs.values())
        generated = self.generated_tokens
        n_done = int(self._c_finished.value)
        phase = {name: {"mean_us": h.mean * 1e6, "count": h.count}
                 for name, h in self.obs._phase_children.items() if h.count}
        return {
            "num_finished": n_done,
            "elapsed_s": elapsed,
            "tokens_per_s": generated / elapsed if elapsed else 0.0,
            "requests_per_s": n_done / elapsed if elapsed else 0.0,
            "latency_p50_s": lat_p50,
            "latency_p99_s": lat_p99,
            "ttft_p50_s": ttft_p50,
            "steps": self.total_steps,
            "prefill_steps": self.prefill_steps,
            "decode_steps": self.decode_steps,
            # fused-step telemetry: mixed steps count once in "steps" but
            # feed the prefill/decode views above by row role; launches is
            # the fused step's headline (jitted calls, fewer when fused)
            "mixed_steps": self.mixed_steps,
            "launches": self.launches,
            "prefill_chunks": self.prefill_chunks,
            "preemptions": self.num_preemptions,
            # prefix-cache telemetry
            "blocks_allocated": self.pool.total_allocs,
            "blocks_saved": self.pool.hit_blocks,
            "cached_tokens": cached,
            "resume_cached_tokens": resume_cached,
            "prefill_tokens_run": self.prefill_tokens_run,
            "cache_hit_rate": cached / max(1, self.prefill_tokens_run
                                           + cached),
            "cow_copies": self.pool.cow_copies,
            "cache_evictions": self.pool.evictions,
            "kv_util_mean": (self._util_sum / self._util_n
                             if self._util_n else 0.0),
            "kv_util_peak": self.pool.peak_used / self.pool.num_total,
            "lamp_recompute_rate": (self.agg_lamp_selected /
                                    self.agg_lamp_valid
                                    if self.agg_lamp_valid else 0.0),
            # per-layer LAMP telemetry (cumulative; the bounded time series
            # lives in engine.layer_rate_series / the trace counter track)
            "lamp_layer_rates": self.lamp_layer_rates(),
            # jit-cache observability (see engine.compile_events for the log)
            "compiles": len(self.compile_events),
            "compile_time_s": sum(e["wall_s"] for e in self.compile_events),
            # per-phase wall time (mean us + sample count per phase)
            "phase": phase,
            # hung-stream visibility: requests still queued or running
            "live_requests": (len(self.scheduler.waiting)
                              + len(self.scheduler.running)),
            # speculative decoding
            "spec_rounds": self.spec_rounds,
            "spec_drafted_tokens": self.spec_drafted,
            "spec_accepted_tokens": self.spec_accepted,
            "spec_acceptance_rate": (self.spec_accepted / self.spec_drafted
                                     if self.spec_drafted else 0.0),
            "spec_tokens_per_round": (self.spec_emitted / self.spec_rounds
                                      if self.spec_rounds else 0.0),
            "verify_recompute_rate": (self.spec_verify_selected /
                                      self.spec_verify_valid
                                      if self.spec_verify_valid else 0.0),
            # adaptive policy loop (serving/policy.py)
            "policy": (self.policy.stats() if self.policy is not None
                       else {"enabled": False}),
            # shadow audit (obs/audit.py): realized LAMP error telemetry
            "audit": (self.auditor.stats() if self.auditor is not None
                      else {"enabled": False}),
            # fault tolerance (serving/faults.py + the recovery ladder)
            "recoveries": self._n_recoveries,
            "failed_requests": self._n_failed,
            "faults": (self.faults.stats() if self.faults is not None
                       else {"enabled": False}),
        }

    def write_trace(self, path: Optional[str] = None) -> str:
        """Write the buffered step-phase trace as Chrome trace JSON
        (loadable in Perfetto / chrome://tracing). Requires
        ObsConfig.trace; `path` defaults to ObsConfig.trace_path."""
        return self.obs.write_trace(path)

    def _hang_diagnostic(self, n_events: int = 16) -> str:
        """Snapshot for the run_to_completion hang error: the registry's
        scalar metrics plus the trace tail, so a hung CI stream is
        debuggable from the log alone."""
        self._sync_gauges()
        scalars = {k: v for k, v in self.obs.registry.snapshot().items()
                   if isinstance(v, (int, float))}
        lines = ["registry snapshot: " + json.dumps(scalars, sort_keys=True)]
        seqs = list(self.scheduler.running) + list(self.scheduler.waiting)
        lines.append("live sequences: " + "; ".join(
            f"req {s.req_id} {s.status.value} gen={s.num_generated}"
            f"/{s.sampling.max_new_tokens} blocks={len(s.block_ids)}"
            for s in seqs[:8]))
        if self.obs.tracer.enabled:
            evs = self.obs.tracer.last(n_events)
            lines.append(f"last {len(evs)} trace events: " + "; ".join(
                f"{name}@{ts:.3f}s+{dur * 1e3:.2f}ms"
                for _, name, _, ts, dur, _ in evs))
        else:
            lines.append("trace ring empty (enable EngineConfig.obs.trace "
                         "for span-level hang forensics)")
        # accuracy regressions that stall acceptance (and therefore
        # progress) show up as flip-rate spikes in the audit ring
        if self.auditor is not None:
            tail = self.auditor.ring_tail()
            lines.append("audit ring tail: " + ("; ".join(tail) if tail
                                                else "(no audited steps)"))
        else:
            lines.append("audit off (set EngineConfig.audit.rate for "
                         "realized-error forensics)")
        return "\n".join(lines)

    def run_to_completion(self, max_steps: int = 100000) -> List[RequestOutput]:
        """Drive step() until every queued request finishes.

        Raises RuntimeError when `max_steps` elapse with requests still
        live, so a hung stream (scheduler stall, runaway generation) is
        loud instead of silently dropping requests; the error carries a
        diagnostic snapshot (registry scalars + trace tail) and
        stats()["live_requests"] exposes the same condition to pollers.

        A stall watchdog runs first: after `EngineConfig.stall_patience`
        consecutive steps with zero progress (no tokens, no prefill, no
        finishes, no failures) it attempts `_stall_recover()` -- clearing
        an injected stall, evicting wedged rows, or failing the oldest
        queued request -- and only raises when recovery changes nothing."""
        out: List[RequestOutput] = []
        idle = 0
        last = None
        for _ in range(max_steps):
            if not self.has_unfinished():
                return out
            out.extend(self.step())
            prog = (self.generated_tokens, self.prefill_tokens_run,
                    int(self._c_finished.value), self._n_failed)
            if prog == last:
                idle += 1
                if idle >= self.econfig.stall_patience:
                    if not self._stall_recover():
                        break
                    idle = 0
            else:
                idle = 0
                last = prog
        live = self.stats()["live_requests"]
        raise RuntimeError(
            f"run_to_completion exceeded max_steps={max_steps} with {live} "
            f"request(s) still live ({int(self._c_finished.value)} finished"
            f"); the "
            f"stream is hung or max_steps is too small\n"
            + self._hang_diagnostic())
