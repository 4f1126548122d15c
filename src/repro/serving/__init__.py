"""Continuous-batching LAMP serving engine.

vLLM-style serving architecture over the repro model stack:

  request.py   -- Request/Sequence lifecycle (WAITING -> PREFILL -> DECODE ->
                  FINISHED), per-request sampling params and LAMP stats
  kv_pool.py   -- paged KV-cache pool: refcounted block tables over a shared
                  (L, n_blocks, Hkv, block_size, hd) arena, chain-hashed
                  prefix index with copy-on-write sharing
  scheduler.py -- continuous-batching scheduler: FCFS admission by free-block
                  budget with prefix matching, chunked prefill windows,
                  preemption-by-eviction, bucketed step composition
  engine.py    -- the step loop: add_request() / step() / stream outputs,
                  cached jitted (windowed) prefill+decode, per-request LAMP
                  and prefix-cache telemetry
  sampling.py  -- shared Gumbel-max sampling primitives (per-request keyed
                  streams, top-k filtering) used by the engine, the
                  static-batch loop, and the speculative accept rule
  speculative.py -- LAMP self-draft speculative decoding: low-precision
                  drafter (rule "none") + selective-recompute verifier over
                  the paged pool, standard accept/residual-resample rule
  policy.py    -- adaptive LAMP policy controller: per-layer threshold
                  actuation (traced operands, zero recompiles) driven by
                  recompute-rate telemetry, with load-aware graceful
                  degradation of draft length and rule tier
  faults.py    -- deterministic fault injection: seeded, hash-sampled fault
                  sites (NaN poisoning, allocation failure, draft
                  corruption, fused-step anomaly, stall) replayable
                  bit-for-bit; drives the engine's health guard, recovery
                  ladder, and watchdog under test

Observability lives in `repro.obs` (metrics registry, step-phase tracer,
compile-event log); every engine carries an `Observability` bundle at
`engine.obs`, configured by `EngineConfig.obs` (an `repro.obs.ObsConfig`).
"""

from repro.obs.audit import AuditConfig

from .engine import EngineConfig, LampEngine, QueueFullError, RequestOutput
from .faults import (FAULT_SITES, ArenaAllocFault, FaultConfig, FaultError,
                     FaultInjector, StepLaunchFault, fault_hash)
from .kv_pool import PagedKVPool
from .policy import (MODE_NAMES, MODE_NORMAL, MODE_RELAXED, MODE_SHED,
                     PolicyActions, PolicyConfig, PolicyController,
                     PolicySignals)
from .request import SamplingParams, Sequence, SequenceStatus
from .scheduler import Scheduler, StepPlan
from .speculative import SpecConfig

__all__ = [
    "EngineConfig", "LampEngine", "RequestOutput", "PagedKVPool",
    "SamplingParams", "Sequence", "SequenceStatus", "Scheduler", "StepPlan",
    "SpecConfig", "PolicyConfig", "PolicyController", "PolicySignals",
    "PolicyActions", "MODE_NAMES", "MODE_NORMAL", "MODE_RELAXED",
    "MODE_SHED", "AuditConfig", "QueueFullError", "FAULT_SITES",
    "FaultConfig", "FaultInjector", "FaultError", "ArenaAllocFault",
    "StepLaunchFault", "fault_hash",
]
