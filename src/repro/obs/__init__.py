"""Serving observability: metrics registry + step-phase tracing.

LAMP's accuracy/throughput trade is steered by *telemetry* -- the recompute
rate is the paper's control variable -- so the serving stack treats
observability as a first-class subsystem rather than a pile of ad-hoc
counters:

  metrics.py  -- Counter / Gauge / Histogram registry with labeled children,
                 dict snapshots and Prometheus text exposition. The engine's
                 `stats()` is a view over one of these.
  tracing.py  -- ring-buffered step-phase span tracer exporting Chrome trace
                 format JSON (chrome://tracing / Perfetto loadable).

`Observability` bundles both behind a single injectable clock: every span it
opens is timed into a per-phase duration histogram (always on -- a dict
lookup and two float adds), enters a `jax.profiler.TraceAnnotation` named
`engine.<phase>` with the span's args (about a microsecond with no profiler
session; inside one, the phase lies on the device trace's clock beside the
device ops), and, when `ObsConfig.trace` is set, is also recorded as a ring
tracer event. Compile events (a new entry appearing in a bucketed jit
cache) are logged with their bucket shape and wall time -- recompile storms
are the canonical silent perf killer of fixed-shape serving, and this makes
them visible in `stats()`, the metrics snapshot, and the trace.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional

from jax.profiler import TraceAnnotation

from .metrics import (Counter, DEFAULT_TIME_EDGES, Gauge, Histogram,
                      MetricsRegistry)
from .tracing import NULL_TRACER, NullTracer, StepTracer

# per-phase duration edges (seconds): 10us .. 10s, ~x3 per bucket
PHASE_EDGES = (1e-5, 3.16e-5, 1e-4, 3.16e-4, 1e-3, 3.16e-3, 1e-2, 3.16e-2,
               1e-1, 3.16e-1, 1.0, 3.16, 10.0)
# profiler annotations of engine phases are named SPAN_PREFIX + phase
SPAN_PREFIX = "engine."


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Observability knobs (hashable: lives inside frozen EngineConfig).

    The metrics registry and per-phase duration histograms are always on
    (their hot-path cost is a cached dict lookup plus float adds);
    `trace` additionally records every phase span into the ring buffer for
    Chrome-trace export."""
    trace: bool = False             # record step-phase spans
    trace_capacity: int = 8192      # ring-buffer size (events)
    trace_path: str = ""            # default write_trace() destination
    series_capacity: int = 512      # per-layer recompute-rate series length
    compile_log_capacity: int = 256  # compile_events retained
    jax_profile_dir: str = ""       # opt-in jax.profiler.trace passthrough


class _ObsSpan:
    """Times one engine phase: always observes the per-phase histogram and
    annotates the profiler's timeline (`engine.<phase>`, with the args),
    and records a trace span when tracing is enabled."""

    __slots__ = ("_obs", "name", "args", "t0", "_ann")

    def __init__(self, obs: "Observability", name: str,
                 args: Optional[Dict[str, Any]]):
        self._obs = obs
        self.name = name
        self.args = args
        self.t0 = 0.0
        self._ann = None

    def __enter__(self) -> "_ObsSpan":
        self._ann = TraceAnnotation(SPAN_PREFIX + self.name,
                                    **(self.args or {}))
        self._ann.__enter__()
        self.t0 = self._obs.now()
        return self

    def __exit__(self, *exc) -> None:
        obs = self._obs
        dt = obs.now() - self.t0
        self._ann.__exit__(*exc)
        obs.phase_hist(self.name).observe(dt)
        if obs.tracer.enabled:
            obs.tracer._record(("X", self.name, "step", self.t0, dt,
                                self.args))

    def set(self, **args) -> None:
        """Add args known only once the span is open (e.g. a step's kind)."""
        self.args = {**(self.args or {}), **args}
        self._ann.set_metadata(**args)

    @property
    def elapsed(self) -> float:
        return self._obs.now() - self.t0


class Observability:
    """One engine's observability bundle: registry + tracer + clock."""

    def __init__(self, config: ObsConfig = ObsConfig(),
                 clock: Optional[Callable[[], float]] = None):
        self.config = config
        self.now: Callable[[], float] = clock or time.monotonic
        self.registry = MetricsRegistry()
        self.tracer = (StepTracer(config.trace_capacity, clock=self.now)
                       if config.trace else NULL_TRACER)
        self._phase_fam = self.registry.histogram(
            "engine_phase_seconds", edges=PHASE_EDGES,
            help="wall time per engine step phase", unit="s",
            labels=("phase",))
        self._phase_children: Dict[str, Histogram] = {}
        self._compile_counter = self.registry.counter(
            "engine_compiles_total", help="jit compiles by step kind",
            labels=("kind",))
        self.compile_events: Deque[Dict[str, Any]] = deque(
            maxlen=config.compile_log_capacity)

    # -- phase spans --------------------------------------------------------

    def phase_hist(self, name: str) -> Histogram:
        h = self._phase_children.get(name)
        if h is None:
            h = self._phase_fam.labels(name)
            self._phase_children[name] = h
        return h

    def span(self, name: str, **args) -> _ObsSpan:
        return _ObsSpan(self, name, args or None)

    @property
    def recording(self) -> bool:
        """Whether a span's args reach any reader (a profiler session or
        the ring tracer): args that cost more than a lookup are computed
        only then."""
        return self.tracer.enabled or TraceAnnotation.is_enabled()

    # -- compile events -----------------------------------------------------

    def record_compile(self, kind: str, shape: Any, wall_s: float,
                       step: int) -> None:
        """Log one jit compile: `shape` is the bucket signature that grew
        the cache (e.g. (batch_bucket, window_bucket)); `wall_s` the wall
        time of the compiling call (dispatch + compile)."""
        self._compile_counter.labels(kind).inc()
        self.compile_events.append({
            "kind": kind, "shape": tuple(shape), "wall_s": wall_s,
            "step": step, "t": self.now(),
        })
        if self.tracer.enabled:
            self.tracer.instant(f"compile:{kind}", cat="compile",
                                shape=str(tuple(shape)),
                                wall_ms=round(wall_s * 1e3, 3))

    # -- export -------------------------------------------------------------

    def write_trace(self, path: Optional[str] = None) -> str:
        path = path or self.config.trace_path
        if not path:
            raise ValueError("no trace path: pass one or set "
                             "ObsConfig.trace_path")
        return self.tracer.write(path)

    @contextlib.contextmanager
    def profile(self):
        """Opt-in `jax.profiler.trace` passthrough around a serving run:
        no-op unless ObsConfig.jax_profile_dir is set."""
        if not self.config.jax_profile_dir:
            yield
            return
        import jax
        jax.profiler.start_trace(self.config.jax_profile_dir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()


__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "NullTracer",
    "NULL_TRACER", "StepTracer", "ObsConfig", "Observability",
    "DEFAULT_TIME_EDGES", "PHASE_EDGES",
]

# imported last: audit.py needs Observability from this module
from .audit import AuditConfig, ShadowAuditor, audit_hash  # noqa: E402
from .error_model import calibrate, derive_target_rates, relax_mask  # noqa: E402

__all__ += ["AuditConfig", "ShadowAuditor", "audit_hash", "calibrate",
            "derive_target_rates", "relax_mask"]
