"""Tests for the serving observability layer (repro.obs + engine wiring).

Covers: metrics-registry semantics (counter monotonicity, histogram bucket
boundaries and streaming quantiles, label children, typed re-registration),
tracer ring-buffer overflow, Chrome-trace JSON schema validity, fake-clock
determinism, and the engine integration -- per-layer LAMP counts summing to
the aggregates, compile-event logging, trace-on vs trace-off token identity,
stats() key compatibility, and the hang-diagnostic dump.
"""

import json

import numpy as np
import pytest

import jax

from repro.configs import get_config, reduced as reduce_cfg
from repro.kernels.paged_attention import kv_heads_per_step
from repro.models import api
from repro.obs import ObsConfig, Observability
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.tracing import NULL_TRACER, StepTracer
from repro.serving import EngineConfig, LampEngine, SamplingParams


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ------------------------------------------------------------------ registry

def test_counter_monotone():
    c = Counter()
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value == 3.5


def test_gauge_last_write_wins():
    g = Gauge()
    g.set(5)
    g.inc(-2)
    assert g.value == 3.0


def test_histogram_bucket_boundaries():
    h = Histogram(edges=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 2.0, 4.0, 9.0):
        h.observe(v)
    # cumulative-le semantics: bucket i counts v <= edges[i]; an observation
    # exactly on an edge lands in that edge's bucket, not the next one
    assert h.counts == [2, 2, 1, 1]       # (<=1, <=2, <=4, +Inf]
    assert h.count == 6
    assert h.sum == pytest.approx(18.0)
    assert h.vmin == 0.5 and h.vmax == 9.0


def test_histogram_rejects_bad_edges():
    for edges in ((), (2.0, 1.0), (1.0, 1.0)):
        with pytest.raises(ValueError):
            Histogram(edges=edges)


def test_histogram_quantile_bounded_and_ordered():
    h = Histogram(edges=(1e-3, 1e-2, 1e-1, 1.0))
    rng = np.random.default_rng(0)
    vals = rng.uniform(0.002, 0.5, size=500)
    for v in vals:
        h.observe(v)
    qs = [h.quantile(q) for q in (0.0, 0.25, 0.5, 0.9, 0.99, 1.0)]
    assert all(a <= b + 1e-12 for a, b in zip(qs, qs[1:]))
    assert all(h.vmin <= q <= h.vmax for q in qs)
    # streaming estimate stays within the true value's bucket span
    true_p50 = np.percentile(vals, 50)
    assert abs(h.quantile(0.5) - true_p50) <= 0.1   # one decade bucket
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_histogram_single_bucket_does_not_smear():
    h = Histogram(edges=(1e-3, 1.0, 100.0))
    for v in (0.4, 0.5, 0.6):
        h.observe(v)
    # all mass in the (1e-3, 1] bucket: interpolation must stay inside the
    # observed [0.4, 0.6], not the raw bucket span
    assert 0.4 <= h.quantile(0.5) <= 0.6


def test_empty_histogram_quantile():
    assert Histogram(edges=(1.0,)).quantile(0.5) == 0.0
    assert Histogram(edges=(1.0,)).mean == 0.0


def test_registry_labels_and_memoization():
    reg = MetricsRegistry()
    fam = reg.counter("steps_total", labels=("kind",))
    a1, a2 = fam.labels("prefill"), fam.labels("prefill")
    assert a1 is a2
    fam.labels("decode").inc(3)
    a1.inc()
    snap = reg.snapshot()
    assert snap["steps_total"] == {"kind=prefill": 1.0, "kind=decode": 3.0}
    with pytest.raises(ValueError):
        fam.labels("a", "b")          # arity mismatch
    # same-name re-registration returns the same family; kind change raises
    assert reg.counter("steps_total", labels=("kind",)) is fam
    with pytest.raises(ValueError):
        reg.gauge("steps_total")


def test_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("reqs_total", help="requests").inc(2)
    h = reg.histogram("lat_seconds", edges=(0.1, 1.0), labels=("phase",))
    h.labels("decode").observe(0.05)
    h.labels("decode").observe(0.5)
    text = reg.to_prometheus()
    assert "# TYPE reqs_total counter" in text
    assert "reqs_total 2" in text
    assert 'lat_seconds_bucket{phase="decode",le="0.1"} 1' in text
    assert 'lat_seconds_bucket{phase="decode",le="+Inf"} 2' in text
    assert 'lat_seconds_count{phase="decode"} 2' in text


def _scrape_histogram(text, name):
    """Parse one histogram family back out of the exposition text:
    {labelset: {"buckets": [(le, cum), ...in emission order],
                "sum": float, "count": float}} where labelset is the
    sorted non-le label pairs (() for the unlabeled child)."""
    out = {}
    for line in text.splitlines():
        if line.startswith("#") or not line.startswith(name):
            continue
        metric, val = line.rsplit(" ", 1)
        series, _, rest = metric.partition("{")
        labels = dict(p.split("=", 1) for p in rest[:-1].split(",") if p)
        labels = {k: v.strip('"') for k, v in labels.items()}
        le = labels.pop("le", None)
        child = out.setdefault(tuple(sorted(labels.items())),
                               {"buckets": [], "sum": None, "count": None})
        if series == f"{name}_bucket":
            child["buckets"].append((le, float(val)))
        elif series == f"{name}_sum":
            child["sum"] = float(val)
        elif series == f"{name}_count":
            child["count"] = float(val)
    return out


def _check_histogram_child(child, edges):
    les = [le for le, _ in child["buckets"]]
    cums = [c for _, c in child["buckets"]]
    # one series per configured finite edge, then the explicit +Inf bucket
    assert les == [f"{e:g}" for e in edges] + ["+Inf"]
    # cumulative counts are monotone non-decreasing toward +Inf
    assert all(a <= b for a, b in zip(cums, cums[1:]))
    # +Inf carries every observation, and _count agrees with it
    assert cums[-1] == child["count"]
    return cums


def test_prometheus_histogram_roundtrip_unlabeled():
    """Scrape-parse the unlabeled `_bucket` emission branch: per-edge
    cumulative monotonicity, the explicit +Inf bucket, and _sum/_count
    consistency with the raw observations."""
    reg = MetricsRegistry()
    edges = (0.01, 0.1, 1.0, 10.0)
    h = reg.histogram("step_seconds", edges=edges, help="step wall")
    obs_vals = [0.005, 0.05, 0.05, 0.5, 5.0, 50.0]   # one past the top edge
    for v in obs_vals:
        h.observe(v)
    parsed = _scrape_histogram(reg.to_prometheus(), "step_seconds")
    assert set(parsed) == {()}
    child = parsed[()]
    cums = _check_histogram_child(child, edges)
    assert cums == [1, 3, 4, 5, 6]     # 50.0 lands only in +Inf
    assert child["count"] == len(obs_vals)
    assert child["sum"] == pytest.approx(sum(obs_vals))


def test_prometheus_histogram_roundtrip_labeled():
    """The labeled `_bucket` branch: every child keeps its own monotone
    cumulative series, `le` composes after the child's own labels, and
    _sum/_count are per-child."""
    reg = MetricsRegistry()
    edges = (0.1, 1.0)
    fam = reg.histogram("lat_seconds", edges=edges, labels=("phase",))
    fam.labels("decode").observe(0.05)
    fam.labels("decode").observe(0.5)
    fam.labels("decode").observe(5.0)
    fam.labels("prefill").observe(0.5)
    text = reg.to_prometheus()
    # the raw series names place le after the child's own label
    assert 'lat_seconds_bucket{phase="decode",le="+Inf"} 3' in text
    parsed = _scrape_histogram(text, "lat_seconds")
    assert set(parsed) == {(("phase", "decode"),), (("phase", "prefill"),)}
    dec = parsed[(("phase", "decode"),)]
    pre = parsed[(("phase", "prefill"),)]
    assert _check_histogram_child(dec, edges) == [1, 2, 3]
    assert _check_histogram_child(pre, edges) == [0, 1, 1]
    assert dec["sum"] == pytest.approx(5.55)
    assert pre["sum"] == pytest.approx(0.5)
    # both emission branches render the same structure for the same
    # observations: an unlabeled twin fed decode's samples parses equal
    reg2 = MetricsRegistry()
    twin = reg2.histogram("lat_seconds", edges=edges)
    for v in (0.05, 0.5, 5.0):
        twin.observe(v)
    t2 = _scrape_histogram(reg2.to_prometheus(), "lat_seconds")[()]
    assert t2["buckets"] == dec["buckets"]
    assert t2["count"] == dec["count"]
    assert t2["sum"] == pytest.approx(dec["sum"])


# -------------------------------------------------------------------- tracer

def test_tracer_fake_clock_spans():
    clk = FakeClock()
    tr = StepTracer(capacity=16, clock=clk)
    with tr.span("prefill", rows=2):
        clk.advance(0.25)
    clk.advance(0.1)
    tr.instant("compile:decode")
    (ph1, n1, _, t1, d1, a1), (ph2, n2, _, t2, d2, _) = tr.events()
    assert (ph1, n1, t1, d1, a1) == ("X", "prefill", 0.0, 0.25, {"rows": 2})
    assert (ph2, n2, t2, d2) == ("i", "compile:decode", 0.35, 0.0)


def test_tracer_ring_overflow():
    clk = FakeClock()
    tr = StepTracer(capacity=4, clock=clk)
    for i in range(10):
        clk.advance(1.0)
        tr.instant(f"e{i}")
    assert tr.dropped == 6
    names = [e[1] for e in tr.events()]
    assert names == ["e6", "e7", "e8", "e9"]    # last `capacity`, oldest first
    assert [e[1] for e in tr.last(2)] == ["e8", "e9"]


def test_chrome_trace_schema():
    clk = FakeClock(100.0)          # nonzero origin: ts must be rebased
    tr = StepTracer(capacity=16, clock=clk)
    for i in range(3):
        with tr.span("decode", bucket=[8]):
            clk.advance(0.002)
        clk.advance(0.001)
    tr.counter("lamp_recompute_rate", layer0=0.5, layer1=0.25)
    doc = tr.to_chrome_trace()
    blob = json.dumps(doc)                       # must be JSON-serializable
    doc = json.loads(blob)
    evs = doc["traceEvents"]
    assert len(evs) == 4
    last_ts = -1.0
    for ev in evs:
        assert {"name", "cat", "ph", "pid", "tid", "ts"} <= set(ev)
        assert ev["ph"] in ("X", "i", "C")
        assert ev["ts"] >= 0.0
        assert ev["ts"] >= last_ts               # recorded in time order
        last_ts = ev["ts"]
        if ev["ph"] == "X":
            assert ev["dur"] == pytest.approx(2000.0)   # 2ms in us
    assert evs[0]["ts"] == 0.0                   # rebased to first event
    assert evs[-1]["args"] == {"layer0": 0.5, "layer1": 0.25}
    assert doc["otherData"]["dropped_events"] == 0


def test_null_tracer_surface():
    with NULL_TRACER.span("x") as sp:
        pass
    assert sp.elapsed == 0.0
    NULL_TRACER.instant("y")
    assert NULL_TRACER.events() == []
    assert not NULL_TRACER.enabled
    with pytest.raises(RuntimeError):
        NULL_TRACER.write("/tmp/nope.json")


def test_tracer_write(tmp_path):
    clk = FakeClock()
    tr = StepTracer(capacity=8, clock=clk)
    with tr.span("s"):
        clk.advance(0.5)
    p = tr.write(str(tmp_path / "t.json"))
    assert json.load(open(p))["traceEvents"][0]["name"] == "s"


# ------------------------------------------------------------- Observability

def test_obs_span_always_feeds_histograms():
    clk = FakeClock()
    obs = Observability(ObsConfig(trace=False), clock=clk)
    with obs.span("decode"):
        clk.advance(0.01)
    h = obs.phase_hist("decode")
    assert h.count == 1 and h.sum == pytest.approx(0.01)
    assert obs.tracer is NULL_TRACER             # no events recorded


def test_obs_span_traces_when_enabled():
    clk = FakeClock()
    obs = Observability(ObsConfig(trace=True), clock=clk)
    with obs.span("prefill", rows=3):
        clk.advance(0.02)
    assert obs.phase_hist("prefill").count == 1
    (ph, name, cat, t0, dur, args), = obs.tracer.events()
    assert (ph, name, dur, args) == ("X", "prefill", 0.02, {"rows": 3})


def test_obs_compile_events():
    clk = FakeClock()
    obs = Observability(ObsConfig(trace=True, compile_log_capacity=2),
                        clock=clk)
    for i in range(3):
        obs.record_compile("decode", (8,), 0.5, step=i)
    assert len(obs.compile_events) == 2          # bounded log
    assert obs.compile_events[-1]["step"] == 2
    assert obs.registry.get("engine_compiles_total") \
        .labels("decode").value == 3
    names = [e[1] for e in obs.tracer.events()]
    assert names == ["compile:decode"] * 3


def test_obs_write_trace_requires_path():
    obs = Observability(ObsConfig(trace=True))
    with pytest.raises(ValueError):
        obs.write_trace()


class FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: logs (name, args) as
    each annotation opens, its metadata as it is set, and its closing."""
    log = []
    enabled = False

    def __init__(self, name, **args):
        self.name, self.args = name, args

    def __enter__(self):
        self.log.append(("enter", self.name, dict(self.args)))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, None))

    def set_metadata(self, **args):
        self.log.append(("set", self.name, args))

    @classmethod
    def is_enabled(cls):
        return cls.enabled


@pytest.fixture
def fake_annotation(monkeypatch):
    import repro.obs as obs_mod
    FakeAnnotation.log, FakeAnnotation.enabled = [], False
    monkeypatch.setattr(obs_mod, "TraceAnnotation", FakeAnnotation)
    return FakeAnnotation


def test_obs_span_opens_trace_annotation(fake_annotation):
    clk = FakeClock()
    obs = Observability(ObsConfig(trace=False), clock=clk)
    with obs.span("step", step=7) as outer:
        with obs.span("sync"):
            with obs.span("wait"):
                clk.advance(0.25)
            clk.advance(0.5)
        outer.set(kind="mixed")
        clk.advance(1.0)
    assert fake_annotation.log == [
        ("enter", "engine.step", {"step": 7}),
        ("enter", "engine.sync", {}),
        ("enter", "engine.wait", {}),
        ("exit", "engine.wait", None),
        ("exit", "engine.sync", None),
        ("set", "engine.step", {"kind": "mixed"}),
        ("exit", "engine.step", None)]
    # nested spans keep their own histograms: each sees its whole extent
    assert obs.phase_hist("wait").sum == pytest.approx(0.25)
    assert obs.phase_hist("sync").sum == pytest.approx(0.75)
    assert obs.phase_hist("step").sum == pytest.approx(1.75)
    assert outer.args == {"step": 7, "kind": "mixed"}
    # span args that cost more than a lookup wait for a reader
    assert not obs.recording
    fake_annotation.enabled = True
    assert obs.recording
    assert Observability(ObsConfig(trace=True)).recording


# --------------------------------------------------------- engine integration

@pytest.fixture(scope="module")
def model():
    cfg = reduce_cfg(get_config("gpt2")).replace(vocab=128)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _run(cfg, params, *, obs=ObsConfig(), clock=None, n=3, spec=False):
    eng = LampEngine(cfg, params, EngineConfig(
        block_size=4, n_blocks=64, max_model_len=64, obs=obs,
        speculative=spec, draft_len=2), clock=clock)
    rng = np.random.default_rng(7)
    for i in range(n):
        eng.add_request(rng.integers(0, cfg.vocab, size=5 + 3 * i).tolist(),
                        SamplingParams(max_new_tokens=6, seed=i))
    return eng, eng.run_to_completion()


def test_engine_per_layer_sums_to_totals(model):
    cfg, params = model
    eng, outs = _run(cfg, params)
    for o in outs:
        assert len(o.lamp_layer_selected) == cfg.n_layers
        assert sum(o.lamp_layer_selected) == pytest.approx(o.lamp_selected)
        assert sum(o.lamp_layer_valid) == pytest.approx(o.lamp_valid)
        assert all(0.0 <= r <= 1.0 for r in o.lamp_layer_rates)
    rates = eng.stats()["lamp_layer_rates"]
    assert len(rates) == cfg.n_layers and all(0.0 < r <= 1.0 for r in rates)
    assert eng.agg_lamp_selected == pytest.approx(
        sum(o.lamp_selected for o in outs))
    # the registry's per-layer counters agree with the numpy accumulators
    fam = eng.obs.registry.get("lamp_kq_products_total")
    for l in range(cfg.n_layers):
        assert fam.labels(str(l), "selected").value == pytest.approx(
            eng._layer_sel[l])
    assert len(eng.layer_rate_series) > 0


def test_engine_trace_on_token_identity_and_stats_compat(model):
    cfg, params = model
    eng_off, outs_off = _run(cfg, params, obs=ObsConfig(trace=False))
    eng_on, outs_on = _run(cfg, params, obs=ObsConfig(trace=True))
    assert {o.req_id: o.tokens for o in outs_on} \
        == {o.req_id: o.tokens for o in outs_off}
    # stats() keeps its public key surface regardless of tracing
    expected = {
        "num_finished", "elapsed_s", "tokens_per_s", "requests_per_s",
        "latency_p50_s", "latency_p99_s", "ttft_p50_s", "steps",
        "prefill_steps", "decode_steps", "prefill_chunks", "preemptions",
        "blocks_allocated", "blocks_saved", "cached_tokens",
        "prefill_tokens_run", "cache_hit_rate", "cow_copies",
        "cache_evictions", "kv_util_mean", "kv_util_peak",
        "lamp_recompute_rate", "lamp_layer_rates", "compiles",
        "compile_time_s", "phase", "live_requests", "spec_rounds",
        "spec_drafted_tokens", "spec_accepted_tokens",
        "spec_acceptance_rate", "spec_tokens_per_round",
        "verify_recompute_rate",
    }
    for eng in (eng_off, eng_on):
        s = eng.stats()
        assert expected <= set(s)
        assert s["live_requests"] == 0
    assert eng_off.obs.tracer.events() == []
    assert len(eng_on.obs.tracer.events()) > 0


def test_engine_phase_spans_on_the_profiler(model, fake_annotation):
    """Every engine phase opens an `engine.*` annotation inside the step's
    root span; while a profiler records, the fused launch span carries its
    rows, bucket, tokens, kernel grid counts and head fold."""
    fake_annotation.enabled = True
    cfg, params = model
    eng, _ = _run(cfg, params)
    log = fake_annotation.log
    names = {n for _, n, _ in log}
    assert all(n.startswith("engine.") for n in names)
    assert {"engine.step", "engine.schedule", "engine.build", "engine.mixed",
            "engine.inputs", "engine.launch", "engine.sync", "engine.wait",
            "engine.readback", "engine.account", "engine.emit"} <= names
    depth = 0
    for kind, name, _ in log:
        if kind == "enter":
            assert (depth == 0) == (name == "engine.step")
            depth += 1
        elif kind == "exit":
            depth -= 1
    assert depth == 0
    steps = [a for k, n, a in log if k == "enter" and n == "engine.step"]
    assert [a["step"] for a in steps] == list(range(eng.total_steps))
    kinds = [a["kind"] for k, n, a in log if k == "set"]
    assert kinds == ["mixed"] * eng.total_steps
    mixed = [a for k, n, a in log if k == "enter" and n == "engine.mixed"]
    assert len(mixed) == eng.mixed_steps
    for a in mixed:
        rows, width = a["bucket"]
        assert a["rows"] <= rows and a["tokens"] <= rows * width
        assert 0 < a["kv_live"] <= a["kv_walked"]
        assert a["kv_walked"] == rows * eng.blocks_per_seq
        assert a["kv_heads_per_step"] == kv_heads_per_step(
            cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, width,
            eng.econfig.block_size, cfg.hd)


def test_engine_phase_extents_fake_clock(model, fake_annotation):
    """schedule, emit and defrag time exactly the work they timed before
    the new phases (the benchmark's host_ms_per_step reads these three):
    the clock moves only inside the scheduler, the finished-request
    collection, the pool's defrag, the batch build and the LAMP
    accounting, each by its own amount."""
    cfg, params = model
    clk = FakeClock()
    eng = LampEngine(cfg, params, EngineConfig(
        block_size=4, n_blocks=64, max_model_len=64), clock=clk)

    def ticking(fn, dt):
        def wrapped(*a, **k):
            clk.advance(dt)
            return fn(*a, **k)
        return wrapped

    eng.scheduler.schedule = ticking(eng.scheduler.schedule, 1.0)
    eng._collect_finished = ticking(eng._collect_finished, 2.0)
    eng.pool.defrag = ticking(eng.pool.defrag, 4.0)
    eng._batch_arrays = ticking(eng._batch_arrays, 8.0)
    eng._account_lamp = ticking(eng._account_lamp, 16.0)
    eng.add_request(list(range(8)), SamplingParams(max_new_tokens=3))
    eng.add_request(list(range(5)), SamplingParams(max_new_tokens=2))
    n = 0
    while eng.has_unfinished():
        eng.step()
        n += 1
    eng.defrag()
    h = eng.obs.phase_hist
    assert n == eng.total_steps == h("step").count == 3
    assert h("schedule").sum == pytest.approx(1.0 * n)
    assert h("emit").sum == pytest.approx(2.0 * n)
    assert h("defrag").sum == pytest.approx(4.0)
    assert h("build").sum == pytest.approx(8.0 * n)
    assert h("account").sum == pytest.approx(16.0 * n)
    assert h("step").sum == pytest.approx(27.0 * n)
    for phase in ("inputs", "launch", "wait", "readback", "sync", "mixed"):
        assert h(phase).count == n and h(phase).sum == 0.0


def test_engine_compile_events_and_phase_histograms(model):
    cfg, params = model
    eng, _ = _run(cfg, params, obs=ObsConfig(trace=True))
    # the jit caches are process-global, so a warm cache may legitimately
    # record zero compiles here; every recorded event carries shape + wall
    # time and the stats() count matches the log
    for e in eng.compile_events:
        assert e["kind"] in ("prefill", "decode", "draft", "verify",
                             "mixed", "audit")
        assert isinstance(e["shape"], tuple) and e["wall_s"] >= 0.0
    assert eng.stats()["compiles"] == len(eng.compile_events)
    for must in ("schedule", "emit", "sync"):
        assert eng.obs.phase_hist(must).count > 0
    # fused default: every step is one mixed launch; the split engine still
    # feeds the per-phase histograms
    assert eng.obs.phase_hist("mixed").count == eng.mixed_steps \
        == eng.total_steps
    split = LampEngine(cfg, params, EngineConfig(
        block_size=4, n_blocks=64, max_model_len=64, fused_step=False,
        obs=ObsConfig(trace=True)))
    split.add_request(list(range(8)), SamplingParams(max_new_tokens=3))
    split.run_to_completion()
    assert split.obs.phase_hist("prefill").count == split.prefill_steps > 0
    assert split.obs.phase_hist("decode").count == split.decode_steps > 0


def test_engine_fake_clock_latencies(model):
    cfg, params = model
    clk = FakeClock(1000.0)
    eng = LampEngine(cfg, params, EngineConfig(
        block_size=4, n_blocks=64, max_model_len=64,
        obs=ObsConfig(trace=True)), clock=clk)
    eng.add_request(list(range(8)), SamplingParams(max_new_tokens=3))
    outs = []
    while eng.has_unfinished():
        outs.extend(eng.step())
        clk.advance(1.0)                # one fake second per step
    (o,) = outs
    # prefill step ends at t=1000 (clock advances after), first token there
    assert o.ttft == pytest.approx(0.0)
    assert o.latency == pytest.approx(2.0)     # 3 tokens = 3 steps, emit @ +2
    # every trace timestamp comes from the same fake clock
    assert all(1000.0 <= e[3] <= clk.t for e in eng.obs.tracer.events())


def test_engine_metrics_snapshot_and_prometheus(model):
    cfg, params = model
    eng, outs = _run(cfg, params)
    snap = eng.metrics_snapshot()
    json.dumps(snap)                             # JSON-serializable
    assert snap["engine_requests_finished_total"] == len(outs)
    assert snap["engine_generated_tokens_total"] == eng.generated_tokens
    assert snap["engine_live_requests"] == 0
    assert snap["engine_request_latency_seconds"]["count"] == len(outs)
    text = eng.obs.registry.to_prometheus()
    assert "engine_steps_total" in text and "lamp_kq_products_total" in text
    # streaming percentiles stay within the exact ones' histogram bounds
    s_stream, s_exact = eng.stats(exact=False), eng.stats(exact=True)
    h = eng._h_latency
    for s in (s_stream, s_exact):
        assert h.vmin - 1e-9 <= s["latency_p50_s"] <= h.vmax + 1e-9


def test_engine_spec_per_layer(model):
    cfg, params = model
    eng, outs = _run(cfg, params, spec=True, n=2)
    assert eng.spec_rounds > 0
    for o in outs:
        assert sum(o.lamp_layer_selected) == pytest.approx(o.lamp_selected)
    assert eng.spec_verify_valid > 0


def test_run_to_completion_hang_diagnostic(model):
    cfg, params = model
    eng = LampEngine(cfg, params, EngineConfig(
        block_size=4, n_blocks=64, max_model_len=64,
        obs=ObsConfig(trace=True)))
    eng.add_request(list(range(6)), SamplingParams(max_new_tokens=20))
    with pytest.raises(RuntimeError, match=r"1 request\(s\) still live") \
            as exc:
        eng.run_to_completion(max_steps=2)
    msg = str(exc.value)
    assert "registry snapshot:" in msg
    assert "trace events:" in msg
    assert "req 0" in msg


def test_serve_stream_fake_clock(model):
    from repro.launch.serve import metrics_line, serve_stream
    cfg, params = model
    clk = FakeClock()
    eng = LampEngine(cfg, params, EngineConfig(
        block_size=4, n_blocks=64, max_model_len=64), clock=clk)
    stream = [(0.0, list(range(6)), SamplingParams(max_new_tokens=2)),
              (5.0, list(range(4)), SamplingParams(max_new_tokens=2))]
    lines = []
    outs = serve_stream(eng, stream, metrics_every=1.0,
                        sleep=clk.advance, log=lines.append,
                        per_request=False)
    assert len(outs) == 2
    # the idle gap to the second arrival was crossed by the fake sleep
    # advancing the same clock the arrivals are timed against
    assert outs[1].ttft >= 0.0 and clk.t >= 5.0
    assert any(line.startswith("[serve] t=") for line in lines)
    assert "live=" in metrics_line(eng, clk.t)
